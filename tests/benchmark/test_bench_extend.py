"""A new configuration, LM block, traffic mix, cell and per-layer metric
take only new files under bench/ and new entries in BENCHMARK.json: the
harness finds each by its name."""
import dataclasses
import json
import re
import shutil
import time

import pytest

from bench_helpers import ROOT, TINY, bench_dir, benchmark_with_tiny
from bench.lib import harness

FALLBACKS = ("def read(run):\n"
             "    return float(sum(w['kernel_fallbacks'] for w in run.windows))\n")


def _throwaway(tmp_path):
    """A bench copy with a throwaway block (the dense block's code under
    another name), a configuration naming it, a mix, a cell and two
    readers, one of them of a window counter the harness does not name."""
    d = bench_dir(tmp_path)
    shutil.copy(ROOT / "bench" / "lm" / "gqa.py", d / "lm" / "throwaway.py")
    conf = json.loads((d / "configs" / "tiny.json").read_text())
    conf["name"] = "throwaway-model"
    conf["lm"]["n_layers"] = 1
    conf["lm"]["block"] = "throwaway"
    (d / "configs" / "throwaway-model.json").write_text(json.dumps(conf))
    mix = json.loads((d / "traffic" / "tiny-sessions.json").read_text())
    mix["scene"]["n_objects"] = 1
    (d / "traffic" / "throwaway-mix.json").write_text(json.dumps(mix))
    (d / "cells" / "throwaway.cell.json").write_text('{"streams": 1}')
    (d / "metrics" / "throwaway.windows.py").write_text(
        "def read(run):\n    return float(len(run.windows)) or None\n")
    (d / "metrics" / "throwaway.fallbacks.py").write_text(FALLBACKS)

    bm = benchmark_with_tiny()
    bm["configs"].append({"name": "throwaway-model", "source": "test",
                          "file": "bench/configs/throwaway-model.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "throwaway.cell", "config":
                            "throwaway-model", "traffic": "throwaway-mix",
                            "chips": 1, "why": "test"})
    for name, unit in (("throwaway.windows", "count"),
                       ("throwaway.fallbacks", "count")):
        bm["per_layer"].append({"name": name, "unit": unit,
                                "better": "lower", "source": "program_counter",
                                "layer": "scheduler", "moves": "windows_per_s",
                                "workloads": ["throwaway.cell"]})
    return d, harness.load_cell("throwaway.cell", bm, d)


@pytest.fixture(scope="module")
def throwaway(tmp_path_factory):
    """The throwaway cell, served and checked once by ``harness.run``:
    (cell, result line, every window record of the run)."""
    d, cell = _throwaway(tmp_path_factory.mktemp("b"))
    records = []
    out = harness.run(cell, 5, 2.0, False, time.perf_counter(),
                      require_tpu=False, compile_cache=False,
                      records=records)
    yield cell, out, records
    shutil.rmtree(d)


def test_throwaway_entries_need_only_new_files(throwaway):
    cell, out, _ = throwaway
    assert cell.conf["lm"]["n_layers"] == 1 and cell.streams == 1
    assert cell.mix.scene.n_objects == 1
    assert cell.block.__file__.endswith("throwaway.py")
    assert [m["name"] for m in cell.per_layer][-2:] == [
        "throwaway.windows", "throwaway.fallbacks"]

    assert out["correct"], out["checks"]
    assert out["metrics"]["windows_per_s"]["value"] > 0
    # the new reader is found by name; the others find no trace to read
    geo = harness.geometry(cell.conf, cell.mix.codec, cell.block)
    assert geo["block"] is cell.block
    win = [{"tokens_refreshed": geo["total"], "tokens_valid": 20,
            "vit_patches": 600, "kernel_fallbacks": 1}] * 3
    view = harness.RunView(cell, geo, 0.0, 1.0, win, win, [], 0, None,
                           {"peak": 1, "limit": 2}, None, "cpu")
    got = harness.per_layer_metrics(cell, view)
    assert got["throwaway.windows"] == {"value": 3.0, "unit": "count"}
    assert got["throwaway.fallbacks"] == {"value": 3.0, "unit": "count"}
    assert "device.idle_share" not in got


def test_window_records_carry_every_window_counter(throwaway):
    """Each window record of the served run holds every ``WindowStats``
    field, so a counter the program adds reaches readers with no harness
    edit."""
    from repro.serving import WindowStats

    cell, _, records = throwaway
    assert len(records) >= 3
    names = {f.name for f in dataclasses.fields(WindowStats)}
    for w in records:
        assert names <= set(w)
        assert {"t", "step", "sid", "window", "yes", "no", "answer",
                "finite"} <= set(w)
        assert (w["yes"], w["no"]) == tuple(w["logits_yes_no"])
        assert w["kv_bytes_per_stream"] > 0
    geo = harness.geometry(cell.conf, cell.mix.codec, cell.block)
    view = harness.RunView(cell, geo, 0.0, 1.0, records, records,
                           [], 0, None, {"peak": 1, "limit": 2}, None, "cpu")
    got = harness.per_layer_metrics(cell, view)
    assert got["throwaway.fallbacks"]["value"] == float(
        sum(w["kernel_fallbacks"] for w in records))


@pytest.mark.parametrize("block", [None, "nonesuch"],
                         ids=["no_block", "unknown_block"])
def test_config_without_a_known_block_is_refused(tmp_path, block):
    d = bench_dir(tmp_path)
    conf = json.loads((d / "configs" / "tiny.json").read_text())
    if block is None:
        del conf["lm"]["block"]
    else:
        conf["lm"]["block"] = block
    (d / "configs" / "tiny.json").write_text(json.dumps(conf))
    with pytest.raises(ValueError, match=re.escape(str(d / "lm"))):
        harness.load_cell(TINY, benchmark_with_tiny(), d)
