"""A new configuration, traffic mix, cell and per-layer metric take only
new files under bench/ and new entries in BENCHMARK.json: the harness
finds each by its name."""
import json
import shutil
import time

from bench_helpers import bench_dir, benchmark_with_tiny
from bench.lib import harness


def test_throwaway_entries_need_only_new_files(tmp_path):
    d = bench_dir(tmp_path)
    conf = json.loads((d / "configs" / "tiny.json").read_text())
    conf["name"] = "throwaway-model"
    conf["lm"]["n_layers"] = 1
    (d / "configs" / "throwaway-model.json").write_text(json.dumps(conf))
    mix = json.loads((d / "traffic" / "tiny-sessions.json").read_text())
    mix["scene"]["n_objects"] = 1
    (d / "traffic" / "throwaway-mix.json").write_text(json.dumps(mix))
    (d / "cells" / "throwaway.cell.json").write_text('{"streams": 1}')
    (d / "metrics" / "throwaway.windows.py").write_text(
        "def read(run):\n    return float(len(run.windows)) or None\n")

    bm = benchmark_with_tiny()
    bm["configs"].append({"name": "throwaway-model", "source": "test",
                          "file": "bench/configs/throwaway-model.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "throwaway.cell", "config":
                            "throwaway-model", "traffic": "throwaway-mix",
                            "chips": 1, "why": "test"})
    bm["per_layer"].append({"name": "throwaway.windows", "unit": "count",
                            "better": "higher", "source": "program_counter",
                            "layer": "scheduler", "moves": "windows_per_s",
                            "workloads": ["throwaway.cell"]})
    cell = harness.load_cell("throwaway.cell", bm, d)
    assert cell.conf["lm"]["n_layers"] == 1 and cell.streams == 1
    assert cell.mix.scene.n_objects == 1
    assert [m["name"] for m in cell.per_layer][-1] == "throwaway.windows"

    out = harness.run(cell, 5, 2.0, False, time.perf_counter(),
                      require_tpu=False, compile_cache=False)
    assert out["correct"], out["checks"]
    assert out["metrics"]["windows_per_s"]["value"] > 0
    # the new reader is found by name; the others find no trace to read
    geo = harness.geometry(cell.conf, cell.mix.codec)
    win = [{"tokens_refreshed": geo["total"], "tokens_valid": 20,
            "vit_patches": 600}] * 3
    view = harness.RunView(cell, geo, 0.0, 1.0, win, win, [], 0, None,
                           {"peak": 1, "limit": 2}, None, "cpu")
    got = harness.per_layer_metrics(cell, view)
    assert got["throwaway.windows"] == {"value": 3.0, "unit": "count"}
    assert "device.idle_share" not in got
    shutil.rmtree(d)
