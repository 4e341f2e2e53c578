"""The table from a program's module name to its serving stage
(``bench/lib/stages.py``) against what the serve path runs: a CPU
profiler trace of the tiny cell through the benchmark's driver, where
every jitted call shows as ``PjitFunction(<function>)``."""
import glob
import re

import jax
import pytest
from jax.profiler import ProfileData

from bench_helpers import ROOT, TINY, bench_dir, benchmark_with_tiny
from bench.lib import harness, stages, traffic, weights


@pytest.fixture(scope="module")
def jitted(tmp_path_factory):
    """Names of the functions jitted inside the serve loop's spans
    (``serve.step``, ``serve.submit``) of a warm tiny cell."""
    from repro.configs import CodecCfg
    from repro.serving import (EngineCfg, KVCfg, Scheduler, SchedulerCfg,
                               ServingPipeline)

    tmp = tmp_path_factory.mktemp("stages")
    cell = harness.load_cell(TINY, benchmark_with_tiny(), bench_dir(tmp))
    F = cell.streams
    cfg, vcfg = harness.program_cfg(cell.conf, cell.block)
    params, vparams = weights.make_weights(cell.conf["lm"],
                                           cell.conf["vit"], 3, cell.block)
    pipe = ServingPipeline(cfg, vcfg, params, vparams, EngineCfg(
        mode="codecflow", codec=CodecCfg(**cell.mix.codec),
        kv=KVCfg(pool_streams=F)))
    drv = harness.Driver(Scheduler(pipe, SchedulerCfg(max_concurrent=F)),
                         traffic.Schedule(cell.mix, F),
                         traffic.build_pool(cell.mix, F))
    drv.start()
    period = cell.mix.segment_windows * F
    with jax.profiler.trace(str(tmp / "trace")):
        while len(drv.windows) < period + F:
            drv.step()
    path = glob.glob(str(tmp / "trace" / "**" / "*.xplane.pb"),
                     recursive=True)[0]
    names = set()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = list(line.events)
            loops = [(e.start_ns, e.start_ns + e.duration_ns) for e in evs
                     if e.name in ("serve.step", "serve.submit")]
            for e in evs:
                m = re.fullmatch(r"PjitFunction\((.*)\)", e.name)
                if m and any(s <= e.start_ns <= t for s, t in loops):
                    names.add(m.group(1))
    return names


def _defined_in_program():
    """Names of the functions the program's own source defines, and of
    the copies it makes under other names (``api._renamed``)."""
    out = set()
    for f in (ROOT / "src" / "repro").rglob("*.py"):
        text = f.read_text()
        out |= set(re.findall(r"^\s*def (\w+)\(", text, re.M))
        out |= set(re.findall(r"_renamed\([\w.]+,\s*\"(\w+)\"", text))
    return out


def test_every_program_of_the_serve_path_has_a_stage(jitted):
    assert "<lambda>" not in jitted
    ours = jitted & _defined_in_program()
    assert {"vit_full", "motion_mask", "select_tokens",
            "encode_packed_tokens", "lm_fresh_prefill_paged",
            "lm_reuse_paged", "lm_selective_paged", "lm_decode_paged",
            "encode_stream", "decode_stream"} <= ours
    missing = {n for n in ours if f"jit_{n}" not in stages.MODULES}
    assert missing == set()


def test_each_stage_has_programs():
    assert set(stages.MODULES.values()) == set(stages.STAGES) - {"other"}


def test_split_counts_every_operation_once():
    op_s = {"jit_lm_selective_paged/fusion.1": 3.0,
            "jit_lm_reuse_paged/rope_shift.2": 0.5,
            "jit_vit_full/fusion.7": 1.0,
            "jit_lm_decode_paged/flash_refresh_paged_pallas.3": 0.25,
            "jit_decode_stream/fusion.2": 0.75,
            "jit_concatenate/concatenate": 0.125, "?/copy.1": 0.0625}
    s = stages.split(op_s)
    assert s == {"codec": 0.75, "vit": 1.0, "prefill": 3.5,
                 "decode": 0.25, "other": 0.1875}
    assert sum(s.values()) == pytest.approx(sum(op_s.values()))
    assert list(stages.top_modules(op_s)) == [("jit_concatenate", 0.125),
                                              ("?", 0.0625)]


@pytest.mark.parametrize("name", ["vit.device_ms_per_window",
                                  "prefill.device_ms_per_window",
                                  "decode.device_ms_per_step"])
def test_stage_readers_find_nothing_without_a_trace(name):
    read = harness._read_metric(ROOT / "bench", name)
    cell = harness.load_cell("ivl3-14b.cctv-sessions")
    geo = harness.geometry(cell.conf, cell.mix.codec, cell.block)
    win = [{"tokens_refreshed": geo["total"]}]
    view = harness.RunView(cell, geo, 0.0, 1.0, win, win, [], 0, None,
                           {"peak": 1, "limit": 2}, None, "cpu")
    assert read(view) is None
