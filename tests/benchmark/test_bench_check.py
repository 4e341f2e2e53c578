"""The comparison that decides ``correct``, at a size a CPU test holds:
the tiny cell is served through the normal path (scheduler, paged KV,
packed ViT) and checked against the plain reference; the fp8 control in
the program's place fails the same limits."""
import time

import pytest

from bench_helpers import TINY, bench_dir, benchmark_with_tiny
from bench.lib import harness


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    return harness.load_cell(TINY, benchmark_with_tiny(),
                             bench_dir(tmp_path_factory.mktemp("b")))


def _run(cell, seed, **kw):
    return harness.run(cell, seed, 2.0, False, time.perf_counter(),
                       require_tpu=False, compile_cache=False, **kw)


def test_tiny_cell_is_correct_and_control_is_not(cell):
    out = _run(cell, 2**32 + 17, control=True)
    lim = cell.conf["limits"]
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"windows_per_s", "answer_gap_p90_s",
                                   "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    # the control: the reference in fp8 in the program's place
    assert out["control"]["logit_err_rel"] > lim["logit_err_rel"]
    assert out["control"]["logit_err_rel"] > 3 * out["checks"]["logit_err_rel"]["value"]


def test_work_tables_match_the_programs_counts(cell):
    """The benchmark's codec and token selection, run over the frames of
    each served window, give the program's own counts exactly: valid
    positions and patches encoded."""
    from bench.lib import traffic, weights
    from repro.configs import CodecCfg
    from repro.serving import (EngineCfg, KVCfg, Scheduler, SchedulerCfg,
                               ServingPipeline)

    conf, mix, F = cell.conf, cell.mix, cell.streams
    cfg, v = harness.program_cfg(conf, cell.block)
    params, vparams = weights.make_weights(conf["lm"], conf["vit"], 3,
                                           cell.block)
    pool = traffic.build_pool(mix, F)
    sch = traffic.Schedule(mix, F)
    pipe = ServingPipeline(cfg, v, params, vparams, EngineCfg(
        mode="codecflow", codec=CodecCfg(**mix.codec),
        kv=KVCfg(pool_streams=F)))
    drv = harness.Driver(Scheduler(pipe, SchedulerCfg(max_concurrent=F)),
                         sch, pool)
    drv.start()
    for _ in range(12):
        drv.step()
    assert len(drv.windows) > 6
    harness.attach_work(drv.windows, drv.segments, pool, cell, sch)
    for w in drv.windows:
        assert int(w["valid"].sum()) == w["tokens_valid"]
        assert sum(w["kept"]) == w["vit_patches"]
