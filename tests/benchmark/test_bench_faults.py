"""A run whose timed path is broken underneath reads ``correct`` false,
for each fault a serving cell can have: a window step that leaves the
stream's state unchanged, half of a fused batch served from the other
half's data, an answer altered where it is produced.  (One chip: there
is no exchange between chips to leave out.)"""
import time

import jax.numpy as jnp
import pytest

from bench_helpers import TINY, bench_dir, benchmark_with_tiny
from bench.lib import harness


@pytest.fixture(scope="module")
def cell(tmp_path_factory):
    return harness.load_cell(TINY, benchmark_with_tiny(),
                             bench_dir(tmp_path_factory.mktemp("b")))


def _state_unchanged(mp):
    # KV reuse returns the slab as it was: the overlap never moves
    from repro.core import kv_pool
    mp.setattr(kv_pool, "reuse_pool_caches",
               lambda cfg, caches, pt, layout, page: caches)


def _half_batch(mp):
    # a fused encode group leaves out its second half: those rows get the
    # mean of the first half's visual tokens
    from repro.serving import api
    orig = api.ServingPipeline.encode_windows

    def encode(self, frames, metas, fresh):
        enc = orig(self, frames, metas, fresh)
        S = enc.vis.shape[0]
        if S < 2:
            return enc
        keep = enc.vis[:S - S // 2]
        rest = jnp.broadcast_to(keep.mean(0, keepdims=True),
                                enc.vis[S - S // 2:].shape)
        return enc._replace(vis=jnp.concatenate(
            [keep, rest.astype(enc.vis.dtype)], 0))
    mp.setattr(api.ServingPipeline, "encode_windows", encode)


def _answer_altered(mp):
    # the yes/no decision is swapped where the decoder produces it
    from repro.serving import api
    orig = api.GreedyDecoder.start

    def start(self, logits, *a, **kw):
        pend = orig(self, logits, *a, **kw)
        return pend._replace(yes_no=pend.yes_no[:, ::-1],
                             answers=~pend.answers)
    mp.setattr(api.GreedyDecoder, "start", start)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _answer_altered],
                         ids=lambda f: f.__name__.strip("_"))
def test_fault_reads_incorrect(cell, fault, monkeypatch):
    fault(monkeypatch)
    out = harness.run(cell, 7, 2.0, False, time.perf_counter(),
                      require_tpu=False, compile_cache=False)
    assert not out["correct"], out["checks"]
