"""Paged KV pool: free-list lifecycle + paged-vs-concat serving parity.

Unit tests for ``core.kv_pool`` accounting (LIFO reuse, exhaustion,
double-free, random-churn invariants) and end-to-end *bitwise* parity of
the paged serving path against the legacy concat/split path — across
modes, GQA grouping and sliding-window geometries.  The slab is an
allocation strategy, never an approximation (docs/paged_kv.md).
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import CodecCfg, ModelCfg, ViTCfg
from repro.core import kv_pool
from repro.data.video import VideoSpec, generate_video
from repro.models import transformer as tfm
from repro.models import vit as vitm
from repro.models.init import ParamBuilder, split_tree
from repro.serving import (
    EngineCfg, KVCfg, Scheduler, ServingPipeline, StreamRequest,
)
from repro.serving.scheduler import _staged_bytes

CODEC = CodecCfg(gop=4, block=16, search_radius=4, window_frames=8,
                 stride_frames=4, keep_ratio=0.4)
LM = ModelCfg(name="tiny-vlm", family="vlm", n_layers=2, d_model=64,
              n_heads=4, n_kv=2, d_ff=128, vocab=64, tied_embeddings=True)
VIT = ViTCfg(n_layers=2, d_model=64, n_heads=4, d_ff=128, patch=14,
             image=112, group=2)
N_STREAMS = 3


# ----------------------------------------------------------------------
# free-list accounting (host-side, no device work)
# ----------------------------------------------------------------------
def test_admit_evict_roundtrip():
    pool = kv_pool.KVPool(LM, 8)
    pages = pool.admit(3)
    assert pool.used_pages == 3 and pool.free_pages == 5
    assert len(set(pages.tolist())) == 3
    pool.evict(pages)
    assert pool.used_pages == 0 and pool.free_pages == 8


def test_admit_streams_disjoint():
    pool = kv_pool.KVPool(LM, 8)
    pt = pool.admit_streams(3, 2)
    assert pt.shape == (3, 2) and pt.dtype == np.int32
    flat = pt.ravel().tolist()
    assert len(set(flat)) == 6          # no page serves two streams


def test_page_reuse_after_evict():
    """LIFO free list: a closed stream's pages are the next admitted —
    the warmest slab rows get recycled first."""
    pool = kv_pool.KVPool(LM, 8)
    first = pool.admit(2)
    pool.evict(first)
    second = pool.admit(2)
    assert set(second.tolist()) == set(first.tolist())


def test_exhaustion_raises_without_leaking():
    pool = kv_pool.KVPool(LM, 4)
    held = pool.admit(3)
    assert not pool.can_admit(2)
    with pytest.raises(kv_pool.PoolExhausted):
        pool.admit(2)
    # the failed admit must not consume pages
    assert pool.free_pages == 1 and pool.used_pages == 3
    pool.evict(held)
    assert pool.can_admit(4)


def test_double_free_is_an_error():
    pool = kv_pool.KVPool(LM, 4)
    pages = pool.admit(2)
    pool.evict(pages)
    with pytest.raises(AssertionError, match="double free"):
        pool.evict(pages)


def test_random_churn_preserves_accounting():
    """Poisson-style stream churn: random admits/evicts never alias a
    page across streams and never lose one."""
    rng = np.random.default_rng(0)
    pool = kv_pool.KVPool(LM, 16)
    live = []
    for _ in range(300):
        if live and (rng.random() < 0.45 or pool.free_pages == 0):
            pool.evict(live.pop(int(rng.integers(len(live)))))
        else:
            want = int(rng.integers(1, 5))
            if pool.can_admit(want):
                live.append(pool.admit(want))
            else:
                with pytest.raises(kv_pool.PoolExhausted):
                    pool.admit(want)
        held = [int(p) for pages in live for p in pages]
        assert len(held) == len(set(held))
        assert pool.used_pages == len(held)
        assert pool.free_pages + pool.used_pages == pool.n_pages
    for pages in live:
        pool.evict(pages)
    assert pool.free_pages == pool.n_pages


def test_logical_to_physical():
    pt = jnp.asarray([[3, 1], [0, 2]], jnp.int32)
    idx = jnp.asarray([0, 127, 128, 200], jnp.int32)
    phys = np.asarray(kv_pool.logical_to_physical(pt, idx, 128))
    np.testing.assert_array_equal(
        phys,
        [[384, 511, 128, 200], [0, 127, 256, 328]],
    )


def test_staged_bytes_attribution_inputs():
    """Paged sessions stage a page table (bytes), concat sessions stage
    whole caches (megabytes) — the scheduler's per-stream t_stage split
    must see that asymmetry."""
    paged_state = {
        "pages": np.zeros((1, 2), np.int32),
        "kv_valid": jnp.zeros((1, 256), bool),
    }
    caches = tfm.init_caches(LM, batch=1, max_len=256)
    dense_state = {"caches": caches, "kv_valid": jnp.zeros((1, 256), bool)}
    assert _staged_bytes(None) == 0
    assert 0 < _staged_bytes(paged_state) < 4096
    assert _staged_bytes(dense_state) > 64 * _staged_bytes(paged_state)


# ----------------------------------------------------------------------
# end-to-end: paged == concat, bitwise, through the Scheduler
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def stack():
    params, _ = tfm.init_params(LM, jax.random.PRNGKey(0))
    pb = ParamBuilder(jax.random.PRNGKey(1))
    vparams, _ = split_tree(vitm.init_vit(pb, VIT, LM.d_model))
    streams = [
        generate_video(VideoSpec(n_frames=16, height=112, width=112,
                                 anomaly=bool(i % 2), seed=3 + i))[0]
        for i in range(N_STREAMS)
    ]
    return params, vparams, streams


def _pipeline(params, vparams, mode, *, paged, cfg=LM, pool_streams=None):
    return ServingPipeline(
        cfg, VIT, params, vparams,
        EngineCfg(mode=mode, codec=CODEC,
                  kv=KVCfg(paged_kv=paged, pool_streams=pool_streams)))


def _serve(pipe, streams, max_concurrent=N_STREAMS):
    sched = Scheduler(pipe, max_concurrent=max_concurrent)
    sids = [sched.submit(StreamRequest(i, f)) for i, f in enumerate(streams)]
    out = sched.run()
    return {
        sid: [tuple(np.asarray(r.stats.logits_yes_no).tolist())
              for r in out[sid]]
        for sid in sids
    }


@pytest.mark.parametrize("mode", ["codecflow", "cacheblend"])
def test_paged_matches_concat_bitwise(stack, mode):
    """Same fleet, paged slab vs per-stream concat: every window's
    logits must be bit-for-bit identical, and the pool must drain."""
    params, vparams, streams = stack
    pipe = _pipeline(params, vparams, mode, paged=True)
    assert pipe.backend.paged
    paged = _serve(pipe, streams)
    pool = pipe.backend.pool
    assert pool is not None and pool.free_pages == pool.n_pages
    concat = _serve(
        _pipeline(params, vparams, mode, paged=False), streams)
    assert paged == concat


@pytest.mark.parametrize("geom", ["gqa-1kv", "sliding-window"])
def test_paged_matches_concat_geometries(geom):
    """Parity must hold across GQA grouping and windowed attention —
    the geometries that change kernel masks and gather shapes."""
    cfg = (
        dataclasses.replace(LM, name="tiny-gqa1", n_kv=1)
        if geom == "gqa-1kv"
        else dataclasses.replace(LM, name="tiny-sw", sliding_window=64)
    )
    params, _ = tfm.init_params(cfg, jax.random.PRNGKey(0))
    vparams, _ = split_tree(
        vitm.init_vit(ParamBuilder(jax.random.PRNGKey(1)), VIT, cfg.d_model))
    streams = [
        generate_video(VideoSpec(n_frames=12, height=112, width=112,
                                 anomaly=bool(i), seed=5 + i))[0]
        for i in range(2)
    ]
    paged = _serve(
        _pipeline(params, vparams, "codecflow", paged=True, cfg=cfg),
        streams, max_concurrent=2)
    concat = _serve(
        _pipeline(params, vparams, "codecflow", paged=False, cfg=cfg),
        streams, max_concurrent=2)
    assert paged == concat


def test_scheduler_throttles_on_pinned_pool(stack):
    """pool_streams pins capacity below max_concurrent: admission must
    throttle gracefully (never PoolExhausted mid-batch) and still
    complete every stream."""
    params, vparams, streams = stack
    pipe = _pipeline(params, vparams, "codecflow", paged=True,
                     pool_streams=1)
    sched = Scheduler(pipe, max_concurrent=2)
    sids = [sched.submit(StreamRequest(i, f))
            for i, f in enumerate(streams)]
    pool = pipe.backend.pool
    assert pool.n_pages == pipe.backend.pages_per_stream  # pinned, no growth
    while not sched.idle:
        sched.poll()
        backed = sum(
            1 for sess in sched._active.values()
            if sess.state and "pages" in sess.state)
        assert backed <= 1                  # capacity honored mid-run
    out = {sid: sched.close(sid) for sid in sids}
    assert all(len(rs) == 3 for rs in out.values())
    assert pool.free_pages == pool.n_pages


def test_sequential_streams_reuse_the_same_pages(stack):
    """max_concurrent=1: stream n+1 must be served out of the exact
    physical pages stream n vacated (LIFO), with zero slab growth."""
    params, vparams, streams = stack
    pipe = _pipeline(params, vparams, "codecflow", paged=True)
    sched = Scheduler(pipe, max_concurrent=1)
    sids = [sched.submit(StreamRequest(i, f))
            for i, f in enumerate(streams[:2])]
    seen = {}
    while not sched.idle:
        sched.poll()
        for sid, sess in sched._active.items():
            if sess.state and "pages" in sess.state:
                seen.setdefault(sid, set()).update(
                    int(p) for p in np.asarray(sess.state["pages"]).ravel())
    assert seen[sids[0]] == seen[sids[1]]
    pool = pipe.backend.pool
    assert pool.n_pages == pipe.backend.pages_per_stream
    assert pool.free_pages == pool.n_pages


def test_pool_growth_requires_empty_pool(stack):
    """ensure_pool may only grow between fleets, never under live
    streams — page ids already handed out must stay stable."""
    params, vparams, _ = stack
    be = _pipeline(params, vparams, "codecflow", paged=True).backend
    be.ensure_pool(1)
    held = be.pool.admit(1)
    with pytest.raises(AssertionError, match="pin pool_streams"):
        be.ensure_pool(2)
    be.pool.evict(held)
    be.ensure_pool(2)                       # legal once drained
    assert be.pool.n_pages == 2 * be.pages_per_stream


def test_paged_session_state_holds_no_kv(stack):
    """The tentpole invariant: a paged session's state is metadata only
    (page table + visibility) — the Scheduler never concatenates KV."""
    params, vparams, streams = stack
    pipe = _pipeline(params, vparams, "codecflow", paged=True)
    sched = Scheduler(pipe, max_concurrent=1)
    sched.submit(StreamRequest("cam", streams[0]))
    sched.poll()                            # first window served
    (sess,) = sched._active.values()
    assert "caches" not in sess.state and "pages" in sess.state
    assert isinstance(sess.state["pages"], np.ndarray)


# ----------------------------------------------------------------------
# two-precision pool: demotion churn + int8 cold-page serving parity
# ----------------------------------------------------------------------
# Long-overlap codec for the quant e2e legs: window 16 / stride 4 at
# keep_ratio=1.0 leaves one full demotable overlap page per stream
# (P=3, D=1), and a 24-frame video spans 3 windows — window 0 prefill,
# window 1 demotes, window 2 reads through the int8 cold page.
QCODEC = CodecCfg(gop=4, block=16, search_radius=4, window_frames=16,
                  stride_frames=4, keep_ratio=1.0)


def _quant_pipeline(params, vparams, mode, *, stale_dtype, cfg=LM):
    return ServingPipeline(
        cfg, VIT, params, vparams,
        EngineCfg(mode=mode, codec=QCODEC,
                  kv=KVCfg(paged_kv=True, stale_page_dtype=stale_dtype)))


@pytest.fixture(scope="module")
def long_streams():
    return [
        generate_video(VideoSpec(n_frames=24, height=112, width=112,
                                 anomaly=bool(i), seed=11 + i))[0]
        for i in range(2)
    ]


def test_random_churn_with_demotion_preserves_accounting():
    """Poisson churn over a two-precision pool: admits (with cold
    reservation), demotes, and evicts — of both demoted and never-
    demoted streams — must never alias a page id across streams or
    precisions, never lose one, and keep the cold reservation exactly
    covering the live streams that have not demoted yet."""
    P, D = 4, 2
    rng = np.random.default_rng(1)
    pool = kv_pool.KVPool(LM, 16, cold_pages=8)
    live = []                       # [page ids (P,), demoted?]
    for _ in range(300):
        r = rng.random()
        undemoted = [s for s in live if not s[1]]
        if undemoted and r < 0.3:
            s = undemoted[int(rng.integers(len(undemoted)))]
            s[0][:D] = pool.demote(s[0][:D])     # unified ids >= n_pages
            s[1] = True
        elif live and (r < 0.6 or not pool.can_admit_streams(1, P, D)):
            pt, demoted = live.pop(int(rng.integers(len(live))))
            if not demoted:
                pool.unreserve_cold(D)           # reservation dies with it
            pool.evict(pt)
        elif pool.can_admit_streams(1, P, D):
            live.append([pool.admit_streams(1, P, D)[0], False])
        held = [int(p) for s in live for p in s[0]]
        assert len(held) == len(set(held))       # no aliasing, either slab
        assert pool.used_pages == len(held)
        hot_held = sum(p < pool.n_pages for p in held)
        assert pool.free_pages == pool.n_pages - hot_held
        assert pool.free_cold_pages == pool.n_cold - (len(held) - hot_held)
        assert pool._reserved_cold == D * len([s for s in live if not s[1]])
        assert pool._reserved_cold <= pool.free_cold_pages
    for pt, demoted in live:
        if not demoted:
            pool.unreserve_cold(D)
        pool.evict(pt)
    assert pool.free_pages == pool.n_pages
    assert pool.free_cold_pages == pool.n_cold
    assert pool._reserved_cold == 0


@pytest.mark.parametrize("mode", ["codecflow", "cacheblend"])
def test_int8_cold_pages_preserve_answers(stack, long_streams, mode):
    """Quantized vs all-bf16 serving through the Scheduler: window 0
    (before any demotion) agrees to f32 rounding, later windows stay
    within the int8 round-trip budget and never flip a yes/no answer,
    and both slabs (hot + cold + reservation) drain on close."""
    params, vparams, _ = stack
    pq = _quant_pipeline(params, vparams, mode, stale_dtype="int8")
    assert pq.backend.quant and pq.backend.cold_per_stream >= 1
    quant = _serve(pq, long_streams, max_concurrent=2)
    pool = pq.backend.pool
    assert pool.free_pages == pool.n_pages
    assert pool.free_cold_pages == pool.n_cold
    assert pool._reserved_cold == 0
    bf16 = _serve(
        _quant_pipeline(params, vparams, mode, stale_dtype="bf16"),
        long_streams, max_concurrent=2)
    for sid in quant:
        # Pre-demotion both slabs hold the same values, but not the same
        # batches: the int8 pool admits streams staggered, so a stream's
        # window 0 may run alone (batch 1) where the bf16 run fused it
        # with another (batch 2).  XLA compiles a different program per
        # batch shape, and float reassociation moves a logit by ~1 f32
        # ulp; the bf16 slab serving that stream alone reproduces the
        # int8 value bit for bit.  So the bound is f32 rounding.
        np.testing.assert_allclose(quant[sid][0], bf16[sid][0],
                                   rtol=1e-6, atol=1e-7)
        for lq, lb in zip(quant[sid], bf16[sid]):
            assert (lq[0] > lq[1]) == (lb[0] > lb[1]), (sid, lq, lb)
            assert max(abs(a - b) for a, b in zip(lq, lb)) < 0.5


@pytest.mark.parametrize("geom", ["gqa-1kv", "sliding-window"])
def test_int8_cold_pages_geometries(geom):
    """Quant parity must also hold where kernel masks and gather shapes
    change: single-KV-head GQA and sliding-window attention."""
    cfg = (
        dataclasses.replace(LM, name="tiny-gqa1", n_kv=1)
        if geom == "gqa-1kv"
        else dataclasses.replace(LM, name="tiny-sw", sliding_window=64)
    )
    params, _ = tfm.init_params(cfg, jax.random.PRNGKey(0))
    vparams, _ = split_tree(
        vitm.init_vit(ParamBuilder(jax.random.PRNGKey(1)), VIT, cfg.d_model))
    streams = [
        generate_video(VideoSpec(n_frames=20, height=112, width=112,
                                 anomaly=bool(i), seed=17 + i))[0]
        for i in range(2)
    ]
    pq = _quant_pipeline(params, vparams, "codecflow",
                         stale_dtype="int8", cfg=cfg)
    assert pq.backend.quant and pq.backend.cold_per_stream >= 1
    quant = _serve(pq, streams, max_concurrent=2)
    bf16 = _serve(
        _quant_pipeline(params, vparams, "codecflow",
                        stale_dtype="bf16", cfg=cfg),
        streams, max_concurrent=2)
    for sid in quant:
        for lq, lb in zip(quant[sid], bf16[sid]):
            assert (lq[0] > lq[1]) == (lb[0] > lb[1]), (sid, lq, lb)
            assert max(abs(a - b) for a, b in zip(lq, lb)) < 0.5
