"""Kernel microbenchmarks: us/call of each compute hot-spot's oracle on
CPU (the Pallas kernels execute only on TPU; interpret mode measures
Python, not hardware — so the jit'd jnp oracle is what we time here).

The refresh-attention section additionally reports the *static* FLOP
accounting of the block-sparse kernel path: the ``WindowLayout``-derived
tile map says exactly which (q-tile, kv-tile) pairs a TPU would visit,
so the dense-vs-sparse FLOP ratio is exact and hardware-independent.

Set ``BENCH_SMOKE=1`` to append a tiny end-to-end serving probe
(windows/s, codecflow vs fullcomp) — the config CI's bench-smoke job
runs to put a throughput number next to the kernel rows.
"""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp

import numpy as np

from repro.configs.base import ViTCfg
from repro.core import (
    WindowLayout, capacity_groups, pack_plan, refresh_block_map,
    select_tokens,
)
from repro.kernels import ref
from repro.kernels import ops as kernel_ops
from repro.kernels.ops import flash_refresh, mv_sad, rope_shift, ssd_scan
from repro.models import layers
from repro.serving.flops import vit_packed_flops, vit_padded_flops

from .common import csv_row


def _timeit(fn, n=10):
    jax.block_until_ready(fn())
    t0 = time.perf_counter()
    for _ in range(n):
        r = fn()
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / n * 1e6


def run(emit) -> dict:
    out = {}
    # every wall-clock row below is block_until_ready-bracketed on THIS
    # backend — tag the platform so a CPU-runner number is never read
    # as a device win in the CI summary or a pasted table
    out["host_platform"] = jax.default_backend()
    emit(csv_row("kernels/host_platform", 0.0,
                 f"wall-clock rows measured on {out['host_platform']}"))
    k = jax.random.PRNGKey(0)

    cur = jax.random.uniform(k, (112, 112)) * 255
    prev = jnp.roll(cur, (2, 1), (0, 1))
    f = jax.jit(lambda a, b: mv_sad(a, b, 16, 4))
    us = _timeit(lambda: f(cur, prev))
    out["mv_sad"] = us
    emit(csv_row("kernels/mv_sad_112px_r4", us, "81-candidate full search"))

    kk = jax.random.normal(k, (1, 4096, 8, 128), jnp.bfloat16)
    d = jnp.full((1, 4096), -100, jnp.int32)
    f = jax.jit(lambda a, b: rope_shift(a, b))
    us = _timeit(lambda: f(kk, d))
    out["rope_shift"] = us
    emit(csv_row("kernels/rope_shift_4k_kv8", us, "Eq.5 position correction"))

    x = jax.random.normal(k, (1, 1024, 8, 64))
    la = -jnp.abs(jax.random.normal(k, (1, 1024, 8))) * 0.3
    b = jax.random.normal(k, (1, 1024, 1, 16))
    c = jax.random.normal(k, (1, 1024, 1, 16))
    f = jax.jit(lambda *a: ssd_scan(*a, chunk=128))
    us = _timeit(lambda: f(x, la, b, c))
    out["ssd_scan"] = us
    emit(csv_row("kernels/ssd_scan_1k_h8", us, "chunked state-space duality"))

    q = jax.random.normal(k, (1, 1024, 8, 64), jnp.bfloat16)
    kv = jax.random.normal(k, (1, 1024, 2, 64), jnp.bfloat16)
    f = jax.jit(lambda a, b, c: ref.flash_prefill_ref(a, b, c))
    us = _timeit(lambda: f(q, kv, kv))
    out["attention"] = us
    emit(csv_row("kernels/causal_attn_1k_gqa", us, "prefill attention"))

    out.update(_refresh_attention(emit))
    out.update(_vit_packing(emit))
    if os.environ.get("BENCH_SMOKE"):
        out.update(_serve_smoke(emit))

    # dispatch-decision ledger across the whole bench run: every op
    # call above routed through the contract registry; a nonzero
    # fallback count here means a bench scenario silently left the
    # kernel path (the CI summary surfaces this next to throughput)
    counts = kernel_ops.dispatch_counts()
    eligible_n = sum(
        c.get("kernel", 0) + c.get("backend:ok", 0) for c in counts.values()
    )
    fallback_n = sum(
        v
        for c in counts.values()
        for key, v in c.items()
        if key not in ("kernel", "backend:ok")
    )
    out["dispatch_kernel_decisions"] = eligible_n
    out["dispatch_fallback_decisions"] = fallback_n
    emit(csv_row(
        "kernels/dispatch_coverage", 0.0,
        f"{eligible_n} kernel-eligible / {fallback_n} fallback decisions",
    ))
    return out


def _refresh_attention(emit) -> dict:
    """Selective-refresh attention (§3.4.1): old dense-mask path vs the
    flash_refresh dispatch, plus the exact block-sparse FLOP ledger."""
    H, Hkv, D = 8, 2, 64
    lay = WindowLayout(window=16, stride=4, gop=4, g_tokens=256,
                       k_tokens=128, query_len=32)
    nr = lay.n_refresh
    # serving rounds cache slots up to the 128-token KV tile; the raw
    # total_len (2592) is not tile-aligned and would silently refuse
    # the kernel path (contract rule 'k-tile' — tools.check catches it)
    S = -(-lay.total_len // 128) * 128
    bm = refresh_block_map(lay, kv_len=S)

    k = jax.random.PRNGKey(1)
    ks = jax.random.split(k, 4)
    q = jax.random.normal(ks[0], (1, nr, H, D), jnp.bfloat16)
    kk = jax.random.normal(ks[1], (1, S, Hkv, D), jnp.bfloat16)
    vv = jax.random.normal(ks[2], (1, S, Hkv, D), jnp.bfloat16)
    kv_valid = (jax.random.uniform(ks[3], (1, S)) > 0.3).at[
        :, lay.total_len:
    ].set(False)
    qpos = jnp.asarray(lay.refresh_token_idx)[None]

    f_dense = jax.jit(
        lambda a, b, c, p, m: layers.mha(a, b, c, p,
                                         jnp.arange(S)[None], m)
    )
    us_dense = _timeit(lambda: f_dense(q, kk, vv, qpos, kv_valid))
    f_new = jax.jit(
        lambda a, b, c, p, m: flash_refresh(a, b, c, p, m, block_map=bm)
    )
    us_new = _timeit(lambda: f_new(q, kk, vv, qpos, kv_valid))

    # per-tile cost: qk^T + pv, each 2*tq*tk*D MACs, over all q heads
    tile_flops = 4 * bm.tq * bm.tk * D * H
    dense_tiles = bm.n_q_tiles * bm.n_kv_tiles
    visited = int(bm.tile_count.sum())
    flops_dense = dense_tiles * tile_flops
    flops_sparse = visited * tile_flops
    emit(csv_row(
        "kernels/refresh_attn_dense_mask", us_dense,
        f"old path: (B,S) mask, n_refresh={nr} S={S}"))
    emit(csv_row(
        "kernels/refresh_attn_dispatch", us_new,
        f"ops.flash_refresh oracle (CPU); kernel path skips "
        f"{dense_tiles - visited}/{dense_tiles} tiles"))
    emit(csv_row(
        "kernels/refresh_attn_block_flops", 0.0,
        f"dense={flops_dense / 1e6:.1f}MF sparse={flops_sparse / 1e6:.1f}MF "
        f"({100 * (1 - bm.density):.0f}% skipped)"))
    return {
        "refresh_dense_us": us_dense,
        "refresh_dispatch_us": us_new,
        # measured dense/sparse wall ratio on this host (see
        # host_platform) — informational next to the exact FLOP ledger
        "refresh_wall_speedup_x": us_dense / max(us_new, 1e-9),
        "refresh_n_q": nr,
        "refresh_kv_len": S,
        "refresh_block_density": bm.density,
        "refresh_tiles_total": dense_tiles,
        "refresh_tiles_visited": visited,
        "refresh_flops_dense": float(flops_dense),
        "refresh_flops_sparse": float(flops_sparse),
    }


def _vit_packing(emit) -> dict:
    """Padded vs packed pruned ViT encode (§3.3.2 made cost-
    proportional): wall-clock patches/s of both jitted paths on this
    host, plus the exact hardware-independent FLOP ledger (the packed
    attention ledger counts only the block map's visited tiles — what a
    TPU pays; the CPU oracle computes dense rows, so wall numbers
    understate the kernel-path win)."""
    import jax.numpy as jnp

    from repro.codec import encode_stream
    from repro.configs.base import CodecCfg
    from repro.core import motion_mask
    from repro.data.video import VideoSpec, generate_video
    from repro.models import vit as vitm
    from repro.models.init import ParamBuilder, split_tree

    v = ViTCfg(n_layers=2, d_model=128, n_heads=4, d_ff=256,
               patch=14, image=224, group=2)
    B = 8
    pb = ParamBuilder(jax.random.PRNGKey(0))
    params, _ = split_tree(vitm.init_vit(pb, v, 128))
    # real codec-reported motion (objects over a static background, as
    # in the paper's CCTV workload) — an iid random mask would mark
    # nearly every group dynamic after group-complete expansion and
    # leave the pruner nothing to prune
    raw, _ = generate_video(VideoSpec(
        n_frames=B + 1, height=v.image, width=v.image, speed=2.0,
        n_objects=2, seed=7,
    ))
    ccfg = CodecCfg(gop=B + 1, block=16, search_radius=4)
    _, md = encode_stream(jnp.asarray(raw, jnp.float32), ccfg)
    dyn_all, sco_all = motion_mask(md, ccfg, v.patches_per_side)
    dyn, sco = dyn_all[1:], sco_all[1:]          # P-frames only
    frames = jnp.asarray(raw[1:], jnp.float32)

    f_padded = jax.jit(
        lambda vp, f, pi, pv: vitm.encode_pruned_tokens(vp, v, f, pi, pv)
    )
    out = {}
    gate_ratio = None
    for keep in (0.5, 0.25):
        kg = capacity_groups(v, keep)
        dec = select_tokens(dyn, sco, v, kg)
        kept = int(np.asarray(dec.patch_valid).sum())
        k_sel = dec.patch_idx.shape[1]

        us_pad = _timeit(
            lambda: f_padded(params, frames, dec.patch_idx, dec.patch_valid)
        )
        plan = pack_plan(dec, v)
        bm = plan.block_map

        def run_packed():
            # plan building is part of the packed path's steady-state
            # cost: rebuild it every call so the comparison is honest
            p = pack_plan(dec, v)
            m = p.block_map
            return vitm.encode_packed_tokens(
                params, v, frames,
                jnp.asarray(p.patch_src), jnp.asarray(p.seg_id),
                jnp.asarray(p.group_src), jnp.asarray(p.group_dst),
                jnp.asarray(m.tile_ids), jnp.asarray(m.tile_count),
                n_out=B * kg, tq=m.tq, tk=m.tk,
            )
        us_pack = _timeit(run_packed)

        fl_pad = vit_padded_flops(v, B, k_sel)
        fl_pack = vit_packed_flops(
            v, plan.n_slots, bm.visited, bm.tq, bm.tk, plan.k_pack
        )
        ratio = fl_pad / fl_pack
        if keep == 0.5:
            gate_ratio = ratio
        pps_pad = kept / (us_pad / 1e6)
        pps_pack = kept / (us_pack / 1e6)
        tag = f"{keep:g}"
        emit(csv_row(
            f"kernels/vit_padded_keep{tag}", us_pad,
            f"{B} frames x K_sel={k_sel} lanes, kept={kept}"))
        emit(csv_row(
            f"kernels/vit_packed_keep{tag}", us_pack,
            f"rows={plan.n_rows} L={plan.l_pack} fill={plan.fill:.2f} "
            f"flops {fl_pad / 1e6:.0f}->{fl_pack / 1e6:.0f}MF "
            f"({100 * (1 - fl_pack / fl_pad):.0f}% saved)"))
        out.update({
            f"vitpack_{tag}_padded_us": us_pad,
            f"vitpack_{tag}_packed_us": us_pack,
            f"vitpack_{tag}_padded_patches_s": pps_pad,
            f"vitpack_{tag}_packed_patches_s": pps_pack,
            f"vitpack_{tag}_kept_patches": kept,
            f"vitpack_{tag}_slots": plan.n_slots,
            f"vitpack_{tag}_fill": plan.fill,
            f"vitpack_{tag}_flops_padded": fl_pad,
            f"vitpack_{tag}_flops_packed": fl_pack,
            f"vitpack_{tag}_flop_speedup": ratio,
            f"vitpack_{tag}_wall_speedup_x": us_pad / max(us_pack, 1e-9),
        })
    # acceptance gate: the packed path must be >= 1.5x on the exact
    # FLOP ledger at keep_ratio <= 0.5 (the hardware-independent form
    # of the patches/s claim; wall-clock is reported above)
    assert gate_ratio is not None and gate_ratio >= 1.5, gate_ratio
    out["vitpack_min_flop_speedup"] = gate_ratio
    return out


def _serve_smoke(emit) -> dict:
    """Tiny end-to-end throughput probe (CI smoke config): 2 short
    streams through the refresh path and the full-recompute baseline.

    Uses randomly-initialized weights — windows/s and the refresh-token
    accounting are properties of the serving system, not of the model
    quality, and skipping the tiny-VLM training keeps this CI-fast.
    """
    from repro.models import transformer as tfm
    from repro.models import vit as vitm
    from repro.models.init import ParamBuilder, split_tree
    from repro.serving import (
        EngineCfg, Scheduler, ServingPipeline, StreamRequest,
    )

    from .common import CODEC, LM, VIT

    params, _ = tfm.init_params(LM, jax.random.PRNGKey(0))
    pb = ParamBuilder(jax.random.PRNGKey(1))
    vparams = split_tree(vitm.init_vit(pb, VIT, LM.d_model))[0]
    rng = np.random.default_rng(0)
    videos = [
        (rng.random((24, VIT.image, VIT.image)) * 255).astype(np.float32)
        for _ in range(2)
    ]

    out = {}
    for mode in ("codecflow", "fullcomp"):
        pipe = ServingPipeline(LM, VIT, params, vparams,
                               EngineCfg(mode=mode, codec=CODEC))
        # warmup traces the fresh + incremental jitted paths
        warm = Scheduler(pipe, max_concurrent=2)
        for i, frames in enumerate(videos):
            warm.submit(StreamRequest(i, frames))
        warm.run()
        sched = Scheduler(pipe, max_concurrent=2)
        t0 = time.perf_counter()
        sids = [sched.submit(StreamRequest(i, frames))
                for i, frames in enumerate(videos)]
        per_session = sched.run()
        wall = time.perf_counter() - t0
        stats = [res.stats for sid in sids for res in per_session[sid]]
        n_windows = len(stats)
        wps = n_windows / max(wall, 1e-9)
        refreshed = sum(s.tokens_refreshed for s in stats) / max(n_windows, 1)
        out[f"smoke_{mode}_windows_per_s"] = wps
        out[f"smoke_{mode}_refreshed_per_window"] = refreshed
        out[f"smoke_{mode}_flops_prefill"] = sum(
            s.flops_prefill for s in stats)
        out[f"smoke_{mode}_pack_util"] = sched.vit_pack_utilization
        out[f"smoke_{mode}_t_overhead"] = sum(
            s.t_overhead for s in stats) / max(n_windows, 1)
        out[f"smoke_{mode}_kv_bytes_per_stream"] = max(
            (s.kv_bytes_per_stream for s in stats), default=0)
        ttft = sched.ttft_quantiles()
        out[f"smoke_{mode}_ttft_p50"] = ttft.get("p50", 0.0)
        out[f"smoke_{mode}_ttft_p99"] = ttft.get("p99", 0.0)
        emit(csv_row(
            f"kernels/smoke_{mode}", 1e6 / max(wps, 1e-9),
            f"windows/s={wps:.2f} refresh/win={refreshed:.0f} "
            f"vit_util={sched.vit_pack_utilization:.2f}"))
    return out
