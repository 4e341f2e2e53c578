"""Paper Fig. 19 — runtime overhead of CodecFlow's decision logic:
motion analysis + token selection (pre-ViT) and KVC reuse bookkeeping
(Eq. 5 correction), as absolute time and as a share of window latency."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.codec import encode_stream
from repro.core import capacity_groups, motion_mask, reuse_caches, select_tokens
from repro.core.kvc import WindowLayout
from repro.models import transformer as tfm

from .common import CODEC, LM, VIT, csv_row, eval_videos, run_mode


def _timeit(fn, n=20):
    fn()  # warm
    t0 = time.perf_counter()
    for _ in range(n):
        r = fn()
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / n


def run(emit) -> dict:
    frames, _ = eval_videos()[0]
    _, md = encode_stream(jnp.asarray(frames, jnp.float32), CODEC)
    w = CODEC.window_frames
    md_w = md.window(0, w)

    t_mask = _timeit(lambda: motion_mask(md_w, CODEC, VIT.patches_per_side))
    dyn, score = motion_mask(md_w, CODEC, VIT.patches_per_side)
    kg = capacity_groups(VIT, CODEC.keep_ratio)
    t_select = _timeit(lambda: select_tokens(dyn, score, VIT, kg))

    lay = WindowLayout(window=w, stride=CODEC.stride_frames, gop=CODEC.gop,
                       g_tokens=VIT.n_groups, k_tokens=kg, query_len=8)
    caches = tfm.init_caches(LM, 1, lay.total_len + 1)
    reuse = jax.jit(lambda c: reuse_caches(LM, c, lay))
    t_reuse = _timeit(lambda: reuse(caches))

    total = run_mode("codecflow")["latency_per_window"]
    pruning_overhead = t_mask + t_select
    out = {
        "t_motion_mask_s": t_mask, "t_select_s": t_select,
        "t_kvc_reuse_s": t_reuse,
        "pruning_overhead_s": pruning_overhead,
        "share_of_window": (pruning_overhead + t_reuse) / max(total, 1e-9),
    }
    emit(csv_row("overhead/token_pruning", pruning_overhead * 1e6,
                 f"mask={t_mask*1e3:.2f}ms select={t_select*1e3:.2f}ms"))
    emit(csv_row("overhead/kvc_refresh", t_reuse * 1e6,
                 f"rope_correction={t_reuse*1e3:.2f}ms"))
    emit(csv_row("overhead/share", 0.0,
                 f"{out['share_of_window']*100:.1f}% of window latency "
                 f"(paper: ~4%)"))

    # fused-window state staging: legacy per-stream cache concat/split
    # vs paged slab (page-table staging only, docs/paged_kv.md).  Same
    # streams, same fleet — the t_overhead delta is pure KV movement.
    concat = run_mode("codecflow", concurrent=4, paged=False)
    paged = run_mode("codecflow", concurrent=4, paged=True)
    out["t_overhead_concat_s"] = concat["t_overhead"]
    out["t_overhead_paged_s"] = paged["t_overhead"]
    out["staging_reduction_x"] = (
        concat["t_overhead"] / max(paged["t_overhead"], 1e-9)
    )
    emit(csv_row(
        "overhead/kv_staging_concat", concat["t_overhead"] * 1e6,
        "per-window cache concat/split at concurrent=4"))
    emit(csv_row(
        "overhead/kv_staging_paged", paged["t_overhead"] * 1e6,
        f"page-table staging ({out['staging_reduction_x']:.1f}x less "
        f"than concat)"))

    # steady-state KV memory per resident stream (deterministic byte
    # count, gated direction-aware in the bench-regression CI step:
    # lower is better).  The int8 cold-page variant is A/B'd at the
    # capacity geometry in bench_streams; this row tracks the default
    # serving config.
    out["kv_bytes_per_stream"] = paged["kv_bytes_per_stream"]
    out["kv_slab_bytes"] = paged["kv_slab_bytes"]
    emit(csv_row(
        "overhead/kv_bytes_per_stream", 0.0,
        f"{paged['kv_bytes_per_stream']:,} B/stream "
        f"(slab {paged['kv_slab_bytes']:,} B at concurrent=4)"))

    # scheduling overhead of the stage-pipelined async engine vs the
    # lockstep loop at the same fleet (docs/async_scheduler.md): the
    # per-window stage times must be unchanged (same math, same
    # groups), so any t_overhead delta is queue/bookkeeping cost,
    # while time to first answer shows what the overlap buys.
    pipelined = run_mode("codecflow", concurrent=4, paged=True,
                         pipelined=True)
    out["t_overhead_async_s"] = pipelined["t_overhead"]
    out["async_windows_per_s"] = pipelined["windows_per_s"]
    out["lockstep_windows_per_s"] = paged["windows_per_s"]
    out["async_ttft_p99_s"] = pipelined["ttft_p99"]
    out["lockstep_ttft_p99_s"] = paged["ttft_p99"]
    emit(csv_row(
        "overhead/async_scheduler", pipelined["t_overhead"] * 1e6,
        f"windows/s={pipelined['windows_per_s']:.2f} "
        f"(lockstep {paged['windows_per_s']:.2f}) "
        f"ttft_p99={pipelined['ttft_p99'] * 1e3:.0f}ms"))
    return out
