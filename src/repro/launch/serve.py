"""Serving launcher: CodecFlow streaming analytics over synthetic CCTV
streams with any registered architecture (smoke variants on CPU).

Single stream (sequential windows):

    PYTHONPATH=src python -m repro.launch.serve --arch internvl3-14b-smoke \
        --mode codecflow --videos 4

Multi-stream batched serving (N concurrent sessions; ready windows of
same-layout streams fused into single batched ViT-encode/prefill calls;
reports aggregate windows/s across sessions):

    PYTHONPATH=src python -m repro.launch.serve --streams 4 --videos 4

Frames are square at the ViT's input resolution (``ViTCfg.image``).  By
default the stage-pipelined async scheduler overlaps codec window
slicing with accelerator work and keeps windows of different streams in
different stages at once (docs/async_scheduler.md); ``--lockstep``
forces the legacy one-group-per-step loop for A/B comparisons.  The
summary reports TTFT and per-stage host occupancy alongside throughput.

``--trace-dir DIR`` records the serve loop with the JAX profiler into
``DIR``: the scheduler's ``serve.`` host spans beside the device's
operations, one clock (docs/async_scheduler.md §Spans).

``serve()`` is the same run as a function returning the report dict
(``chip_smoke.py`` drives it on the chip).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import time
from pathlib import Path

import jax
import numpy as np

from ..configs import CodecCfg, ViTCfg, get_config
from ..data.pipeline import anomaly_dataset
from ..models import transformer as tfm
from ..models import vit as vitm
from ..models.init import ParamBuilder, split_tree
from ..serving import (
    Engine, EngineCfg, KVCfg, Scheduler, SchedulerCfg, ServingPipeline,
    StreamRequest, StreamThrottled, WindowDone,
    precision_recall_f1, video_prediction,
)
from ..training import checkpoint

#: persistent compile cache used when JAX_COMPILATION_CACHE_DIR is unset:
#: a fixed directory at the checkout root (the path is part of the key)
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its path.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    left alone.  Otherwise the cache goes to ``CACHE_DIR``.  Called by
    entry points only, never at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def default_vit(cfg) -> ViTCfg:
    return cfg.vit or ViTCfg(
        n_layers=2, d_model=128, n_heads=4, d_ff=256, patch=14,
        image=112, group=2,
    )


def init_weights(cfg, v: ViTCfg, seed: int = 0):
    """Random (LM params, ViT params) from ``seed``.

    One jit of the keys: every leaf is drawn on the device straight into
    its final stacked storage-dtype buffer, with no f32 or per-layer
    copy held beside it (at full width those copies would not fit).
    The keys are ``rbg``, which the backend's own bit generator draws:
    the one-chip InternVL3-14B has 6.4e9 values to draw."""
    def build(k_lm, k_vit):
        params, _ = tfm.init_params(cfg, k_lm)
        vparams, _ = split_tree(
            vitm.init_vit(ParamBuilder(k_vit), v, cfg.d_model))
        return params, vparams

    return jax.jit(build)(jax.random.key(seed, impl="rbg"),
                          jax.random.key(seed + 1, impl="rbg"))


def build_pipeline(arch: str, mode: str, codec: CodecCfg,
                   ckpt: str | None = None, seed: int = 0,
                   stale_dtype: str = "bf16",
                   weights=None) -> ServingPipeline:
    """``weights`` (from ``init_weights``) lets several pipelines share
    one set of device weights."""
    cfg = get_config(arch)
    v = default_vit(cfg)
    params, vparams = weights or init_weights(cfg, v, seed)
    if ckpt:
        params, _ = checkpoint.load(ckpt, params)
    return ServingPipeline(
        cfg, v, params, vparams,
        EngineCfg(mode=mode, codec=codec,
                  kv=KVCfg(stale_page_dtype=stale_dtype)))


def build_engine(arch: str, mode: str, codec: CodecCfg,
                 ckpt: str | None = None, seed: int = 0) -> Engine:
    """Legacy single-stream entry point (thin wrapper over the stages)."""
    return Engine.from_pipeline(build_pipeline(arch, mode, codec, ckpt, seed))


def serve(
    arch: str = "internvl3-14b-smoke",
    mode: str = "codecflow",
    *,
    videos: int = 4,
    frames: int = 32,
    gop: int = 4,
    window: int = 16,
    stride: int = 4,
    keep_ratio: float = 0.5,
    streams: int = 1,
    lockstep: bool = False,
    ingest_workers: int = 2,
    stale_dtype: str = "bf16",
    ckpt: str | None = None,
    seed: int = 0,
    weights=None,
    trace_dir: str | None = None,
) -> dict:
    """Serve ``videos`` synthetic streams, ``streams`` at a time, and
    return the run's report (see ``main`` for the flags)."""
    codec = CodecCfg(
        gop=gop, window_frames=window, stride_frames=stride,
        keep_ratio=keep_ratio,
    )
    pipeline = build_pipeline(arch, mode, codec, ckpt, seed=seed,
                              stale_dtype=stale_dtype, weights=weights)
    hw = pipeline.v.image
    clips = list(anomaly_dataset(videos, frames, hw, hw, seed=seed))

    sched = Scheduler(pipeline, SchedulerCfg(
        max_concurrent=max(1, streams),
        pipelined=not lockstep,
        ingest_workers=ingest_workers,
    ))
    traced = (jax.profiler.trace(trace_dir) if trace_dir
              else contextlib.nullcontext())
    t0 = time.time()
    with traced:
        sids = [
            sched.submit(StreamRequest(i, np.asarray(clip), tag=label))
            for i, (clip, label) in enumerate(clips)
        ]
        n_throttled = 0
        for ev in sched.events():
            if isinstance(ev, StreamThrottled):
                n_throttled += 1
            elif isinstance(ev, WindowDone) and ev.window == 0:
                print(f"# stream {ev.stream_id}: first answer "
                      f"{ev.stats.answer}")
    per_session = {sid: sched.session(sid).results for sid in sids}
    wall = time.time() - t0

    preds, truths = [], []
    agg = dict(flops=0.0, t_vit=0.0, t_prefill=0.0, t_decode=0.0,
               t_overhead=0.0, windows=0)
    max_fallbacks, finite = 0, True
    for sid in sids:
        sess = sched.session(sid)
        results = per_session[sid]
        preds.append(video_prediction([r.stats.answer for r in results]))
        truths.append(sess.request.tag)
        for r in results:
            s = r.stats
            agg["flops"] += s.flops_vit + s.flops_prefill + s.flops_decode
            agg["t_vit"] += s.t_vit
            agg["t_prefill"] += s.t_prefill
            agg["t_decode"] += s.t_decode
            agg["t_overhead"] += s.t_overhead
            agg["windows"] += 1
            max_fallbacks = max(max_fallbacks, s.kernel_fallbacks)
            finite &= all(math.isfinite(x) for x in s.logits_yes_no)
    p, r, f1 = precision_recall_f1(preds, truths)
    ttft = sched.ttft_quantiles()
    dev = jax.devices()[0]
    return {
        "arch": arch, "mode": mode, "streams": streams,
        "scheduler": "lockstep" if lockstep else "pipelined",
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "precision": p, "recall": r, "f1": f1,
        "ttft_p50_s": ttft.get("p50", 0.0),
        "ttft_p99_s": ttft.get("p99", 0.0),
        "stage_occupancy": {k: round(v, 4)
                            for k, v in sched.stage_occupancy().items()},
        "streams_throttled": n_throttled,
        "GFLOP_per_window": agg["flops"] / max(agg["windows"], 1) / 1e9,
        "host_s_per_window": (agg["t_vit"] + agg["t_prefill"]
                                 + agg["t_decode"] + agg["t_overhead"])
        / max(agg["windows"], 1),
        "overhead_per_window_s": agg["t_overhead"] / max(agg["windows"], 1),
        "kernel_fallbacks": sched.kernel_fallbacks,
        "max_window_kernel_fallbacks": max_fallbacks,
        "logits_finite": finite,
        "windows_total": agg["windows"],
        "windows_per_s": agg["windows"] / max(wall, 1e-9),
        "wall_s": wall,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internvl3-14b-smoke")
    ap.add_argument("--mode", default="codecflow")
    ap.add_argument("--videos", type=int, default=4)
    ap.add_argument("--frames", type=int, default=32)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--gop", type=int, default=4)
    ap.add_argument("--window", type=int, default=16)
    ap.add_argument("--stride", type=int, default=4)
    ap.add_argument("--keep-ratio", type=float, default=0.5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--streams", type=int, default=1,
                    help="concurrent sessions admitted by the scheduler; "
                         ">1 batches same-phase windows across streams")
    ap.add_argument("--lockstep", action="store_true",
                    help="disable the stage-pipelined async engine (one "
                         "fused group per step, fully synced)")
    ap.add_argument("--ingest-workers", type=int, default=2,
                    help="host threads slicing codec windows while the "
                         "accelerator runs earlier groups")
    ap.add_argument("--stale-dtype", default="bf16",
                    choices=("bf16", "int8"),
                    help="storage dtype for stale (non-refreshed) KV "
                         "pages; int8 demotes them to the cold slab "
                         "(docs/paged_kv.md §Quantized cold pages)")
    ap.add_argument("--trace-dir", default=None,
                    help="record the serve loop with the JAX profiler "
                         "into this directory (host spans and device "
                         "operations on one clock)")
    args = ap.parse_args()
    enable_compile_cache()
    print(json.dumps(serve(**vars(args)), indent=1))


if __name__ == "__main__":
    main()
