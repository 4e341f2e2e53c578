"""Shared benchmark stack: tiny trained VLM + synthetic video corpus.

All paper-figure benchmarks evaluate the SAME trained weights on the
SAME streams across system variants, so differences are attributable to
the serving system, not the model.
"""
from __future__ import annotations

import functools
import os
import time
from typing import Dict

import numpy as np

from repro.configs.base import CodecCfg, ModelCfg, ViTCfg
from repro.data.pipeline import anomaly_dataset
from repro.data.video import motion_level_spec, generate_video
from repro.serving import (
    Engine, EngineCfg, EventProtocolValidator, KVCfg, Scheduler,
    SchedulerCfg, ServingPipeline, StreamRequest, precision_recall_f1,
    video_prediction,
)
from repro.training.anomaly_task import train_tiny_vlm

CODEC = CodecCfg(gop=4, block=16, search_radius=4, window_frames=16,
                 stride_frames=4, keep_ratio=0.5, mv_threshold=0.25)
LM = ModelCfg(name="bench-vlm", family="vlm", n_layers=4, d_model=96,
              n_heads=4, n_kv=2, d_ff=192, vocab=64, tied_embeddings=True)
VIT = ViTCfg(n_layers=2, d_model=96, n_heads=4, d_ff=192, patch=14,
             image=112, group=2)
CKPT = os.path.join(os.path.dirname(__file__), "..", "experiments",
                    "tiny_vlm.npz")


@functools.lru_cache(maxsize=1)
def trained_stack():
    os.makedirs(os.path.dirname(CKPT), exist_ok=True)
    lm_params, vit_params = train_tiny_vlm(
        LM, VIT, CODEC, n_videos=36, n_frames=28, steps=250, batch=16,
        cache_path=CKPT, verbose=True,
    )
    return lm_params, vit_params


@functools.lru_cache(maxsize=4)
def eval_videos(n: int = 6, n_frames: int = 28, seed: int = 100):
    return tuple(
        (frames, label)
        for frames, label in anomaly_dataset(n, n_frames, VIT.image,
                                             VIT.image, seed=seed)
    )


def make_pipeline(mode: str, codec: CodecCfg = CODEC,
                  paged: bool = True, stale_dtype: str = "bf16",
                  pool_streams=None) -> ServingPipeline:
    lm_params, vit_params = trained_stack()
    return ServingPipeline(
        LM, VIT, lm_params, vit_params,
        EngineCfg(mode=mode, codec=codec,
                  kv=KVCfg(paged_kv=paged, stale_page_dtype=stale_dtype,
                           pool_streams=pool_streams)))


def make_engine(mode: str, codec: CodecCfg = CODEC) -> Engine:
    return Engine.from_pipeline(make_pipeline(mode, codec))


def run_mode(mode: str, codec: CodecCfg = CODEC, videos=None,
             concurrent: int = 1, paged: bool = True,
             pipelined: bool = False) -> Dict:
    """Aggregate one system variant over the eval corpus.

    ``concurrent=1`` (default) serves streams sequentially — per-window
    wall-clock timings are directly comparable to the paper's batch=1
    latency figures.  ``concurrent>1`` admits that many sessions and
    fuses same-phase windows into batched stage calls (throughput mode).
    ``paged=False`` forces the legacy concat/split KV staging (the
    paged-vs-concat A/B in bench_overhead).  ``pipelined=True`` runs the
    stage-pipelined async scheduler instead of the lockstep loop — the
    default stays lockstep so per-stage wall-clock shares keep the
    paper-figure serial semantics; the async-vs-lockstep A/B lives in
    ``bench_streams.py``.
    """
    videos = videos if videos is not None else eval_videos()
    pipeline = make_pipeline(mode, codec, paged=paged)
    eng = Engine.from_pipeline(pipeline)
    # warmup: trace the batch=1 jitted paths (fresh-prefill window and
    # selective windows), and the batched paths at the first wave's
    # group size; smaller tail waves may still trace inside the timed
    # region (median latency resists those outliers)
    eng.run_stream(np.asarray(videos[0][0]))
    wave = min(concurrent, len(videos))
    if wave > 1:
        warm = Scheduler(pipeline, SchedulerCfg(max_concurrent=wave,
                                                pipelined=pipelined))
        for i in range(wave):
            warm.submit(StreamRequest(i, np.asarray(videos[0][0])))
        warm.run()
    sched = Scheduler(pipeline, SchedulerCfg(max_concurrent=concurrent,
                                             pipelined=pipelined))
    t0 = time.perf_counter()
    sids = [sched.submit(StreamRequest(i, np.asarray(frames), tag=label))
            for i, (frames, label) in enumerate(videos)]
    # drain through the runtime protocol validator: every bench run
    # (including the bench_streams async-vs-lockstep A/B) also asserts
    # the per-stream event protocol, for free
    validator = EventProtocolValidator()
    for _ in validator.wrap(sched.events()):
        pass
    validator.assert_complete()
    per_session = {sid: sched.session(sid).results for sid in sids}
    wall = time.perf_counter() - t0
    preds, truths = [], []
    agg = dict(flops_vit=0.0, flops_prefill=0.0, flops_decode=0.0,
               t_codec=0.0, t_vit=0.0, t_prefill=0.0, t_decode=0.0,
               t_overhead=0.0,
               tokens=0, tokens_valid=0, patches=0, refreshed=0, windows=0)
    window_answers = []
    lat_samples = []
    for sid in sids:
        results = per_session[sid]
        answers = [res.stats.answer for res in results]
        window_answers.append(answers)
        preds.append(video_prediction(answers))
        truths.append(sched.session(sid).request.tag)
        for res in results:
            r = res.stats
            agg["flops_vit"] += r.flops_vit
            agg["flops_prefill"] += r.flops_prefill
            agg["flops_decode"] += r.flops_decode
            agg["t_codec"] += r.t_codec
            agg["t_vit"] += r.t_vit
            agg["t_prefill"] += r.t_prefill
            agg["t_decode"] += r.t_decode
            agg["t_overhead"] += r.t_overhead
            agg["tokens"] += r.tokens_vis
            agg["tokens_valid"] += r.tokens_valid
            agg["patches"] += r.vit_patches
            agg["refreshed"] += r.tokens_refreshed
            agg["windows"] += 1
            # include selection/staging overhead so mode latencies stay
            # comparable (the monolith counted selection inside t_prefill)
            lat_samples.append(r.t_vit + r.t_prefill + r.t_decode + r.t_overhead)
    p, r, f1 = precision_recall_f1(preds, truths)
    w = max(agg["windows"], 1)
    return {
        "mode": mode,
        "precision": p, "recall": r, "f1": f1,
        "preds": preds, "window_answers": window_answers,
        "flops_total": agg["flops_vit"] + agg["flops_prefill"] + agg["flops_decode"],
        "flops_vit": agg["flops_vit"], "flops_prefill": agg["flops_prefill"],
        "latency_per_window": float(np.median(lat_samples)),
        "t_vit": agg["t_vit"] / w, "t_prefill": agg["t_prefill"] / w,
        "t_decode": agg["t_decode"] / w, "t_codec": agg["t_codec"] / w,
        "t_overhead": agg["t_overhead"] / w,
        "tokens_per_window": agg["tokens_valid"] / w,
        "patches_per_window": agg["patches"] / w,
        "refreshed_per_window": agg["refreshed"] / w,
        "windows": agg["windows"],
        "windows_per_s": agg["windows"] / max(wall, 1e-9),
        "scheduler": "pipelined" if pipelined else "lockstep",
        # time-to-first-token, from the scheduler's own samples
        "ttft_p50": sched.ttft_quantiles().get("p50", 0.0),
        "ttft_p99": sched.ttft_quantiles().get("p99", 0.0),
        "stage_occupancy": sched.stage_occupancy(),
        # steady-state KV memory: deterministic byte counts (paged slab
        # share, or the dense per-stream allocation when paged=False)
        "kv_bytes_per_stream": sched.kv_memory()["bytes_per_stream"],
        "kv_slab_bytes": sched.kv_memory()["slab_bytes"],
    }


def motion_videos(level: str, n: int = 3, n_frames: int = 28, seed: int = 50):
    out = []
    for i in range(n):
        spec = motion_level_spec(level, seed=seed + i, n_frames=n_frames,
                                 height=VIT.image, width=VIT.image,
                                 anomaly=(i % 2 == 0),
                                 anomaly_start=8, anomaly_len=10)
        frames, labels = generate_video(spec)
        out.append((frames, int(labels.any())))
    return out


def csv_row(name: str, us_per_call: float, derived: str) -> str:
    return f"{name},{us_per_call:.1f},{derived}"
