"""Chip smoke test: serve InternVL3-14B (one-chip depth cut, published
widths) on one TPU through the normal entry point, and check the answers.

    python3 chip_smoke.py

Drives ``repro.launch.serve.serve`` — Scheduler -> ServingPipeline ->
paged KV -> Pallas kernels — with random weights from a seed over two
seeded synthetic 448-px camera streams (28 frames = 4 windows of 16
frames at stride 4, GOP 4), pipelined scheduler, first in ``codecflow``
mode and then in the ``fullcomp`` baseline.  It fails (non-zero exit, no
result line) when

  * JAX finds no TPU (no CPU fallback);
  * any main-path op took the jnp oracle instead of its kernel, by the
    dispatch counters or a window's ``kernel_fallbacks``;
  * a mode served other than 2 streams x 4 windows;
  * a logit is not finite;
  * the fullcomp window-0 prefill of one stream disagrees with the same
    prefill on the jnp oracles beyond ``REF_TOL``.

The last line of standard output is the JSON result.  Timings printed
here are smoke readings of a cold process (compiles included), not
benchmark metrics.  Runs in one process; the persistent compile cache
goes where ``repro.launch.serve.enable_compile_cache`` puts it.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import jax

ARCH = "internvl3-14b-1chip"
STREAMS, FRAMES, WINDOW, STRIDE, GOP = 2, 28, 16, 4, 4
N_WINDOWS = (FRAMES - WINDOW) // STRIDE + 1
SEED = 0
# ops whose oracle fallback would mean the main path left the chip's
# kernels: codec motion search, packed ViT, KV reuse, paged attention
MAIN_OPS = ("mv_sad", "flash_packed", "rope_shift", "flash_prefill_paged",
            "flash_refresh_paged")
# Kernel vs oracle, relative to the oracle's largest |logit|.  The two
# paths round differently: the kernel keeps q, k and the softmax weights
# in f32, the oracle rounds q and the weights to bf16 before its matmuls,
# and the bf16 residual stream carries each layer's difference through
# 16 layers and the LM head.  On the CPU (interpret mode, same model at
# smoke width) the gap is below 1%; 5% still fails a kernel that drops
# or misplaces attention mass, which moves the logits by O(1).
REF_TOL = 0.05


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


def prefill_gap(arch: str, weights, clip) -> tuple[float, bool]:
    """(max |kernel - oracle| / max |oracle|, all finite) of one
    stream's fullcomp window-0 prefill logits."""
    import numpy as np

    from repro.configs import CodecCfg
    from repro.kernels import ops
    from repro.launch import serve

    codec = CodecCfg(gop=GOP, window_frames=WINDOW, stride_frames=STRIDE)
    pipe = serve.build_pipeline(arch, "fullcomp", codec, weights=weights)
    frames, meta, _ = pipe.frontend.window(pipe.frontend.open(clip), 0)
    enc = pipe.encode_windows(frames[None], [meta], fresh=True)
    got = np.asarray(pipe.backend.fresh(enc.vis, enc.vval, enc.qe).logits)
    with ops.kernel_mode("ref"):
        # a new pipeline: its jits trace (and dispatch) under "ref"
        ref_pipe = serve.build_pipeline(arch, "fullcomp", codec,
                                        weights=weights)
        want = np.asarray(
            ref_pipe.backend.fresh(enc.vis, enc.vval, enc.qe).logits)
    finite = bool(np.isfinite(got).all() and np.isfinite(want).all())
    gap = float(np.abs(got - want).max() / np.abs(want).max())
    return gap, finite


def main() -> None:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX sees {dev.platform} devices")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.configs import get_config
    from repro.data.pipeline import anomaly_dataset
    from repro.kernels import ops
    from repro.launch import serve

    print(f"cache_dir {serve.enable_compile_cache()}")
    print(f"device_kind {dev.device_kind}")
    cfg = get_config(ARCH)
    v = serve.default_vit(cfg)
    t0 = time.perf_counter()
    weights = jax.block_until_ready(serve.init_weights(cfg, v, SEED))
    print(f"init_weights_s {time.perf_counter() - t0:.3f}")

    problems = []
    for mode in ("codecflow", "fullcomp"):
        ops.reset_dispatch_counts()
        rep = serve.serve(
            ARCH, mode, videos=STREAMS, frames=FRAMES, gop=GOP,
            window=WINDOW, stride=STRIDE, streams=STREAMS, seed=SEED,
            weights=weights,
        )
        counts = ops.dispatch_counts()
        print(f"[{mode}] first_window_s {rep['ttft_p99_s']:.3f} "
              f"(cold: codec ingest + compiles)")
        print(f"[{mode}] windows_total {rep['windows_total']} "
              f"windows_per_s {rep['windows_per_s']:.4f} "
              f"(smoke reading, not a metric)")
        print(f"[{mode}] dispatch {json.dumps(counts, sort_keys=True)}")
        print(f"[{mode}] report {json.dumps(rep, sort_keys=True)}")
        for op in MAIN_OPS:
            bad = {k: n for k, n in counts.get(op, {}).items()
                   if k.startswith(("guard:", "backend:"))}
            if bad:
                problems.append(f"{mode}: {op} took the oracle {bad}")
        if rep["max_window_kernel_fallbacks"] > 0:
            problems.append(f"{mode}: a window reports "
                            f"{rep['max_window_kernel_fallbacks']} "
                            "kernel fallbacks")
        if rep["windows_total"] != STREAMS * N_WINDOWS:
            problems.append(f"{mode}: served {rep['windows_total']} windows, "
                            f"want {STREAMS * N_WINDOWS}")
        if not rep["logits_finite"]:
            problems.append(f"{mode}: non-finite yes/no logits")

    clip = anomaly_dataset(1, FRAMES, v.image, v.image, seed=SEED)[0][0]
    gap, finite = prefill_gap(ARCH, weights, clip)
    print(f"fullcomp window-0 prefill: kernel vs oracle max rel gap "
          f"{gap:.3e} (tolerance {REF_TOL})")
    if not finite:
        problems.append("non-finite prefill logits")
    if not gap <= REF_TOL:  # NaN fails too
        problems.append(f"kernel vs oracle gap {gap:.3e} > {REF_TOL}")

    stats = dev.memory_stats() or {}
    print(f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} "
          f"bytes_limit {stats.get('bytes_limit')}")
    if problems:
        fail("; ".join(problems))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
