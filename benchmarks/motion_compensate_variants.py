"""Motion compensation on a TPU: the per-pixel gather against static shifts.

    PYTHONPATH=src python3 -m benchmarks.motion_compensate_variants [--out FILE]

Times three formulations of ``codec.encoder.motion_compensate`` on
448-px f32 frames with 16-px blocks, each inside a ``lax.scan`` over
92 frames (one segment of the camera cells): the per-pixel gather with
clamped coordinates (``_gather_compensate``, the path of a bitstream
without a radius), the program's two-stage select over an edge-padded
reference (``_shift_compensate``), and a one-stage select over all
(2r+1)^2 shifted slices (``_one_stage``, kept here only).  Each is
timed at radii 1, 2, 4, 7 and 16; the one-stage select stops at
radius 8.  Then ``encode_stream`` and ``decode_stream`` at
radius 4 are timed with each formulation swapped in, and their
reconstructed frames and motion vectors are compared with the
gather's bit for bit.  Times are milliseconds per frame from the host
clock around back-to-back calls ended by ``block_until_ready``.
Exits 1 on a backend other than TPU.
"""
from __future__ import annotations

import argparse
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.codec import decoder, encoder
from repro.configs.base import CodecCfg

from .bench_kernels import _timeit

H = W = 448
BLOCK = 16
FRAMES = 92
RADII = (1, 2, 4, 7, 16)


def _one_stage(ref_frame, mv, block, radius):
    """Select among all (2r+1)^2 static slices of the padded reference."""
    h, w = ref_frame.shape
    r, n = radius, 2 * radius + 1
    pad = jnp.pad(ref_frame, r, mode="edge")
    idx = (mv[..., 0] + r) * n + (mv[..., 1] + r)
    idx = jnp.repeat(jnp.repeat(idx, block, 0), block, 1)
    out = pad[r:r + h, r:r + w]
    for c in range(n * n):
        oy, ox = divmod(c, n)
        out = jnp.where(idx == c, pad[oy:oy + h, ox:ox + w], out)
    return out


VARIANTS = {
    "gather": lambda f, mv, b, r: encoder._gather_compensate(f, mv, b),
    "two_stage": encoder._shift_compensate,
    "one_stage": _one_stage,
}


def _frames(t: int, seed: int = 0) -> np.ndarray:
    """A textured scene panned a few pixels per frame, so blocks move."""
    rng = np.random.default_rng(seed)
    base = rng.uniform(0, 255, (H + 64, W + 64)).astype(np.float32)
    out = np.empty((t, H, W), np.float32)
    for i in range(t):
        oy, ox = 32 + (i % 7) - 3, 32 + (i % 5) - 2
        out[i] = base[oy:oy + H, ox:ox + W]
    return out


def _scan_ms(fn, refs, mvs, radius) -> float:
    run = jax.jit(lambda f, m: jax.lax.scan(
        lambda c, x: (c, fn(x[0], x[1], BLOCK, radius)), 0, (f, m))[1])
    return _timeit(lambda: run(refs, mvs), n=5) / 1e3 / refs.shape[0]


def _swap(fn):
    def mc(ref_frame, mv, block, radius=None):
        return fn(ref_frame, mv, block, radius)
    encoder.motion_compensate = decoder.motion_compensate = mc
    jax.clear_caches()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    a = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print(f"needs a TPU, found {jax.default_backend()}", file=sys.stderr)
        return 1
    dev = jax.devices()[0]
    res = {"device_kind": dev.device_kind, "frames": FRAMES, "px": H,
           "block": BLOCK, "ms_per_frame": {}, "stream_ms_per_frame": {},
           "bit_equal": {}}
    rng = np.random.default_rng(1)
    refs = jnp.asarray(_frames(FRAMES))
    for r in RADII:
        mvs = jnp.asarray(rng.integers(-r, r + 1, (FRAMES, H // BLOCK, W // BLOCK, 2)),
                          jnp.int32)
        for name, fn in VARIANTS.items():
            if name == "one_stage" and r > 8:
                continue
            ms = _scan_ms(fn, refs, mvs, r)
            res["ms_per_frame"][f"{name}.r{r}"] = ms
            print(f"motion_compensate {name:9s} r={r:2d}: {ms:.4f} ms/frame", flush=True)

    cfg = CodecCfg(gop=4, block=BLOCK, search_radius=4)
    frames = refs
    keep = encoder.motion_compensate
    outs = {}
    for name, fn in VARIANTS.items():
        _swap(fn)
        enc = lambda: encoder.encode_stream(frames, cfg)  # noqa: E731
        bs, md = enc()
        dec = lambda: decoder.decode_stream(bs, BLOCK)  # noqa: E731
        res["stream_ms_per_frame"][f"encode.{name}"] = _timeit(enc, n=5) / 1e3 / FRAMES
        res["stream_ms_per_frame"][f"decode.{name}"] = _timeit(dec, n=5) / 1e3 / FRAMES
        outs[name] = (np.asarray(md.mv), np.asarray(dec()))
        print(name, {k: v for k, v in res["stream_ms_per_frame"].items() if name in k},
              flush=True)
    encoder.motion_compensate = decoder.motion_compensate = keep
    jax.clear_caches()
    for name, (mv, rec) in outs.items():
        res["bit_equal"][name] = bool(
            np.array_equal(mv, outs["gather"][0])
            and np.array_equal(rec.view(np.uint32), outs["gather"][1].view(np.uint32)))
    print(json.dumps(res))
    if a.out:
        with open(a.out, "w") as f:
            json.dump(res, f, indent=1)
    return 0 if all(res["bit_equal"].values()) else 1


if __name__ == "__main__":
    sys.exit(main())
