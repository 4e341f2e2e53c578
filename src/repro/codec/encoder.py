"""Software video encoder: GOP structure, motion search, residual coding.

The paper's premise is that codec metadata (MVs, residuals, frame types)
already exists as a byproduct of compression.  This module *is* that
codec for our system: a block-based inter-frame encoder in JAX whose
side outputs are exactly the ``CodecMetadata`` the serving pipeline
consumes.  The motion search is the compute hot spot and runs on the
``mv_sad`` Pallas kernel (TPU) / its jnp oracle (CPU).

Motion compensation without a gather
------------------------------------
The prediction of pixel (y, x) in block (i, j) with vector (dy, dx) is
``ref[clip(y + dy), clip(x + dx)]``.  A per-pixel gather computes that
for any vector, but a TPU runs a 2-D gather one element at a time.  The
search bounds every vector by the static radius r, and clamping a read
displaced by at most r to the frame is exactly reading the reference
edge-padded by r: ``clip(y + dy) = y + dy + r`` in padded coordinates.
So the prediction is built from static slices of the padded frame P:

1. dx: each block row i takes its band of P's rows
   ``[i*block, i*block + block + 2r)`` (shape (Hb, block + 2r, W + 2r));
   for each of the 2r + 1 offsets, the band shifted left by ``dx + r``
   columns replaces a pixel where its block's dx equals that offset.
2. dy: the result, shifted up by ``dy + r`` rows inside the band,
   replaces a pixel where its block's dy equals the offset.

Both stages pick, per block, one static slice, so pixel (y, x) ends as
``P[y + dy + r, x + dx + r]``, the gather's value bit for bit: no
arithmetic touches a pixel, only selects.  The cost is 2r + 1 selects
over the band (1 + 2r/block times the frame) plus 2r + 1 over the
frame, (2r + 1)(2 + 2r/block) per pixel (22.5 at r = 4, block 16),
against (2r + 1)^2 (81) for one select over every displacement at
once; both are streaming passes with no data-dependent address.  On a
TPU v5e at 448 px (``benchmarks/motion_compensate_variants.py``) the
gather takes 2.06 ms per frame at any radius, the two-stage select
0.010 ms at r = 1, 0.020 at r = 4 and 0.076 at r = 16, and the
one-stage select 0.094 at r = 4: no radius up to 16 favours the
gather, so the radius chooses no path.  A vector outside [-r, r]^2
matches no offset, so the radius travels with the vectors: the
``Bitstream`` carries the encoder's as static data, and a bitstream
without one (vectors not known to be bounded) is decoded by the gather.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import CodecCfg
from ..kernels import ops
from .metadata import Bitstream, CodecMetadata, I_FRAME, gop_frame_types


def _gather_compensate(ref_frame: jnp.ndarray, mv: jnp.ndarray, block: int) -> jnp.ndarray:
    """Per-pixel gather with clamped source coordinates (any mv)."""
    H, W = ref_frame.shape
    yy, xx = jnp.meshgrid(jnp.arange(H), jnp.arange(W), indexing="ij")
    dy = jnp.repeat(jnp.repeat(mv[..., 0], block, 0), block, 1)
    dx = jnp.repeat(jnp.repeat(mv[..., 1], block, 0), block, 1)
    src_y = jnp.clip(yy + dy, 0, H - 1)
    src_x = jnp.clip(xx + dx, 0, W - 1)
    return ref_frame[src_y, src_x]


def _shift_compensate(
    ref_frame: jnp.ndarray, mv: jnp.ndarray, block: int, radius: int
) -> jnp.ndarray:
    """Two-stage select over static shifts of the edge-padded reference
    (module docstring); exact for every mv in [-radius, radius]^2."""
    H, W = ref_frame.shape
    r = radius
    hb = H // block
    pad = jnp.pad(ref_frame, r, mode="edge")
    band = jnp.stack([pad[i * block:i * block + block + 2 * r] for i in range(hb)])
    # stage 1, dx: (hb, block + 2r, W) rows of each block row's band
    dx = jnp.repeat(mv[..., 1], block, 1)[:, None, :]
    cols = band[:, :, r:r + W]
    for o in range(-r, r + 1):
        cols = jnp.where(dx == o, band[:, :, r + o:r + o + W], cols)
    # stage 2, dy: the block's own rows, shifted inside the band
    dy = jnp.repeat(mv[..., 0], block, 1)[:, None, :]
    out = cols[:, r:r + block]
    for o in range(-r, r + 1):
        out = jnp.where(dy == o, cols[:, r + o:r + o + block], out)
    return out.reshape(H, W)


def motion_compensate(
    ref_frame: jnp.ndarray, mv: jnp.ndarray, block: int, radius: int | None = None
) -> jnp.ndarray:
    """Build the prediction frame by shifting each block by its MV.

    ref_frame: (H, W); mv: (Hb, Wb, 2) int32 (dy, dx).  Out-of-bounds
    reads clamp to the frame edge (matches the padded search).  With
    ``radius`` (the search radius, static) every mv must lie in
    [-radius, radius]^2 and the prediction is built from static shifts;
    without it, by a per-pixel gather that takes any mv.  Both give the
    same frame bit for bit where both apply.
    """
    if radius is None:
        return _gather_compensate(ref_frame, mv, block)
    return _shift_compensate(ref_frame, mv, block, radius)


def _quantize(x: jnp.ndarray, step: float) -> jnp.ndarray:
    return jnp.round(x / step) * step


@functools.partial(jax.jit, static_argnames=("cfg", "quant_step"))
def encode_stream(
    frames: jnp.ndarray, cfg: CodecCfg, quant_step: float = 4.0
) -> Tuple[Bitstream, CodecMetadata]:
    """Encode a luma stream.

    Args:
      frames: (T, H, W) float32 in [0, 255].
      cfg: codec config (gop, block, search radius).
      quant_step: residual quantizer step (pixel units).

    Returns:
      (Bitstream, CodecMetadata).  The encoder tracks the *reconstructed*
      previous frame as its reference (like a real codec — the decoder
      must be able to follow), so decode(encode(x)) is exact by
      construction.
    """
    T, H, W = frames.shape
    hb, wb = H // cfg.block, W // cfg.block
    ftypes = gop_frame_types(T, cfg.gop)

    def step(prev_recon, inp):
        frame, ftype = inp
        is_i = ftype == I_FRAME

        mv, sad = ops.mv_sad(frame, prev_recon, cfg.block, cfg.search_radius)
        mv = jnp.where(is_i, jnp.zeros_like(mv), mv)
        pred = motion_compensate(prev_recon, mv, cfg.block, cfg.search_radius)
        resid = frame - pred
        resid_q = _quantize(resid, quant_step)
        recon_p = pred + resid_q
        recon_i = _quantize(frame, quant_step / 2.0)

        recon = jnp.where(is_i, recon_i, recon_p)
        iframe_data = jnp.where(is_i, recon_i, jnp.zeros_like(frame))
        resid_out = jnp.where(is_i, jnp.zeros_like(frame), resid_q)
        # per-block mean |residual| (pre-quantization, the true SAD signal)
        blk_resid = jnp.where(
            is_i,
            jnp.zeros((hb, wb), jnp.float32),
            jnp.abs(resid).reshape(hb, cfg.block, wb, cfg.block).mean((1, 3)),
        )
        return recon, (iframe_data, mv, resid_out, blk_resid)

    init = jnp.zeros((H, W), jnp.float32)
    _, (idata, mvs, resids, blk_resids) = jax.lax.scan(
        step, init, (frames.astype(jnp.float32), ftypes)
    )
    bs = Bitstream(ftypes, idata, mvs, resids, cfg.search_radius)
    md = CodecMetadata(ftypes, mvs, blk_resids)
    return bs, md


def estimate_bits(bitstream: Bitstream, quant_step: float = 4.0) -> dict:
    """Empirical-entropy size model of the encoded stream (numpy, offline).

    Real codecs entropy-code quantized residuals/MVs; we lower-bound the
    stream size with the empirical symbol entropy, which is what the
    transmission-reduction benchmark (paper Fig. 11 'Trans') reports.
    """
    out = {}
    ft = np.asarray(bitstream.frame_types)
    i_mask, p_mask = ft == I_FRAME, ft != I_FRAME

    def entropy_bits(sym: np.ndarray) -> float:
        if sym.size == 0:
            return 0.0
        _, counts = np.unique(sym, return_counts=True)
        p = counts / sym.size
        return float(sym.size * -(p * np.log2(p)).sum())

    idata = np.asarray(bitstream.iframe_data)[i_mask]
    resid = np.asarray(bitstream.residual_q)[p_mask]
    mv = np.asarray(bitstream.mv)[p_mask]
    out["iframe_bits"] = entropy_bits(np.round(idata / (quant_step / 2)).astype(np.int32))
    out["residual_bits"] = entropy_bits(np.round(resid / quant_step).astype(np.int32))
    out["mv_bits"] = entropy_bits(mv.reshape(-1))
    out["total_bits"] = out["iframe_bits"] + out["residual_bits"] + out["mv_bits"]
    T, H, W = bitstream.iframe_data.shape
    out["raw_bits"] = float(T * H * W * 8)
    # The all-intra (per-frame JPEG-like) baseline is produced by encoding
    # with gop=1 and calling this function again — see bench_latency.
    out["compression_ratio"] = out["raw_bits"] / max(out["total_bits"], 1.0)
    return out
