"""Pallas TPU kernel: full-search block-matching motion estimation.

This is the codec substrate's hot spot — the paper gets motion vectors
"for free" from NVDEC; on TPU we produce them with a VMEM-resident SAD
search (DESIGN.md §3).  One grid program handles one row of macroblocks:
the current-frame block row and the (edge-padded) reference frame stay in
VMEM, and the (2r+1)^2 candidate displacements are an unrolled VPU loop
of shifted absolute-difference reductions.

Layout notes (TPU):
  * the whole padded reference frame is mapped into VMEM once
    (448x448 f32 ~ 0.8 MB << 16 MB VMEM);
  * per-candidate work is (block x W) elementwise, a sublane sum over
    the block rows, and a (1, W) x (W, W/block) matmul against a 0/1
    block selector that sums each block's lanes — Mosaic cannot lay out
    a reshape that splits the lane axis into (W/block, block);
  * outputs are (H/block, 1, W/block) so each program's block is a full
    (1, W/block) tile (a (1, W/block) block of a 2-D output would have a
    second-minor dim that is neither a multiple of 8 nor the full dim).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _mv_sad_kernel(
    cur_ref, prev_ref, mvy_ref, mvx_ref, sad_ref, *, block: int, radius: int, w: int
):
    wb = w // block
    n_cand = 2 * radius + 1
    cur = cur_ref[...]  # (block, W)
    # this block-row's (block+2r)-row band of the padded ref, loaded once
    # from a sublane-aligned start; candidates slice it statically
    row0 = pl.multiple_of(pl.program_id(0) * block, block)
    band = prev_ref[pl.dslice(row0, block + 2 * radius), :]
    # sel[x, j] = 1 iff pixel column x lies in macroblock column j
    col = jax.lax.broadcasted_iota(jnp.int32, (w, wb), 0)
    blk = jax.lax.broadcasted_iota(jnp.int32, (w, wb), 1)
    sel = (col // block == blk).astype(jnp.float32)

    best_sad = jnp.full((1, wb), jnp.inf, jnp.float32)
    best_idx = jnp.zeros((1, wb), jnp.int32)
    for idx in range(n_cand * n_cand):  # unrolled: static candidate count
        dy, dx = idx // n_cand, idx % n_cand
        win = band[dy: dy + block, dx: dx + w]
        diff = jnp.abs(cur - win)
        colsum = diff.sum(axis=0, keepdims=True)              # (1, W)
        sads = jax.lax.dot_general(
            colsum, sel, (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )                                                     # (1, wb)
        take = sads < best_sad
        best_sad = jnp.where(take, sads, best_sad)
        best_idx = jnp.where(take, idx, best_idx)

    mvy_ref[0] = best_idx // n_cand - radius
    mvx_ref[0] = best_idx % n_cand - radius
    sad_ref[0] = best_sad


@functools.partial(jax.jit, static_argnames=("block", "radius", "interpret"))
def mv_sad_pallas(
    cur: jnp.ndarray,
    prev: jnp.ndarray,
    block: int = 16,
    radius: int = 4,
    interpret: bool = False,
):
    """Block-matching motion search.  See ``ref.mv_sad_ref`` for semantics."""
    H, W = cur.shape
    hb, wb = H // block, W // block
    prev_pad = jnp.pad(prev.astype(jnp.float32), radius, mode="edge")

    kernel = functools.partial(
        _mv_sad_kernel, block=block, radius=radius, w=W
    )
    mvy, mvx, sad = pl.pallas_call(
        kernel,
        grid=(hb,),
        in_specs=[
            pl.BlockSpec((block, W), lambda i: (i, 0)),
            # The candidate windows of adjacent block rows overlap by 2r
            # rows, which BlockSpec striding cannot express — so the whole
            # padded reference frame is mapped into VMEM once and the
            # kernel dslices its own (block+2r)-row band.
            pl.BlockSpec(prev_pad.shape, lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, wb), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, wb), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, 1, wb), lambda i: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((hb, 1, wb), jnp.int32),
            jax.ShapeDtypeStruct((hb, 1, wb), jnp.int32),
            jax.ShapeDtypeStruct((hb, 1, wb), jnp.float32),
        ],
        interpret=interpret,
    )(cur.astype(jnp.float32), prev_pad)
    mv = jnp.stack([mvy[:, 0], mvx[:, 0]], axis=-1)
    return mv, sad[:, 0]
