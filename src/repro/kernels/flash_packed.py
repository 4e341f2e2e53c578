"""Pallas TPU kernel: block-diagonal flash attention for packed ViT rows.

The packed ViT encode (paper §3.3.2, DESIGN.md §3 pruning made
cost-proportional) lays the kept patch groups of MANY P-frames out as
contiguous runs inside shared ``(rows, L_pack)`` buffers.  Attention
must stay strictly *within* each frame's run — a block-diagonal mask
over variable-length segments — while padding slots (segment id ``-1``)
must contribute nothing and produce exact zeros.

This is the ViT-side twin of ``flash_refresh``: the same online-softmax
tile loop and scalar-prefetched visit-list machinery, but

  * the mask is segment-id equality instead of causality + ``kv_valid``
    (ViT attention is bidirectional, so there is no positional band);
  * the visit list is **per row**: every packed row has its own segment
    layout, so ``tile_ids``/``tile_count`` carry a leading row axis and
    are passed as *dynamic* arrays (shape-static, value-dynamic) — one
    compilation serves every packing layout of the same geometry;
  * a kv tile is visited iff it shares at least one live segment with
    the q tile, so cross-frame tiles are never DMA'd and kernel cost is
    proportional to the block-diagonal area, not ``L_pack**2``.

Grid: (rows, H, n_q_tiles, t_max) with the visit list innermost;
(m, l, acc) online-softmax scratch persists across it.  Ragged per-row
visit counts are gated with ``pl.when(it < count)``; fully-masked rows
(bucket padding) produce zeros.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


# ======================================================================
# Static visit list (host-side; values are dynamic kernel inputs)
# ======================================================================
@dataclasses.dataclass(frozen=True)
class PackBlockMap:
    """Per-(row, q-tile) kv-tile visit list for the packed kernel.

    Unlike ``RefreshBlockMap`` the values here are PER PACKING LAYOUT
    (they depend on which frames landed in which row), so they are fed
    to the kernel as dynamic int32 arrays; only the *shapes* — fixed by
    the ``(rows, L_pack)`` bucket and ``t_max`` — key compilations.

    Attributes:
      tq, tk: tile sizes the map was built for.
      tile_ids: (rows, n_q_tiles, t_max) int32 kv-tile ids per (row, q
        tile), right-padded by repeating the last live id (id 0 when a
        row is empty).
      tile_count: (rows, n_q_tiles) int32 live entries per visit list.
    """

    tq: int
    tk: int
    tile_ids: np.ndarray
    tile_count: np.ndarray

    @property
    def n_rows(self) -> int:
        return self.tile_ids.shape[0]

    @property
    def n_q_tiles(self) -> int:
        return self.tile_ids.shape[1]

    @property
    def t_max(self) -> int:
        return self.tile_ids.shape[2]

    @property
    def visited(self) -> int:
        return int(self.tile_count.sum())

    @property
    def density(self) -> float:
        """Visited fraction of the dense (row, q-tile, kv-tile) grid."""
        total = self.tile_count.size * max(
            1, -(-self.tile_ids.shape[1] * self.tq // self.tk)
        )
        return self.visited / max(total, 1)


def build_pack_map(
    seg_id,
    *,
    tq: int = 128,
    tk: int = 128,
    t_max: int | None = None,
) -> PackBlockMap:
    """Visit list from a packed segment-id layout.

    ``seg_id``: (rows, L_pack) int32, ``-1`` for padding slots.  A kv
    tile is visited iff it shares a live segment id with the q tile —
    exact for contiguous segments (and still correct, merely less tight,
    for any layout).  ``t_max`` bounds the innermost grid axis; default
    is the next power of two above the max live count (fewer distinct
    shapes -> fewer recompiles), clamped to the kv tile count.
    """
    seg = np.asarray(seg_id, np.int32)
    rows, L = seg.shape
    assert L % tq == 0 and L % tk == 0, (L, tq, tk)
    nq, nk = L // tq, L // tk
    active = np.zeros((rows, nq, nk), bool)
    qt = seg.reshape(rows, nq, tq)
    kt = seg.reshape(rows, nk, tk)
    for r in range(rows):
        ksets = [set(kt[r, j][kt[r, j] >= 0].tolist()) for j in range(nk)]
        for i in range(nq):
            live = set(qt[r, i][qt[r, i] >= 0].tolist())
            if not live:
                continue
            for j in range(nk):
                if live & ksets[j]:
                    active[r, i, j] = True

    counts = active.sum(axis=2).astype(np.int32)
    need = max(1, int(counts.max(initial=0)))
    if t_max is None:
        t_max = 1 << (need - 1).bit_length()
    t_max = min(max(t_max, need), nk) if nk else 1
    tile_ids = np.zeros((rows, nq, t_max), np.int32)
    for r in range(rows):
        for i in range(nq):
            ids = np.nonzero(active[r, i])[0].astype(np.int32)
            if ids.size:
                tile_ids[r, i, : ids.size] = ids[:t_max]
                tile_ids[r, i, ids.size:] = ids[-1]
    return PackBlockMap(tq=tq, tk=tk, tile_ids=tile_ids, tile_count=counts)


def dense_pack_map(
    seg_id, *, tq: int = 128, tk: int = 128
) -> PackBlockMap:
    """Every kv tile visited for every (row, q tile) — the unskipped
    twin used by the block-skipping property test and A/B benchmarks."""
    seg = np.asarray(seg_id, np.int32)
    rows, L = seg.shape
    nq, nk = L // tq, L // tk
    ids = np.broadcast_to(
        np.arange(nk, dtype=np.int32), (rows, nq, nk)
    ).copy()
    return PackBlockMap(
        tq=tq, tk=tk, tile_ids=ids,
        tile_count=np.full((rows, nq), nk, np.int32),
    )


# ======================================================================
# Kernel
# ======================================================================
def _packed_kernel(
    ids_ref, cnt_ref,                        # scalar-prefetch (SMEM)
    q_ref, qseg_ref, k_ref, v_ref, kseg_ref,  # VMEM tiles
    o_ref, m_ref, l_ref, acc_ref,
    *, t_max: int, scale: float,
):
    ir = pl.program_id(0)
    iq = pl.program_id(2)
    it = pl.program_id(3)

    @pl.when(it == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(it < cnt_ref[ir, iq])
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale       # (Tq, D)
        k = k_ref[0, 0].astype(jnp.float32)               # (Tk, D)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                                  # (Tq, Tk)
        qs = qseg_ref[0, 0]                                # (Tq, 1)
        ks = kseg_ref[0, 0]                                # (1, Tk)
        mask = (qs == ks) & (qs >= 0)
        logits = jnp.where(mask, logits, NEG_INF)

        m_prev = m_ref[...]                                # (Tq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        # multiply by the mask, not just NEG_INF-fill: for an all-masked
        # tile m_new stays NEG_INF and exp(logits - m_new) would be 1.
        p = jnp.where(mask, jnp.exp(logits - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        v = v_ref[0, 0].astype(jnp.float32)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

    @pl.when(it == t_max - 1)
    def _finish():
        # fully-masked rows (bucket padding) have l == 0: exact zeros
        o_ref[0, 0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tq", "tk", "interpret"))
def flash_packed_pallas(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    seg_id: jnp.ndarray,
    tile_ids: jnp.ndarray,
    tile_count: jnp.ndarray,
    *,
    tq: int = 128,
    tk: int = 128,
    interpret: bool = False,
):
    """Block-diagonal (segment-masked) GQA attention over packed rows.

    Args:
      q: (R, L, H, D) packed queries; L % tq == 0.
      k, v: (R, L, Hkv, D); L % tk == 0.
      seg_id: (R, L) int32 segment id per slot, -1 for padding.
      tile_ids / tile_count: the ``PackBlockMap`` visit list (dynamic
        values, static shapes).

    Returns (R, L, H, D); padding slots are exact zeros.
    """
    R, L, H, D = q.shape
    Hkv = k.shape[2]
    g = H // Hkv
    assert L % tq == 0 and L % tk == 0, (L, tq, tk)
    n_q_tiles = L // tq
    t_max = tile_ids.shape[2]
    assert tile_ids.shape[:2] == (R, n_q_tiles), (tile_ids.shape, R, n_q_tiles)
    scale = D ** -0.5

    qt = q.transpose(0, 2, 1, 3)                       # (R, H, L, D)
    kt = k.transpose(0, 2, 1, 3)                       # (R, Hkv, L, D)
    vt = v.transpose(0, 2, 1, 3)
    seg = seg_id.astype(jnp.int32)
    # query segments as per-tile columns, key segments as per-tile rows:
    # every block's two minor dims are then full (tq, 1) / (1, tk) tiles
    qseg = seg.reshape(R, n_q_tiles, tq, 1)
    kseg = seg.reshape(R, L // tk, 1, tk)

    kernel = functools.partial(_packed_kernel, t_max=t_max, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(R, H, n_q_tiles, t_max),
        in_specs=[
            pl.BlockSpec((1, 1, tq, D), lambda r, h, iq, it, ids, cnt: (r, h, iq, 0)),
            pl.BlockSpec(
                (1, 1, tq, 1), lambda r, h, iq, it, ids, cnt: (r, iq, 0, 0)
            ),
            pl.BlockSpec(
                (1, 1, tk, D),
                lambda r, h, iq, it, ids, cnt: (r, h // g, ids[r, iq, it], 0),
            ),
            pl.BlockSpec(
                (1, 1, tk, D),
                lambda r, h, iq, it, ids, cnt: (r, h // g, ids[r, iq, it], 0),
            ),
            pl.BlockSpec(
                (1, 1, 1, tk),
                lambda r, h, iq, it, ids, cnt: (r, ids[r, iq, it], 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, tq, D), lambda r, h, iq, it, ids, cnt: (r, h, iq, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((tq, 1), jnp.float32),   # running max  m
            pltpu.VMEM((tq, 1), jnp.float32),   # running norm l
            pltpu.VMEM((tq, D), jnp.float32),   # accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((R, H, L, D), q.dtype),
        interpret=interpret,
    )(tile_ids.astype(jnp.int32), tile_count.astype(jnp.int32),
      qt, qseg, kt, vt, kseg)
    return out.transpose(0, 2, 1, 3)
