"""Pallas TPU kernel: block-sparse masked flash attention for KVC refresh.

CodecFlow's selective refresh (paper §3.4.1) recomputes a *gathered* set
of query tokens — I-frame anchors at non-contiguous positions plus the
new-stride + query tail — against the reused KV cache.  Unlike
``flash_prefill`` the mask here is not a positional band: query
positions are arbitrary (they come from ``WindowLayout``'s refresh
index set) and cache validity is a dynamic per-token ``kv_valid`` mask
(pruned P-frame slots are holes).

Sparsity structure: the refresh set is tiny relative to the window
(anchors + tail), and most (q-tile, kv-tile) pairs are fully out of
causal range or fully invalid.  A *static block map* — computed once
per ``WindowLayout`` by ``build_block_map`` — lists, for every q tile,
only the kv tiles that can contribute.  The kernel's key-axis grid runs
over this list (scalar-prefetched tile ids select the DMA'd kv tile),
so cost is proportional to live cache content instead of
O(n_refresh x total_len) dense work.

Grid: the sparse key axis is innermost; (m, l, acc) online-softmax
scratch persists across it.  Ragged per-tile counts are handled with
``pl.when(it < count)``; fully-masked query rows (block-map padding,
all-invalid caches) produce zeros.

* Paged kernel (``flash_refresh_paged_pallas``, single precision):
  grid (B, Hkv, n_q_tiles, t_max).  One step takes all g = H // Hkv
  query heads of one kv head: a (1, g, tq, D) q block of the
  (B, H, Sq, D) transpose, used as one (g*tq, D) MXU operand against
  the page DMA'd once for the group, and a (1, g, tq, D) output block;
  the scratch has g*tq rows.  The mask is computed once per step as
  (tq, tk) and shared by the g heads.  The ``group-vmem`` contract rule
  (``paged_group_fits_vmem``) routes a group too large for VMEM to the
  oracle.
* Unpaged kernel (``flash_refresh_pallas``) and the paged int8 twin
  (``_refresh_paged_quant_kernel``): grid (B, H, n_q_tiles, t_max), one
  query head per step (kv head h // g).

Every kernel here casts its operands to f32 in the kernel; the
statistics and the accumulator are f32.
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
# Static block map
# ======================================================================
@dataclasses.dataclass(frozen=True)
class RefreshBlockMap:
    """Per-(q-tile, kv-tile) visit list for the refresh kernel.

    Built once per (query positions, kv length, tile sizes) — for the
    serving path that means once per ``WindowLayout`` — and reused for
    every window and every layer.

    Attributes:
      tq, tk: tile sizes the map was built for.
      n_q: unpadded query count (callers slice kernel output to this).
      kv_len: key/value sequence length the map covers.
      q_pos: (n_q_tiles * tq,) int32 query token positions, padded with
        -1 (padding rows are masked by causality: no key pos <= -1).
      tile_ids: (n_q_tiles, t_max) int32 kv-tile indices to visit per q
        tile, right-padded by repeating the last live id.
      tile_count: (n_q_tiles,) int32 number of live entries per row.
      causal, window: the positional-mask configuration the map was
        built for — dispatch refuses a map built for a different mask.
    """

    tq: int
    tk: int
    n_q: int
    kv_len: int
    q_pos: np.ndarray
    tile_ids: np.ndarray
    tile_count: np.ndarray
    causal: bool = True
    window: int | None = None

    @property
    def n_q_tiles(self) -> int:
        return self.tile_ids.shape[0]

    @property
    def t_max(self) -> int:
        return self.tile_ids.shape[1]

    @property
    def n_kv_tiles(self) -> int:
        return -(-self.kv_len // self.tk)

    @property
    def density(self) -> float:
        """Visited fraction of the dense (q-tile, kv-tile) grid."""
        total = self.n_q_tiles * self.n_kv_tiles
        return float(self.tile_count.sum()) / max(total, 1)


def build_block_map(
    q_pos,
    kv_len: int,
    *,
    tq: int = 128,
    tk: int = 128,
    causal: bool = True,
    window: int | None = None,
) -> RefreshBlockMap:
    """Compute the static (q-tile -> kv-tile) visit list.

    A kv tile is visited iff some (q, k) pair in the tile pair can pass
    the positional mask — conservative per-tile bounds (qmin/qmax vs
    tile extent), so the map may over-include but never skips a live
    pair; in-kernel element masking handles the rest.  The dynamic
    ``kv_valid`` mask is NOT consulted here: it is batch-dependent and
    applied per-element inside the kernel.
    """
    q_pos = np.asarray(q_pos, np.int32).reshape(-1)
    n_q = q_pos.shape[0]
    assert n_q > 0 and kv_len > 0, (n_q, kv_len)
    pad = (-n_q) % tq
    qp = np.concatenate([q_pos, np.full((pad,), -1, np.int32)])
    n_q_tiles = qp.shape[0] // tq
    n_kv_tiles = -(-kv_len // tk)
    k_lo = np.arange(n_kv_tiles, dtype=np.int64) * tk
    k_hi = np.minimum(k_lo + tk, kv_len) - 1

    active = np.zeros((n_q_tiles, n_kv_tiles), bool)
    qt = qp.reshape(n_q_tiles, tq)
    for i in range(n_q_tiles):
        live = qt[i][qt[i] >= 0]
        if live.size == 0:
            continue
        row = k_lo < kv_len
        if causal:
            row &= k_lo <= int(live.max())
        if window is not None:
            row &= k_hi > int(live.min()) - window
        active[i] = row

    t_max = max(1, int(active.sum(axis=1).max(initial=0)))
    tile_ids = np.zeros((n_q_tiles, t_max), np.int32)
    tile_count = active.sum(axis=1).astype(np.int32)
    for i in range(n_q_tiles):
        ids = np.nonzero(active[i])[0].astype(np.int32)
        if ids.size:
            tile_ids[i, : ids.size] = ids
            tile_ids[i, ids.size:] = ids[-1]
    return RefreshBlockMap(
        tq=tq, tk=tk, n_q=n_q, kv_len=kv_len,
        q_pos=qp, tile_ids=tile_ids, tile_count=tile_count,
        causal=causal, window=window,
    )


@functools.lru_cache(maxsize=256)
def span_block_map(
    start: int,
    length: int,
    kv_len: int,
    *,
    causal: bool = True,
    window: int | None = None,
) -> RefreshBlockMap:
    """Visit list for the contiguous query positions ``[start, start +
    length)``: a decode step, or a chunk written at a static cache
    offset.  Cached, since serving decodes at the same layout-static
    positions every window."""
    return build_block_map(
        np.arange(start, start + length, dtype=np.int32), kv_len,
        causal=causal, window=window,
    )


def dense_block_map(
    q_pos,
    kv_len: int,
    *,
    tq: int = 128,
    tk: int = 128,
    causal: bool = True,
    window: int | None = None,
) -> RefreshBlockMap:
    """Every kv tile visited for every q tile — the unskipped twin used
    by the block-skipping property test and A/B benchmarks."""
    q_pos = np.asarray(q_pos, np.int32).reshape(-1)
    pad = (-q_pos.shape[0]) % tq
    qp = np.concatenate([q_pos, np.full((pad,), -1, np.int32)])
    n_q_tiles = qp.shape[0] // tq
    n_kv_tiles = -(-kv_len // tk)
    ids = np.broadcast_to(
        np.arange(n_kv_tiles, dtype=np.int32), (n_q_tiles, n_kv_tiles)
    ).copy()
    return RefreshBlockMap(
        tq=tq, tk=tk, n_q=q_pos.shape[0], kv_len=kv_len, q_pos=qp,
        tile_ids=ids,
        tile_count=np.full((n_q_tiles,), n_kv_tiles, np.int32),
        causal=causal, window=window,
    )


# ======================================================================
# Kernel
# ======================================================================
def _refresh_kernel(
    ids_ref, cnt_ref,                       # scalar-prefetch (SMEM)
    q_ref, qpos_ref, k_ref, v_ref, kvm_ref,  # VMEM tiles
    o_ref, m_ref, l_ref, acc_ref,
    *, tk: int, t_max: int, scale: float, causal: bool, window: int | None,
):
    iq = pl.program_id(2)
    it = pl.program_id(3)

    @pl.when(it == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(it < cnt_ref[iq])
    def _compute():
        kid = ids_ref[iq, it]
        q = q_ref[0, 0].astype(jnp.float32) * scale     # (Tq, D)
        k = k_ref[0, 0].astype(jnp.float32)             # (Tk, D)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )                                                # (Tq, Tk)
        qp = qpos_ref[0]                                 # (Tq, 1)
        kp = kid * tk + jax.lax.iota(jnp.int32, tk)[None, :]
        mask = kvm_ref[0, 0] != 0                        # (1, Tk) dynamic
        if causal:
            mask &= kp <= qp
        if window is not None:
            mask &= kp > qp - window
        logits = jnp.where(mask, logits, NEG_INF)

        m_prev = m_ref[...]                              # (Tq, 1)
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
        # fully-masked rows have l == 0 and output exact zeros
        o_ref[0, 0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "tq", "tk", "interpret"),
)
def flash_refresh_pallas(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_pos: jnp.ndarray,
    kv_valid: jnp.ndarray,
    tile_ids: jnp.ndarray,
    tile_count: jnp.ndarray,
    *,
    causal: bool = True,
    window: int | None = None,
    tq: int = 128,
    tk: int = 128,
    interpret: bool = False,
):
    """Block-sparse masked GQA attention over gathered query positions.

    Args:
      q: (B, Sq, H, D) gathered refresh queries, Sq % tq == 0 (callers
        pad; padding rows must carry q_pos == -1).
      k, v: (B, Sk, Hkv, D) full KV cache, Sk % tk == 0.
      q_pos: (Sq,) int32 token position of each query row (layout-static,
        shared across the batch), -1 for padding rows.
      kv_valid: (B, Sk) bool/int per-token cache validity.
      tile_ids / tile_count: the ``RefreshBlockMap`` visit list.

    Returns (B, Sq, H, D); fully-masked query rows are exact zeros.
    """
    B, Sq, H, D = q.shape
    _, Sk, Hkv, _ = k.shape
    g = H // Hkv
    assert Sq % tq == 0 and Sk % tk == 0, (Sq, tq, Sk, tk)
    n_q_tiles = Sq // tq
    t_max = tile_ids.shape[1]
    assert tile_ids.shape[0] == n_q_tiles, (tile_ids.shape, n_q_tiles)
    scale = D ** -0.5

    qt = q.transpose(0, 2, 1, 3)                      # (B, H, Sq, D)
    kt = k.transpose(0, 2, 1, 3)                      # (B, Hkv, Sk, D)
    vt = v.transpose(0, 2, 1, 3)
    # positions as per-tile columns and validity as per-tile rows: every
    # block's two minor dims are then full (tq, 1) / (1, tk) tiles
    qp2 = q_pos.astype(jnp.int32).reshape(n_q_tiles, tq, 1)
    kvm = kv_valid.astype(jnp.int32).reshape(B, Sk // tk, 1, tk)

    kernel = functools.partial(
        _refresh_kernel, tk=tk, t_max=t_max, scale=scale,
        causal=causal, window=window,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, H, n_q_tiles, t_max),
        in_specs=[
            pl.BlockSpec((1, 1, tq, D), lambda b, h, iq, it, ids, cnt: (b, h, iq, 0)),
            pl.BlockSpec((1, tq, 1), lambda b, h, iq, it, ids, cnt: (iq, 0, 0)),
            pl.BlockSpec(
                (1, 1, tk, D),
                lambda b, h, iq, it, ids, cnt: (b, h // g, ids[iq, it], 0),
            ),
            pl.BlockSpec(
                (1, 1, tk, D),
                lambda b, h, iq, it, ids, cnt: (b, h // g, ids[iq, it], 0),
            ),
            pl.BlockSpec(
                (1, 1, 1, tk),
                lambda b, h, iq, it, ids, cnt: (b, ids[iq, it], 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, tq, D), lambda b, h, iq, it, ids, cnt: (b, h, iq, 0)
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
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        interpret=interpret,
    )(tile_ids.astype(jnp.int32), tile_count.astype(jnp.int32),
      qt, qp2, kt, vt, kvm)
    return out.transpose(0, 2, 1, 3)


# ======================================================================
# Paged kernel (visit list -> page table -> kv tile)
# ======================================================================
def _refresh_paged_kernel(
    ids_ref, cnt_ref, pt_ref,               # scalar-prefetch (SMEM)
    q_ref, qpos_ref, k_ref, v_ref, kvm_ref,  # VMEM tiles
    o_ref, m_ref, l_ref, acc_ref,
    *, tk: int, t_max: int, scale: float, causal: bool, window: int | None,
):
    """One grid step: all ``g`` query heads of one kv head against one
    slab page.

    ``q_ref`` is the (1, g, tq, D) block of those heads, taken as one
    (g*tq, D) MXU operand, so the page DMA'd for this step is read once
    for the whole group.  Operands are cast to f32 in the kernel, as in
    ``_refresh_kernel``.  ``pt_ref`` is consumed by the BlockSpec index
    maps (visit list gives a *logical* tile id, the page table turns it
    into a physical page); the in-kernel mask stays logical, computed
    once as (tq, tk) and shared by the g heads."""
    del pt_ref  # only used in the index maps
    iq = pl.program_id(2)
    it = pl.program_id(3)
    _, g, tq, d = q_ref.shape

    @pl.when(it == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(it < cnt_ref[iq])
    def _compute():
        kid = ids_ref[iq, it]
        q = q_ref[0].reshape(g * tq, d).astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)                # (Tk, D) slab page
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ).reshape(g, tq, tk)
        qp = qpos_ref[0]                                # (Tq, 1)
        kp = kid * tk + jax.lax.iota(jnp.int32, tk)[None, :]
        mask = kvm_ref[0, 0] != 0                       # (1, Tk)
        if causal:
            mask &= kp <= qp
        if window is not None:
            mask &= kp > qp - window
        logits = jnp.where(mask, logits, NEG_INF)

        m_prev = m_ref[...]                             # (g, Tq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        # multiply by the mask, not just NEG_INF-fill: for an all-masked
        # tile m_new stays NEG_INF and exp(logits - m_new) would be 1.
        p = jnp.where(mask, jnp.exp(logits - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        v = v_ref[0].astype(jnp.float32)
        pv = jax.lax.dot_general(
            p.reshape(g * tq, tk), v,
            (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        ).reshape(g, tq, d)
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

    @pl.when(it == t_max - 1)
    def _finish():
        # fully-masked rows have l == 0 and output exact zeros
        o_ref[0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


def _refresh_paged_quant_kernel(
    ids_ref, cnt_ref, pt_ref, ks_ref, vs_ref,  # scalar-prefetch (SMEM)
    q_ref, qpos_ref, kh_ref, kc_ref, vh_ref, vc_ref, kvm_ref,  # VMEM tiles
    o_ref, m_ref, l_ref, acc_ref,
    *, tk: int, t_max: int, scale: float, causal: bool, window: int | None,
    n_hot: int, n_cold: int, g: int,
):
    """Two-precision twin of ``_refresh_paged_kernel``, on the per-head
    grid (one query head per step).

    The page table carries the precision bit: entry < n_hot is a hot
    (float) page, entry >= n_hot is cold page ``entry - n_hot`` in the
    int8 slab.  Both candidate tiles are DMA'd per grid step (clamped
    index maps keep the dead one in-bounds); the kernel selects one and
    dequantizes the cold tile in-register — ``int8 * scale`` rounded
    through the hot storage dtype, so the fused path matches the
    gather-dequant oracle bitwise — before the f32 QK^T.  ``ks/vs`` are
    per-(cold-page, kv-head) f32 scales prefetched to SMEM.
    """
    b = pl.program_id(0)
    kvh = pl.program_id(1) // g
    iq = pl.program_id(2)
    it = pl.program_id(3)

    @pl.when(it == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(it < cnt_ref[iq])
    def _compute():
        kid = ids_ref[iq, it]
        entry = pt_ref[b, kid]
        is_cold = entry >= n_hot
        ci = jnp.clip(entry - n_hot, 0, n_cold - 1)
        q = q_ref[0, 0].astype(jnp.float32) * scale     # (Tq, D)
        kh = kh_ref[0]                                  # (Tk, D) hot page
        kc = kc_ref[0]                                  # (Tk, D) int8 page
        k_deq = (kc.astype(jnp.float32) * ks_ref[ci, kvh]).astype(kh.dtype)
        k = jnp.where(is_cold, k_deq, kh).astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        qp = qpos_ref[0]
        kp = kid * tk + jax.lax.iota(jnp.int32, tk)[None, :]
        mask = kvm_ref[0, 0] != 0
        if causal:
            mask &= kp <= qp
        if window is not None:
            mask &= kp > qp - window
        logits = jnp.where(mask, logits, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(logits, axis=-1, keepdims=True))
        p = jnp.where(mask, jnp.exp(logits - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * corr + p.sum(axis=-1, keepdims=True)
        vh = vh_ref[0]
        vc = vc_ref[0]
        v_deq = (vc.astype(jnp.float32) * vs_ref[ci, kvh]).astype(vh.dtype)
        v = jnp.where(is_cold, v_deq, vh).astype(jnp.float32)
        pv = jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = m_new

    @pl.when(it == t_max - 1)
    def _finish():
        o_ref[0, 0] = (
            acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        ).astype(o_ref.dtype)


# The TPU compiler's default scoped VMEM limit (v5e): what one grid
# step's blocks, scratch and temporaries must fit in.
SCOPED_VMEM_BYTES = 16 * 2**20


def paged_group_fits_vmem(g: int, d: int, q_dtype, kv_dtype,
                          tq: int = 128, tk: int = 128) -> bool:
    """Whether one step of ``_refresh_paged_kernel`` fits the scoped VMEM.

    The step's working set, as ``flash_refresh_paged_pallas`` lays it
    out: the (g*tq)-row q and output blocks, double-buffered; two
    double-buffered kv pages; the lane-padded f32 running max and norm
    and the f32 accumulator; the f32 copy of q and three (g*tq, tk) f32
    tiles (logits, probabilities, their masked copy).  Checked against
    compiles for a described v5e: at D 128 it admits bf16 g <= 28 (29
    fits, 30 runs out) and f32 g <= 22 (24 fits, 25 runs out); at D 256
    bf16 g <= 19 (24 fits, 28 runs out).  It errs on the side of the
    oracle.
    """
    rows = g * tq
    qo = 2 * jnp.dtype(q_dtype).itemsize
    blocks = 2 * rows * d * qo + 4 * tk * d * jnp.dtype(kv_dtype).itemsize
    scratch = rows * (2 * 128 * 4 + d * 4)
    temporaries = rows * (d * 4 + 3 * tk * 4)
    return blocks + scratch + temporaries <= SCOPED_VMEM_BYTES


@functools.partial(
    jax.jit,
    static_argnames=("page", "causal", "window", "tq", "tk", "interpret"),
)
def flash_refresh_paged_pallas(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    q_pos: jnp.ndarray,
    kv_valid: jnp.ndarray,
    page_table: jnp.ndarray,
    tile_ids: jnp.ndarray,
    tile_count: jnp.ndarray,
    *,
    page: int = 128,
    causal: bool = True,
    window: int | None = None,
    tq: int = 128,
    tk: int = 128,
    interpret: bool = False,
    cold=None,
):
    """Paged ``flash_refresh_pallas``: KV lives in one shared slab.

    Args:
      q: (B, Sq, H, D) gathered refresh queries, Sq % tq == 0.
      k, v: (P_phys, Hkv, D) the pooled slab for this layer — batchless;
        P_phys % page == 0.
      q_pos: (Sq,) int32 logical query positions, -1 for padding rows.
      kv_valid: (B, S_logical) per-stream *logical* validity where
        S_logical = page_table.shape[1] * page.
      page_table: (B, n_pages) int32 per-stream page table; entry ``p``
        maps logical tile ``p`` to slab rows [pt*page, (pt+1)*page).
      tile_ids / tile_count: logical visit list (``RefreshBlockMap``).
      cold: optional ``(k8, v8, k_scale, v_scale)`` int8 cold-page group:
        (Pc_phys, Hkv, D) int8 slabs + (n_cold, Hkv) f32 scales.  When
        present, page-table entries >= n_hot select dequantized cold
        tiles (``_refresh_paged_quant_kernel``); when None this function
        traces *exactly* the single-precision kernel — the bf16 control
        stays bitwise identical.

    Requires tk == page so one visit-list entry is one slab page (the
    "page-tile" eligibility rule).  Without ``cold`` one grid step takes
    the g = H // Hkv query heads of a kv head together (module
    docstring, "Grid").
    Returns (B, Sq, H, D).
    """
    B, Sq, H, D = q.shape
    P_phys, Hkv, _ = k.shape
    g = H // Hkv
    assert tk == page, (tk, page)
    assert Sq % tq == 0 and P_phys % page == 0, (Sq, tq, P_phys, page)
    n_pages = page_table.shape[1]
    Sk = n_pages * page
    assert kv_valid.shape == (B, Sk), (kv_valid.shape, B, Sk)
    n_q_tiles = Sq // tq
    t_max = tile_ids.shape[1]
    assert tile_ids.shape[0] == n_q_tiles, (tile_ids.shape, n_q_tiles)
    scale = D ** -0.5

    kt = k.transpose(1, 0, 2)                         # (Hkv, P_phys, D)
    vt = v.transpose(1, 0, 2)
    qp2 = q_pos.astype(jnp.int32).reshape(n_q_tiles, tq, 1)
    kvm = kv_valid.astype(jnp.int32).reshape(B, n_pages, 1, tk)

    if cold is not None:
        qt = q.transpose(0, 2, 1, 3)                  # (B, H, Sq, D)
        k8, v8, k_scale, v_scale = cold
        n_hot = P_phys // page
        Pc_phys = k8.shape[0]
        assert Pc_phys % page == 0, (Pc_phys, page)
        n_cold = Pc_phys // page
        k8t = k8.transpose(1, 0, 2)                   # (Hkv, Pc_phys, D)
        v8t = v8.transpose(1, 0, 2)

        def _hot_map(b, h, iq, it, ids, cnt, pt, ks, vs):
            return (h // g, jnp.minimum(pt[b, ids[iq, it]], n_hot - 1), 0)

        def _cold_map(b, h, iq, it, ids, cnt, pt, ks, vs):
            return (h // g,
                    jnp.clip(pt[b, ids[iq, it]] - n_hot, 0, n_cold - 1), 0)

        kernel = functools.partial(
            _refresh_paged_quant_kernel, tk=tk, t_max=t_max, scale=scale,
            causal=causal, window=window, n_hot=n_hot, n_cold=n_cold, g=g,
        )
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(B, H, n_q_tiles, t_max),
            in_specs=[
                pl.BlockSpec(
                    (1, 1, tq, D),
                    lambda b, h, iq, it, ids, cnt, pt, ks, vs: (b, h, iq, 0),
                ),
                pl.BlockSpec(
                    (1, tq, 1),
                    lambda b, h, iq, it, ids, cnt, pt, ks, vs: (iq, 0, 0),
                ),
                pl.BlockSpec((1, tk, D), _hot_map),
                pl.BlockSpec((1, tk, D), _cold_map),
                pl.BlockSpec((1, tk, D), _hot_map),
                pl.BlockSpec((1, tk, D), _cold_map),
                pl.BlockSpec(
                    (1, 1, 1, tk),
                    lambda b, h, iq, it, ids, cnt, pt, ks, vs:
                        (b, ids[iq, it], 0, 0),
                ),
            ],
            out_specs=pl.BlockSpec(
                (1, 1, tq, D),
                lambda b, h, iq, it, ids, cnt, pt, ks, vs: (b, h, iq, 0),
            ),
            scratch_shapes=[
                pltpu.VMEM((tq, 1), jnp.float32),
                pltpu.VMEM((tq, 1), jnp.float32),
                pltpu.VMEM((tq, D), jnp.float32),
            ],
        )
        out = pl.pallas_call(
            kernel,
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
            interpret=interpret,
        )(tile_ids.astype(jnp.int32), tile_count.astype(jnp.int32),
          page_table.astype(jnp.int32),
          k_scale.astype(jnp.float32), v_scale.astype(jnp.float32),
          qt, qp2, kt, k8t, vt, v8t, kvm)
        return out.transpose(0, 2, 1, 3)

    qt = q.transpose(0, 2, 1, 3)                      # (B, H, Sq, D)
    kernel = functools.partial(
        _refresh_paged_kernel, tk=tk, t_max=t_max, scale=scale,
        causal=causal, window=window,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(B, Hkv, n_q_tiles, t_max),
        in_specs=[
            pl.BlockSpec(
                (1, g, tq, D), lambda b, h, iq, it, ids, cnt, pt: (b, h, iq, 0)
            ),
            pl.BlockSpec(
                (1, tq, 1), lambda b, h, iq, it, ids, cnt, pt: (iq, 0, 0)
            ),
            # visit list -> page table -> physical kv tile
            pl.BlockSpec(
                (1, tk, D),
                lambda b, h, iq, it, ids, cnt, pt: (h, pt[b, ids[iq, it]], 0),
            ),
            pl.BlockSpec(
                (1, tk, D),
                lambda b, h, iq, it, ids, cnt, pt: (h, pt[b, ids[iq, it]], 0),
            ),
            # validity stays logical (per stream, not per slab row)
            pl.BlockSpec(
                (1, 1, 1, tk),
                lambda b, h, iq, it, ids, cnt, pt: (b, ids[iq, it], 0, 0),
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, g, tq, D), lambda b, h, iq, it, ids, cnt, pt: (b, h, iq, 0)
        ),
        scratch_shapes=[
            pltpu.VMEM((g, tq, 1), jnp.float32),   # running max  m
            pltpu.VMEM((g, tq, 1), jnp.float32),   # running norm l
            pltpu.VMEM((g, tq, D), jnp.float32),   # accumulator
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        interpret=interpret,
    )(tile_ids.astype(jnp.int32), tile_count.astype(jnp.int32),
      page_table.astype(jnp.int32), qt, qp2, kt, vt, kvm)
    return out.transpose(0, 2, 1, 3)
