"""Neural-net layers: norm, RoPE, GQA attention (+cache), MLP, MoE, Mamba-2.

All functions are pure; parameters are plain pytrees created by the
``init_*`` companions (which return Param trees with logical sharding
axes).  Attention and the SSD scan route through ``repro.kernels.ops``
so they hit Pallas on TPU and the jnp oracle elsewhere.

Memory discipline: prefill attention is *chunked over queries* (peak
activation ~ chunk x S_k instead of S_q x S_k) so 32k-token prefill
lowers within HBM on the production mesh.
"""
from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelCfg, MoECfg
from ..kernels import ops
from ..kernels.flash_refresh import span_block_map
from ..kernels.ref import apply_rope_ref
from ..sharding.ctx import constrain
from .init import ParamBuilder

NEG_INF = -1e30
F32 = jnp.float32


# ======================================================================
# Norm
# ======================================================================
def init_rmsnorm(pb: ParamBuilder, d: int):
    return {"scale": pb.ones((d,), (None,))}


def rmsnorm(p, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(F32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * p["scale"].astype(F32)).astype(x.dtype)


# ======================================================================
# Attention (GQA + RoPE, unified train / prefill / chunked / decode)
# ======================================================================
class KVCache(NamedTuple):
    """Dense KV cache for one attention position in the block pattern.

    k, v: (B, S_max, n_kv, d_head).  The live length is tracked by the
    caller (static where possible, dynamic int32 during serving).
    """

    k: jnp.ndarray
    v: jnp.ndarray


class QuantKVCache(NamedTuple):
    """Two-precision paged KV slab for one attention position.

    Hot (live) pages stay in the storage float dtype; cold (demoted)
    pages hold int8 values with one f32 scale per (page, kv head) —
    symmetric quantization, ``value = int8 * scale``.  The page-id space
    is unified: a page-table entry ``< n_hot`` rows into ``k``/``v``, an
    entry ``>= n_hot`` rows into ``k8``/``v8`` at ``entry - n_hot`` —
    the precision bit IS the page id (docs/paged_kv.md §Quantized cold
    pages).

      k, v:             (n_hot * page, n_kv, d_head) float slab
      k8, v8:           (n_cold * page, n_kv, d_head) int8 slab
      k_scale, v_scale: (n_cold, n_kv) f32 per-page-per-head scales
    """

    k: jnp.ndarray
    v: jnp.ndarray
    k8: jnp.ndarray
    v8: jnp.ndarray
    k_scale: jnp.ndarray
    v_scale: jnp.ndarray


INT8_QMAX = 127.0


def page_quant_scale(vals: jnp.ndarray, axes: Tuple[int, ...]) -> jnp.ndarray:
    """Symmetric int8 scale from the abs-max over ``axes``.

    All-zero pages get scale 1.0 so quantize/dequantize round-trips them
    to exact zeros (0 / 1 -> 0 -> 0 * 1); the guard is baked into the
    STORED scale so the write and read paths always agree."""
    amax = jnp.max(jnp.abs(vals.astype(F32)), axis=axes)
    return jnp.where(amax > 0, amax / INT8_QMAX, 1.0)


def quantize_kv(vals: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    """vals (..., n_kv, d_head) float; scale (..., n_kv) f32 -> int8.

    Values beyond the scale's range clip saturate at +-127 (refresh
    writes into a cold page reuse the page's current scale)."""
    q = jnp.round(vals.astype(F32) / scale[..., None])
    return jnp.clip(q, -INT8_QMAX, INT8_QMAX).astype(jnp.int8)


def dequantize_kv(vals: jnp.ndarray, scale: jnp.ndarray,
                  dtype) -> jnp.ndarray:
    """int8 (..., n_kv, d_head) * f32 scale (..., n_kv) -> storage dtype.

    Rounds through the hot storage dtype so the kernel's in-register
    dequant and the oracle's gathered logical view agree bitwise."""
    return (vals.astype(F32) * scale[..., None]).astype(dtype)


def init_attention(pb: ParamBuilder, cfg: ModelCfg):
    d, dh = cfg.d_model, cfg.d_head
    p = {
        "wq": pb.dense((d, cfg.n_heads * dh), ("embed", "heads")),
        "wk": pb.dense((d, cfg.n_kv * dh), ("embed", "kv")),
        "wv": pb.dense((d, cfg.n_kv * dh), ("embed", "kv")),
        "wo": pb.dense((cfg.n_heads * dh, d), ("heads", "embed")),
    }
    if cfg.qkv_bias:
        p["bq"] = pb.zeros((cfg.n_heads * dh,), ("heads",))
        p["bk"] = pb.zeros((cfg.n_kv * dh,), ("kv",))
        p["bv"] = pb.zeros((cfg.n_kv * dh,), ("kv",))
    return p


def _qkv(p, cfg: ModelCfg, x: jnp.ndarray, positions: jnp.ndarray):
    """x: (B, T, d) -> q (B,T,H,dh), k/v (B,T,K,dh), RoPE applied."""
    B, T, _ = x.shape
    dh = cfg.d_head
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    q = constrain(q.reshape(B, T, cfg.n_heads, dh), "batch", None, "model", None)
    k = constrain(k.reshape(B, T, cfg.n_kv, dh), "batch", None, "model", None)
    v = constrain(v.reshape(B, T, cfg.n_kv, dh), "batch", None, "model", None)
    q = apply_rope_ref(q, positions, cfg.rope_theta)
    k = apply_rope_ref(k, positions, cfg.rope_theta)
    return q, k, v


def mha(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    qpos: jnp.ndarray,
    kpos: jnp.ndarray,
    kvalid: Optional[jnp.ndarray] = None,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_chunk: int = 1024,
) -> jnp.ndarray:
    """Masked GQA attention, chunked over queries when S_q > q_chunk.

    q: (B, Sq, H, dh); k, v: (B, Sk, K, dh); qpos: (B, Sq); kpos: (B, Sk);
    kvalid: (B, Sk) bool or None.
    """
    B, Sq, H, dh = q.shape
    Sk, K = k.shape[1], k.shape[2]
    g = H // K
    scale = dh ** -0.5

    def block(qc, qpc):
        # qc: (B, Tq, H, dh).  K/V stay in their storage dtype (bf16) with
        # f32 accumulation — upcasting the cache would materialize an
        # f32 copy of the whole KV (measured 19.5 GiB/device on
        # decode_32k before this fix).
        Tq = qc.shape[1]
        qq = (qc.astype(F32) * scale).astype(k.dtype).reshape(B, Tq, K, g, dh)
        logits = jnp.einsum(
            "btkgd,bskd->bkgts", qq, k,
            preferred_element_type=F32,
        )  # (B, K, g, Tq, Sk) f32
        m = jnp.ones((B, Tq, Sk), bool)
        if causal:
            m &= kpos[:, None, :] <= qpc[:, :, None]
        if window is not None:
            m &= kpos[:, None, :] > qpc[:, :, None] - window
        if kvalid is not None:
            m &= kvalid[:, None, :]
        logits = jnp.where(m[:, None, None], logits, NEG_INF)
        p = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
        out = jnp.einsum(
            "bkgts,bskd->btkgd", p, v,
            preferred_element_type=F32,
        )
        return out.reshape(B, Tq, H, dh).astype(q.dtype)

    if Sq <= q_chunk:
        return block(q, qpos)
    pad = (-Sq) % q_chunk
    if pad:
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        qpos = jnp.pad(qpos, ((0, 0), (0, pad)))
    nq = (Sq + pad) // q_chunk
    qs = q.reshape(B, nq, q_chunk, H, dh).transpose(1, 0, 2, 3, 4)
    ps = qpos.reshape(B, nq, q_chunk).transpose(1, 0, 2)
    outs = jax.lax.map(lambda t: block(*t), (qs, ps))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq + pad, H, dh)
    return out[:, :Sq]


def attention_block(
    p,
    cfg: ModelCfg,
    x: jnp.ndarray,
    positions: jnp.ndarray,
    valid: Optional[jnp.ndarray] = None,
    cache: Optional[KVCache] = None,
    cache_offset: Optional[jnp.ndarray] = None,
    cache_len: Optional[int] = None,
    scatter_idx: Optional[jnp.ndarray] = None,
    kv_valid: Optional[jnp.ndarray] = None,
    *,
    causal: bool = True,
    q_chunk: int = 1024,
    block_map=None,
    page_table: Optional[jnp.ndarray] = None,
    page_size: int = 128,
) -> Tuple[jnp.ndarray, Optional[KVCache]]:
    """Unified attention block.

    Without a cache: self-attention over ``x`` (train / full prefill).
    With a cache: writes this chunk's K/V then attends to
    cache[:cache_len].  Two write modes:
      * contiguous (``cache_offset``): chunked prefill / decode (T==1);
      * scatter (``scatter_idx`` (T,) token positions): CodecFlow's
        selective KVC refresh — anchors sit at non-contiguous positions.
        ``kv_valid`` (B, S) must then describe the full cache validity.
    Both cached modes dispatch through ``ops.flash_refresh`` (keys live
    in cache coordinates): the Pallas block-sparse kernel when a
    ``block_map`` for this geometry is supplied, the q-chunked oracle
    otherwise — no dense (B, S) score mask is materialized on the
    kernel path.  A caller's ``block_map`` applies only to the scatter
    mode: its ``q_pos`` must equal the scatter positions.  The
    contiguous mode builds its own map when ``cache_offset`` is a
    Python int (serving decodes at layout-static positions); a traced
    offset leaves the positions unknown at trace time, so it takes the
    oracle.

    Paged mode (``page_table`` (B, n_pages) int32): ``cache`` is the
    *batchless* per-layer slab of the shared KV pool (P_phys, n_kv, dh)
    from ``core/kv_pool.py``; both write modes map logical slots through
    the page table (slot s -> pt[s // page_size] * page_size + s %
    page_size) and reads dispatch through ``ops.flash_refresh_paged``.
    ``cache_len`` is then mandatory and must equal n_pages * page_size.
    A ``QuantKVCache`` slab adds int8 cold pages: writes are routed per
    token by the page-table precision bit (entry >= n_hot) and the cold
    slab + scales ride to the kernel as the ``cold`` operand group.
    """
    B, T, _ = x.shape
    q, k, v = _qkv(p, cfg, x, positions)
    window = cfg.sliding_window
    if (cache is not None and scatter_idx is None
            and isinstance(cache_offset, int)):
        # a static offset (decode at a layout-static position) fixes the
        # query positions, so the contiguous mode gets a visit list and
        # runs the kernel; a traced offset cannot
        kv_len = cache_len if cache_len is not None else cache.k.shape[1]
        block_map = span_block_map(cache_offset, T, kv_len,
                                   causal=causal, window=window)

    if cache is None:
        out = mha(q, k, v, positions, positions, valid, causal=causal,
                  window=window, q_chunk=q_chunk)
        new_cache = None
    elif page_table is not None:
        S = cache_len
        assert S is not None and S == page_table.shape[1] * page_size, (
            S, page_table.shape, page_size,
        )
        if scatter_idx is not None:
            idx = scatter_idx
        else:
            idx = cache_offset + jnp.arange(T, dtype=jnp.int32)
        entries = page_table[:, idx // page_size]            # (B, T)
        phys = entries * page_size + idx % page_size
        if isinstance(cache, QuantKVCache):
            # Two-precision slab: route each token's write by its page's
            # precision.  Hot writes go through phys as usual — a cold
            # entry's phys lands past the hot slab and mode="drop"
            # discards it.  Cold writes quantize through the destination
            # page's CURRENT scale (set by this window's reuse requant /
            # demote pass) and are dropped for hot entries.
            n_hot = cache.k.shape[0] // page_size
            n_cold = cache.k8.shape[0] // page_size
            is_cold = entries >= n_hot
            ck = cache.k.at[phys].set(k.astype(cache.k.dtype), mode="drop")
            cv = cache.v.at[phys].set(v.astype(cache.v.dtype), mode="drop")
            cold_pg = jnp.clip(entries - n_hot, 0, n_cold - 1)
            cold_rows = jnp.where(
                is_cold, cold_pg * page_size + idx % page_size,
                cache.k8.shape[0],
            )
            k8 = cache.k8.at[cold_rows].set(
                quantize_kv(k, cache.k_scale[cold_pg]), mode="drop"
            )
            v8 = cache.v8.at[cold_rows].set(
                quantize_kv(v, cache.v_scale[cold_pg]), mode="drop"
            )
            new_cache = QuantKVCache(ck, cv, k8, v8,
                                     cache.k_scale, cache.v_scale)
            cold = (k8, v8, cache.k_scale, cache.v_scale)
        else:
            ck = cache.k.at[phys].set(k.astype(cache.k.dtype))
            cv = cache.v.at[phys].set(v.astype(cache.v.dtype))
            new_cache = KVCache(ck, cv)
            cold = None
        if scatter_idx is not None:
            kval = (kv_valid[:, :S] if kv_valid is not None
                    else jnp.ones((B, S), bool))
            bm = block_map
        else:
            kpos = jnp.arange(S)[None]
            kval = jnp.broadcast_to(kpos <= (cache_offset + T - 1), (B, S))
            if kv_valid is not None:
                kval &= kv_valid[:, :S]
            if valid is not None:
                kval &= jax.lax.dynamic_update_slice_in_dim(
                    jnp.ones((B, S), bool), valid, cache_offset, 1
                )
            bm = block_map
        out = ops.flash_refresh_paged(
            q, ck, cv, positions, kval, page_table, page=page_size,
            causal=causal, window=window, block_map=bm, q_chunk=q_chunk,
            cold=cold,
        )
    elif scatter_idx is not None:
        ck = cache.k.at[:, scatter_idx].set(k.astype(cache.k.dtype))
        cv = cache.v.at[:, scatter_idx].set(v.astype(cache.v.dtype))
        new_cache = KVCache(ck, cv)
        S = cache_len if cache_len is not None else ck.shape[1]
        kk, vv = ck[:, :S], cv[:, :S]
        kval = kv_valid[:, :S] if kv_valid is not None else None
        out = ops.flash_refresh(q, kk, vv, positions, kval, causal=causal,
                                window=window, block_map=block_map,
                                q_chunk=q_chunk)
    else:
        ck = jax.lax.dynamic_update_slice_in_dim(cache.k, k.astype(cache.k.dtype), cache_offset, 1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache.v, v.astype(cache.v.dtype), cache_offset, 1)
        new_cache = KVCache(ck, cv)
        S = cache_len if cache_len is not None else ck.shape[1]
        kk = ck[:, :S]
        vv = cv[:, :S]
        kpos = jnp.arange(S)[None]
        kval = jnp.broadcast_to(kpos <= (cache_offset + T - 1), (B, S))
        if kv_valid is not None:
            kval &= kv_valid[:, :S]
        if valid is not None:
            kval &= jax.lax.dynamic_update_slice_in_dim(
                jnp.ones((B, ck.shape[1]), bool), valid, cache_offset, 1
            )[:, :S]
        out = ops.flash_refresh(q, kk, vv, positions, kval, causal=causal,
                                window=window, block_map=block_map,
                                q_chunk=q_chunk)

    out = out.reshape(B, T, cfg.n_heads * cfg.d_head) @ p["wo"]
    return out, new_cache


# ======================================================================
# Cross-attention (whisper decoder)
# ======================================================================
def init_cross_attention(pb: ParamBuilder, cfg: ModelCfg):
    d, dh = cfg.d_model, cfg.d_head
    return {
        "wq": pb.dense((d, cfg.n_heads * dh), ("embed", "heads")),
        "wk": pb.dense((d, cfg.n_kv * dh), ("embed", "kv")),
        "wv": pb.dense((d, cfg.n_kv * dh), ("embed", "kv")),
        "wo": pb.dense((cfg.n_heads * dh, d), ("heads", "embed")),
    }


def cross_attention_block(p, cfg: ModelCfg, x: jnp.ndarray, enc_kv: Tuple[jnp.ndarray, jnp.ndarray]):
    """x: (B, T, d); enc_kv: precomputed (k, v) (B, S_enc, K, dh)."""
    B, T, _ = x.shape
    dh = cfg.d_head
    q = (x @ p["wq"]).reshape(B, T, cfg.n_heads, dh)
    k, v = enc_kv
    qpos = jnp.zeros((B, T), jnp.int32)
    kpos = jnp.zeros((B, k.shape[1]), jnp.int32)
    out = mha(q, k, v, qpos, kpos, causal=False)
    return out.reshape(B, T, cfg.n_heads * dh) @ p["wo"]


def cross_attention_kv(p, cfg: ModelCfg, enc_out: jnp.ndarray):
    B, S, _ = enc_out.shape
    dh = cfg.d_head
    k = (enc_out @ p["wk"]).reshape(B, S, cfg.n_kv, dh)
    v = (enc_out @ p["wv"]).reshape(B, S, cfg.n_kv, dh)
    return k, v


# ======================================================================
# Dense MLP (SwiGLU)
# ======================================================================
def init_mlp(pb: ParamBuilder, d: int, d_ff: int):
    return {
        "wg": pb.dense((d, d_ff), ("embed", "ffn")),
        "wu": pb.dense((d, d_ff), ("embed", "ffn")),
        "wd": pb.dense((d_ff, d), ("ffn", "embed")),
    }


def mlp_block(p, x: jnp.ndarray) -> jnp.ndarray:
    hidden = jax.nn.silu(x @ p["wg"]) * (x @ p["wu"])
    hidden = constrain(hidden, *(("batch",) + (None,) * (hidden.ndim - 2) + ("model",)))
    return hidden @ p["wd"]


# ======================================================================
# Mixture of Experts (token-choice top-k, sort-based static dispatch)
# ======================================================================
def init_moe(pb: ParamBuilder, d: int, cfg: MoECfg, d_ff_dense: int):
    p = {
        "router": pb.dense((d, cfg.n_experts), ("embed", None), scale=0.02),
        "wg": pb.dense((cfg.n_experts, d, cfg.d_ff_expert), ("experts", "embed", None)),
        "wu": pb.dense((cfg.n_experts, d, cfg.d_ff_expert), ("experts", "embed", None)),
        "wd": pb.dense((cfg.n_experts, cfg.d_ff_expert, d), ("experts", None, "embed")),
    }
    if cfg.dense_residual:
        p["residual"] = init_mlp(pb, d, d_ff_dense)
    return p


def moe_block(p, cfg: MoECfg, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x: (B, T, d).  Returns (out, aux_loss).

    TPU adaptation: static-capacity dispatch.  (token, k) assignments are
    sorted by expert id; each expert processes up to C slots; overflow is
    dropped (contributes zero).  See DESIGN.md §3.
    """
    B, T, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    x2 = x.reshape(B * T, d)
    n = B * T

    gates = jax.nn.softmax((x2 @ p["router"]).astype(F32), axis=-1)  # (n, E)
    topw, tope = jax.lax.top_k(gates, k)                             # (n, k)
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)

    # aux load-balance loss (Switch):  E * sum_e f_e * p_e
    dispatch_frac = jnp.zeros((E,), F32).at[tope.reshape(-1)].add(1.0) / (n * k)
    gate_frac = gates.mean(0)
    aux = E * jnp.sum(dispatch_frac * gate_frac)

    cap = int(cfg.capacity_factor * n * k / E) + 1

    flat_e = tope.reshape(-1)                       # (n*k,)
    flat_w = topw.reshape(-1).astype(x.dtype)
    flat_t = jnp.repeat(jnp.arange(n), k)
    order = jnp.argsort(flat_e)                     # stable: token priority
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts            # exclusive prefix
    pos = jnp.arange(n * k) - starts[se]
    keep = pos < cap
    slot = se * cap + jnp.where(keep, pos, cap - 1)

    gathered = constrain(x2[st], "batch", None)     # (n*k, d) token-sharded
    buf = jnp.zeros((E * cap, d), x.dtype).at[slot].add(
        jnp.where(keep[:, None], gathered, 0)
    )
    # expert-parallel layout: experts on 'model', slots on 'data'
    buf = constrain(buf.reshape(E, cap, d), "model", "batch", None)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["wg"])) * jnp.einsum(
        "ecd,edf->ecf", buf, p["wu"]
    )
    h = constrain(h, "model", "batch", None)
    out_e = jnp.einsum("ecf,efd->ecd", h, p["wd"]).reshape(E * cap, d)

    y = constrain(out_e[slot], "batch", None) * jnp.where(keep, sw, 0)[:, None]
    out = constrain(jnp.zeros((n, d), x.dtype).at[st].add(y), "batch", None)

    if "residual" in p:
        out = out + mlp_block(p["residual"], x2)
    return out.reshape(B, T, d), aux


# ======================================================================
# Mamba-2 (SSD) mixer
# ======================================================================
class SSMCache(NamedTuple):
    """Recurrent state for one mamba position: conv tail + SSD state."""

    conv: jnp.ndarray   # (B, d_conv-1, conv_dim)
    ssm: jnp.ndarray    # (B, H, P, N) float32


def init_mamba(pb: ParamBuilder, cfg: ModelCfg):
    s = cfg.ssm
    d = cfg.d_model
    di = s.d_inner(d)
    nh = s.n_heads(d)
    gn = s.n_groups * s.d_state
    proj_in = 2 * di + 2 * gn + nh
    conv_dim = di + 2 * gn
    return {
        "in_proj": pb.dense((d, proj_in), ("embed", "ssm_inner")),
        "conv_w": pb.dense((s.d_conv, conv_dim), (None, "ssm_inner"), scale=0.5),
        "conv_b": pb.zeros((conv_dim,), ("ssm_inner",)),
        "A_log": pb.value(jnp.log(jnp.linspace(1.0, 16.0, nh)), (None,)),
        "D": pb.ones((nh,), (None,)),
        "dt_bias": pb.value(
            jnp.log(jnp.expm1(jnp.linspace(1e-3, 1e-1, nh))), (None,)
        ),
        "norm": pb.ones((di,), (None,)),
        "out_proj": pb.dense((di, d), ("ssm_inner", "embed")),
    }


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray, tail: Optional[jnp.ndarray]):
    """Depthwise causal conv via shifted adds.  x: (B, T, C); w: (K, C)."""
    K = w.shape[0]
    if tail is None:
        xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    T = x.shape[1]
    out = b.astype(F32)
    acc = jnp.zeros(x.shape, F32) + out
    for i in range(K):
        acc = acc + xp[:, i:i + T].astype(F32) * w[i].astype(F32)
    new_tail = xp[:, -(K - 1):] if K > 1 else None
    return jax.nn.silu(acc).astype(x.dtype), new_tail


def mamba_block(
    p,
    cfg: ModelCfg,
    x: jnp.ndarray,
    cache: Optional[SSMCache] = None,
    *,
    return_cache: bool = False,
) -> Tuple[jnp.ndarray, Optional[SSMCache]]:
    """Mamba-2 mixer (prefill / train path).  x: (B, T, d)."""
    s = cfg.ssm
    B, T, d = x.shape
    di = s.d_inner(d)
    nh = s.n_heads(d)
    gn = s.n_groups * s.d_state
    P = s.head_dim

    zxbcdt = constrain(x @ p["in_proj"], "batch", None, "model")
    z, xin, bc, dt = jnp.split(zxbcdt, [di, 2 * di, 2 * di + 2 * gn], axis=-1)
    conv_in = jnp.concatenate([xin, bc], axis=-1)
    tail = cache.conv if cache is not None else None
    conv_out, new_tail = _causal_conv(conv_in, p["conv_w"], p["conv_b"], tail)
    xin, b, c = jnp.split(conv_out, [di, di + gn], axis=-1)

    dt = jax.nn.softplus(dt.astype(F32) + p["dt_bias"].astype(F32))  # (B,T,nh)
    A = -jnp.exp(p["A_log"].astype(F32))                             # (nh,)
    log_a = dt * A[None, None, :]
    xh = (xin.astype(F32) * dt[..., None].repeat(P, -1).reshape(B, T, di)).reshape(B, T, nh, P)
    bg = b.reshape(B, T, s.n_groups, s.d_state)
    cg = c.reshape(B, T, s.n_groups, s.d_state)

    init = cache.ssm if cache is not None else None
    y, final_state = ops.ssd_scan(
        xh.astype(x.dtype), log_a, bg.astype(x.dtype), cg.astype(x.dtype),
        init, chunk=s.chunk,
    )
    y = y.reshape(B, T, di).astype(F32) + xin.astype(F32) * p["D"].astype(F32)[
        jnp.repeat(jnp.arange(nh), P)
    ][None, None, :]

    # gated RMSNorm
    y = y * jax.nn.silu(z.astype(F32))
    var = jnp.mean(y * y, axis=-1, keepdims=True)
    y = y * jax.lax.rsqrt(var + cfg.norm_eps) * p["norm"].astype(F32)
    out = y.astype(x.dtype) @ p["out_proj"]
    new_cache = SSMCache(new_tail, final_state) if return_cache else None
    return out, new_cache


def mamba_decode(p, cfg: ModelCfg, x: jnp.ndarray, cache: SSMCache):
    """Single-token recurrent step.  x: (B, 1, d)."""
    from ..kernels.ref import ssd_decode_ref

    s = cfg.ssm
    B, _, d = x.shape
    di = s.d_inner(d)
    nh = s.n_heads(d)
    gn = s.n_groups * s.d_state
    P = s.head_dim

    zxbcdt = x[:, 0] @ p["in_proj"]
    z, xin, bc, dt = jnp.split(zxbcdt, [di, 2 * di, 2 * di + 2 * gn], axis=-1)
    conv_in = jnp.concatenate([xin, bc], axis=-1)[:, None]      # (B,1,C)
    window = jnp.concatenate([cache.conv.astype(conv_in.dtype), conv_in], 1)  # (B,K,C)
    acc = p["conv_b"].astype(F32) + jnp.einsum(
        "bkc,kc->bc", window.astype(F32), p["conv_w"].astype(F32)
    )
    # round through the storage dtype exactly as the prefill path does
    # (mamba_block casts the conv output and the SSD operands to x.dtype
    # before the scan) so decode stays on the prefill numeric trajectory.
    conv_out = jax.nn.silu(acc).astype(x.dtype).astype(F32)
    new_tail = window[:, 1:]
    xin, b, c = jnp.split(conv_out, [di, di + gn], axis=-1)

    dt = jax.nn.softplus(dt.astype(F32) + p["dt_bias"].astype(F32))   # (B,nh)
    A = -jnp.exp(p["A_log"].astype(F32))
    log_a = dt * A[None, :]
    xh = (xin * jnp.repeat(dt, P, -1)).reshape(B, nh, P).astype(x.dtype)
    bg = jnp.repeat(b.reshape(B, s.n_groups, s.d_state), nh // s.n_groups, 1)
    cg = jnp.repeat(c.reshape(B, s.n_groups, s.d_state), nh // s.n_groups, 1)
    y, new_state = ssd_decode_ref(
        cache.ssm, xh, log_a, bg.astype(x.dtype), cg.astype(x.dtype)
    )
    y = y.astype(F32).reshape(B, di) + xin * p["D"].astype(F32)[
        jnp.repeat(jnp.arange(nh), P)][None]

    y = y * jax.nn.silu(z.astype(F32))
    var = jnp.mean(y * y, axis=-1, keepdims=True)
    y = y * jax.lax.rsqrt(var + cfg.norm_eps) * p["norm"].astype(F32)
    out = (y.astype(x.dtype) @ p["out_proj"])[:, None]
    return out, SSMCache(new_tail, new_state)
