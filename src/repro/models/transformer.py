"""Unified decoder-only / encoder-decoder transformer over all families.

A model is a repeating *pattern* of blocks (``cfg.block_pattern`` /
``cfg.ffn_pattern``).  Parameters for each pattern position are stacked
along a leading ``repeats`` axis and the stack is traversed with
``jax.lax.scan`` — one HLO while-loop regardless of depth, which keeps
dry-run compiles of 88-layer models fast and small.

Three execution paths share the block code:
  * ``forward_train``: full-sequence causal self-attention, no cache.
  * ``prefill``: builds the KV / SSM caches (optionally chunked against
    an existing cache — the machinery CodecFlow's selective refresh uses).
  * ``decode_step``: single-token step against the caches.
"""
from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelCfg
from .init import ParamBuilder, split_tree
from . import layers
from .layers import KVCache, SSMCache

F32 = jnp.float32


class Caches(NamedTuple):
    """Per-pattern-position stacked caches (leading dim = repeats)."""

    blocks: Tuple[Any, ...]           # KVCache | SSMCache | None per position
    cross: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None  # whisper enc K/V


# ======================================================================
# Init
# ======================================================================
def _init_block(pb: ParamBuilder, cfg: ModelCfg, pos: int):
    mixer, ffn = cfg.block_kind(pos)
    p = {"ln1": layers.init_rmsnorm(pb, cfg.d_model),
         "ln2": layers.init_rmsnorm(pb, cfg.d_model)}
    if mixer == "attn":
        p["mixer"] = layers.init_attention(pb, cfg)
    else:
        p["mixer"] = layers.init_mamba(pb, cfg)
    if ffn == "moe":
        p["ffn"] = layers.init_moe(pb, cfg.d_model, cfg.moe, cfg.d_ff)
    elif ffn == "none":
        del p["ln2"]
    else:
        p["ffn"] = layers.init_mlp(pb, cfg.d_model, cfg.d_ff)
    if cfg.enc_dec:
        p["lnx"] = layers.init_rmsnorm(pb, cfg.d_model)
        p["xattn"] = layers.init_cross_attention(pb, cfg)
    return p


def init_params(cfg: ModelCfg, key: jax.Array, abstract: bool = False):
    """Returns (params, logical_specs) pytrees.

    ``abstract=True`` returns ShapeDtypeStructs (dry-run; no allocation).
    """
    pb = ParamBuilder(
        key, dtype=jnp.bfloat16 if cfg.dtype == "bfloat16" else F32,
        abstract=abstract,
    )
    tree = {
        "embed": pb.dense((cfg.vocab, cfg.d_model), ("vocab", "embed"), scale=0.02),
        "final_norm": layers.init_rmsnorm(pb, cfg.d_model),
    }
    if not cfg.tied_embeddings:
        tree["lm_head"] = pb.dense((cfg.d_model, cfg.vocab), ("embed", "vocab"))
    tree["blocks"] = tuple(
        pb.stacked(cfg.repeats, lambda b, pos=pos: _init_block(b, cfg, pos))
        for pos in range(cfg.period)
    )
    if cfg.enc_dec:
        enc_cfg = cfg  # same width; depth = enc_layers
        tree["encoder"] = pb.stacked(cfg.enc_layers, lambda b: {
            "ln1": layers.init_rmsnorm(b, cfg.d_model),
            "mixer": layers.init_attention(b, enc_cfg),
            "ln2": layers.init_rmsnorm(b, cfg.d_model),
            "ffn": layers.init_mlp(b, cfg.d_model, cfg.d_ff),
        })
        tree["enc_norm"] = layers.init_rmsnorm(pb, cfg.d_model)
        tree["enc_embed"] = pb.dense((cfg.d_model, cfg.d_model), (None, "embed"))
    return split_tree(tree)


def init_caches(
    cfg: ModelCfg, batch: int, max_len: int, dtype=jnp.bfloat16
) -> Caches:
    blocks = []
    for pos in range(cfg.period):
        mixer, _ = cfg.block_kind(pos)
        R = cfg.repeats
        if mixer == "attn":
            shape = (R, batch, max_len, cfg.n_kv, cfg.d_head)
            blocks.append(KVCache(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)))
        else:
            s = cfg.ssm
            di = s.d_inner(cfg.d_model)
            conv_dim = di + 2 * s.n_groups * s.d_state
            blocks.append(SSMCache(
                jnp.zeros((R, batch, s.d_conv - 1, conv_dim), dtype),
                jnp.zeros((R, batch, s.n_heads(cfg.d_model), s.head_dim, s.d_state), F32),
            ))
    return Caches(tuple(blocks), None)


# ======================================================================
# Block application
# ======================================================================
def _apply_block(
    cfg: ModelCfg,
    pos: int,
    p,
    h: jnp.ndarray,
    positions: jnp.ndarray,
    valid,
    cache,
    cache_offset,
    cache_len,
    cross_kv,
    *,
    decode: bool,
    q_chunk: int,
    scatter_idx=None,
    kv_valid=None,
    block_map=None,
    page_table=None,
    page_size: int = 128,
):
    mixer, ffn = cfg.block_kind(pos)
    hn = layers.rmsnorm(p["ln1"], h, cfg.norm_eps)
    new_cache = None
    if mixer == "attn":
        out, new_cache = layers.attention_block(
            p["mixer"], cfg, hn, positions, valid,
            cache=cache, cache_offset=cache_offset, cache_len=cache_len,
            scatter_idx=scatter_idx, kv_valid=kv_valid,
            q_chunk=q_chunk, block_map=block_map,
            page_table=page_table, page_size=page_size,
        )
    else:
        if decode:
            out, new_cache = layers.mamba_decode(p["mixer"], cfg, hn, cache)
        else:
            out, new_cache = layers.mamba_block(
                p["mixer"], cfg, hn, cache, return_cache=cache is not None
            )
    h = h + out
    if cfg.enc_dec and cross_kv is not None:
        hx = layers.rmsnorm(p["lnx"], h, cfg.norm_eps)
        h = h + layers.cross_attention_block(p["xattn"], cfg, hx, cross_kv)
    aux = jnp.zeros((), F32)
    if ffn == "none":
        return h, new_cache, aux
    hn = layers.rmsnorm(p["ln2"], h, cfg.norm_eps)
    if ffn == "moe":
        out, aux = layers.moe_block(p["ffn"], cfg.moe, hn)
    else:
        out = layers.mlp_block(p["ffn"], hn)
    return h + out, new_cache, aux


def run_stack(
    cfg: ModelCfg,
    params,
    h: jnp.ndarray,
    positions: jnp.ndarray,
    valid=None,
    caches: Optional[Caches] = None,
    cache_offset=None,
    cache_len: Optional[int] = None,
    *,
    decode: bool = False,
    q_chunk: int = 1024,
    remat: bool = False,
    scatter_idx=None,
    kv_valid=None,
    block_map=None,
    page_table=None,
    page_size: int = 128,
):
    """Scan the block stack.  Returns (h, new_caches, aux_sum).

    ``block_map`` (a ``kernels.flash_refresh.RefreshBlockMap``) is the
    static tile-visit list for the cached attention modes; the same
    geometry applies to every attention layer in the stack.

    ``page_table`` (B, n_pages) int32 switches the attention layers to
    the paged KV pool (``core/kv_pool.py``): ``caches`` then holds the
    shared *batchless* slab and ``cache_len`` must be the logical
    per-stream length (n_pages * page_size).
    """
    use_cache = caches is not None
    has_cross = use_cache and caches.cross is not None
    xs = (params["blocks"],)
    if has_cross:
        xs += (caches.cross,)  # ((R,B,S,K,dh), (R,B,S,K,dh)) sliced per layer
    if use_cache:
        xs += (jnp.arange(cfg.repeats),)

    # The stacked caches travel in the scan CARRY (sliced/updated by layer
    # index), not as xs->ys streams: while-loop carries are aliased
    # in-place by XLA, whereas separate xs and ys buffers double the cache
    # footprint (measured +2x cache bytes on decode_32k).
    def body(carry, xs_t):
        h, aux, cstate = carry
        from ..sharding import ctx as shctx
        if shctx.seq_sharding() and h.shape[1] > 1:
            # TP-SP boundary: keep the carried residual stream sharded
            # over (batch, seq) so remat saves shrink by the TP degree
            h = shctx.constrain(h, "batch", "model", None)
        lp = xs_t[0]
        cross_kv = xs_t[1] if has_cross else None
        if use_cache:
            idx = xs_t[-1]
            lc = jax.tree_util.tree_map(
                lambda c: jax.lax.dynamic_index_in_dim(c, idx, 0, keepdims=False),
                cstate,
            )
        else:
            lc = tuple(None for _ in range(cfg.period))
        new_caches = []
        for pos in range(cfg.period):
            h, nc, a = _apply_block(
                cfg, pos, lp[pos], h, positions, valid,
                lc[pos], cache_offset, cache_len, cross_kv,
                decode=decode, q_chunk=q_chunk,
                scatter_idx=scatter_idx, kv_valid=kv_valid,
                block_map=block_map,
                page_table=page_table, page_size=page_size,
            )
            new_caches.append(nc)
            aux = aux + a
        if use_cache:
            cstate = jax.tree_util.tree_map(
                lambda full, new: jax.lax.dynamic_update_index_in_dim(
                    full, new.astype(full.dtype), idx, 0
                ),
                cstate, tuple(new_caches),
            )
        return (h, aux, cstate), None

    if remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)

    init_cstate = caches.blocks if use_cache else None
    (h, aux, cstate), _ = jax.lax.scan(
        body, (h, jnp.zeros((), F32), init_cstate), xs
    )
    new_caches = Caches(cstate, caches.cross if has_cross else None) if use_cache else None
    return h, new_caches, aux


# ======================================================================
# Embedding / head
# ======================================================================
def embed_tokens(cfg: ModelCfg, params, tokens: jnp.ndarray) -> jnp.ndarray:
    return params["embed"][tokens]


def embed_inputs(
    cfg: ModelCfg, params, tokens: jnp.ndarray,
    inputs_embeds: Optional[jnp.ndarray] = None,
    embed_mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Token embeddings, optionally overridden at multimodal positions."""
    h = embed_tokens(cfg, params, tokens)
    if inputs_embeds is not None:
        if embed_mask is None:
            h = inputs_embeds.astype(h.dtype)
        else:
            h = jnp.where(embed_mask[..., None], inputs_embeds.astype(h.dtype), h)
    return h


def lm_logits(cfg: ModelCfg, params, h: jnp.ndarray) -> jnp.ndarray:
    head = params["embed"].T if cfg.tied_embeddings else params["lm_head"]
    return (h @ head).astype(F32)


# ======================================================================
# Encoder (whisper)
# ======================================================================
def run_encoder(cfg: ModelCfg, params, feats: jnp.ndarray, q_chunk: int = 1024,
                remat: bool = False):
    """feats: (B, S_enc, d) stub frontend embeddings -> encoder output."""
    h = feats.astype(params["enc_embed"].dtype) @ params["enc_embed"]
    B, S, _ = h.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    def body(h, lp):
        hn = layers.rmsnorm(lp["ln1"], h, cfg.norm_eps)
        out, _ = layers.attention_block(
            lp["mixer"], cfg, hn, pos, causal=False, q_chunk=q_chunk
        )
        h = h + out
        hn = layers.rmsnorm(lp["ln2"], h, cfg.norm_eps)
        return h + layers.mlp_block(lp["ffn"], hn), None

    if remat:
        body = jax.checkpoint(
            body, policy=jax.checkpoint_policies.nothing_saveable)
    h, _ = jax.lax.scan(body, h, params["encoder"])
    return layers.rmsnorm(params["enc_norm"], h, cfg.norm_eps)


def build_cross_kv(cfg: ModelCfg, params, enc_out: jnp.ndarray):
    """Per-layer cross K/V, stacked over the decoder scan axis."""
    def per_layer(lp):
        return layers.cross_attention_kv(lp["xattn"], cfg, enc_out)
    kv = jax.vmap(per_layer, in_axes=(0,))(params["blocks"][0])
    return kv  # ((R,B,S,K,dh), (R,B,S,K,dh))


# ======================================================================
# Top-level paths
# ======================================================================
def forward_hidden(
    cfg: ModelCfg, params, tokens: jnp.ndarray,
    inputs_embeds=None, embed_mask=None, valid=None,
    enc_feats=None, *, q_chunk: int = 1024, remat: bool = True,
):
    """Full-sequence forward up to the final norm (pre-head).

    Training loss uses this + ``chunked_cross_entropy`` so the (B, S, V)
    logits tensor is never materialized.
    """
    h = embed_inputs(cfg, params, tokens, inputs_embeds, embed_mask)
    B, S, _ = h.shape
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    caches = None
    if cfg.enc_dec:
        enc_out = run_encoder(cfg, params, enc_feats, q_chunk, remat=remat)
        cross = build_cross_kv(cfg, params, enc_out)
        caches = _cross_only_caches(cfg, cross)
    h, _, aux = run_stack(
        cfg, params, h, pos, valid, caches,
        cache_offset=jnp.zeros((), jnp.int32) if caches else None,
        cache_len=S if caches else None,
        q_chunk=q_chunk, remat=remat,
    )
    return layers.rmsnorm(params["final_norm"], h, cfg.norm_eps), aux


def forward_train(
    cfg: ModelCfg, params, tokens: jnp.ndarray,
    inputs_embeds=None, embed_mask=None, valid=None,
    enc_feats=None, *, q_chunk: int = 1024, remat: bool = True,
):
    """Full-sequence forward.  Returns (logits (B,S,V) f32, aux).

    Materializes full logits — use only at small scale (smoke tests,
    the serving engine's tiny models); the train step goes through
    ``forward_hidden`` + chunked CE.
    """
    h, aux = forward_hidden(
        cfg, params, tokens, inputs_embeds, embed_mask, valid, enc_feats,
        q_chunk=q_chunk, remat=remat,
    )
    return lm_logits(cfg, params, h), aux


def _cross_only_caches(cfg: ModelCfg, cross) -> Caches:
    """Self-attention caches sized to the full sequence for the enc-dec
    train path (queries==keys), so the unified stack signature works."""
    return Caches(tuple(None for _ in range(cfg.period)), cross)


def prefill(
    cfg: ModelCfg, params, tokens: jnp.ndarray,
    caches: Caches, positions=None, valid=None,
    inputs_embeds=None, embed_mask=None,
    cache_offset=0, *, q_chunk: int = 1024,
):
    """Run prefill over ``tokens`` writing the caches.

    Returns (logits of last position (B, V), new caches, full hidden (B,S,d)).
    """
    h = embed_inputs(cfg, params, tokens, inputs_embeds, embed_mask)
    B, S, _ = h.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S)[None] + cache_offset, (B, S))
    off = jnp.asarray(cache_offset, jnp.int32)
    cache_len = caches_max_len(cfg, caches)
    h, new_caches, aux = run_stack(
        cfg, params, h, positions, valid, caches,
        cache_offset=off, cache_len=cache_len, q_chunk=q_chunk,
    )
    hn = layers.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    return lm_logits(cfg, params, hn[:, -1]), new_caches, h


def decode_step(
    cfg: ModelCfg, params, token: jnp.ndarray, caches: Caches, cur_len,
    page_table=None, cache_len: Optional[int] = None, page_size: int = 128,
):
    """One decode step.  token: (B, 1) int32; cur_len: the new token's
    position / write index, a Python int (static) or a scalar int32.
    Returns (logits (B,V), caches).

    With ``page_table``, ``caches`` is the shared paged slab and
    ``cache_len`` must be passed explicitly (the slab's physical row
    count says nothing about the per-stream logical length)."""
    h = embed_tokens(cfg, params, token)
    B = h.shape[0]
    positions = jnp.broadcast_to(jnp.asarray(cur_len)[None, None], (B, 1)).astype(jnp.int32)
    # a Python-int position stays static: attention then runs the kernel
    # with a visit list for it (layers.attention_block)
    off = cur_len if isinstance(cur_len, int) else jnp.asarray(cur_len, jnp.int32)
    if cache_len is None:
        assert page_table is None, "paged decode needs an explicit cache_len"
        cache_len = caches_max_len(cfg, caches)
    h, new_caches, _ = run_stack(
        cfg, params, h, positions, None, caches,
        cache_offset=off, cache_len=cache_len, decode=True,
        page_table=page_table, page_size=page_size,
    )
    hn = layers.rmsnorm(params["final_norm"], h, cfg.norm_eps)
    return lm_logits(cfg, params, hn[:, -1]), new_caches


def caches_max_len(cfg: ModelCfg, caches: Caches) -> Optional[int]:
    for pos in range(cfg.period):
        if cfg.block_kind(pos)[0] == "attn":
            return caches.blocks[pos].k.shape[2]
    return None
