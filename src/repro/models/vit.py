"""ViT encoder + pixel-unshuffle projector, with patch-pruned execution.

This is the CodecFlow pruning target (paper §3.3.2): the encoder can run
on a *selected subset* of patches (static capacity K_sel — the TPU
adaptation of dynamic pruning, DESIGN.md §3), scatter the encoded
patches back to the full grid, and apply the native 2x2 pixel-unshuffle
projection so the downstream LLM token layout is unchanged.

Two pruned execution paths:

  * ``encode_pruned_tokens`` — the legacy *padded* path: every frame
    carries ``K_sel`` lanes (slack masked), the full patch grid is
    scattered back, and the projector consumes all ``n_groups`` rows.
    Compute is proportional to worst-case capacity.
  * ``encode_packed_tokens`` — the *packed* path: kept patch groups of
    many frames share ``(rows, L_pack)`` buffers (``core.pruning
    .pack_plan``), attention is block-diagonal per frame
    (``ops.flash_packed``), and the projection gathers/projects/
    scatters only kept groups.  Compute is proportional to codec-
    reported motion, not capacity (docs/vit_packing.md).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ViTCfg
from ..kernels import ops
from . import layers
from .init import ParamBuilder

F32 = jnp.float32


def init_vit(pb: ParamBuilder, v: ViTCfg, d_lm: int):
    def block(b: ParamBuilder):
        return {
            "ln1": layers.init_rmsnorm(b, v.d_model),
            "wq": b.dense((v.d_model, v.d_model), ("embed", "heads")),
            "wk": b.dense((v.d_model, v.d_model), ("embed", "heads")),
            "wv": b.dense((v.d_model, v.d_model), ("embed", "heads")),
            "wo": b.dense((v.d_model, v.d_model), ("heads", "embed")),
            "ln2": layers.init_rmsnorm(b, v.d_model),
            "ffn": layers.init_mlp(b, v.d_model, v.d_ff),
        }
    return {
        "patch_embed": pb.dense((v.patch * v.patch, v.d_model), (None, "embed")),
        "pos_embed": pb.dense((v.n_patches, v.d_model), (None, "embed"), scale=0.02),
        "blocks": pb.stacked(v.n_layers, block),
        "final_norm": layers.init_rmsnorm(pb, v.d_model),
        "projector": pb.dense((v.group * v.group * v.d_model, d_lm), (None, "embed")),
    }


def patchify(frames: jnp.ndarray, v: ViTCfg) -> jnp.ndarray:
    """frames (B, H, W) luma [0,255] -> (B, P, patch*patch) in [-1, 1]."""
    B, H, W = frames.shape
    pp = v.patches_per_side
    x = frames.reshape(B, pp, v.patch, pp, v.patch).transpose(0, 1, 3, 2, 4)
    return (x.reshape(B, pp * pp, v.patch * v.patch) / 127.5) - 1.0


def _encoder(params, v: ViTCfg, h: jnp.ndarray, valid: Optional[jnp.ndarray], eps: float):
    """h: (B, T, d); valid: (B, T) bool or None (masked attention)."""
    B, T, _ = h.shape
    pos = jnp.zeros((B, T), jnp.int32)  # no RoPE in ViT; positions unused

    def body(h, lp):
        hn = layers.rmsnorm(lp["ln1"], h, eps)
        dh = v.d_model // v.n_heads
        q = (hn @ lp["wq"]).reshape(B, T, v.n_heads, dh)
        k = (hn @ lp["wk"]).reshape(B, T, v.n_heads, dh)
        vv = (hn @ lp["wv"]).reshape(B, T, v.n_heads, dh)
        out = layers.mha(q, k, vv, pos, pos, valid, causal=False)
        h = h + out.reshape(B, T, v.d_model) @ lp["wo"]
        hn = layers.rmsnorm(lp["ln2"], h, eps)
        return h + layers.mlp_block(lp["ffn"], hn), None

    h, _ = jax.lax.scan(body, h, params["blocks"])
    return layers.rmsnorm(params["final_norm"], h, eps)


def encode_full(params, v: ViTCfg, frames: jnp.ndarray, eps: float = 1e-5):
    """Unpruned path: (B, H, W) -> (B, n_groups, d_lm) visual tokens."""
    x = patchify(frames, v).astype(params["patch_embed"].dtype)
    h = x @ params["patch_embed"] + params["pos_embed"][None]
    h = _encoder(params, v, h, None, eps)
    return project(params, v, h)


def encode_pruned(
    params, v: ViTCfg, frames: jnp.ndarray,
    sel_idx: jnp.ndarray, sel_valid: jnp.ndarray, eps: float = 1e-5,
) -> jnp.ndarray:
    """Pruned path (paper §3.3.2, static capacity).

    Args:
      frames: (B, H, W).
      sel_idx: (B, K_sel) int32 — patch indices to encode (group-complete;
        padded entries repeat index 0).
      sel_valid: (B, K_sel) bool — padding mask.

    Returns:
      (B, n_patches, d_vit) full-grid encoded patches, zeros at pruned
      positions (the projector then consumes the native layout).
    """
    B = frames.shape[0]
    x = patchify(frames, v).astype(params["patch_embed"].dtype)
    emb = x @ params["patch_embed"] + params["pos_embed"][None]   # (B, P, d)
    sel = jnp.take_along_axis(emb, sel_idx[..., None], axis=1)    # (B, K, d)
    h = _encoder(params, v, sel, sel_valid, eps)
    h = jnp.where(sel_valid[..., None], h, 0)
    full = jnp.zeros((B, v.n_patches, v.d_model), h.dtype)
    # scatter back; padded lanes all hit index 0 with zero contribution
    full = full.at[jnp.arange(B)[:, None], sel_idx].add(h)
    return full


def project(params, v: ViTCfg, patch_feats: jnp.ndarray) -> jnp.ndarray:
    """2x2 pixel-unshuffle + linear projection to LM width.

    patch_feats: (B, n_patches, d_vit) in row-major patch order.
    Returns (B, n_groups, d_lm).
    """
    B = patch_feats.shape[0]
    pp, g = v.patches_per_side, v.group
    gs = v.groups_per_side
    x = patch_feats.reshape(B, gs, g, gs, g, v.d_model)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(B, gs * gs, g * g * v.d_model)
    return x @ params["projector"]


def encode_pruned_tokens(
    params, v: ViTCfg, frames: jnp.ndarray,
    sel_idx: jnp.ndarray, sel_valid: jnp.ndarray, eps: float = 1e-5,
) -> jnp.ndarray:
    """Pruned ViT -> projected visual tokens (B, n_groups, d_lm)."""
    full = encode_pruned(params, v, frames, sel_idx, sel_valid, eps)
    return project(params, v, full)


# ======================================================================
# Packed variable-capacity path (cost proportional to kept content)
# ======================================================================
def _encoder_packed(
    params, v: ViTCfg, h: jnp.ndarray, seg_id: jnp.ndarray,
    tile_ids: jnp.ndarray, tile_count: jnp.ndarray, eps: float,
    tq: int, tk: int,
):
    """ViT blocks over packed rows; attention is block-diagonal per
    segment (frame) via ``ops.flash_packed``."""
    R, L, _ = h.shape
    dh = v.d_model // v.n_heads

    def body(h, lp):
        hn = layers.rmsnorm(lp["ln1"], h, eps)
        q = (hn @ lp["wq"]).reshape(R, L, v.n_heads, dh)
        k = (hn @ lp["wk"]).reshape(R, L, v.n_heads, dh)
        vv = (hn @ lp["wv"]).reshape(R, L, v.n_heads, dh)
        out = ops.flash_packed(q, k, vv, seg_id, tile_ids, tile_count,
                               tq=tq, tk=tk)
        h = h + out.reshape(R, L, v.d_model) @ lp["wo"]
        hn = layers.rmsnorm(lp["ln2"], h, eps)
        return h + layers.mlp_block(lp["ffn"], hn), None

    h, _ = jax.lax.scan(body, h, params["blocks"])
    return layers.rmsnorm(params["final_norm"], h, eps)


@functools.partial(
    jax.jit, static_argnames=("v", "n_out", "tq", "tk", "eps")
)
def encode_packed_tokens(
    params, v: ViTCfg, frames: jnp.ndarray,
    patch_src: jnp.ndarray, seg_id: jnp.ndarray,
    group_src: jnp.ndarray, group_dst: jnp.ndarray,
    tile_ids: jnp.ndarray, tile_count: jnp.ndarray,
    n_out: int, tq: int = 128, tk: int = 128, eps: float = 1e-5,
) -> jnp.ndarray:
    """Packed pruned ViT -> projected visual tokens, flat (n_out, d_lm).

    Index arrays come from a ``core.pruning.PackPlan`` (host-built,
    bucket-shaped): compute at every stage is proportional to kept
    content instead of the padded ``K_sel`` capacity —

      * patch embedding runs on the gathered kept patches only (the
        padded path embeds the FULL grid before gathering);
      * the encoder runs over ``rows * L_pack`` packed slots with
        block-diagonal attention (dead cross-frame tiles skipped by the
        kernel's visit list);
      * the projector consumes only the ``k_pack`` kept group rows and
        scatters tokens to their ``(frame, slot)`` destinations —
        no full-grid scatter + dense ``n_groups`` matmul.

    Returns (n_out, d_lm); slots of dropped/invalid groups are zeros,
    matching ``encode_pruned_tokens``'s masked semantics.
    """
    x = patchify(frames, v).astype(params["patch_embed"].dtype)
    flat = x.reshape(-1, x.shape[-1])                     # (B*P, patch^2)
    sel = flat[patch_src]                                 # (R, Lp, patch^2)
    pos = params["pos_embed"][patch_src % v.n_patches]
    h = sel @ params["patch_embed"] + pos                 # (R, Lp, d)
    h = _encoder_packed(params, v, h, seg_id, tile_ids, tile_count,
                        eps, tq, tk)
    R, Lp, d = h.shape
    hf = h.reshape(R * Lp, d)
    g2 = v.group ** 2
    grp = hf[group_src.reshape(-1)].reshape(-1, g2 * d)   # (Kp, g^2*d)
    tok = grp @ params["projector"]                       # (Kp, d_lm)
    out = jnp.zeros((n_out + 1, tok.shape[-1]), tok.dtype)
    out = out.at[group_dst].set(tok)                      # pad row -> n_out
    return out[:n_out]
