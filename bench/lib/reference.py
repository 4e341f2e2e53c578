"""Plain float32 reference of the served computation.

What one camera stream is served, written out from the system's
description and independent of the program (it imports nothing of it):

  1. codec: GOP-structured block-matching encoder (I-frames quantized
     with step 2; P-frames motion-compensated from the reconstructed
     previous frame, full search over +-radius px, first minimum in
     raster order of the displacement, residual quantized with step 4)
     and its reconstruction, which is what the vision tower sees;
  2. motion mask: block motion-vector magnitude resampled to the patch
     grid, dynamic where it reaches the threshold, accumulated over
     the GOP, I-frames fully dynamic;
  3. token selection: per P-frame, 2x2 patch groups ranked dynamic
     first then by motion score (lower index first on ties), the top
     ``k_tokens`` slots kept, only dynamic groups valid;
  4. vision tower: patch embedding + position embedding, pre-norm
     blocks (RMSNorm, bidirectional multi-head attention over the
     frame's kept patches, SwiGLU), final RMSNorm, 2x2 pixel-unshuffle
     projection to the LM width;
  5. language model over [frame tokens..., query tokens], its layers
     the configuration's block (``bench/lm/<block>.py``, which holds
     the stream's cache): a full causal prefill on a stream's first
     window; on every later window the overlap's cache entries move
     ``shift`` slots left with their positions, and the refresh set
     (I-frame anchors of the overlap, the new stride, the query) is
     recomputed against that cache;
  6. the answer: the last position's yes/no logits.

Everything is float32 with every matrix product at ``highest``
precision, layer by layer, so that it fits beside the served weights.
``precision="fp8"`` is the control: every matrix product's operands
rounded to float8 (e4m3, scaled per row and per column), the precision
step below the bfloat16 the configurations serve in.
``precision="bf16"`` rounds them to bfloat16 instead: the error of
plain arithmetic in the served precision, the yardstick that the
program's error is measured in.
"""
from __future__ import annotations

import functools
from types import ModuleType
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from .ops import F32, Frozen, einsum, mm, rmsnorm, swiglu

# the serving protocol: the standing question's tokens and the answers
QUERY_IDS = (5, 6, 7, 8, 9, 10, 11, 12)
YES, NO = 2, 3


# ======================================================================
# 1-3. codec, motion mask, token selection (exact integer arithmetic)
# ======================================================================
@functools.partial(jax.jit, static_argnames=("gop", "block", "radius"))
def codec(frames, gop: int, block: int, radius: int):
    """frames (T, H, W) uint8 -> (reconstructed frames (T, H, W) f32,
    motion vectors (T, H/block, W/block, 2) int32 as (dy, dx)).

    Reads outside the reference frame take its edge pixel."""
    T, H, W = frames.shape
    hb, wb = H // block, W // block
    n = 2 * radius + 1

    def step(prev, inp):
        frame, t = inp
        x = frame.astype(F32)
        pad = jnp.pad(prev, radius, mode="edge")

        def sad(c):
            win = jax.lax.dynamic_slice(pad, (c // n, c % n), (H, W))
            d = jnp.abs(x - win)
            return d.reshape(hb, block, wb, block).sum(axis=(1, 3))

        sads = jax.lax.map(sad, jnp.arange(n * n))        # (n*n, hb, wb)
        best = jnp.argmin(sads, axis=0)                   # first minimum
        mv = jnp.stack([best // n - radius, best % n - radius], -1)
        is_i = t % gop == 0
        mv = jnp.where(is_i, 0, mv).astype(jnp.int32)
        yy = jnp.arange(H)[:, None] + jnp.repeat(mv[..., 0], block, 0).repeat(block, 1)
        xx = jnp.arange(W)[None, :] + jnp.repeat(mv[..., 1], block, 0).repeat(block, 1)
        pred = prev[jnp.clip(yy, 0, H - 1), jnp.clip(xx, 0, W - 1)]
        recon_p = pred + jnp.round((x - pred) / 4.0) * 4.0
        recon_i = jnp.round(x / 2.0) * 2.0
        recon = jnp.where(is_i, recon_i, recon_p)
        return recon, (recon, mv)

    _, (recon, mv) = jax.lax.scan(
        step, jnp.zeros((H, W), F32), (frames, jnp.arange(T)))
    return recon, mv


def select(mv: np.ndarray, gop: int, pp: int, group: int, k_tokens: int,
           tau: float):
    """Per frame: (slot group indices (T, k), slot valid (T, k)).

    I-frames keep every group in raster order (all valid)."""
    T, hb, wb = mv.shape[:3]
    mag = np.sqrt((mv.astype(np.float32) ** 2).sum(-1)).astype(np.float32)
    ys = (np.arange(pp) * hb) // pp
    xs = (np.arange(pp) * wb) // pp
    score = mag[:, ys[:, None], xs[None, :]]              # (T, pp, pp)
    own = score >= tau
    dyn = np.zeros_like(own)
    acc = np.zeros(own.shape[1:], bool)
    for t in range(T):
        if t % gop == 0:
            acc[:] = False
            dyn[t] = True
        else:
            acc |= own[t]
            dyn[t] = acc
    gs = pp // group
    gd = dyn.reshape(T, gs, group, gs, group).any(axis=(2, 4)).reshape(T, -1)
    gscore = score.reshape(T, gs, group, gs, group).max(axis=(2, 4)).reshape(T, -1)
    rank = np.where(gd, gscore + np.float32(1e6), gscore).astype(np.float32)
    idx = np.argsort(-rank, axis=1, kind="stable")[:, :k_tokens]
    valid = np.take_along_axis(gd, idx, axis=1)
    return idx.astype(np.int32), valid


# ======================================================================
# 4. vision tower
# ======================================================================
def _patchify(frame, patch):
    H, W = frame.shape
    pp = H // patch
    x = frame.reshape(pp, patch, pp, patch).transpose(0, 2, 1, 3)
    return x.reshape(pp * pp, patch * patch) / 127.5 - 1.0


def _vit_frame(vp, frame, patch_ids, patch_valid, cfg, rnd: str):
    """Encoded patch features (n, d) of ``patch_ids`` of one frame;
    attention over the valid ones only."""
    patch, nh, eps = cfg["patch"], cfg["n_heads"], cfg["norm_eps"]
    x = _patchify(frame, patch)[patch_ids]
    h = mm(x, vp["patch_embed"].astype(F32), rnd) + \
        vp["pos_embed"].astype(F32)[patch_ids]
    n, d = h.shape
    dh = d // nh

    def layer(h, lp):
        hn = rmsnorm(h, lp["ln1"]["scale"], eps)
        q = mm(hn, lp["wq"].astype(F32), rnd).reshape(n, nh, dh)
        k = mm(hn, lp["wk"].astype(F32), rnd).reshape(n, nh, dh)
        v = mm(hn, lp["wv"].astype(F32), rnd).reshape(n, nh, dh)
        s = einsum("qhd,khd->hqk", q, k, rnd) * dh ** -0.5
        s = jnp.where(patch_valid[None, None, :], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o = einsum("hqk,khd->qhd", a, v, rnd, axes=(-1, 0)).reshape(n, d)
        h = h + mm(o, lp["wo"].astype(F32), rnd)
        hn = rmsnorm(h, lp["ln2"]["scale"], eps)
        return h + swiglu(lp["ffn"], hn, rnd), None

    h, _ = jax.lax.scan(layer, h, vp["blocks"])
    return rmsnorm(h, vp["final_norm"]["scale"], eps)


@functools.partial(jax.jit, static_argnames=("cfg", "rnd"))
def vit_tokens(vp, frames, patch_ids, patch_valid, cfg, rnd: str):
    """Tokens (n, k, d_lm) of n frames: each frame's patches
    ``patch_ids`` (n, k*g*g) in slot order, projected per 2x2 group;
    zero where the slot is not valid."""
    feats = jax.vmap(lambda f, i, v: _vit_frame(vp, f, i, v, cfg, rnd))(
        frames, patch_ids, patch_valid)
    n, m, d = feats.shape
    g2 = cfg["group"] ** 2
    tok = mm(feats.reshape(n, m // g2, g2 * d),
             vp["projector"].astype(F32), rnd)
    gval = patch_valid.reshape(n, m // g2, g2)[..., 0]
    return jnp.where(gval[..., None], tok, 0.0)


def group_patches(gidx: np.ndarray, pp: int, g: int) -> np.ndarray:
    """Patch indices (..., k*g*g) of the groups ``gidx`` (..., k), each
    group's patches in raster order (pixel-unshuffle order)."""
    gs = pp // g
    gy, gx = gidx // gs, gidx % gs
    dy = np.arange(g)[:, None]
    dx = np.arange(g)[None, :]
    pid = (gy[..., None, None] * g + dy) * pp + gx[..., None, None] * g + dx
    return pid.reshape(gidx.shape[:-1] + (-1,)).astype(np.int32)


# ======================================================================
# 5-6. language model over one stream's windows
# ======================================================================
class Layout:
    """Static token geometry of a window (frames then query tokens)."""

    def __init__(self, codec: Dict[str, Any], vit: Dict[str, Any]):
        self.gop = codec["gop"]
        self.window = codec["window_frames"]
        self.stride = codec["stride_frames"]
        pp = vit["image"] // vit["patch"]
        self.g_tokens = (pp // vit["group"]) ** 2
        self.k_tokens = max(1, min(self.g_tokens, int(
            np.ceil(codec["keep_ratio"] * self.g_tokens))))
        self.tokens = [self.g_tokens if f % self.gop == 0 else self.k_tokens
                       for f in range(self.window)]
        self.vis_len = int(sum(self.tokens))
        self.total = self.vis_len + len(QUERY_IDS)
        self.shift = int(sum(self.tokens[: self.stride]))
        self.overlap = self.vis_len - self.shift
        offsets = np.concatenate([[0], np.cumsum(self.tokens)[:-1]])
        anchors = [np.arange(offsets[f], offsets[f] + self.g_tokens)
                   for f in range(0, self.window - self.stride, self.gop)]
        self.refresh = np.concatenate(
            anchors + [np.arange(self.overlap, self.total)]).astype(np.int32)


class Reference:
    """Serves one stream's windows in float32, or with every matrix
    product's operands rounded to bfloat16 or (the control) float8."""

    VIT_CHUNK = 8        # frames per vision-tower call

    def __init__(self, lm: Dict[str, Any], vit: Dict[str, Any],
                 codec: Dict[str, Any], params, vparams, block: ModuleType,
                 precision: str = "f32"):
        if precision not in ("f32", "bf16", "fp8"):
            raise ValueError(precision)
        if codec.get("alpha", 0.0) != 0.0:
            raise ValueError("the reference's motion score is the motion-"
                             "vector magnitude alone (alpha = 0)")
        self.vit, self.codec = vit, codec
        self.params, self.vparams = params, vparams
        self.rnd = "" if precision == "f32" else precision
        self.layout = Layout(codec, vit)
        self._vitk = Frozen({k: vit[k] for k in (
            "patch", "n_heads", "norm_eps", "group")})
        self.lm_ref = block.Reference(lm, params, self.layout.total,
                                      self.rnd)
        self.qe = self.lm_ref.embed(QUERY_IDS)

    def _tokens(self, recon, gidx, gval):
        """Tokens of the frames ``recon`` (n, H, W) for groups ``gidx``
        (n, k) in slot order, in fixed-size chunks."""
        v = self.vit
        pp = v["image"] // v["patch"]
        g2 = v["group"] ** 2
        pid = group_patches(gidx, pp, v["group"])
        pval = np.repeat(gval, g2, axis=1)
        n, c = len(gidx), self.VIT_CHUNK
        out = []
        for lo in range(0, n, c):
            sel = np.minimum(np.arange(lo, lo + c), n - 1)   # pad by repeat
            out.append(vit_tokens(self.vparams, recon[sel], pid[sel],
                                  pval[sel], self._vitk, self.rnd))
        return jnp.concatenate(out, 0)[:n]

    def frame_tokens(self, frames: np.ndarray):
        """Per frame of a clip: (tokens (slots, d) on the device, slot
        validity (slots,))."""
        c, v, lay = self.codec, self.vit, self.layout
        recon, mv = codec(jnp.asarray(frames), c["gop"], c["block"],
                          c["search_radius"])
        pp = v["image"] // v["patch"]
        gidx, gval = select(np.asarray(mv), c["gop"], pp, v["group"],
                            lay.k_tokens, c["mv_threshold"])
        T = frames.shape[0]
        is_i = np.arange(T) % c["gop"] == 0
        ii, pi = np.nonzero(is_i)[0], np.nonzero(~is_i)[0]
        full = np.broadcast_to(np.arange(lay.g_tokens, dtype=np.int32),
                               (len(ii), lay.g_tokens))
        ti = self._tokens(recon[ii], full, np.ones(full.shape, bool))
        tp = self._tokens(recon[pi], gidx[pi], gval[pi])
        toks, vals = [None] * T, [None] * T
        for j, t in enumerate(ii):
            toks[t], vals[t] = ti[j], np.ones(lay.g_tokens, bool)
        for j, t in enumerate(pi):
            toks[t], vals[t] = tp[j], gval[t]
        return toks, vals

    def serve(self, frames: np.ndarray, n_windows: int) -> List[np.ndarray]:
        """All logits (V,) of each of the stream's first ``n_windows``
        windows."""
        lay, lm = self.layout, self.lm_ref
        toks, vals = self.frame_tokens(frames)
        cache = lm.cache()
        out = []
        prev_valid = None
        for w in range(n_windows):
            f0 = w * lay.stride
            vis = jnp.concatenate(toks[f0: f0 + lay.window], 0)
            vval = np.concatenate(vals[f0: f0 + lay.window])
            embeds = jnp.concatenate([vis, self.qe], 0)
            valid = np.concatenate([vval, np.ones(len(QUERY_IDS), bool)])
            if w == 0:
                idx = np.arange(lay.total, dtype=np.int32)
                kvmask = valid.copy()
            else:
                sh, ov, vl = lay.shift, lay.overlap, lay.vis_len
                cache = lm.slide(cache, sh, ov, vl)
                kvmask = np.zeros(lay.total, bool)
                kvmask[:ov] = prev_valid[sh:vl]
                idx = lay.refresh
                kvmask[idx] = valid[idx]
            hn, cache = lm.window(embeds, jnp.asarray(idx), cache,
                                  jnp.asarray(kvmask))
            out.append(lm.head(hn))
            prev_valid = valid
        return out
