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
  5. language model over [frame tokens..., query tokens] with RoPE
     (rotate-half), GQA, SwiGLU: a full causal prefill on a stream's
     first window; on every later window the overlap's keys and values
     move ``shift`` slots left with their positions (keys re-rotated),
     and the refresh set (I-frame anchors of the overlap, the new
     stride, the query) is recomputed against that cache;
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
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST

# the serving protocol: the standing question's tokens and the answers
QUERY_IDS = (5, 6, 7, 8, 9, 10, 11, 12)
YES, NO = 2, 3

FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def _fp8(x, axis):
    """Round ``x`` through float8 e4m3 with a scale per slice along
    every axis but ``axis`` (the contraction axis)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(FP8).astype(F32) * s


def _round(x, axis, rnd: str):
    """A matrix product's operand as the precision ``rnd`` holds it: as
    it is (""), rounded to bfloat16, or through scaled float8."""
    if rnd == "fp8":
        return _fp8(x, axis)
    if rnd == "bf16":
        return x.astype(jnp.bfloat16).astype(F32)
    return x


def _mm(a, b, rnd: str):
    """a (..., k) @ b (k, n)."""
    a, b = _round(a, -1, rnd), _round(b, 0, rnd)
    return jnp.matmul(a, b, precision=HIGHEST)


def _einsum(spec, a, b, rnd: str, axes=(-1, -1)):
    a, b = _round(a, axes[0], rnd), _round(b, axes[1], rnd)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def _swiglu(p, x, rnd):
    h = jax.nn.silu(_mm(x, p["wg"].astype(F32), rnd)) * _mm(
        x, p["wu"].astype(F32), rnd)
    return _mm(h, p["wd"].astype(F32), rnd)


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
    h = _mm(x, vp["patch_embed"].astype(F32), rnd) + \
        vp["pos_embed"].astype(F32)[patch_ids]
    n, d = h.shape
    dh = d // nh

    def layer(h, lp):
        hn = _rmsnorm(h, lp["ln1"]["scale"], eps)
        q = _mm(hn, lp["wq"].astype(F32), rnd).reshape(n, nh, dh)
        k = _mm(hn, lp["wk"].astype(F32), rnd).reshape(n, nh, dh)
        v = _mm(hn, lp["wv"].astype(F32), rnd).reshape(n, nh, dh)
        s = _einsum("qhd,khd->hqk", q, k, rnd) * dh ** -0.5
        s = jnp.where(patch_valid[None, None, :], s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o = _einsum("hqk,khd->qhd", a, v, rnd, axes=(-1, 0)).reshape(n, d)
        h = h + _mm(o, lp["wo"].astype(F32), rnd)
        hn = _rmsnorm(h, lp["ln2"]["scale"], eps)
        return h + _swiglu(lp["ffn"], hn, rnd), None

    h, _ = jax.lax.scan(layer, h, vp["blocks"])
    return _rmsnorm(h, vp["final_norm"]["scale"], eps)


@functools.partial(jax.jit, static_argnames=("cfg", "rnd"))
def vit_tokens(vp, frames, patch_ids, patch_valid, cfg, rnd: str):
    """Tokens (n, k, d_lm) of n frames: each frame's patches
    ``patch_ids`` (n, k*g*g) in slot order, projected per 2x2 group;
    zero where the slot is not valid."""
    feats = jax.vmap(lambda f, i, v: _vit_frame(vp, f, i, v, cfg, rnd))(
        frames, patch_ids, patch_valid)
    n, m, d = feats.shape
    g2 = cfg["group"] ** 2
    tok = _mm(feats.reshape(n, m // g2, g2 * d),
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
def _rope_tables(n: int, dh: int, theta: float):
    half = dh // 2
    freqs = 1.0 / (theta ** (np.arange(half, dtype=np.float64) / half))
    ang = np.arange(n, dtype=np.float64)[:, None] * freqs[None]
    return (jnp.asarray(np.cos(ang), F32), jnp.asarray(np.sin(ang), F32))


def _rope(x, cos, sin):
    """x (n, h, dh); cos/sin (n, dh/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c, s = cos[:, None], sin[:, None]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _lm_pass(blocks, h, pos, idx, K, V, kvmask, cos, sin, lm, rnd, q_chunk):
    """Run the refresh set ``idx`` (positions ``pos``) through every
    layer against the cache; returns (h, K, V).  K holds keys before
    RoPE, so a key's rotation always follows its current slot."""
    H, Kh, dh = lm["n_heads"], lm["n_kv"], lm["d_head"]
    g = H // Kh
    eps = lm["norm_eps"]
    n = h.shape[0]
    S = K.shape[1]
    kpos = jnp.arange(S)
    pad = (-n) % q_chunk

    def layer(h, xs):
        lp, Kl, Vl = xs
        mp = lp["mixer"]
        hn = _rmsnorm(h, lp["ln1"]["scale"], eps)
        q = _mm(hn, mp["wq"].astype(F32), rnd)
        k = _mm(hn, mp["wk"].astype(F32), rnd)
        v = _mm(hn, mp["wv"].astype(F32), rnd)
        if lm["qkv_bias"]:
            q, k, v = (q + mp["bq"].astype(F32), k + mp["bk"].astype(F32),
                       v + mp["bv"].astype(F32))
        Kl = Kl.at[idx].set(k.reshape(n, Kh, dh))
        Vl = Vl.at[idx].set(v.reshape(n, Kh, dh))
        qr = _rope(q.reshape(n, H, dh), cos[pos], sin[pos]) * dh ** -0.5
        kr = _rope(Kl, cos[:S], sin[:S])
        qr = jnp.pad(qr, ((0, pad), (0, 0), (0, 0))).reshape(
            -1, q_chunk, Kh, g, dh)
        qp = jnp.pad(pos, (0, pad)).reshape(-1, q_chunk)

        def attend(args):
            qc, pc = args
            s = _einsum("tkgd,skd->kgts", qc, kr, rnd)
            m = kvmask[None, :] & (kpos[None, :] <= pc[:, None])
            s = jnp.where(m[None, None], s, -jnp.inf)
            a = jax.nn.softmax(s, axis=-1)
            return _einsum("kgts,skd->tkgd", a, Vl, rnd, axes=(-1, 0))

        o = jax.lax.map(attend, (qr, qp)).reshape(-1, H * dh)[:n]
        h = h + _mm(o, mp["wo"].astype(F32), rnd)
        hn = _rmsnorm(h, lp["ln2"]["scale"], eps)
        return h + _swiglu(lp["ffn"], hn, rnd), (Kl, Vl)

    h, (K, V) = jax.lax.scan(layer, h, (blocks, K, V))
    return h, K, V


def _lm_window(params, embeds, idx, K, V, kvmask, cos, sin, lm, rnd,
               q_chunk=512):
    """One window: refresh slots ``idx`` of ``embeds`` (a slot is its
    position); returns (last position's final hidden state, K, V)."""
    h = embeds[idx]
    h, K, V = _lm_pass(params["blocks"][0], h, idx, idx, K, V, kvmask,
                       cos, sin, lm, rnd, q_chunk)
    return _rmsnorm(h[-1], params["final_norm"]["scale"], lm["norm_eps"]), K, V


HEAD_BLOCK = 8192


@functools.partial(jax.jit, static_argnames=("tied", "rnd"))
def lm_head(params, hn, tied: bool, rnd: str):
    """All logits (V,) of one final hidden state; the head is read in
    blocks of rows so it never exists whole in float32."""
    head = params["embed"] if tied else params["lm_head"]
    axis = 0 if tied else 1                     # the vocabulary axis
    Vn = head.shape[axis]
    B = min(HEAD_BLOCK, Vn)
    n = -(-Vn // B)

    def one(i):
        start = jnp.minimum(i * B, Vn - B)
        w = jax.lax.dynamic_slice_in_dim(head, start, B, axis)
        w = w.astype(F32)
        return _mm(w, hn[:, None], rnd)[:, 0] if tied else \
            _mm(hn[None], w, rnd)[0]

    out = jax.lax.map(one, jnp.arange(n))        # (n, B)
    # the last block is shifted back to end at Vn; take its tail
    last = Vn - (n - 1) * B
    return jnp.concatenate([out[:-1].reshape(-1), out[-1, B - last:]])


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


class _Frozen(dict):
    """A hashable read-only dict, for static jit arguments."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


class Reference:
    """Serves one stream's windows in float32, or with every matrix
    product's operands rounded to bfloat16 or (the control) float8."""

    VIT_CHUNK = 8        # frames per vision-tower call

    def __init__(self, lm: Dict[str, Any], vit: Dict[str, Any],
                 codec: Dict[str, Any], params, vparams,
                 precision: str = "f32"):
        if precision not in ("f32", "bf16", "fp8"):
            raise ValueError(precision)
        if codec.get("alpha", 0.0) != 0.0:
            raise ValueError("the reference's motion score is the motion-"
                             "vector magnitude alone (alpha = 0)")
        self.lm, self.vit, self.codec = lm, vit, codec
        self.params, self.vparams = params, vparams
        self.rnd = "" if precision == "f32" else precision
        self.layout = Layout(codec, vit)
        self._lmk = _Frozen({k: lm[k] for k in (
            "n_heads", "n_kv", "d_head", "norm_eps", "qkv_bias")})
        self._vitk = _Frozen({k: vit[k] for k in (
            "patch", "n_heads", "norm_eps", "group")})
        self.cos, self.sin = _rope_tables(self.layout.total, lm["d_head"],
                                          lm["rope_theta"])
        self.qe = params["embed"][jnp.asarray(QUERY_IDS)].astype(F32)
        # the cache is consumed by each window: donate it, where the
        # backend implements donation
        donate = () if jax.default_backend() == "cpu" else (3, 4)
        self._window = jax.jit(_lm_window, static_argnames=("lm", "rnd"),
                               donate_argnums=donate)

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
        lay, lm = self.layout, self.lm
        toks, vals = self.frame_tokens(frames)
        L, Kh, dh = lm["n_layers"], lm["n_kv"], lm["d_head"]
        K = jnp.zeros((L, lay.total, Kh, dh), F32)
        V = jnp.zeros((L, lay.total, Kh, dh), F32)
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
                K = K.at[:, :ov].set(K[:, sh:vl])
                V = V.at[:, :ov].set(V[:, sh:vl])
                kvmask = np.zeros(lay.total, bool)
                kvmask[:ov] = prev_valid[sh:vl]
                idx = lay.refresh
                kvmask[idx] = valid[idx]
            hn, K, V = self._window(
                self.params, embeds, jnp.asarray(idx), K, V,
                jnp.asarray(kvmask), self.cos, self.sin, lm=self._lmk,
                rnd=self.rnd)
            out.append(np.asarray(lm_head(
                self.params, hn, bool(lm["tied_embeddings"]), self.rnd)))
            prev_valid = valid
        return out
