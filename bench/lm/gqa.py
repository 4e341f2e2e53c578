"""The dense block: grouped-query attention with RoPE (rotate-half) and
optional q/k/v biases, a SwiGLU FFN, RMSNorm before each, one dense FFN
per layer (Qwen2.5, InternVL3's language models).

Its plain reference holds a key/value cache of shape
``(L, total, n_kv, d_head)`` with keys before RoPE, so a key's rotation
always follows its current slot.  Every matrix product goes through the
reference's arithmetic (``bench/lib/ops.py``), so that it rounds as the
rest of the reference does.
"""
from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import flops
from bench.lib.ops import F32, Frozen, einsum, mm, rmsnorm, swiglu
from bench.lib.weights import BF16, Leaf, dense

KERNELS = ("flash_refresh_paged",)

CFG_FIELDS = ("n_layers", "d_model", "n_heads", "n_kv", "d_head", "d_ff",
              "vocab", "qkv_bias", "rope_theta", "norm_eps",
              "tied_embeddings", "dtype", "img_tokens")


def model_cfg(conf: Dict[str, Any], vit):
    """The program's ModelCfg: a registry entry by name, checked against
    the file's numbers, or the file's inline numbers."""
    from repro.configs import ModelCfg, get_config

    lm = dict(conf["lm"])
    fields = {k: lm[k] for k in CFG_FIELDS}
    if conf.get("arch"):
        cfg = get_config(conf["arch"])
        got = {k: getattr(cfg, k) for k in fields}
        if got != fields or cfg.vit != vit or cfg.family != "vlm":
            raise ValueError(f"registry entry {conf['arch']} differs from "
                             f"its configuration file: {got} vs {fields}")
        return cfg
    return ModelCfg(name=conf["name"], family="vlm", vit=vit,
                    source=conf["source"], **fields)


# ======================================================================
# weights
# ======================================================================
def layout(lm: Dict[str, Any]) -> Dict[str, Any]:
    d, H, K = lm["d_model"], lm["n_heads"], lm["n_kv"]
    dh, f, R, V = lm["d_head"], lm["d_ff"], lm["n_layers"], lm["vocab"]
    mixer = {
        "wq": dense((R, d, H * dh), d),
        "wk": dense((R, d, K * dh), d),
        "wv": dense((R, d, K * dh), d),
        "wo": dense((R, H * dh, d), H * dh),
    }
    if lm["qkv_bias"]:
        mixer["bq"] = Leaf((R, H * dh), BF16, "bias", 0.02)
        mixer["bk"] = Leaf((R, K * dh), BF16, "bias", 0.02)
        mixer["bv"] = Leaf((R, K * dh), BF16, "bias", 0.02)
    block = {
        "ln1": {"scale": Leaf((R, d), F32, "norm")},
        "ln2": {"scale": Leaf((R, d), F32, "norm")},
        "mixer": mixer,
        "ffn": {"wg": dense((R, d, f), d), "wu": dense((R, d, f), d),
                "wd": dense((R, f, d), f)},
    }
    tree = {"embed": dense((V, d), scale=0.02),
            "final_norm": {"scale": Leaf((d,), F32, "norm")}}
    if not lm["tied_embeddings"]:
        tree["lm_head"] = dense((d, V), d)
    tree["blocks"] = (block,)
    return tree


# ======================================================================
# work counts
# ======================================================================
def work(q, pairs: float, n_valid: int, w: Dict[str, Any],
         lm: Dict[str, Any]) -> Dict[str, float]:
    """The LM's part of one window's work (``flops.window_work``): the
    refreshed valid queries ``q``, their causal (query, key) ``pairs``,
    and the window's ``n_valid`` positions that hold a token."""
    L, H, K, dh = lm["n_layers"], lm["n_heads"], lm["n_kv"], lm["d_head"]
    lm_f = L * (len(q) * flops.layer_flops_per_token(lm)
                + flops.attn_flops(lm, pairs))
    head = 2.0 * lm["d_model"] * lm["vocab"]
    # the decode step attends every valid position and itself
    decode = L * (flops.layer_flops_per_token(lm)
                  + flops.attn_flops(lm, n_valid + 1)) + head
    # paged flash attention (prefill or refresh, then the decode step):
    # Q in and O out per valid query, K and V in once per valid key
    ra_f = L * (flops.attn_flops(lm, pairs) + flops.attn_flops(lm, n_valid + 1))
    ra_b = L * 2.0 * (2 * (len(q) + 1) * H * dh + 2 * 2 * (n_valid + 1) * K * dh)
    return {"lm": lm_f, "head": head, "decode": decode,
            "refresh_attn_flops": ra_f, "refresh_attn_bytes": ra_b}


# ======================================================================
# plain reference
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
        hn = rmsnorm(h, lp["ln1"]["scale"], eps)
        q = mm(hn, mp["wq"].astype(F32), rnd)
        k = mm(hn, mp["wk"].astype(F32), rnd)
        v = mm(hn, mp["wv"].astype(F32), rnd)
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
            s = einsum("tkgd,skd->kgts", qc, kr, rnd)
            m = kvmask[None, :] & (kpos[None, :] <= pc[:, None])
            s = jnp.where(m[None, None], s, -jnp.inf)
            a = jax.nn.softmax(s, axis=-1)
            return einsum("kgts,skd->tkgd", a, Vl, rnd, axes=(-1, 0))

        o = jax.lax.map(attend, (qr, qp)).reshape(-1, H * dh)[:n]
        h = h + mm(o, mp["wo"].astype(F32), rnd)
        hn = rmsnorm(h, lp["ln2"]["scale"], eps)
        return h + swiglu(lp["ffn"], hn, rnd), (Kl, Vl)

    h, (K, V) = jax.lax.scan(layer, h, (blocks, K, V))
    return h, K, V


def _lm_window(params, embeds, idx, K, V, kvmask, cos, sin, lm, rnd,
               q_chunk=512):
    """One window: refresh slots ``idx`` of ``embeds`` (a slot is its
    position); returns (last position's final hidden state, K, V)."""
    h = embeds[idx]
    h, K, V = _lm_pass(params["blocks"][0], h, idx, idx, K, V, kvmask,
                       cos, sin, lm, rnd, q_chunk)
    return rmsnorm(h[-1], params["final_norm"]["scale"], lm["norm_eps"]), K, V


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
        return mm(w, hn[:, None], rnd)[:, 0] if tied else \
            mm(hn[None], w, rnd)[0]

    out = jax.lax.map(one, jnp.arange(n))        # (n, B)
    # the last block is shifted back to end at Vn; take its tail
    last = Vn - (n - 1) * B
    return jnp.concatenate([out[:-1].reshape(-1), out[-1, B - last:]])


class Reference:
    """The LM half of the plain reference, over windows of ``total``
    slots, every matrix product's operands rounded as ``rnd`` says."""

    def __init__(self, lm: Dict[str, Any], params, total: int, rnd: str):
        self.lm, self.params, self.total, self.rnd = lm, params, total, rnd
        self._lmk = Frozen({k: lm[k] for k in (
            "n_heads", "n_kv", "d_head", "norm_eps", "qkv_bias")})
        self.cos, self.sin = _rope_tables(total, lm["d_head"],
                                          lm["rope_theta"])
        # the cache is consumed by each window: donate it, where the
        # backend implements donation
        donate = () if jax.default_backend() == "cpu" else (3, 4)
        self._window = jax.jit(_lm_window, static_argnames=("lm", "rnd"),
                               donate_argnums=donate)

    def embed(self, ids):
        return self.params["embed"][jnp.asarray(ids)].astype(F32)

    def cache(self):
        """A stream's empty cache: keys and values of every slot."""
        L, Kh, dh = self.lm["n_layers"], self.lm["n_kv"], self.lm["d_head"]
        K = jnp.zeros((L, self.total, Kh, dh), F32)
        V = jnp.zeros((L, self.total, Kh, dh), F32)
        return K, V

    def slide(self, cache, sh: int, ov: int, vl: int):
        """The overlap's ``ov`` slots, ``sh`` slots left, from
        ``[sh, vl)`` to ``[0, ov)``."""
        K, V = cache
        K = K.at[:, :ov].set(K[:, sh:vl])
        V = V.at[:, :ov].set(V[:, sh:vl])
        return K, V

    def window(self, embeds, idx, cache, kvmask):
        """Refresh slots ``idx`` of ``embeds`` against the cache, keys
        ``kvmask`` visible; returns (final hidden state, cache)."""
        K, V = cache
        hn, K, V = self._window(
            self.params, embeds, idx, K, V, kvmask, self.cos, self.sin,
            lm=self._lmk, rnd=self.rnd)
        return hn, (K, V)

    def head(self, hn) -> np.ndarray:
        return np.asarray(lm_head(
            self.params, hn, bool(self.lm["tied_embeddings"]), self.rnd))
