"""Pallas TPU kernel: RoPE position correction of cached keys (Eq. 5).

K_hat(j) = R(p_new(j) - p_old(j)) K(j)

This runs once per sliding-window advance over the *reused* region of the
KV cache, so it is on the critical path of CodecFlow's selective refresh.
One VMEM pass: the key tile and its per-token delta tile are loaded, the
rotation angles are synthesized in-register from an iota (no cos/sin
tables in HBM), and the rotated tile is written back.

Tiling: grid (B, S/Ts); block (1, Ts, n_kv, d_h).  d_h is 64–128 for all
assigned archs -> the lane dim holds a full head; n_kv*Ts rows per tile.
The deltas ride as a (B, S, 1) column so each program's (Ts, 1) block
puts the tokens on sublanes, matching the key tile (a (1, Ts) block of a
(B, S) array has a second-minor dim the TPU tiling refuses).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rope_shift_kernel(k_ref, delta_ref, out_ref, *, theta: float):
    k = k_ref[...].astype(jnp.float32)        # (1, Ts, Hk, D)
    delta = delta_ref[...].astype(jnp.float32)  # (1, Ts, 1)
    d_h = k.shape[-1]
    half = d_h // 2
    # TPU iota is integer-only: build the exponent ramp in int32
    ramp = jax.lax.iota(jnp.int32, half).astype(jnp.float32)
    freqs = 1.0 / (theta ** (ramp / half))
    ang = delta * freqs                       # (1, Ts, half)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    k1, k2 = k[..., :half], k[..., half:]
    out = jnp.concatenate([k1 * cos - k2 * sin, k2 * cos + k1 * sin], axis=-1)
    out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("theta", "seq_tile", "interpret")
)
def rope_shift_pallas(
    k: jnp.ndarray,
    delta: jnp.ndarray,
    theta: float = 10_000.0,
    seq_tile: int = 128,
    interpret: bool = False,
):
    """Rotate cached keys by per-token position deltas.

    Args:
      k: (B, S, n_kv, d_h); delta: (B, S) int32.
    Returns: corrected keys, dtype of ``k``.
    """
    B, S, Hk, D = k.shape
    ts = min(seq_tile, S)
    assert S % ts == 0, (S, ts)
    return pl.pallas_call(
        functools.partial(_rope_shift_kernel, theta=theta),
        grid=(B, S // ts),
        in_specs=[
            pl.BlockSpec((1, ts, Hk, D), lambda b, i: (b, i, 0, 0)),
            pl.BlockSpec((1, ts, 1), lambda b, i: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, ts, Hk, D), lambda b, i: (b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(k.shape, k.dtype),
        interpret=interpret,
    )(k, delta.reshape(B, S, 1))
