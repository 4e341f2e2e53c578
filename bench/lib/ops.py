"""The plain reference's arithmetic, shared by ``reference.py`` and the
LM blocks (``bench/lm/<block>.py``), so that every part of the
reference rounds alike.

Every matrix product runs in float32 at ``highest`` precision, its
operands first rounded as ``rnd`` says: as they are (""), to bfloat16
(``"bf16"``), or through float8 e4m3 scaled per row and per column
(``"fp8"``, the control).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST

FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def fp8(x, axis):
    """Round ``x`` through float8 e4m3 with a scale per slice along
    every axis but ``axis`` (the contraction axis)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / s).astype(FP8).astype(F32) * s


def round_to(x, axis, rnd: str):
    """A matrix product's operand as the precision ``rnd`` holds it: as
    it is (""), rounded to bfloat16, or through scaled float8."""
    if rnd == "fp8":
        return fp8(x, axis)
    if rnd == "bf16":
        return x.astype(jnp.bfloat16).astype(F32)
    return x


def mm(a, b, rnd: str):
    """a (..., k) @ b (k, n)."""
    a, b = round_to(a, -1, rnd), round_to(b, 0, rnd)
    return jnp.matmul(a, b, precision=HIGHEST)


def einsum(spec, a, b, rnd: str, axes=(-1, -1)):
    """``jnp.einsum`` of two operands, contracted along ``axes``."""
    a, b = round_to(a, axes[0], rnd), round_to(b, axes[1], rnd)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rmsnorm(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale


def swiglu(p, x, rnd):
    """SwiGLU FFN with weights ``p["wg"]``, ``p["wu"]``, ``p["wd"]``."""
    h = jax.nn.silu(mm(x, p["wg"].astype(F32), rnd)) * mm(
        x, p["wu"].astype(F32), rnd)
    return mm(h, p["wd"].astype(F32), rnd)


class Frozen(dict):
    """A hashable read-only dict, for static jit arguments."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))
