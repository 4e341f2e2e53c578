"""Pallas TPU kernel: Mamba-2 SSD chunked scan (arXiv:2405.21060).

State-space duality: within a chunk of Q timesteps the recurrence is a
small (Q x Q) masked matmul (MXU work); across chunks only the (P x N)
state is carried.  One grid program handles one (batch, head, chunk)
cell; the chunk axis is innermost/sequential and the state lives in VMEM
scratch, so HBM traffic is exactly one read of x/a/b/c and one write of y
— the TPU-native replacement for the paper-adjacent GPU scan kernels.

Grid: (B, H, L/Q).  B/C tensors are stored per-group (n_groups <= H) and
the group index is resolved in the BlockSpec index map, mirroring GQA.
The within-chunk cumulative log-decay is computed outside the kernel and
fed twice, as a (Q, 1) column and a (1, Q) row, so the kernel needs no
in-register cumsum or transpose (and every block's minor dims are full
tiles).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(
    x_ref, cc_ref, cr_ref, b_ref, c_ref, init_ref, y_ref, st_ref, state,
    *, q: int, n_chunks: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        state[...] = init_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)      # (Q, P)
    cum = cc_ref[0, 0]                       # (Q, 1) within-chunk cumsum
    cum_row = cr_ref[0, 0]                   # (1, Q) the same, as a row
    b = b_ref[0, 0].astype(jnp.float32)      # (Q, N)
    c = c_ref[0, 0].astype(jnp.float32)      # (Q, N)

    # intra-chunk: y[t] = sum_{s<=t} exp(cum_t - cum_s) (c_t . b_s) x_s
    seg = cum - cum_row                      # (Q, Q) t, s
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    decay = jnp.where(t_idx >= s_idx, jnp.exp(seg), 0.0)
    cb = jax.lax.dot_general(
        c, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                         # (Q, Q)
    y = jax.lax.dot_general(
        cb * decay, x, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                         # (Q, P)

    # inter-chunk: y[t] += exp(cum_t) c_t . S_prev
    s_prev = state[...]                       # (P, N)
    y += jnp.exp(cum) * jax.lax.dot_general(
        c, s_prev, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    # state update: S = exp(cum_end) S_prev + sum_s exp(cum_end - cum_s) x_s b_s^T
    cum_end = cum[q - 1:, :]                  # (1, 1)
    w = jnp.exp(cum_end - cum)                # (Q, 1)
    upd = jax.lax.dot_general(
        x, b * w, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )                                         # (P, N)
    # (1, 1) -> (1, N) -> (P, N): Mosaic broadcasts one axis at a time
    decay_end = jnp.exp(jnp.broadcast_to(cum_end, (1, s_prev.shape[1])))
    state[...] = decay_end * s_prev + upd

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ic == n_chunks - 1)
    def _final():
        st_ref[0, 0] = state[...].astype(st_ref.dtype)


@functools.partial(jax.jit, static_argnames=("chunk", "n_groups", "interpret"))
def ssd_scan_pallas(
    x: jnp.ndarray,
    log_a: jnp.ndarray,
    b: jnp.ndarray,
    c: jnp.ndarray,
    init_state: jnp.ndarray | None = None,
    chunk: int = 128,
    n_groups: int = 1,
    interpret: bool = False,
):
    """Chunked SSD.  See ``ref.ssd_scan_ref``.

    Args:
      x: (B, L, H, P); log_a: (B, L, H); b, c: (B, L, G, N) per-group.
    Returns: y (B, L, H, P), final state (B, H, P, N).
    """
    B, L, H, P = x.shape
    N = b.shape[-1]
    G = b.shape[2]
    assert G == n_groups
    gsz = H // G
    q = min(chunk, L)
    assert L % q == 0, (L, q)
    nc = L // q
    if init_state is None:
        init_state = jnp.zeros((B, H, P, N), jnp.float32)

    xt = x.transpose(0, 2, 1, 3)              # (B, H, L, P)
    at = log_a.astype(jnp.float32).transpose(0, 2, 1)  # (B, H, L)
    cum = jnp.cumsum(at.reshape(B, H, nc, q), axis=-1)  # per-chunk cumsum
    cum_col = cum.reshape(B, H, L, 1)
    cum_row = cum.reshape(B, H, nc, 1, q)
    bt = b.transpose(0, 2, 1, 3)              # (B, G, L, N)
    ct = c.transpose(0, 2, 1, 3)

    kernel = functools.partial(_ssd_kernel, q=q, n_chunks=nc)
    y, st = pl.pallas_call(
        kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, q, P), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, q, 1), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec(
                (1, 1, None, 1, q), lambda ib, ih, ic: (ib, ih, ic, 0, 0)
            ),
            pl.BlockSpec((1, 1, q, N), lambda ib, ih, ic: (ib, ih // gsz, ic, 0)),
            pl.BlockSpec((1, 1, q, N), lambda ib, ih, ic: (ib, ih // gsz, ic, 0)),
            pl.BlockSpec((1, 1, P, N), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, q, P), lambda ib, ih, ic: (ib, ih, ic, 0)),
            pl.BlockSpec((1, 1, P, N), lambda ib, ih, ic: (ib, ih, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, L, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, P, N), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((P, N), jnp.float32)],
        interpret=interpret,
    )(xt, cum_col, cum_row, bt, ct, init_state)
    return y.transpose(0, 2, 1, 3), st
