"""Compressed-domain metadata structures (paper §2.4.1, §3.2).

``CodecMetadata`` is what the Codec Processor hands to the Motion
Analyzer: per-frame frame types, block-level motion vectors and residual
energies — exactly the signals an H.264-class encoder emits as a
byproduct of inter-frame prediction.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

I_FRAME = 0
P_FRAME = 1


class CodecMetadata(NamedTuple):
    """Per-stream compressed-domain signals.

    Attributes:
      frame_types: (T,) int32 — I_FRAME or P_FRAME.
      mv: (T, Hb, Wb, 2) int32 — block motion vectors (dy, dx), zero on
        I-frames.
      residual: (T, Hb, Wb) float32 — per-block mean absolute residual
        after motion compensation (pixel units), zero on I-frames.
    """

    frame_types: jnp.ndarray
    mv: jnp.ndarray
    residual: jnp.ndarray

    @property
    def mv_magnitude(self) -> jnp.ndarray:
        """(T, Hb, Wb) float32 — ||v|| per block (paper Eq. 1)."""
        return jnp.linalg.norm(self.mv.astype(jnp.float32), axis=-1)

    def window(self, start: int, length: int) -> "CodecMetadata":
        return CodecMetadata(
            jax.lax.dynamic_slice_in_dim(self.frame_types, start, length, 0),
            jax.lax.dynamic_slice_in_dim(self.mv, start, length, 0),
            jax.lax.dynamic_slice_in_dim(self.residual, start, length, 0),
        )


class Bitstream(NamedTuple):
    """A (simulated) encoded stream: everything the decoder needs.

    Attributes:
      frame_types: (T,) int32.
      iframe_data: (T, H, W) float32 — quantized intra frame, zero rows
        for P-frames (a real bitstream would only ship I-frames; the
        dense layout keeps this jit-friendly; *size accounting* uses the
        entropy model in ``encoder.estimate_bits``).
      mv: (T, Hb, Wb, 2) int32.
      residual_q: (T, H, W) float32 — quantized P-frame residuals.
      radius: the encoder's search radius, which bounds every vector of
        ``mv``; static (pytree metadata, not a leaf), so the decoder
        builds its prediction from static shifts.  None where the
        vectors are not known to be bounded: the decoder then gathers.
    """

    frame_types: jnp.ndarray
    iframe_data: jnp.ndarray
    mv: jnp.ndarray
    residual_q: jnp.ndarray
    radius: Optional[int] = None


jax.tree_util.register_pytree_node(
    Bitstream,
    lambda b: (tuple(b[:4]), b.radius),
    lambda radius, leaves: Bitstream(*leaves, radius),
)


def gop_frame_types(n_frames: int, gop: int) -> jnp.ndarray:
    """I at every GOP boundary, P elsewhere."""
    t = jnp.arange(n_frames)
    return jnp.where(t % gop == 0, I_FRAME, P_FRAME).astype(jnp.int32)
