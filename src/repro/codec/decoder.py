"""Decoder + single-pass stream buffer (paper §3.2).

A naive sliding-window pipeline decodes each frame once per window it
appears in (w/s times).  ``StreamDecoder`` decodes the bitstream
sequentially in a single pass, buffers reconstructed frames, and serves
every overlapping window from the shared buffer — the paper's
'decode-once' design.  Codec metadata is extracted in the same pass.
"""
from __future__ import annotations

import functools
from typing import Iterator, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import CodecCfg
from .encoder import motion_compensate
from .metadata import Bitstream, CodecMetadata, I_FRAME


@functools.partial(jax.jit, static_argnames=("block",))
def decode_stream(bitstream: Bitstream, block: int = 16) -> jnp.ndarray:
    """Reconstruct all frames (exact inverse of ``encode_stream``)."""

    def step(prev_recon, inp):
        ftype, idata, mv, resid = inp
        is_i = ftype == I_FRAME
        pred = motion_compensate(prev_recon, mv, block)
        recon = jnp.where(is_i, idata, pred + resid)
        return recon, recon

    H, W = bitstream.iframe_data.shape[1:]
    init = jnp.zeros((H, W), jnp.float32)
    _, frames = jax.lax.scan(
        step,
        init,
        (bitstream.frame_types, bitstream.iframe_data, bitstream.mv,
         bitstream.residual_q),
    )
    return frames


class StreamDecoder:
    """Single-pass decode + shared window buffer.

    decode_count tracks how many times each frame was decoded — the unit
    test asserts it is exactly 1 under arbitrary window/stride schedules
    (vs w/s for the naive design, paper §2.2).
    """

    def __init__(self, cfg: CodecCfg):
        self.cfg = cfg
        self._frames: np.ndarray | None = None
        self._meta: CodecMetadata | None = None
        self.decode_count: np.ndarray | None = None

    def ingest(self, bitstream: Bitstream, meta: CodecMetadata) -> None:
        self.load(np.asarray(decode_stream(bitstream, self.cfg.block)), meta)

    def load(self, frames: np.ndarray, meta: CodecMetadata) -> None:
        """Buffer frames already decoded (``decode_stream``) and fetched
        to the host, with their metadata."""
        self._frames = frames
        self._meta = meta
        self.decode_count = np.ones(frames.shape[0], np.int32)

    def window(self, k: int) -> Tuple[np.ndarray, CodecMetadata]:
        """k-th sliding window: frames [k*s, k*s + w)."""
        w, s = self.cfg.window_frames, self.cfg.stride_frames
        lo = k * s
        hi = lo + w
        if self._frames is None or hi > self._frames.shape[0]:
            raise IndexError(f"window {k} out of range")
        md = CodecMetadata(
            self._meta.frame_types[lo:hi],
            self._meta.mv[lo:hi],
            self._meta.residual[lo:hi],
        )
        return self._frames[lo:hi], md

    def n_windows(self) -> int:
        if self._frames is None:
            return 0
        w, s = self.cfg.window_frames, self.cfg.stride_frames
        return max(0, (self._frames.shape[0] - w) // s + 1)

    def iter_windows(self) -> Iterator[Tuple[int, np.ndarray, CodecMetadata]]:
        for k in range(self.n_windows()):
            frames, md = self.window(k)
            yield k, frames, md


class NaiveDecoder:
    """Baseline: re-decodes the covering prefix for every window (the
    redundant design the paper's single-pass front end replaces)."""

    def __init__(self, cfg: CodecCfg):
        self.cfg = cfg
        self._bs: Bitstream | None = None
        self._meta: CodecMetadata | None = None
        self.decode_count: np.ndarray | None = None

    def ingest(self, bitstream: Bitstream, meta: CodecMetadata) -> None:
        self._bs = bitstream
        self._meta = meta
        self.decode_count = np.zeros(bitstream.frame_types.shape[0], np.int32)

    def window(self, k: int) -> Tuple[np.ndarray, CodecMetadata]:
        w, s = self.cfg.window_frames, self.cfg.stride_frames
        lo, hi = k * s, k * s + w
        # inter-frame decoding must start at the stream head (or at least
        # the previous I-frame); naive engines re-run the decode prefix.
        frames = np.asarray(decode_stream(self._bs, self.cfg.block))[:hi]
        self.decode_count[:hi] += 1
        md = CodecMetadata(
            self._meta.frame_types[lo:hi],
            self._meta.mv[lo:hi],
            self._meta.residual[lo:hi],
        )
        return frames[lo:hi], md
