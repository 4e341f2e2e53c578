"""Composable serving stages + per-stream session state (paper Fig. 8).

The monolithic ``Engine`` is split into four typed stages so a scheduler
can batch work across concurrent streams at each stage boundary:

  CodecFrontend           encode/ingest + single-pass decode + window
      |                   slicing; codec metadata; ingest-time
      v                   amortization lives HERE, not in the engine.
  VisualEncoder           full (I-frame) / pruned (P-frame) ViT encode,
      |                   batched over streams x frames.
      v
  PrefillBackend          one protocol, two implementations:
      |                     * AttentionPrefill — fresh prefill and
      |                       KVC reuse + selective refresh (Eq. 5).
      |                     * RecurrentPrefill — SSM/hybrid boundary-
      v                       state streaming (DESIGN.md §4).
  GreedyDecoder           answer extraction + greedy continuation.

``ServingPipeline`` composes the stages and serves a *batch* of windows
(one per stream, same layout/phase) in single jitted calls; batch size 1
reproduces the legacy per-stream path exactly.  ``repro.serving.engine``
keeps ``Engine`` as a thin compatibility wrapper, and
``repro.serving.scheduler`` drives N concurrent ``StreamSession``s
through the batched path.

Modes (paper §5 Baselines): ``codecflow`` | ``fullcomp`` | ``prune_only``
| ``refresh_only`` | ``cacheblend`` | ``vlcache`` — semantics unchanged
from the monolith (see module docstring history in engine.py).
"""
from __future__ import annotations

import dataclasses
import types
from typing import (
    Any, Dict, List, NamedTuple, Optional, Protocol, Sequence, Tuple,
)

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import CodecCfg, ModelCfg, ViTCfg
from ..codec import StreamDecoder, decode_stream, encode_stream
from ..codec.metadata import CodecMetadata
from ..core import (
    WindowLayout, capacity_groups, motion_mask, pack_plan,
    refresh_block_map, reuse_caches, select_tokens,
)
from ..core import kv_pool
from ..kernels import ops as kernel_ops
from ..kernels.flash_refresh import build_block_map
from ..models import layers
from ..models import transformer as tfm
from . import metrics
from . import tracing
from ..models import vit as vitm
from . import flops as flopcount
from .config import (                       # re-exported; grouped cfgs
    EngineCfg, KVCfg, PruneCfg, RefreshCfg, SchedulerCfg,
)

F32 = jnp.float32


def _donate(*argnums: int) -> Tuple[int, ...]:
    """Buffer-donation argnums for jitted calls that thread the paged
    KV slab functionally (input slab -> output slab): on TPU/GPU the
    input buffer is reused in place instead of copied every window.
    CPU does not implement donation (it would only warn), so donation
    is disabled there."""
    return argnums if jax.default_backend() != "cpu" else ()


def _renamed(fn, name: str):
    """``fn`` under another name: a jit of it compiles to the module
    ``jit_<name>``, so each serving program keeps a name of its own in a
    device trace (one body can back two programs, e.g. a dense and a
    paged twin)."""
    out = types.FunctionType(fn.__code__, fn.__globals__, name,
                             fn.__defaults__, fn.__closure__)
    out.__kwdefaults__ = fn.__kwdefaults__
    out.__qualname__ = name
    return out


def _recurrent(cfg: ModelCfg) -> bool:
    """SSM/hybrid stacks stream boundary state (``RecurrentPrefill``);
    pure-attention stacks reuse KV (``AttentionPrefill``)."""
    return cfg.family in ("ssm", "hybrid")


# token conventions for the anomaly-detection workload
PAD, BOS, YES, NO = 0, 1, 2, 3
QUERY_IDS = (5, 6, 7, 8, 9, 10, 11, 12)   # "describe ... abuse? yes/no"

MODES = ("codecflow", "fullcomp", "prune_only", "refresh_only",
         "cacheblend", "vlcache")


# EngineCfg and its grouped sub-configs (PruneCfg / RefreshCfg / KVCfg,
# plus SchedulerCfg for the multi-stream scheduler) live in
# ``repro.serving.config`` — imported above and re-exported here for
# compatibility.  Legacy flat kwargs/attributes still work with a
# DeprecationWarning (docs/serving_api.md §Configuration).
__cfg_exports = (EngineCfg, PruneCfg, RefreshCfg, KVCfg, SchedulerCfg)


@dataclasses.dataclass
class WindowStats:
    answer: int
    logits_yes_no: Tuple[float, float]
    tokens_vis: int
    tokens_valid: int
    tokens_refreshed: int
    vit_patches: int
    vit_slots: int               # ViT lanes actually computed (packed
    flops_vit: float             # buffer slots or padded capacity)
    flops_prefill: float
    flops_decode: float
    # Host seconds of this window's share of each stage, read from the
    # stage's ``serve.`` spans (``serving/tracing.py``): time spent
    # dispatching, not device time (the device side is in a profiler
    # trace).  t_codec: ``serve.codec.open`` over the stream's windows;
    # t_vit: ``serve.vit.encode``; t_prefill: ``serve.prefill.dispatch``
    # less the refresh selection; t_decode: ``serve.decode.dispatch``
    # plus the answers' fetch; t_overhead: refresh selection and state
    # (de)staging.
    t_codec: float
    t_vit: float
    t_prefill: float
    t_decode: float
    t_overhead: float
    # Kernel dispatch decisions during this window's batched stage call
    # that were NOT kernel-eligible (silent oracle fallbacks for
    # flash_refresh / flash_packed).  Dispatch runs at trace time, so
    # steady-state windows (no retrace) report 0; every row of one
    # batched call shares the same value.
    kernel_fallbacks: int = 0
    # Steady-state KV bytes this stream occupies (paged slab share or
    # dense per-stream allocation) — the memory axis of the capacity
    # benches; int8 cold pages roughly halve it at fixed context.
    kv_bytes_per_stream: int = 0


# ======================================================================
# Session dataclasses
# ======================================================================
@dataclasses.dataclass(frozen=True)
class StreamRequest:
    """One stream of raw luma frames submitted to the scheduler."""

    stream_id: Any
    frames: np.ndarray               # (T, H, W) raw luma in [0, 255]
    tag: Any = None                  # opaque caller payload (e.g. label)


@dataclasses.dataclass(frozen=True)
class WindowResult:
    """Per-window outcome carried by ``WindowDone`` events (and the
    deprecated ``Scheduler.poll``)."""

    stream_id: Any
    session_id: int
    window: int
    stats: WindowStats


@dataclasses.dataclass
class CodecStream:
    """Codec front-end state: the single-pass decode buffer + metadata."""

    decoder: StreamDecoder
    t_ingest: float                  # host seconds of ``serve.codec.open``
    n_windows: int


class StreamSession:
    """Per-stream serving state: codec buffer + KVC/layout state.

    Lifecycle: ``Scheduler.submit`` creates the session (codec ingest),
    the scheduler drives it window-by-window through the batched stage
    pipeline, and ``Scheduler.close`` releases its cache state.
    """

    def __init__(self, sid: int, request: StreamRequest, stream: CodecStream):
        self.sid = sid
        self.request = request
        self.stream = stream
        self.next_window = 0
        self.state: Optional[Dict[str, Any]] = None   # backend KV state
        self.results: List[WindowResult] = []

    @property
    def done(self) -> bool:
        return self.next_window >= self.stream.n_windows

    @property
    def answers(self) -> List[int]:
        return [r.stats.answer for r in self.results]


# ======================================================================
# Stage 1: codec front end
# ======================================================================
class CodecFrontend:
    """Encode/ingest + single-pass decode + sliding-window slicing.

    Owns codec-time accounting: ingest cost is amortized over the
    stream's windows *at this stage* so per-window timings are
    attributed where they were incurred.
    """

    def __init__(self, codec: CodecCfg):
        self.codec = codec

    def open(self, frames: np.ndarray) -> CodecStream:
        with tracing.span("serve.codec.open", frames=len(frames)) as sp:
            with tracing.span("serve.codec.encode"):
                bs, meta = encode_stream(jnp.asarray(frames, F32), self.codec)
            dec = StreamDecoder(self.codec)
            with tracing.span("serve.codec.decode"):
                recon = decode_stream(bs, self.codec.block)
                with tracing.span("serve.codec.decode.fetch"):
                    dec.load(np.asarray(recon), meta)
        return CodecStream(dec, sp.seconds, dec.n_windows())

    def window_host(
        self, cs: CodecStream, k: int
    ) -> Tuple[np.ndarray, CodecMetadata, float]:
        """k-th window as HOST arrays: (frames (W, H, Wd), metadata,
        amortized t_codec).  Pure numpy slicing of the single-pass
        decode buffer — safe to run on an ingest worker thread while
        the main thread dispatches device work for earlier windows
        (the async scheduler's stage-1 surface)."""
        wframes, wmeta = cs.decoder.window(k)
        return wframes, wmeta, cs.t_ingest / max(cs.n_windows, 1)

    def window(
        self, cs: CodecStream, k: int
    ) -> Tuple[jnp.ndarray, CodecMetadata, float]:
        """k-th window: (frames (W, H, Wd), metadata, amortized t_codec)."""
        wframes, wmeta, t_codec = self.window_host(cs, k)
        return jnp.asarray(wframes), wmeta, t_codec


# ======================================================================
# Stage 2: visual encoder
# ======================================================================
class VisualEncoder:
    """Full/pruned ViT encode of window frames, batched across streams.

    Frames are batched by coding type: all I-frames of all streams in
    one full-capacity ViT call, all P-frames in one pruned call — two
    jit invocations per *batch of windows* instead of two per stream.

    The pruned call packs the kept patch groups of ALL streams' P-frames
    into shared variable-capacity buffers (``core.pruning.pack_plan`` +
    ``vitm.encode_packed_tokens``): one stream's quiet scene donates its
    slack to another's busy one, and ViT compute tracks codec-reported
    motion instead of the padded ``K_sel`` worst case.  ``packed=False``
    keeps the legacy padded path (A/B benchmarks, parity tests).
    """

    # packed-buffer kv tile; plan row lengths are bucket multiples of it
    PACK_TILE = 128

    def __init__(self, v: ViTCfg, vparams, codec: CodecCfg,
                 layout: WindowLayout, prune: bool, packed: bool = True):
        self.v = v
        self.vparams = vparams
        self.codec = codec
        self.layout = layout
        self.prune = prune
        self.packed = packed and prune
        self._range_cache: Dict[Tuple[int, int], tuple] = {}

        def vit_full(vp, f):
            return vitm.encode_full(vp, v, f)

        def vit_pruned(vp, f, pi, pv):
            return vitm.encode_pruned_tokens(vp, v, f, pi, pv)

        self._jit_full = jax.jit(vit_full)
        self._jit_pruned = jax.jit(vit_pruned)

    def _split_range(self, frame_range: range) -> tuple:
        """(i_idx, p_idx, i_arr, p_arr) for a window frame range, cached
        so the I/P membership scan and the ``jnp.asarray`` staging run
        once per distinct range instead of on every encode call."""
        key = (frame_range.start, frame_range.stop)
        hit = self._range_cache.get(key)
        if hit is None:
            lay = self.layout
            i_idx = [f for f in frame_range
                     if lay.frame_is_i(f) or not self.prune]
            i_set = frozenset(i_idx)
            p_idx = [f for f in frame_range if f not in i_set]
            hit = (i_idx, p_idx,
                   jnp.asarray(i_idx) if i_idx else None,
                   jnp.asarray(p_idx) if p_idx else None)
            self._range_cache[key] = hit
        return hit

    def _encode_packed(self, pframes: jnp.ndarray, dec) -> Tuple[jnp.ndarray, int]:
        """Packed pruned encode of a flat (B, H, W) P-frame batch.

        Returns ((B, k_tokens, d_lm) tokens, packed slot count)."""
        v, kg = self.v, self.layout.k_tokens
        with tracing.span("serve.vit.pack_plan") as sp:
            with tracing.span("serve.vit.pack_plan.fetch"):
                # check: allow-host-sync-under-jit(the host packs the kept groups: one fetch of the decision per encode group)
                gv, pi = jax.device_get((dec.group_valid, dec.patch_idx))
            plan = pack_plan(dec._replace(group_valid=gv, patch_idx=pi), v,
                             tile=self.PACK_TILE)
            sp.set(rows=plan.n_rows, slots=plan.n_slots)
        bm = plan.block_map
        with tracing.span("serve.vit.packed"):
            toks = vitm.encode_packed_tokens(
                self.vparams, v, pframes,
                jnp.asarray(plan.patch_src), jnp.asarray(plan.seg_id),
                jnp.asarray(plan.group_src), jnp.asarray(plan.group_dst),
                jnp.asarray(bm.tile_ids), jnp.asarray(bm.tile_count),
                n_out=plan.n_frames * kg, tq=bm.tq, tk=bm.tk,
            )
        return toks.reshape(plan.n_frames, kg, -1), plan.n_slots

    def encode(
        self,
        frames: jnp.ndarray,                 # (S, W, H, Wd)
        metas: Sequence[CodecMetadata],      # len S, per-window metadata
        frame_range: range,
    ) -> Tuple[jnp.ndarray, jnp.ndarray, np.ndarray, np.ndarray]:
        """Encode frames [range) of every stream's window.

        Returns (embeds (S, n_tok, d), valid (S, n_tok), patches (S,),
        slots (S,)): per-stream token embeds packed per the layout;
        ``slots`` counts the ViT lanes actually computed per stream
        (packed buffer share or padded capacity).
        """
        lay, v = self.layout, self.v
        S = frames.shape[0]
        i_idx, p_idx, i_arr, p_arr = self._split_range(frame_range)
        toks_by_frame: dict = {}
        val_by_frame: dict = {}
        patches = np.zeros((S,), np.int64)
        slots = np.zeros((S,), np.int64)

        if i_idx:
            with tracing.span("serve.vit.full", frames=S * len(i_idx)):
                sel = frames[:, i_arr]                       # (S, Ni, H, Wd)
                batch = sel.reshape((S * len(i_idx),) + sel.shape[2:])
                toks = self._jit_full(self.vparams, batch)   # (S*Ni, G, d)
                toks = toks.reshape((S, len(i_idx)) + toks.shape[1:])
            for j, f in enumerate(i_idx):
                n_tok = lay.frame_tokens[f]
                toks_by_frame[f] = toks[:, j, :n_tok]
                val_by_frame[f] = jnp.ones((S, n_tok), bool)
            patches += len(i_idx) * v.n_patches
            slots += len(i_idx) * v.n_patches

        if p_idx:
            with tracing.span("serve.vit.motion_mask", windows=S):
                dyn, sco = [], []
                for m in metas:
                    d, s = motion_mask(m, self.codec, v.patches_per_side)
                    dyn.append(d)
                    sco.append(s)
                dyn = jnp.stack(dyn)                         # (S, W, pp, pp)
                sco = jnp.stack(sco)
            Np = len(p_idx)
            with tracing.span("serve.vit.select", frames=S * Np):
                dsel = dyn[:, p_arr].reshape((S * Np,) + dyn.shape[2:])
                ssel = sco[:, p_arr].reshape((S * Np,) + sco.shape[2:])
                dec = select_tokens(dsel, ssel, v, lay.k_tokens)
            pframes = frames[:, p_arr].reshape((S * Np,) + frames.shape[2:])
            if self.packed:
                toks, n_slots = self._encode_packed(pframes, dec)
                # shared buffer: attribute slots evenly across streams
                slots += -(-n_slots // S)
            else:
                with tracing.span("serve.vit.pruned", frames=S * Np):
                    toks_full = self._jit_pruned(
                        self.vparams, pframes, dec.patch_idx,
                        dec.patch_valid,
                    )                                        # (S*Np, G, d)
                    toks = jnp.take_along_axis(
                        toks_full, dec.group_idx[..., None], 1
                    )
                slots += Np * dec.patch_idx.shape[1]
            toks = toks.reshape((S, Np) + toks.shape[1:])
            gval = dec.group_valid.reshape(S, Np, -1)
            with tracing.span("serve.vit.count.fetch"):
                # check: allow-host-sync-under-jit(per-window stats fetch; one scalar per stream, after dispatch)
                patches += np.asarray(
                    dec.patch_valid.reshape(S, -1).sum(axis=1), np.int64
                )
            for j, f in enumerate(p_idx):
                n_tok = lay.frame_tokens[f]
                toks_by_frame[f] = toks[:, j, :n_tok]
                val_by_frame[f] = gval[:, j, :n_tok]

        embeds = jnp.concatenate([toks_by_frame[f] for f in frame_range], 1)
        valids = jnp.concatenate([val_by_frame[f] for f in frame_range], 1)
        return embeds, valids, patches, slots


# ======================================================================
# Stage 3: prefill backends (one protocol, two families)
# ======================================================================
class PrefillResult(NamedTuple):
    """Uniform output of a prefill backend for one batch of windows."""

    logits: jnp.ndarray          # (S, V) last-position logits
    decode_caches: Any           # caches the decoder continues from
    decode_start: int            # position of the first decoded token
    flops_len: Any               # i -> attended context len of step i
    state: Dict[str, Any]        # batched per-stream state for window k+1
    tokens_vis: int              # visual tokens processed this window
    tokens_valid: np.ndarray     # (S,) valid-token count per stream
    n_refreshed: int             # tokens recomputed through the LLM
    flops: float                 # prefill FLOPs per stream
    t_select: float              # host seconds of ``serve.prefill.select``
    page_table: Any = None       # (S, pages/stream) slab pages, paged mode


class PrefillBackend(Protocol):
    """LLM context construction over a batch of same-layout windows.

    One protocol, two implementations (attention KVC reuse vs
    SSM/hybrid boundary-state streaming).  ``fresh`` consumes the full
    window's visual tokens, ``step`` only the new-stride tokens plus the
    previous window's ``state``; both take query embeds ``qe`` and
    return a ``PrefillResult``.  ``absorb_decode`` folds the decoder's
    cache mutations back into the stream state (a no-op for backends
    that fork the query/decode cache).
    """

    batchable_step: bool

    def fresh(self, vis, vval, qe) -> PrefillResult: ...
    def step(self, vis, vval, qe, state) -> PrefillResult: ...
    def absorb_decode(self, state, caches) -> None: ...


class AttentionPrefill:
    """Fresh prefill + windowed KVC reuse / selective refresh (Eq. 5)."""

    # kv tile size of the flash_refresh kernel; the cache allocation is
    # rounded up to it so the refresh pass attends a tile-aligned buffer
    # (real layouts' total_len is never 128-aligned — without padding
    # the kernel dispatch would silently fall back to the oracle)
    KV_TILE = 128

    def __init__(self, cfg: ModelCfg, params, layout: WindowLayout,
                 ecfg: EngineCfg):
        self.cfg = cfg
        self.params = params
        self.layout = layout
        self.ecfg = ecfg
        need = layout.total_len + ecfg.max_new_tokens
        self.cache_slots = -(-need // self.KV_TILE) * self.KV_TILE
        qc = ecfg.q_chunk

        def lm_reuse(caches):
            return reuse_caches(cfg, caches, layout)

        self._jit_reuse = jax.jit(lm_reuse)
        # Static-refresh modes recompute exactly the layout's refresh
        # set every window, so the flash_refresh tile map is a per-layout
        # constant (closed over by the jitted call below).  It covers
        # the FULL padded allocation — the selective pass attends the
        # whole tile-aligned cache, with the slots past total_len (decode
        # scratch + padding) masked by causality alone (every refresh
        # query position < total_len <= their positions).  cacheblend /
        # vlcache pick their scatter set online — no static map; their
        # dispatch falls back to the oracle path.
        self.block_map = (
            refresh_block_map(layout, window=cfg.sliding_window,
                              kv_len=self.cache_slots)
            if ecfg.mode in ("codecflow", "refresh_only") else None
        )
        block_map = self.block_map
        alloc = self.cache_slots

        def lm_selective(params, caches, remb, rval, kvv, idx,
                         page_table=None):
            B = remb.shape[0]
            positions = jnp.broadcast_to(idx[None], (B, idx.shape[0]))
            kv_full = kvv.at[:, idx].set(rval)
            h = remb.astype(params["embed"].dtype)
            h, new_caches, _ = tfm.run_stack(
                cfg, params, h, positions, None, caches,
                cache_offset=None, cache_len=alloc,
                scatter_idx=idx, kv_valid=kv_full, q_chunk=qc,
                block_map=block_map, page_table=page_table,
                page_size=self.KV_TILE,
            )
            hn = layers.rmsnorm(params["final_norm"], h, cfg.norm_eps)
            logits = tfm.lm_logits(cfg, params, hn[:, -1])
            return logits, new_caches, h

        self._jit_selective = jax.jit(lm_selective)
        # paged twin donates the input slab: the selective pass threads
        # the shared KV slab functionally (slab in -> slab out), so on
        # TPU/GPU XLA updates the pages in place instead of copying the
        # whole slab per window.  Every call site immediately rebinds
        # ``pool.slab`` to the output — the donated input is never read
        # again (docs/async_scheduler.md §Donation).
        self._jit_selective_paged = jax.jit(
            _renamed(lm_selective, "lm_selective_paged"),
            donate_argnums=_donate(1),
        )

        # -- paged KV: shared slab + per-stream page tables ------------
        # Reuse modes on the attention family keep per-stream KV in one
        # pre-allocated slab (core/kv_pool.py).  Fresh/step/selective
        # run the SAME math as the dense path through a page-table
        # indirection, so paged == concat bit-for-bit on the oracle
        # backend; stream admit/evict only moves page indices.
        assert self.KV_TILE == kv_pool.PAGE_SIZE
        self.paged = bool(
            ecfg.kv.paged_kv
            and ecfg.mode in ("codecflow", "refresh_only", "cacheblend",
                              "vlcache")
        )
        self.pages_per_stream = self.cache_slots // self.KV_TILE
        self.pool: Optional[kv_pool.KVPool] = None
        self._pool_hint = ecfg.kv.pool_streams or 1
        # -- quantized cold pages (docs/paged_kv.md §Quantized) --------
        # stale_page_dtype="int8" demotes overlap pages the refresh
        # selector has not rewritten for ``demote_after`` windows into
        # an int8 cold slab; the kernels dequantize in-register.  The
        # demotable set is layout-static (pages fully inside the
        # overlap — see kv_pool.demotable_pages), so cold capacity is
        # reserved per stream at admission.
        assert ecfg.kv.stale_page_dtype in ("bf16", "int8"), \
            ecfg.kv.stale_page_dtype
        self.quant = bool(self.paged and ecfg.kv.stale_page_dtype == "int8")
        self.cold_per_stream = (
            len(kv_pool.demotable_pages(layout, self.KV_TILE))
            if self.quant else 0
        )
        self.demote_after = max(1, ecfg.kv.demote_after)
        self._jit_demote = jax.jit(
            _renamed(kv_pool.demote_pool_caches, "kv_demote"),
            static_argnums=3, donate_argnums=_donate(0),
        )
        # fresh windows (paged slab or dense caches) go through
        # scatter-mode run_stack; their q positions are the full
        # [0, total_len) range, so the visit list is a per-layout
        # constant exactly like the refresh map.
        self.fresh_map = build_block_map(
            np.arange(layout.total_len, dtype=np.int32),
            self.cache_slots, causal=True, window=cfg.sliding_window,
        )
        fresh_map = self.fresh_map
        total = layout.total_len

        def lm_fresh_prefill(params, caches, page_table, embeds, valid):
            S = embeds.shape[0]
            idx = jnp.arange(total, dtype=jnp.int32)
            positions = jnp.broadcast_to(idx[None], (S, total))
            kvv = jnp.zeros((S, alloc), bool).at[:, idx].set(valid)
            h = embeds.astype(params["embed"].dtype)
            h, new_caches, _ = tfm.run_stack(
                cfg, params, h, positions, None, caches,
                cache_offset=None, cache_len=alloc,
                scatter_idx=idx, kv_valid=kvv, q_chunk=qc,
                block_map=fresh_map, page_table=page_table,
                page_size=self.KV_TILE,
            )
            hn = layers.rmsnorm(params["final_norm"], h, cfg.norm_eps)
            logits = tfm.lm_logits(cfg, params, hn[:, -1])
            return logits, new_caches

        def lm_reuse_paged(caches, pt):
            return kv_pool.reuse_pool_caches(cfg, caches, pt, layout,
                                             self.KV_TILE)

        self._jit_fresh = jax.jit(lm_fresh_prefill)
        self._jit_paged_fresh = jax.jit(
            _renamed(lm_fresh_prefill, "lm_fresh_prefill_paged"),
            donate_argnums=_donate(1),
        )
        self._jit_paged_reuse = jax.jit(lm_reuse_paged,
                                        donate_argnums=_donate(0))

    # -- paged pool lifecycle ------------------------------------------
    def ensure_pool(self, n_streams: int) -> None:
        """Make sure the slab can hold ``n_streams`` concurrent streams.

        The scheduler calls this with its ``max_concurrent`` before any
        stream is admitted; growing is only legal while no pages are in
        use (``pool_streams`` pins the capacity instead)."""
        if not self.paged:
            return
        if self.ecfg.kv.pool_streams is not None:
            want = self.ecfg.kv.pool_streams
        else:
            self._pool_hint = max(self._pool_hint, n_streams)
            want = self._pool_hint
        if self.quant:
            # Steady-state streams hold P-D hot pages (tail) + D cold
            # pages (demoted overlap); admission is all-hot, so one
            # extra stream's worth of demotable pages stays hot until
            # its first demote window: hot = N*(P-D) + D, cold = N*D.
            # Streams therefore admit staggered (the scheduler's
            # throttling path) — that is the memory saving.
            D = self.cold_per_stream
            need = want * (self.pages_per_stream - D) + D
            need_cold = want * D
        else:
            need, need_cold = want * self.pages_per_stream, 0
        if self.pool is None:
            self.pool = kv_pool.KVPool(self.cfg, need, page=self.KV_TILE,
                                       cold_pages=need_cold)
        elif self.pool.n_pages < need or self.pool.n_cold < need_cold:
            assert self.pool.used_pages == 0, \
                "cannot grow a pool with pages in use; pin pool_streams"
            self.pool = kv_pool.KVPool(self.cfg, need, page=self.KV_TILE,
                                       cold_pages=need_cold)

    def can_admit(self, n_streams: int) -> bool:
        if not self.paged or self.pool is None:
            return True
        if self.quant:
            return self.pool.can_admit_streams(
                n_streams, self.pages_per_stream, self.cold_per_stream
            )
        return self.pool.can_admit(n_streams * self.pages_per_stream)

    def release(self, state: Optional[Dict[str, Any]]) -> None:
        """Return a finished stream's pages to the free list (no copy)."""
        if state is None:
            return
        pages = state.pop("pages", None)
        if pages is not None and self.pool is not None:
            if self.quant and not (
                np.asarray(pages) >= self.pool.n_pages
            ).any():
                # evicted before its first demote window: release the
                # admission-time cold reservation too
                self.pool.unreserve_cold(self.cold_per_stream)
            self.pool.evict(pages)

    def kv_bytes_per_stream(self) -> int:
        """Steady-state KV bytes one admitted stream occupies.

        Paged: slab bytes of its resident pages (hot tail + demoted
        int8 overlap, scales included, in quant mode).  Dense concat:
        the full per-stream bf16 cache allocation."""
        if self.paged and self.pool is not None:
            D = self.cold_per_stream
            return self.pool.bytes_per_stream(self.pages_per_stream - D, D)
        cfg = self.cfg
        return (cfg.repeats * cfg.period * 2 * self.cache_slots
                * cfg.n_kv * cfg.d_head * 2)      # k+v, bf16

    def _result(self, logits, vis, vval, caches, kv_valid, valid,
                n_refreshed, flops, t_select, pages=None,
                page_table=None, age=None) -> PrefillResult:
        lay = self.layout
        with tracing.span("serve.prefill.valid.fetch"):
            # check: allow-host-sync-under-jit(WindowStats needs concrete counts; stage output already awaited)
            tokens_valid = np.asarray(valid.sum(axis=1))
        if pages is not None:
            # paged: KV lives in the shared slab; the per-stream state
            # carries only page indices (host ints — staging them is the
            # whole t_overhead of a fused window).
            state = {"vis": vis, "vval": vval, "kv_valid": kv_valid,
                     "pages": pages}
            if age is not None:
                # windows each stream's overlap pages have survived
                # untouched — the demote clock (quant mode only)
                state["age"] = age
        else:
            state = {"vis": vis, "vval": vval, "caches": caches,
                     "kv_valid": kv_valid}
        return PrefillResult(
            logits=logits, decode_caches=caches,
            decode_start=lay.total_len,
            flops_len=lambda i: lay.total_len + i + 1,
            state=state, tokens_vis=lay.vis_len,
            tokens_valid=tokens_valid, n_refreshed=n_refreshed,
            flops=flops, t_select=t_select, page_table=page_table,
        )

    # -- fresh window --------------------------------------------------
    def fresh(self, vis: jnp.ndarray, vval: jnp.ndarray,
              qe: jnp.ndarray) -> PrefillResult:
        lay, alloc = self.layout, self.cache_slots
        S = vis.shape[0]
        embeds = jnp.concatenate([vis, qe], 1)
        valid = jnp.concatenate(
            [vval, jnp.ones((S, lay.query_len), bool)], 1
        )
        if self.paged:
            self.ensure_pool(S)
            pool = self.pool
            pages = pool.admit_streams(S, self.pages_per_stream,
                                       self.cold_per_stream)
            pt = jnp.asarray(pages, jnp.int32)
            with tracing.span("serve.prefill.fresh", windows=S,
                              refreshed=lay.total_len):
                logits, slab = self._jit_paged_fresh(
                    self.params, pool.slab, pt, embeds, valid
                )
                pool.slab = slab
            kv_valid = jnp.pad(valid, ((0, 0), (0, alloc - lay.total_len)))
            flops = flopcount.prefill_flops(
                self.cfg, lay.total_len, lay.total_len
            )
            age = np.zeros((S,), np.int32) if self.quant else None
            return self._result(logits, vis, vval, slab, kv_valid, valid,
                                lay.total_len, flops, 0.0,
                                pages=pages, page_table=pt, age=age)
        caches = tfm.init_caches(self.cfg, S, alloc)
        with tracing.span("serve.prefill.fresh", windows=S,
                          refreshed=lay.total_len):
            logits, caches = self._jit_fresh(
                self.params, caches, None, embeds, valid
            )
        kv_valid = jnp.pad(valid, ((0, 0), (0, alloc - lay.total_len)))
        flops = flopcount.prefill_flops(self.cfg, lay.total_len, lay.total_len)
        return self._result(logits, vis, vval, caches, kv_valid, valid,
                            lay.total_len, flops, 0.0)

    # -- incremental window (reuse + selective refresh) ----------------
    def step(self, vis_new: jnp.ndarray, vval_new: jnp.ndarray,
             qe: jnp.ndarray, state) -> PrefillResult:
        lay, alloc = self.layout, self.cache_slots
        S = vis_new.shape[0]
        # splice cached overlap embeddings with the new-stride tokens
        # (the ViT is NOT re-run for the overlap, §3.4.1)
        vis = jnp.concatenate([state["vis"][:, lay.shift_tokens:], vis_new], 1)
        vval = jnp.concatenate(
            [state["vval"][:, lay.shift_tokens:], vval_new], 1
        )
        embeds = jnp.concatenate([vis, qe], 1)
        valid = jnp.concatenate(
            [vval, jnp.ones((S, lay.query_len), bool)], 1
        )
        pages = pt = age = None
        if self.paged:
            pages = state["pages"]
            pt = jnp.asarray(pages, jnp.int32)
            with tracing.span("serve.prefill.reuse", windows=S):
                caches = self._jit_paged_reuse(self.pool.slab, pt)
            if self.quant:
                # reuse first (it rewrote the overlap at full precision),
                # THEN demote newly-eligible streams' overlap pages —
                # the selective refresh below reads/writes through the
                # updated mixed-precision page table.
                age = state["age"] + 1
                with tracing.span("serve.prefill.demote", windows=S):
                    caches, pages, pt = self._demote(caches, pages, age)
            self.pool.slab = caches
        else:
            with tracing.span("serve.prefill.reuse", windows=S):
                caches = self._jit_reuse(state["caches"])
        prev_valid = state["kv_valid"]
        kvv = jnp.zeros((S, alloc), bool)
        kvv = kvv.at[:, : lay.overlap_tokens].set(
            prev_valid[:, lay.shift_tokens: lay.vis_len]
        )
        with tracing.span("serve.prefill.select") as sel:
            ridx = self.refresh_indices(embeds, caches, page_table=pt)
        with tracing.span("serve.prefill.selective", windows=S,
                          refreshed=len(ridx)):
            remb = jnp.take_along_axis(
                embeds, jnp.asarray(ridx)[None, :, None], axis=1
            )
            rval = jnp.take_along_axis(valid, jnp.asarray(ridx)[None],
                                       axis=1)
            jit_selective = (self._jit_selective_paged if self.paged
                             else self._jit_selective)
            logits, caches, _ = jit_selective(
                self.params, caches, remb, rval, kvv, jnp.asarray(ridx), pt
            )
            if self.paged:
                self.pool.slab = caches
        kv_valid = kvv.at[:, jnp.asarray(ridx)].set(rval)
        flops = flopcount.prefill_flops(self.cfg, len(ridx), lay.total_len)
        return self._result(logits, vis, vval, caches, kv_valid, valid,
                            len(ridx), flops, sel.seconds,
                            pages=pages, page_table=pt, age=age)

    def _demote(self, caches, pages: np.ndarray, age: np.ndarray):
        """Codec-guided demotion: quantize eligible streams' overlap
        pages into the int8 cold slab (kv_pool.demote_pool_caches, jit
        with a donated slab) and swap the cold ids into their page
        tables.  A stream is eligible once its overlap pages survived
        ``demote_after`` reuse windows and it has not demoted yet; the
        demotable set is the layout-static prefix pages [0, D)."""
        D = self.cold_per_stream
        if D == 0:
            return caches, pages, jnp.asarray(pages, jnp.int32)
        pool = self.pool
        demoted = (pages[:, :D] >= pool.n_pages).any(axis=1)
        rows = np.nonzero((age >= self.demote_after) & ~demoted)[0]
        if rows.size:
            src = pages[rows][:, :D]
            dst = pool.demote(src).reshape(src.shape)
            caches = self._jit_demote(
                caches, jnp.asarray(src, jnp.int32),
                jnp.asarray(dst, jnp.int32), self.KV_TILE,
            )
            pages = pages.copy()
            pages[rows[:, None], np.arange(D)[None, :]] = dst
        return caches, pages, jnp.asarray(pages, jnp.int32)

    def absorb_decode(self, state, caches) -> None:
        """Decode extends the stream caches in place; the decode slots
        become valid for the next window's shift."""
        lay, nd = self.layout, self.ecfg.max_new_tokens
        if "pages" in state:
            self.pool.slab = caches        # decode wrote the shared slab
        else:
            state["caches"] = caches
        state["kv_valid"] = state["kv_valid"].at[
            :, lay.total_len: lay.total_len + nd
        ].set(True)

    # -- refresh policy (the *when/where* of C2) -----------------------
    @property
    def batchable_step(self) -> bool:
        """cacheblend ranks per-stream online; its scatter set differs
        across streams so incremental windows cannot share one call."""
        return self.ecfg.mode != "cacheblend"

    def refresh_indices(self, embeds, reused_caches,
                        page_table=None) -> np.ndarray:
        mode, lay = self.ecfg.mode, self.layout
        if mode in ("codecflow", "refresh_only"):
            return lay.refresh_token_idx
        tail = np.arange(lay.overlap_tokens, lay.total_len, dtype=np.int32)
        budget = len(lay.anchor_token_idx)
        if mode == "vlcache":
            r = max(1, int(self.ecfg.refresh.vlcache_ratio * lay.overlap_tokens))
            sel = np.linspace(
                0, lay.overlap_tokens - 1, min(r, budget) or 1
            ).astype(np.int32)
            return np.unique(np.concatenate([sel, tail]))
        if mode == "cacheblend":
            assert embeds.shape[0] == 1, "cacheblend refresh is per-stream"
            # online probe: layer-0 K deviation between the corrected
            # reused keys and keys recomputed from current embeddings.
            p0 = jax.tree_util.tree_map(
                lambda x: x[0], self.params["blocks"][0]
            )
            hn = layers.rmsnorm(
                p0["ln1"], embeds[:, : lay.overlap_tokens], self.cfg.norm_eps
            )
            kq = (hn @ p0["mixer"]["wk"]).reshape(
                1, lay.overlap_tokens, self.cfg.n_kv, self.cfg.d_head
            )
            from ..kernels.ref import apply_rope_ref
            pos = jnp.arange(lay.overlap_tokens)[None]
            k_new = apply_rope_ref(kq, pos, self.cfg.rope_theta)
            b0 = reused_caches.blocks[0]
            blk0 = b0.k[0]
            if page_table is not None:
                # paged slab: gather this stream's logical view first
                # (precision-routed — demoted pages dequantize through
                # the storage dtype, exactly what the kernel reads)
                from ..kernels.ref import (
                    paged_gather_quant_ref, paged_gather_ref,
                )
                if isinstance(b0, layers.QuantKVCache):
                    blk0 = paged_gather_quant_ref(
                        blk0, b0.k8[0], b0.k_scale[0],
                        page_table, self.KV_TILE,
                    )
                else:
                    blk0 = paged_gather_ref(blk0, page_table, self.KV_TILE)
            k_reused = blk0[:, : lay.overlap_tokens]
            dev = jnp.linalg.norm(
                (k_new - k_reused.astype(k_new.dtype)).astype(F32),
                axis=(-1, -2),
            )[0]
            with tracing.span("serve.prefill.select.fetch"):
                # check: allow-host-sync-under-jit(cacheblend selects its scatter set online: data-dependent indices must be concrete)
                top = np.asarray(jnp.argsort(-dev)[:budget], np.int32)
            return np.unique(np.concatenate([top, tail]))
        raise ValueError(mode)


class RecurrentPrefill:
    """SSM / hybrid boundary-state streaming (DESIGN.md §4).

    The stream state IS the recurrent cache: each window appends only
    the new frames; query+decode run on a forked cache so they do not
    pollute the boundary state.
    """

    def __init__(self, cfg: ModelCfg, params, layout: WindowLayout,
                 ecfg: EngineCfg):
        self.cfg = cfg
        self.params = params
        self.layout = layout
        self.ecfg = ecfg
        qc = ecfg.q_chunk

        def lm_stream_prefill(params, tokens, caches, valid, embeds, off):
            return tfm.prefill(cfg, params, tokens, caches, valid=valid,
                               inputs_embeds=embeds, cache_offset=off,
                               q_chunk=qc)

        self._jit_prefill = jax.jit(lm_stream_prefill)

    batchable_step = True

    def default_max_hist(self) -> int:
        lay = self.layout
        return 4 * lay.vis_len + lay.query_len + self.ecfg.max_new_tokens

    def fresh(self, vis, vval, qe) -> PrefillResult:
        return self._append(vis, vval, qe, None)

    def step(self, vis, vval, qe, state) -> PrefillResult:
        return self._append(vis, vval, qe, state)

    def absorb_decode(self, state, caches) -> None:
        """No-op: query + decode ran on a forked cache so they do not
        pollute the boundary state."""

    def _append(self, vis, vval, qe, state) -> PrefillResult:
        """Extend the boundary state with new visual tokens, then fork
        for the query."""
        lay = self.layout
        S = vis.shape[0]
        max_hist = state["max_hist"] if state else self.default_max_hist()
        if state is None:
            caches = tfm.init_caches(self.cfg, S, max_hist)
            offset = 0
        else:
            caches = state["caches"]
            offset = state["offset"]
        n_new = vis.shape[1]
        with tracing.span("serve.prefill.append", windows=S,
                          refreshed=n_new + lay.query_len):
            _, caches, _ = self._jit_prefill(
                self.params, jnp.zeros((S, n_new), jnp.int32), caches,
                vval, vis, offset,
            )
            offset_vis = offset + n_new
            q_logits, q_caches, _ = self._jit_prefill(
                self.params, jnp.zeros((S, lay.query_len), jnp.int32),
                caches, jnp.ones((S, lay.query_len), bool), qe, offset_vis,
            )
        with tracing.span("serve.prefill.valid.fetch"):
            # check: allow-host-sync-under-jit(WindowStats needs concrete counts; stage output already awaited)
            tokens_valid = np.asarray(vval.sum(axis=1))
        flops = flopcount.prefill_flops(
            self.cfg, n_new + lay.query_len, offset_vis + lay.query_len
        )
        return PrefillResult(
            logits=q_logits, decode_caches=q_caches,
            decode_start=offset_vis + lay.query_len,
            flops_len=lambda i: offset_vis + lay.query_len + i,
            state={"caches": caches, "offset": offset_vis,
                   "max_hist": max_hist},
            tokens_vis=n_new,
            tokens_valid=tokens_valid,
            n_refreshed=n_new + lay.query_len, flops=flops, t_select=0.0,
        )


# ======================================================================
# Stage 4: decoder
# ======================================================================
class DecodePending(NamedTuple):
    """In-flight greedy decode: every field except ``flops_decode`` is a
    device array that has been dispatched but not synced.  Fetching
    ``answers``/``yes_no`` (``ServingPipeline.finalize_stats``) is the
    only host sync of a window's serve path."""

    answers: jnp.ndarray         # (S,) device bool: yes-logit > no-logit
    yes_no: jnp.ndarray          # (S, 2) device last-prefill yes/no logits
    caches: Any                  # caches after the greedy continuation
    flops_decode: float


class GreedyDecoder:
    """Yes/no answer extraction + greedy continuation, batched."""

    def __init__(self, cfg: ModelCfg, params, ecfg: EngineCfg):
        self.cfg = cfg
        self.params = params
        self.max_new_tokens = ecfg.max_new_tokens
        # Attention stacks decode at layout-static positions (total_len
        # + i every window), so the position is a static argument and
        # the attention layers get a visit list for it: decode runs the
        # refresh kernel.  Recurrent stacks' decode start grows every
        # window, so there it stays a traced operand.
        def lm_decode(params, tok, caches, pos):
            return tfm.decode_step(cfg, params, tok, caches, pos)

        # paged twin: caches are the shared slab, so the logical extent
        # cannot be read off the cache shape — it is a static closure of
        # the jit (cache_len) with the page table as a traced operand.
        def lm_decode_paged(params, tok, caches, pos, pt, clen):
            return tfm.decode_step(cfg, params, tok, caches, pos,
                                   page_table=pt, cache_len=clen)

        self._jit_decode = jax.jit(
            lm_decode, static_argnums=() if _recurrent(cfg) else (3,),
        )
        self._jit_decode_paged = jax.jit(
            lm_decode_paged, static_argnums=(3, 5),
            donate_argnums=_donate(2),
        )

    def start(self, logits: jnp.ndarray, caches, start_pos: int,
              flops_len, page_table=None, cache_len: Optional[int] = None,
              ) -> "DecodePending":
        """Dispatch the greedy continuation WITHOUT a host sync.

        The yes/no decision and every continuation token are computed
        on device (``jnp.where`` / ``jnp.argmax``), so this returns as
        soon as the decode steps are enqueued — the async scheduler
        keeps dispatching later windows' stages and only fetches the
        answers when the window's ``WindowDone`` event is finalized
        (docs/async_scheduler.md §Async dispatch)."""
        yes_no = logits[:, (YES, NO)]
        answers = yes_no[:, 0] > yes_no[:, 1]
        tok = jnp.where(answers, YES, NO)[:, None].astype(jnp.int32)
        f_decode = 0.0
        for i in range(self.max_new_tokens):
            if page_table is not None:
                logits_d, caches = self._jit_decode_paged(
                    self.params, tok, caches, start_pos + i,
                    page_table, cache_len,
                )
            else:
                logits_d, caches = self._jit_decode(
                    self.params, tok, caches, start_pos + i
                )
            tok = jnp.argmax(logits_d, -1)[:, None].astype(jnp.int32)
            f_decode += flopcount.decode_flops(self.cfg, flops_len(i))
        return DecodePending(answers, yes_no, caches, f_decode)

    def decode(self, logits: jnp.ndarray, caches, start_pos: int,
               flops_len, page_table=None, cache_len: Optional[int] = None,
               ) -> Tuple[np.ndarray, np.ndarray, Any, float]:
        """Synchronous twin of ``start``: same dispatch, answers fetched
        before returning.  ``flops_len(i)`` gives the attended context
        length of decode step i (family-specific); ``page_table`` +
        ``cache_len`` switch to paged-slab decode.

        Returns (answers (S,), yes_no (S, 2), caches, flops_decode)."""
        pend = self.start(logits, caches, start_pos, flops_len,
                          page_table=page_table, cache_len=cache_len)
        yes_no = np.asarray(pend.yes_no, np.float64)
        answers = np.asarray(pend.answers).astype(np.int64)
        return answers, yes_no, pend.caches, pend.flops_decode


# ======================================================================
# Pipeline: stage composition
# ======================================================================
class EncodedWindows(NamedTuple):
    """Output of the encode stage for one fused group of windows."""

    vis: jnp.ndarray             # (S, T, D) visual embeds (dispatched)
    vval: jnp.ndarray            # (S, T) validity mask
    qe: jnp.ndarray              # (S, Q, D) query embeds
    patches: np.ndarray          # (S,) decoded patch counts (host)
    slots: np.ndarray            # (S,) packed-slot counts (host)
    fresh: bool
    t_vit: float                 # host seconds of ``serve.vit.encode``
    fallbacks: int


class PrefilledWindows(NamedTuple):
    """Output of the prefill stage for one fused group of windows."""

    pr: PrefillResult
    # host seconds of ``serve.prefill.dispatch`` less the refresh selection
    t_prefill: float
    fallbacks: int


class DecodedWindows(NamedTuple):
    """Output of the decode stage: answers dispatched, not yet synced."""

    pend: DecodePending
    t_decode: float              # host seconds of ``serve.decode.dispatch``
    fallbacks: int


class ServingPipeline:
    """Composes the four stages; serves a batch of same-phase windows
    (one per stream) through single jitted stage calls."""

    def __init__(self, cfg: ModelCfg, vit_cfg: ViTCfg, params_lm,
                 params_vit, ecfg: EngineCfg):
        assert cfg.vit is None or cfg.vit == vit_cfg
        assert ecfg.mode in MODES, ecfg.mode
        self.cfg = cfg
        self.v = vit_cfg
        self.params = params_lm
        self.vparams = params_vit
        self.ecfg = ecfg
        c = ecfg.codec
        prune = ecfg.mode in ("codecflow", "prune_only", "cacheblend", "vlcache")
        kg = capacity_groups(vit_cfg, c.keep_ratio) if prune else vit_cfg.n_groups
        self.layout = WindowLayout(
            window=c.window_frames, stride=c.stride_frames, gop=c.gop,
            g_tokens=vit_cfg.n_groups, k_tokens=kg,
            query_len=len(QUERY_IDS),
        )
        self.prune = prune
        self.reuse = ecfg.mode in ("codecflow", "refresh_only", "cacheblend",
                                   "vlcache")
        self.is_streaming_family = _recurrent(cfg)

        self.frontend = CodecFrontend(c)
        self.encoder = VisualEncoder(vit_cfg, params_vit, c, self.layout,
                                     prune, packed=ecfg.prune.packed_vit)
        self.backend: PrefillBackend = (
            RecurrentPrefill(cfg, params_lm, self.layout, ecfg)
            if self.is_streaming_family
            else AttentionPrefill(cfg, params_lm, self.layout, ecfg)
        )
        self.decoder = GreedyDecoder(cfg, params_lm, ecfg)
        self.cache_slots = getattr(
            self.backend, "cache_slots",
            self.layout.total_len + ecfg.max_new_tokens,
        )
        self.paged = getattr(self.backend, "paged", False)

    # -- paged pool lifecycle (no-ops for non-paged backends) ----------
    def ensure_capacity(self, n_streams: int) -> None:
        """Pre-size the shared KV pool for ``n_streams`` streams."""
        if self.paged:
            self.backend.ensure_pool(n_streams)

    def can_admit(self, n_streams: int = 1) -> bool:
        """True if the KV pool can host ``n_streams`` more streams."""
        if self.paged:
            return self.backend.can_admit(n_streams)
        return True

    def release_state(self, state: Optional[Dict[str, Any]]) -> None:
        """Return a finished/closed stream's slab pages (never copies)."""
        if self.paged:
            self.backend.release(state)

    def kv_bytes_per_stream(self) -> int:
        """Steady-state KV bytes one admitted stream occupies (0 for
        backends without a KV-byte notion, e.g. recurrent families)."""
        fn = getattr(self.backend, "kv_bytes_per_stream", None)
        return fn() if fn is not None else 0

    # ------------------------------------------------------------------
    def _query_embeds(self, S: int) -> jnp.ndarray:
        ids = jnp.asarray(QUERY_IDS, jnp.int32)[None]
        qe = tfm.embed_tokens(self.cfg, self.params, ids)
        return jnp.broadcast_to(qe, (S,) + qe.shape[1:])

    def batch_key(self, state: Optional[Dict[str, Any]]) -> tuple:
        """Windows sharing a key may be fused into one batched call."""
        if state is None or not self.reuse:
            return ("fresh",)
        if self.is_streaming_family:
            return ("inc", state["offset"])
        if not self.backend.batchable_step:
            return ("inc", id(state))     # never batched (cacheblend)
        return ("inc",)

    # -- stage surfaces (docs/async_scheduler.md) ----------------------
    # Each stage takes the previous stage's output and returns as soon
    # as its device work is DISPATCHED; ``finalize_stats`` is the only
    # host sync.  ``serve_batch`` composes them back-to-back, so the
    # lockstep scheduler, the async scheduler, and the batch=1 Engine
    # all run the exact same stage code (and therefore the exact same
    # numerics) — they differ only in how stages interleave.

    def encode_windows(
        self,
        frames: jnp.ndarray,                 # (S, W, H, Wd)
        metas: Sequence[CodecMetadata],
        fresh: bool,
    ) -> EncodedWindows:
        """Stage 2: ViT-encode one fused group (full window if fresh,
        last stride otherwise).  Needs no per-stream KV state, so the
        async scheduler may run it ahead of the previous window's
        prefill/decode (lookahead)."""
        lay = self.layout
        disp0 = kernel_ops.dispatch_counts()
        if fresh:
            rng = range(lay.window)
        else:
            rng = range(lay.window - lay.stride, lay.window)
        with tracing.span("serve.vit.encode", windows=frames.shape[0],
                          fresh=fresh) as sp:
            vis, vval, patches, slots = self.encoder.encode(frames, metas,
                                                            rng)
            qe = self._query_embeds(frames.shape[0])
        fb = metrics.kernel_fallback_delta(
            disp0, kernel_ops.dispatch_counts()
        )
        return EncodedWindows(vis, vval, qe, patches, slots, fresh,
                              sp.seconds, fb)

    def prefill_windows(
        self,
        enc: EncodedWindows,
        state: Optional[Dict[str, Any]],     # batched per-stream state
    ) -> PrefilledWindows:
        """Stage 3: build/extend LLM context for one fused group.
        ``state`` is the batched session state from the previous window
        (None for fresh groups).  Family differences live entirely
        behind the ``PrefillBackend`` protocol."""
        disp0 = kernel_ops.dispatch_counts()
        with tracing.span("serve.prefill.dispatch",
                          windows=enc.vis.shape[0], fresh=enc.fresh) as sp:
            if enc.fresh:
                pr = self.backend.fresh(enc.vis, enc.vval, enc.qe)
            else:
                pr = self.backend.step(enc.vis, enc.vval, enc.qe, state)
        fb = metrics.kernel_fallback_delta(
            disp0, kernel_ops.dispatch_counts()
        )
        return PrefilledWindows(pr, sp.seconds - pr.t_select, fb)

    def decode_windows(self, pf: PrefilledWindows) -> DecodedWindows:
        """Stage 4: dispatch the greedy continuation and fold the decode
        caches back into the stream state.  No host sync — the answers
        stay on device until ``finalize_stats``."""
        pr = pf.pr
        disp0 = kernel_ops.dispatch_counts()
        with tracing.span("serve.decode.dispatch",
                          windows=pr.logits.shape[0]) as sp:
            pend = self.decoder.start(
                pr.logits, pr.decode_caches, pr.decode_start, pr.flops_len,
                page_table=pr.page_table,
                cache_len=(self.cache_slots if pr.page_table is not None
                           else None),
            )
            self.backend.absorb_decode(pr.state, pend.caches)
        fb = metrics.kernel_fallback_delta(
            disp0, kernel_ops.dispatch_counts()
        )
        return DecodedWindows(pend, sp.seconds, fb)

    def finalize_stats(
        self,
        enc: EncodedWindows,
        pf: PrefilledWindows,
        dec: DecodedWindows,
    ) -> List[WindowStats]:
        """Stage 5: sync the window's answers off device and assemble
        per-stream ``WindowStats``.  The sync wall time is charged to
        the decode share (it is the tail of the decode stream)."""
        pr, pend = pf.pr, dec.pend
        S = pend.answers.shape[0]
        with tracing.span("serve.finalize.fetch") as sp:
            yes_no = np.asarray(pend.yes_no, np.float64)
            answers = np.asarray(pend.answers).astype(np.int64)
        t_decode = dec.t_decode + sp.seconds
        n_fallback = enc.fallbacks + pf.fallbacks + dec.fallbacks
        patches, slots = enc.patches, enc.slots
        kv_bytes = self.kv_bytes_per_stream()
        return [
            WindowStats(
                answer=int(answers[i]),
                logits_yes_no=(float(yes_no[i, 0]), float(yes_no[i, 1])),
                tokens_vis=pr.tokens_vis,
                tokens_valid=int(pr.tokens_valid[i]),
                tokens_refreshed=pr.n_refreshed,
                vit_patches=int(patches[i]),
                vit_slots=int(slots[i]),
                flops_vit=flopcount.vit_flops(self.v, int(patches[i])),
                flops_prefill=pr.flops,
                flops_decode=pend.flops_decode,
                t_codec=0.0, t_vit=enc.t_vit / S,
                t_prefill=pf.t_prefill / S,
                t_decode=t_decode / S, t_overhead=pr.t_select / S,
                kernel_fallbacks=n_fallback,
                kv_bytes_per_stream=kv_bytes,
            )
            for i in range(S)
        ]

    # ------------------------------------------------------------------
    def serve_batch(
        self,
        frames: jnp.ndarray,                  # (S, W, H, Wd)
        metas: Sequence[CodecMetadata],
        state: Optional[Dict[str, Any]],      # batched per-stream state
    ) -> Tuple[List[WindowStats], Dict[str, Any]]:
        """Serve one window of S same-layout, same-phase streams: the
        synchronous composition of the four stage surfaces above."""
        fresh = state is None or not self.reuse
        enc = self.encode_windows(frames, metas, fresh)
        pf = self.prefill_windows(enc, state)
        dec = self.decode_windows(pf)
        stats = self.finalize_stats(enc, pf, dec)
        return stats, pf.pr.state
