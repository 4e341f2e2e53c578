"""Stage-pipelined multi-stream scheduler over the serving pipeline.

Two execution engines behind one event-driven API
(docs/async_scheduler.md):

  * **pipelined** (default) — per-stage queues with overlapped
    execution.  Codec window slicing runs on host worker threads while
    the accelerator serves earlier groups; each stage forms its own
    fused group from whatever is ready (continuous batching), so a
    stream can be ViT-encoding window k+1 while its window k is still
    in prefill/decode.  Device results are not fetched until a window
    is *finalized*: the encode/prefill/decode stage surfaces of
    ``ServingPipeline`` only dispatch, exploiting JAX async dispatch
    (and, on non-CPU backends, buffer donation of the paged KV slab).
  * **lockstep** (``SchedulerCfg(pipelined=False)``) — the legacy loop:
    ONE fused group per step through the synchronous ``serve_batch``,
    fully synced before the next.  Kept as the A/B baseline of
    ``benchmarks/bench_streams.py``; numerics are identical per window.

Admission + batching policy (both engines):

  * ``submit`` performs codec ingest (stage 1) and queues the session;
    up to ``max_concurrent`` sessions are *admitted* (hold KV state) at
    a time — finished sessions free their slot for queued ones, and
    paged backends refuse admission the KV pool cannot back
    (``StreamThrottled``).
  * Fused groups only join windows that share a batch key (same layout
    + same phase: fresh vs incremental; recurrent families additionally
    require an equal boundary-state offset), so the jitted stage
    functions trace once per (batch size, phase) pair.
  * Per-stream KV states are concatenated along the batch axis before a
    fused call and split back after; that (de)staging cost is measured
    and reported as ``WindowStats.t_overhead``.  A mis-grouped batch
    raises ``SchedulerError`` (with the stream ids) instead of
    asserting.

Drive the scheduler with ``events()`` / ``step()`` (typed
``SchedulerEvent``s) or ``run()``; ``poll()`` survives as a deprecated
lockstep shim.
"""
from __future__ import annotations

import dataclasses
import threading
import time
import warnings
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from typing import (
    Any, Dict, Iterator, List, NamedTuple, Optional, Sequence,
)

import jax
import jax.numpy as jnp
import numpy as np

from . import flops as flopcount
from . import tracing
from .api import (
    EncodedWindows, ServingPipeline, StreamRequest, StreamSession,
    WindowResult, WindowStats,
)
from .config import SchedulerCfg
from .events import (
    SchedulerError, SchedulerEvent, StreamAdmitted, StreamDone,
    StreamThrottled, WindowDone,
)

STAGES = ("ingest", "encode", "prefill", "decode", "finalize")


# ----------------------------------------------------------------------
# batched-state (de)staging
# ----------------------------------------------------------------------
def _concat_states(states: List[Dict[str, Any]],
                   sids: Sequence[int] = ()) -> Dict[str, Any]:
    """Stack per-session (batch=1) KV states into one batched state.

    ``caches`` pytrees carry batch on axis 1 (leading axis is the layer
    repeat), plain arrays on axis 0; ``pages`` rows are host page
    indices into the shared slab (paged mode — the KV itself is never
    copied); python scalars (e.g. the recurrent ``offset``) must agree
    across the group.
    """
    out: Dict[str, Any] = {}
    for key in states[0]:
        vals = [s[key] for s in states]
        if key == "caches":
            out[key] = jax.tree_util.tree_map(
                lambda *xs: jnp.concatenate(xs, axis=1), *vals
            )
        elif key == "pages":
            out[key] = np.concatenate(vals, axis=0)
        elif isinstance(vals[0], np.ndarray):
            # host-side per-stream metadata (e.g. the quant demote
            # clock "age") stays numpy — no device staging
            out[key] = np.concatenate(vals, axis=0)
        elif isinstance(vals[0], (int, float)):
            if not all(v == vals[0] for v in vals):
                raise SchedulerError(
                    f"cannot fuse windows: scalar state {key!r} differs "
                    f"across the group ({vals})", stream_ids=sids,
                )
            out[key] = vals[0]
        else:
            out[key] = jnp.concatenate(vals, axis=0)
    return out


def _split_state(state: Dict[str, Any], n: int) -> List[Dict[str, Any]]:
    """Inverse of ``_concat_states``: n per-session batch=1 states."""
    outs: List[Dict[str, Any]] = [dict() for _ in range(n)]
    for key, val in state.items():
        if key == "caches":
            for i in range(n):
                outs[i][key] = jax.tree_util.tree_map(
                    lambda x: x[:, i: i + 1], val
                )
        elif isinstance(val, (int, float)):
            for i in range(n):
                outs[i][key] = val
        else:
            for i in range(n):
                outs[i][key] = val[i: i + 1]
    return outs


def _staged_bytes(state: Optional[Dict[str, Any]]) -> int:
    """Bytes one session contributes to fused-call state staging.

    Paged sessions carry page indices instead of KV pytrees, so their
    staged footprint is orders of magnitude below a dense session's —
    this is what ``WindowStats.t_overhead`` attribution weighs."""
    if not state:
        return 0
    total = 0
    for key, val in state.items():
        if key == "caches":
            total += sum(
                x.size * x.dtype.itemsize
                for x in jax.tree_util.tree_leaves(val)
            )
        elif hasattr(val, "nbytes"):
            total += int(val.nbytes)
    return total


# ----------------------------------------------------------------------
# per-stream pipeline program (async engine bookkeeping)
# ----------------------------------------------------------------------
class _EncRow(NamedTuple):
    """One stream's row of a fused encode call, queued for the prefill
    stage.  The row keeps a reference to the whole batched encode
    output (``enc``, ``idx``) instead of slicing eagerly: when the
    prefill group turns out to be exactly the encode group (the steady
    state), the batched arrays are passed straight through with zero
    re-staging."""

    window: int
    enc: EncodedWindows              # the fused encode output (batched)
    idx: int                         # this stream's row in ``enc``
    patches: int
    slots: int
    fresh: bool
    t_vit: float                     # per-stream share of the fused call
    fallbacks: int                   # whole encode group's count (shared)
    t_codec: float                   # amortized codec time (stage 1)


class _Inflight(NamedTuple):
    """One fused prefill+decode group dispatched but not yet finalized."""

    progs: List["_Program"]
    rows: List[_EncRow]
    pf: Any                          # PrefilledWindows
    dec: Any                         # DecodedWindows
    t_stage: float                   # state (de)staging wall time
    shares: List[float]              # per-stream staging attribution
    tick: int                        # scheduler tick that dispatched it


@dataclasses.dataclass
class _Program:
    """Stage cursors of one admitted session.

    ``next_ingest``/``next_encode``/``next_prefill`` are the first
    window index the stage has NOT yet taken; ``sess.next_window`` (the
    finalize cursor) advances only when a window's results are synced.
    """

    sess: StreamSession
    t_submit: float
    futs: Dict[int, Any] = dataclasses.field(default_factory=dict)
    enc_rows: Dict[int, _EncRow] = dataclasses.field(default_factory=dict)
    next_ingest: int = 0
    next_encode: int = 0
    next_prefill: int = 0


def _chunks(seq: List[Any], n: int) -> Iterator[List[Any]]:
    for i in range(0, len(seq), n):
        yield seq[i: i + n]


# ----------------------------------------------------------------------
class Scheduler:
    """Admits N concurrent ``StreamSession``s and serves ready windows
    of same-layout streams in batched, stage-pipelined calls.

    Usage::

        sched = Scheduler(pipeline, SchedulerCfg(max_concurrent=8))
        sid = sched.submit(StreamRequest("cam-0", frames))
        for ev in sched.events():
            match ev:
                case WindowDone():  ...   # per-window result
                case StreamDone():  ...   # KV state already released
        results = sched.close(sid)        # per-stream window results
    """

    def __init__(self, pipeline: ServingPipeline,
                 cfg: Optional[SchedulerCfg] = None, *,
                 max_concurrent: Optional[int] = None,
                 max_batch: Optional[int] = None,
                 pipelined: Optional[bool] = None,
                 ingest_workers: Optional[int] = None,
                 lookahead: Optional[int] = None):
        cfg = cfg or SchedulerCfg()
        overrides = {
            k: v for k, v in dict(
                max_concurrent=max_concurrent, max_batch=max_batch,
                pipelined=pipelined, ingest_workers=ingest_workers,
                lookahead=lookahead,
            ).items() if v is not None
        }
        if overrides:
            cfg = dataclasses.replace(cfg, **overrides)
        assert cfg.max_concurrent >= 1
        self.cfg = cfg
        self.pipeline = pipeline
        self.max_concurrent = cfg.max_concurrent
        self.max_batch = cfg.max_batch or cfg.max_concurrent
        # paged backends: size the shared KV slab for the concurrency
        # ceiling ONCE — admission below never triggers an allocation
        pipeline.ensure_capacity(cfg.max_concurrent)
        self._queue: deque[StreamSession] = deque()
        self._active: Dict[int, StreamSession] = {}
        self._sessions: Dict[int, StreamSession] = {}
        self._programs: Dict[int, _Program] = {}
        self._inflight: deque[_Inflight] = deque()
        self._event_buffer: List[SchedulerEvent] = []
        self._throttled: set = set()
        self._t_submit: Dict[int, float] = {}
        self._executor: Optional[ThreadPoolExecutor] = None
        # guards stage_busy: the one accumulator both ingest worker
        # threads and the main loop write (everything else in the
        # metrics block below is main-thread-only — see the
        # shared-state inventory in docs/static_analysis.md)
        self._metrics_lock = threading.Lock()
        self._next_sid = 0
        self._tick = 0
        # -- fleet metrics ---------------------------------------------
        self.windows_served = 0
        self.t_serve = 0.0               # host seconds of serve.step spans
        # fleet-level ViT packing efficiency: kept patches vs lanes the
        # encoder actually computed (padded capacity or packed buffer)
        self.vit_patches = 0
        self.vit_slots = 0
        # silent kernel→oracle fallbacks observed across all batched
        # stage calls (rows of one call share the count: add it once)
        self.kernel_fallbacks = 0
        # host seconds per stage, read from the stages' ``serve.``
        # spans (dispatch and fetch wall, not device time); with >1
        # ingest worker, ingest busy time can exceed scheduler wall
        self.stage_busy: Dict[str, float] = {s: 0.0 for s in STAGES}
        # per-stream time to first answer: submit -> first window
        # finalized (its answers fetched)
        self.ttft: Dict[int, float] = {}

    # -- session lifecycle ---------------------------------------------
    def submit(self, request: StreamRequest) -> int:
        """Open a session (codec ingest) and queue it for admission."""
        with tracing.span("serve.submit", frames=len(request.frames)):
            stream = self.pipeline.frontend.open(request.frames)
            sess = StreamSession(self._next_sid, request, stream)
            self._next_sid += 1
            self._sessions[sess.sid] = sess
            self._queue.append(sess)
            self._t_submit[sess.sid] = time.perf_counter()
        return sess.sid

    def session(self, sid: int) -> StreamSession:
        return self._sessions[sid]

    def close(self, sid: int) -> List[WindowResult]:
        """Release the session's KV state; returns its window results.

        Closing a stream with dispatched-but-unfinalized windows first
        drains every inflight group up to and including that stream's
        (FIFO, so other streams' window order is preserved); their
        events are delivered by the next ``step()``."""
        sess = self._sessions.pop(sid)
        while any(p.sess.sid == sid
                  for g in self._inflight for p in g.progs):
            self._finalize_group(self._inflight.popleft(),
                                 self._event_buffer)
        self._active.pop(sid, None)
        self._programs.pop(sid, None)
        self._throttled.discard(sid)
        try:
            self._queue.remove(sess)
        except ValueError:
            pass
        self.pipeline.release_state(sess.state)
        sess.state = None
        return sess.results

    @property
    def idle(self) -> bool:
        return (not self._queue and not self._inflight
                and all(s.done for s in self._active.values()))

    # -- admission -----------------------------------------------------
    def _admit(self, events: Optional[List[SchedulerEvent]]) -> None:
        with tracing.span("serve.admit"):
            for sid in [s for s, sess in self._active.items() if sess.done]:
                del self._active[sid]
                self._programs.pop(sid, None)
            # paged backends: an admitted session claims its slab pages on
            # its first fresh window — count sessions not yet holding pages
            # and refuse admission the pool cannot back, instead of letting
            # the fresh call hit PoolExhausted mid-batch
            n_unbacked = sum(
                1 for sess in self._active.values()
                if not (sess.state and "pages" in sess.state)
            )
            while self._queue and len(self._active) < self.max_concurrent:
                if not self.pipeline.can_admit(n_unbacked + 1):
                    head = self._queue[0]
                    if events is not None and head.sid not in self._throttled:
                        self._throttled.add(head.sid)
                        events.append(StreamThrottled(
                            head.sid, head.request.stream_id
                        ))
                    break                    # wait for a stream to release
                sess = self._queue.popleft()
                self._throttled.discard(sess.sid)
                if events is not None:
                    events.append(StreamAdmitted(
                        sess.sid, sess.request.stream_id
                    ))
                if not sess.done:            # zero-window streams finish here
                    self._active[sess.sid] = sess
                    self._programs[sess.sid] = _Program(
                        sess, self._t_submit[sess.sid]
                    )
                    n_unbacked += 1
                elif events is not None:
                    events.append(StreamDone(
                        sess.sid, sess.request.stream_id, n_windows=0
                    ))

    # ==================================================================
    # event-driven API
    # ==================================================================
    def step(self) -> List[SchedulerEvent]:
        """Advance the scheduler by one tick; returns the events it
        produced (possibly none when idle)."""
        events = self._event_buffer
        self._event_buffer = []
        with tracing.step("serve.step", step_num=self._tick) as sp:
            self._admit(events)
            if not self.cfg.pipelined:
                self._serve_one_group(events)
            else:
                # dispatch order minimizes answer latency: windows whose
                # encode landed last tick go to prefill+decode FIRST,
                # then the next windows' encode (lookahead) queues
                # behind them on the device, then the oldest inflight
                # group is synced — by which time the device is already
                # busy with this tick's dispatches and the ingest
                # threads with the next windows' slicing.
                did_prefill = self._prefill_pass()
                did_encode = self._encode_pass()
                if did_encode and not did_prefill:
                    # first-window catch-up
                    did_prefill = self._prefill_pass()
                # groups dispatched this tick are only synced next tick
                # — unless nothing was dispatched, in which case drain
                # fully so the scheduler always makes progress toward
                # idle
                self._finalize_pass(events, drain=not (did_prefill
                                                       or did_encode))
                self._tick += 1
        self.t_serve += sp.seconds
        return events

    def events(self) -> Iterator[SchedulerEvent]:
        """Drive the scheduler to idle, yielding events as they occur.

        Raises ``SchedulerError`` if the scheduler stalls (admission
        blocked with no work in flight — e.g. a KV pool pinned smaller
        than a single stream's page need)."""
        stalls = 0
        while True:
            evs = self.step()
            yield from evs
            if self.idle and not self._event_buffer:
                self._shutdown_ingest()
                return
            # a dispatch-only tick (results sync next tick) can yield no
            # events once; three in a row means nothing is moving
            stalls = 0 if evs else stalls + 1
            if stalls >= 3:
                raise SchedulerError(
                    "scheduler stalled: admission blocked and no work "
                    "in flight (KV pool too small for one stream?)",
                    stream_ids=sorted(
                        [s.sid for s in self._queue] + list(self._active)
                    ),
                )

    def run(self) -> Dict[int, List[WindowResult]]:
        """Drain every open session; per-session window results.

        Sessions already ``close``d are not included — ``close`` returned
        their results."""
        for _ in self.events():
            pass
        return {sid: sess.results for sid, sess in self._sessions.items()}

    # -- deprecated pull API -------------------------------------------
    def poll(self) -> List[WindowResult]:
        """Deprecated: serve ONE fused group synchronously (lockstep
        semantics regardless of ``cfg.pipelined``); [] when nothing is
        ready.  Use ``step()``/``events()`` instead."""
        warnings.warn(
            "Scheduler.poll() is deprecated; drive the scheduler with "
            "step()/events()/run() (docs/async_scheduler.md)",
            DeprecationWarning, stacklevel=2,
        )
        with tracing.step("serve.step", step_num=self._tick) as sp:
            self._finalize_pass(self._event_buffer)  # flush async inflight
            for prog in self._programs.values():
                # drop stage-ahead work so a window dispatched by step()
                # is never re-served by the lockstep path (don't mix the
                # APIs)
                prog.enc_rows.clear()
                prog.futs.clear()
                prog.next_ingest = prog.next_encode = prog.next_prefill = \
                    prog.sess.next_window
            # events go to the deferred buffer, not to the caller (poll
            # predates the event API and returns raw WindowResults) —
            # but they MUST still be emitted, or a consumer that mixes
            # poll() with events() sees WindowDone/StreamDone with no
            # admission and the per-stream protocol breaks (tools/check
            # event-protocol pass; EventProtocolValidator).  The buffer
            # is delivered by the next step().
            self._admit(self._event_buffer)
            results = self._serve_one_group(self._event_buffer)
            for prog in self._programs.values():
                # re-sync stage cursors AFTER serving: programs created
                # by this poll's admission start at window 0, and the
                # lockstep serve advanced sess.next_window without
                # moving the pipelined cursors — leaving them behind
                # would make the next step() re-serve (and re-admit KV
                # pages for) a window poll already delivered
                prog.next_ingest = prog.next_encode = prog.next_prefill = \
                    prog.sess.next_window
        self.t_serve += sp.seconds
        return results

    # ==================================================================
    # lockstep engine (A/B baseline + poll shim)
    # ==================================================================
    def _ready_groups(self) -> List[List[StreamSession]]:
        groups: Dict[tuple, List[StreamSession]] = {}
        for sess in self._active.values():
            if sess.done:
                continue
            key = self.pipeline.batch_key(sess.state)
            groups.setdefault(key, []).append(sess)
        return list(groups.values())

    def _serve_one_group(
        self, events: Optional[List[SchedulerEvent]]
    ) -> List[WindowResult]:
        """Serve the largest ready group through the synchronous
        ``serve_batch`` composition (ingest→…→finalize back-to-back)."""
        groups = self._ready_groups()
        if not groups:
            return []
        group = max(groups, key=len)[: self.max_batch]

        # stage 1: window slices (+ amortized codec time)
        with tracing.span("serve.codec.slice", windows=len(group)) as sp:
            frames_l, metas, t_codecs = [], [], []
            for sess in group:
                wf, wm, tc = self.pipeline.frontend.window(
                    sess.stream, sess.next_window
                )
                frames_l.append(wf)
                metas.append(wm)
                t_codecs.append(tc)
            frames = jnp.stack(frames_l, 0)
        self._bump_stage("ingest", sp.seconds)

        # batched-state staging (measured scheduler overhead); singleton
        # groups bypass it — the batch=1 path stays copy-free like the
        # legacy Engine
        fresh = group[0].state is None or not self.pipeline.reuse
        staged = [_staged_bytes(sess.state) for sess in group]
        tot_staged = sum(staged)
        with tracing.span("serve.prefill.state_concat") as concat:
            if fresh:
                state = None
            elif len(group) == 1:
                state = group[0].state
            else:
                state = _concat_states([s.state for s in group],
                                       sids=[s.sid for s in group])

        stats, new_state = self.pipeline.serve_batch(frames, metas, state)

        with tracing.span("serve.prefill.state_split") as split:
            if not self.pipeline.reuse:
                # non-reuse modes never consume state: skip the split
                # and don't pin dead cache pytrees on the sessions
                per_states = [None] * len(group)
            elif len(group) == 1:
                per_states = [new_state]
            else:
                per_states = _split_state(new_state, len(group))
        t_stage = concat.seconds + split.seconds

        results = []
        now = time.perf_counter()
        for i, sess in enumerate(group):
            st = stats[i]
            st.t_codec += t_codecs[i]
            # staging cost is attributed by the KV bytes each stream
            # actually moved through the fused call, not uniformly —
            # paged sessions stage page indices, dense ones full caches
            share = staged[i] / tot_staged if tot_staged else 1 / len(group)
            st.t_overhead += t_stage * share
            res = WindowResult(sess.request.stream_id, sess.sid,
                               sess.next_window, st)
            sess.results.append(res)
            window = sess.next_window
            sess.next_window += 1
            # completed sessions keep results but release their KV state
            # immediately — KV-cache memory scales with max_concurrent,
            # not with the total number of submitted streams (decoded
            # frame buffers, by contrast, live from submit-time ingest);
            # paged sessions hand their slab pages back to the pool
            if sess.done:
                self.pipeline.release_state(per_states[i])
                sess.state = None
            else:
                sess.state = per_states[i]
            results.append(res)
            self.vit_patches += st.vit_patches
            self.vit_slots += st.vit_slots
            self._bump_stage("encode", st.t_vit)
            self._bump_stage("prefill", st.t_prefill)
            self._bump_stage("decode", st.t_decode)
            if window == 0:
                self.ttft[sess.sid] = now - self._t_submit[sess.sid]
            if events is not None:
                events.append(WindowDone(
                    sess.sid, sess.request.stream_id, res
                ))
                if sess.done:
                    events.append(StreamDone(
                        sess.sid, sess.request.stream_id,
                        n_windows=sess.next_window,
                    ))
        self.kernel_fallbacks += stats[0].kernel_fallbacks
        self.windows_served += len(results)
        return results

    # ==================================================================
    # pipelined engine (per-stage passes)
    # ==================================================================
    def _ingest_pool(self) -> Optional[ThreadPoolExecutor]:
        if self.cfg.ingest_workers <= 0:
            return None
        if self._executor is None:
            self._executor = ThreadPoolExecutor(
                max_workers=self.cfg.ingest_workers,
                thread_name_prefix="codec-ingest",
            )
        return self._executor

    def _shutdown_ingest(self) -> None:
        if self._executor is not None:
            self._executor.shutdown(wait=True)
            self._executor = None

    def _bump_stage(self, stage: str, dt: float) -> None:
        """Accumulate stage-busy wall time.  ``stage_busy`` is the one
        metrics dict touched from both ingest worker threads
        (``_ingest_one``) and the main loop, so every access — either
        side — goes through ``_metrics_lock``; a bare ``+=`` on the
        shared float is a lost-update race under the pool."""
        with self._metrics_lock:
            self.stage_busy[stage] += dt

    def _ingest_one(self, sess: StreamSession, k: int):
        with tracing.span("serve.codec.slice", window=k) as sp:
            out = self.pipeline.frontend.window_host(sess.stream, k)
        self._bump_stage("ingest", sp.seconds)
        return out

    def _ensure_ingest(self, prog: _Program) -> None:
        """Submit window slices to the worker pool up to the lookahead
        bound (ingest runs one window ahead of encode)."""
        bound = min(
            prog.sess.stream.n_windows,
            prog.next_prefill + 1 + self.cfg.lookahead,
        )
        pool = self._ingest_pool()
        while prog.next_ingest < bound:
            k = prog.next_ingest
            prog.futs[k] = (pool.submit(self._ingest_one, prog.sess, k)
                            if pool is not None else None)
            prog.next_ingest += 1

    def _take_ingest(self, prog: _Program, k: int):
        fut = prog.futs.pop(k)
        if fut is None:                      # inline (ingest_workers=0)
            return self._ingest_one(prog.sess, k)
        with tracing.span("serve.encode.ingest_wait", window=k):
            return fut.result()

    def _encode_pass(self) -> bool:
        """Fuse + dispatch ViT encode for every stream whose next
        window is sliced and within the lookahead bound."""
        with tracing.span("serve.encode"):
            ready: Dict[bool, List[_Program]] = {}
            for prog in self._programs.values():
                self._ensure_ingest(prog)
                w = prog.next_encode
                if w >= prog.sess.stream.n_windows:
                    continue
                if w > prog.next_prefill + self.cfg.lookahead:
                    continue
                fresh = w == 0 or not self.pipeline.reuse
                ready.setdefault(fresh, []).append(prog)
            did = False
            for fresh, progs in ready.items():
                for chunk in _chunks(progs, self.max_batch):
                    self._encode_group(chunk, fresh)
                    did = True
        return did

    def _encode_group(self, progs: List[_Program], fresh: bool) -> None:
        S = len(progs)
        with tracing.span("serve.encode.group", windows=S,
                          fresh=fresh) as sp:
            frames_l, metas, t_codecs = [], [], []
            for prog in progs:
                frames, meta, tc = self._take_ingest(prog, prog.next_encode)
                frames_l.append(frames)
                metas.append(meta)
                t_codecs.append(tc)
            enc = self.pipeline.encode_windows(
                jnp.asarray(np.stack(frames_l, 0)), metas, fresh
            )
            sp.set(kept=int(enc.patches.sum()), slots=int(enc.slots.sum()))
        self._bump_stage("encode", sp.seconds)
        self.kernel_fallbacks += enc.fallbacks
        for i, prog in enumerate(progs):
            w = prog.next_encode
            prog.enc_rows[w] = _EncRow(
                window=w, enc=enc, idx=i,
                patches=int(enc.patches[i]), slots=int(enc.slots[i]),
                fresh=fresh, t_vit=enc.t_vit / S,
                fallbacks=enc.fallbacks, t_codec=t_codecs[i],
            )
            prog.next_encode += 1

    def _prefill_pass(self) -> bool:
        """Fuse + dispatch prefill AND decode for every stream whose
        next window is encoded (its state is ready by construction:
        window k-1's decode was dispatched before ``next_prefill``
        advanced to k)."""
        with tracing.span("serve.prefill"):
            groups: Dict[tuple, List[_Program]] = {}
            for prog in self._programs.values():
                row = prog.enc_rows.get(prog.next_prefill)
                if row is None:
                    continue
                key = (("fresh",) if row.fresh
                       else self.pipeline.batch_key(prog.sess.state))
                groups.setdefault(key, []).append(prog)
            did = False
            for key, progs in groups.items():
                for chunk in _chunks(progs, self.max_batch):
                    self._dispatch_group(chunk)
                    did = True
        return did

    def _dispatch_group(self, progs: List[_Program]) -> None:
        rows = [prog.enc_rows.pop(prog.next_prefill) for prog in progs]
        S = len(progs)
        fresh = rows[0].fresh
        with tracing.span("serve.prefill.group", windows=S,
                          fresh=fresh) as group:
            src = rows[0].enc
            if (all(r.enc is src for r in rows)
                    and [r.idx for r in rows] == list(range(S))
                    and src.vis.shape[0] == S):
                # prefill group == encode group (steady state): pass the
                # fused arrays straight through, no re-staging
                enc_g = src
            else:
                enc_g = EncodedWindows(
                    vis=jnp.concatenate(
                        [r.enc.vis[r.idx: r.idx + 1] for r in rows], 0),
                    vval=jnp.concatenate(
                        [r.enc.vval[r.idx: r.idx + 1] for r in rows], 0),
                    qe=jnp.concatenate(
                        [r.enc.qe[r.idx: r.idx + 1] for r in rows], 0),
                    patches=np.array([r.patches for r in rows]),
                    slots=np.array([r.slots for r in rows]),
                    fresh=fresh, t_vit=0.0, fallbacks=0,
                )
            staged = [_staged_bytes(p.sess.state) for p in progs]
            tot_staged = sum(staged)
            with tracing.span("serve.prefill.state_concat") as concat:
                if fresh:
                    state = None
                elif S == 1:
                    state = progs[0].sess.state
                else:
                    state = _concat_states([p.sess.state for p in progs],
                                           sids=[p.sess.sid for p in progs])

            pf = self.pipeline.prefill_windows(enc_g, state)
            dec = self.pipeline.decode_windows(pf)

            with tracing.span("serve.prefill.state_split") as split:
                if not self.pipeline.reuse:
                    per_states = [None] * S
                elif S == 1:
                    per_states = [pf.pr.state]
                else:
                    per_states = _split_state(pf.pr.state, S)
            group.set(refreshed=pf.pr.n_refreshed)
        t_stage = concat.seconds + split.seconds
        # the new state is live as soon as it is dispatched — window
        # k+1's prefill chains on it through device data dependencies,
        # no host sync needed (done streams release at finalize)
        for prog, st in zip(progs, per_states):
            prog.sess.state = st
        self._bump_stage("prefill", group.seconds - dec.t_decode)
        self._bump_stage("decode", dec.t_decode)
        self.kernel_fallbacks += pf.fallbacks + dec.fallbacks
        shares = [b / tot_staged if tot_staged else 1 / S for b in staged]
        self._inflight.append(
            _Inflight(list(progs), rows, pf, dec, t_stage, shares,
                      self._tick)
        )
        for prog in progs:
            prog.next_prefill += 1

    def _finalize_pass(self, events: List[SchedulerEvent],
                       drain: bool = True) -> None:
        """Sync + emit inflight groups, oldest first.  With
        ``drain=False`` only groups dispatched on an EARLIER tick are
        synced — the groups dispatched this tick stay queued on the
        device, so the host blocks on window k only after window k+1's
        prefill/decode is already lined up behind it."""
        with tracing.span("serve.finalize"):
            while self._inflight and (
                    drain or self._inflight[0].tick < self._tick):
                self._finalize_group(self._inflight.popleft(), events)

    def _finalize_group(self, g: _Inflight,
                        events: List[SchedulerEvent]) -> None:
        """Sync one fused group's answers off device and emit its
        ``WindowDone`` (and possibly ``StreamDone``) events."""
        pend = g.dec.pend
        pr = g.pf.pr
        S = len(g.progs)
        with tracing.span("serve.finalize.group", windows=S) as group:
            with tracing.span("serve.finalize.fetch") as fetch:
                yes_no = np.asarray(pend.yes_no, np.float64)
                answers = np.asarray(pend.answers).astype(np.int64)
            now = time.perf_counter()
            # the fetch is the decode tail
            t_decode = g.dec.t_decode + fetch.seconds
            kv_bytes = self.pipeline.kv_bytes_per_stream()
            with tracing.span("serve.finalize.stats"):
                for i, (prog, row) in enumerate(zip(g.progs, g.rows)):
                    sess = prog.sess
                    st = WindowStats(
                        answer=int(answers[i]),
                        logits_yes_no=(float(yes_no[i, 0]),
                                       float(yes_no[i, 1])),
                        tokens_vis=pr.tokens_vis,
                        tokens_valid=int(pr.tokens_valid[i]),
                        tokens_refreshed=pr.n_refreshed,
                        vit_patches=row.patches,
                        vit_slots=row.slots,
                        flops_vit=flopcount.vit_flops(self.pipeline.v,
                                                      row.patches),
                        flops_prefill=pr.flops,
                        flops_decode=pend.flops_decode,
                        t_codec=row.t_codec,
                        t_vit=row.t_vit,
                        t_prefill=g.pf.t_prefill / S,
                        t_decode=t_decode / S,
                        t_overhead=(pr.t_select / S
                                    + g.t_stage * g.shares[i]),
                        kernel_fallbacks=(row.fallbacks + g.pf.fallbacks
                                          + g.dec.fallbacks),
                        kv_bytes_per_stream=kv_bytes,
                    )
                    res = WindowResult(sess.request.stream_id, sess.sid,
                                       row.window, st)
                    sess.results.append(res)
                    sess.next_window += 1
                    self.windows_served += 1
                    self.vit_patches += st.vit_patches
                    self.vit_slots += st.vit_slots
                    if row.window == 0:
                        self.ttft[sess.sid] = now - prog.t_submit
                    events.append(WindowDone(sess.sid,
                                             sess.request.stream_id, res))
                    if sess.done:
                        self.pipeline.release_state(sess.state)
                        sess.state = None
                        events.append(StreamDone(
                            sess.sid, sess.request.stream_id,
                            n_windows=sess.next_window,
                        ))
        self._bump_stage("finalize", group.seconds)

    # ==================================================================
    # fleet metrics
    # ==================================================================
    def kv_memory(self) -> Dict[str, int]:
        """Fleet KV memory: total slab bytes (paged pools; 0 for dense
        and recurrent backends) + steady-state bytes per admitted
        stream.  The denominator of the capacity benches — int8 cold
        pages roughly halve bytes_per_stream at fixed context."""
        pool = getattr(self.pipeline.backend, "pool", None)
        return {
            "slab_bytes": int(pool.slab_bytes) if pool is not None else 0,
            "bytes_per_stream": int(self.pipeline.kv_bytes_per_stream()),
        }

    @property
    def vit_pack_utilization(self) -> float:
        """Kept-patch fraction of the ViT lanes computed so far — the
        cross-stream packing win the padded path cannot express (its
        utilization is pinned at keep-fraction x capacity)."""
        return self.vit_patches / max(self.vit_slots, 1)

    def ttft_quantiles(self) -> Dict[str, float]:
        """p50/p99/mean of per-stream time-to-first-token (submit →
        first window finalized), seconds."""
        vals = list(self.ttft.values())
        if not vals:
            return {}
        return {
            "p50": float(np.percentile(vals, 50)),
            "p99": float(np.percentile(vals, 99)),
            "mean": float(np.mean(vals)),
        }

    def stage_occupancy(self) -> Dict[str, float]:
        """Per-stage host seconds per second inside ``step()``, both read
        from the ``serve.`` spans: host dispatch and fetch time, not
        device time (a profiler trace has that).  Ingest can exceed 1.0
        with multiple worker threads; a lockstep run sums to ~1.0 across
        stages (no overlap by construction)."""
        wall = max(self.t_serve, 1e-9)
        with self._metrics_lock:
            busy = dict(self.stage_busy)
        return {k: v / wall for k, v in busy.items()}
