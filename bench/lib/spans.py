"""The serving loop's own host spans in a profiler trace, against the
device's idle time.

The program writes ``serve.`` spans (``repro/serving/tracing.py``) with
``jax.profiler.TraceAnnotation``; the benchmark writes ``bench.`` spans.
Both land on the host plane, one line per thread, on the clock of the
device planes (``bench/lib/trace.py``).  This module reads them with
their thread and arguments, and from them:

  * names each idle gap of the device by the shortest ``serve.`` span
    on the scheduler's thread (the one that holds ``bench.window``)
    that covers at least half of the gap, else by the host span that
    covers most of it (``trace.py``'s rule);
  * counts the blocking fetches (spans whose name ends in ``.fetch``)
    and times them;
  * the share of the window in which the device is idle while the
    scheduler thread does host work (inside a ``serve.`` span, not in a
    fetch);
  * codec open time per frame opened.
"""
from __future__ import annotations

import bisect
import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

from . import trace as tracemod

PREFIXES = ("serve.", "bench.")
FETCH = ".fetch"


@dataclasses.dataclass
class Span:
    name: str
    start: int          # ns
    end: int
    thread: int         # index of the host line (one per thread)
    args: Dict[str, float]

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9


def load(path: str) -> List[Span]:
    """Every ``serve.`` and ``bench.`` span of the trace, by start."""
    from jax.profiler import ProfileData

    out: List[Span] = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIXES):
                    s = int(ev.start_ns)
                    out.append(Span(ev.name, s, s + int(ev.duration_ns), i,
                                    dict(ev.stats)))
    out.sort(key=lambda sp: (sp.start, -sp.end))
    return out


def scheduler_thread(spans: Sequence[Span]) -> int:
    """The thread that holds ``bench.window``: it drives the scheduler."""
    for sp in spans:
        if sp.name == tracemod.WINDOW_SPAN:
            return sp.thread
    raise ValueError(f"no {tracemod.WINDOW_SPAN!r} span in the trace")


def bounds(spans: Sequence[Span]) -> Tuple[int, int]:
    win = [sp for sp in spans if sp.name == tracemod.WINDOW_SPAN]
    if not win:
        raise ValueError(f"no {tracemod.WINDOW_SPAN!r} span in the trace")
    return win[-1].start, win[-1].end


def inside(spans: Sequence[Span], lo: int, hi: int, prefix: str = "serve.",
           thread: Optional[int] = None) -> List[Span]:
    """Spans named ``prefix...`` that start in [lo, hi)."""
    return [sp for sp in spans if sp.name.startswith(prefix)
            and lo <= sp.start < hi
            and (thread is None or sp.thread == thread)]


# ----------------------------------------------------------------------
# device idle time
# ----------------------------------------------------------------------
def idle(tr: tracemod.Trace, lo: int, hi: int) -> List[Tuple[int, int]]:
    """Stretches of [lo, hi) in which no operation ran on the device
    (the first device plane; a serving cell runs on one chip)."""
    ops = next(iter(tr.devices.values()))
    busy = tracemod._union([(s, e) for _, s, e in tracemod._clip(ops, lo, hi)])
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    return [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]


def _measure(ivals: Sequence[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in tracemod._union(ivals))


def _intersect(a: Sequence[Tuple[int, int]],
               b: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    """Intersection of two unions of intervals."""
    a, b = tracemod._union(a), tracemod._union(b)
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


class Labeler:
    """Names idle gaps: the shortest ``serve.`` span on the scheduler
    thread covering at least half of the gap, else the host span
    covering most of it (``trace.py``'s rule)."""

    def __init__(self, spans: Sequence[Span]):
        th = scheduler_thread(spans)
        self.own = [sp for sp in spans
                    if sp.thread == th and sp.name.startswith("serve.")]
        self.starts = [sp.start for sp in self.own]
        self.longest = max((sp.end - sp.start for sp in self.own),
                           default=0)
        self.rest = [(sp.name, sp.start, sp.end) for sp in spans
                     if sp.name != tracemod.WINDOW_SPAN]

    def __call__(self, a: int, b: int) -> str:
        best, length = None, None
        i = bisect.bisect_left(self.starts, a - self.longest)
        j = bisect.bisect_left(self.starts, b)
        for sp in self.own[i:j]:
            if 2 * (min(b, sp.end) - max(a, sp.start)) >= b - a and (
                    length is None or sp.end - sp.start < length):
                best, length = sp.name, sp.end - sp.start
        return best if best is not None else tracemod._label(self.rest, a, b)


def gaps(tr: tracemod.Trace, spans: Sequence[Span],
         n: int = 10) -> List[Tuple[str, float]]:
    """The ``n`` longest idle gaps of the window, longest first, each
    with its label and seconds."""
    lo, hi = bounds(spans)
    name = Labeler(spans)
    holes = sorted(idle(tr, lo, hi), key=lambda h: h[0] - h[1])[:n]
    return [(name(a, b), (b - a) * 1e-9) for a, b in holes]


def idle_by_label(tr: tracemod.Trace,
                  spans: Sequence[Span]) -> Dict[str, float]:
    """Idle seconds of the whole window, by gap label."""
    lo, hi = bounds(spans)
    name = Labeler(spans)
    out: Dict[str, float] = {}
    for a, b in idle(tr, lo, hi):
        k = name(a, b)
        out[k] = out.get(k, 0.0) + (b - a) * 1e-9
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


# ----------------------------------------------------------------------
# per-layer numbers
# ----------------------------------------------------------------------
def fetches(spans: Sequence[Span]) -> List[Span]:
    """The window's blocking fetches on the scheduler thread."""
    lo, hi = bounds(spans)
    th = scheduler_thread(spans)
    return [sp for sp in inside(spans, lo, hi, thread=th)
            if sp.name.endswith(FETCH)]


def windows_answered(spans: Sequence[Span]) -> int:
    """Windows finalized inside the window (``serve.finalize.group``'s
    ``windows`` argument)."""
    lo, hi = bounds(spans)
    return int(sum(sp.args.get("windows", 0) for sp in
                   inside(spans, lo, hi, "serve.finalize.group")))


def fetches_per_window(spans: Sequence[Span]) -> Optional[float]:
    n = windows_answered(spans)
    return len(fetches(spans)) / n if n else None


def _within(child: Span, parents: Sequence[Span]) -> bool:
    return any(p.thread == child.thread and p.start <= child.start
               and child.end <= p.end for p in parents)


def vit_fetch_ms_per_window(spans: Sequence[Span]) -> Optional[float]:
    """Scheduler-thread milliseconds in fetches under ``serve.encode``,
    over the windows encoded inside the window."""
    lo, hi = bounds(spans)
    th = scheduler_thread(spans)
    groups = inside(spans, lo, hi, "serve.encode.group", th)
    n = sum(sp.args.get("windows", 0) for sp in groups)
    if not n:
        return None
    passes = inside(spans, lo, hi, "serve.encode", th)
    t = sum(sp.seconds for sp in fetches(spans) if _within(sp, passes))
    return 1e3 * t / n


def codec_open_ms_per_frame(spans: Sequence[Span]) -> Optional[float]:
    """``serve.codec.open`` milliseconds per frame opened, over the opens
    inside the window."""
    lo, hi = bounds(spans)
    opens = inside(spans, lo, hi, "serve.codec.open")
    frames = sum(sp.args.get("frames", 0) for sp in opens)
    if not frames:
        return None
    return 1e3 * sum(sp.seconds for sp in opens) / frames


def idle_in_host_work_share(tr: tracemod.Trace,
                            spans: Sequence[Span]) -> Optional[float]:
    """Share of the window, in %, in which the device is idle while the
    scheduler thread is inside a ``serve.`` span and not in a fetch."""
    lo, hi = bounds(spans)
    th = scheduler_thread(spans)
    own = [sp for sp in spans if sp.thread == th
           and sp.name.startswith("serve.") and sp.end > lo and sp.start < hi]
    if not own:
        return None
    work = [(max(sp.start, lo), min(sp.end, hi)) for sp in own
            if not sp.name.endswith(FETCH)]
    waits = [(max(sp.start, lo), min(sp.end, hi)) for sp in own
             if sp.name.endswith(FETCH)]
    holes = idle(tr, lo, hi)
    t = _measure(_intersect(holes, work)) - _measure(
        _intersect(_intersect(holes, work), waits))
    return 100.0 * t / (hi - lo)


def seconds_by_name(spans: Sequence[Span],
                    thread: Optional[int] = None) -> Dict[str, float]:
    """Host seconds per ``serve.`` span name inside the window."""
    lo, hi = bounds(spans)
    out: Dict[str, float] = {}
    for sp in inside(spans, lo, hi, thread=thread):
        out[sp.name] = out.get(sp.name, 0.0) + sp.seconds
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def counts_by_name(spans: Sequence[Span]) -> Dict[str, int]:
    lo, hi = bounds(spans)
    out: Dict[str, int] = {}
    for sp in inside(spans, lo, hi):
        out[sp.name] = out.get(sp.name, 0) + 1
    return out
