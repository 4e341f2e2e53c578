"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

Reads the trace with ``jax.profiler.ProfileData`` alone.  The device
planes (``/device:TPU:<n>``) carry one event per executed XLA operation
on their ``XLA Ops`` line; the host plane carries the benchmark's own
spans (``bench.window``, ``bench.step``, ``bench.submit``), written with
``jax.profiler.TraceAnnotation``.  Both are on the host's clock; on a
v5e the device events read about 1.5 ms early against the host spans,
which is noise against a window of seconds.

  * busy: the union of operation intervals inside the window, averaged
    over the device planes; idle share is one minus busy over window;
  * operation time by ``<module>/<instruction>``, and by kernel (a
    Pallas kernel's instruction is named after the jitted function that
    calls it, e.g. ``flash_refresh_paged_pallas.3``);
  * idle gaps: the stretches inside the window where no operation ran,
    each named by the host span that covers most of it.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
# operations whose interval holds other operations' intervals
CONTROL_FLOW = ("while", "conditional", "call")


@dataclasses.dataclass
class Op:
    start: int          # ns
    end: int
    name: str           # "<module>/<instruction>"
    meta: str           # the instruction's HLO text


def _instruction(text: str) -> str:
    """``%name.3 = f32[..] kind(...)`` -> ``name.3``."""
    head = text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def _module(name: str) -> str:
    """``jit_fn(123)`` -> ``jit_fn``."""
    return name.split("(", 1)[0]


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Op]]
    host: List[Tuple[str, int, int]]     # (span name, start ns, end ns)


def find(directory: str) -> str:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return paths[-1]


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    devices: Dict[str, List[Op]] = {}
    host: List[Tuple[str, int, int]] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX) and plane.name[
                len(DEVICE_PREFIX):].isdigit():
            mods, raw = [], []
            for line in plane.lines:
                for ev in line.events:
                    s = int(ev.start_ns)
                    e = s + int(ev.duration_ns)
                    if line.name == MODULES_LINE:
                        mods.append((s, e, _module(ev.name)))
                    elif line.name == OPS_LINE:
                        raw.append((s, e, ev.name))
            mods.sort()
            starts = [m[0] for m in mods]
            ops = []
            for s, e, text in sorted(raw):
                i = bisect.bisect_right(starts, s) - 1
                mod = mods[i][2] if i >= 0 and mods[i][1] >= s else "?"
                ops.append(Op(s, e, f"{mod}/{_instruction(text)}", text))
            devices[plane.name] = ops
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        s = int(ev.start_ns)
                        host.append((ev.name, s, s + int(ev.duration_ns)))
    host.sort(key=lambda h: h[1])
    return Trace(devices, host)


def _union(ivals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for s, e in sorted(ivals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(ops: List[Op], lo: int, hi: int):
    for o in ops:
        s, e = max(o.start, lo), min(o.end, hi)
        if e > s:
            yield o, s, e


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                      # averaged over device planes
    n_devices: int
    op_s: Dict[str, float]             # per operation name, summed
    kernel_s: Dict[str, float]         # per requested kernel
    gaps: List[Tuple[str, float]]      # idle gaps, longest first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def window_bounds(trace: Trace, name: str = WINDOW_SPAN) -> Tuple[int, int]:
    spans = [(s, e) for n, s, e in trace.host if n == name]
    if not spans:
        raise ValueError(f"no {name!r} span in the trace")
    return spans[-1]


def summarize(trace: Trace, kernels: Sequence[str] = (),
              bounds: Optional[Tuple[int, int]] = None,
              n_gaps: int = 10) -> Summary:
    lo, hi = bounds or window_bounds(trace)
    if not trace.devices:
        raise ValueError("the trace has no device plane")
    busy_total = 0.0
    op_s: Dict[str, float] = defaultdict(float)
    kernel_s: Dict[str, float] = {k: 0.0 for k in kernels}
    holes: List[Tuple[int, int]] = []
    spans = [(n, s, e) for n, s, e in trace.host if n != WINDOW_SPAN]
    for ops in trace.devices.values():
        clipped = list(_clip(ops, lo, hi))
        busy = _union([(s, e) for _, s, e in clipped])
        busy_total += sum(e - s for s, e in busy)
        for o, s, e in clipped:
            instr = o.name.rsplit("/", 1)[-1]
            if instr.startswith(CONTROL_FLOW):
                continue        # its body's operations are counted
            op_s[o.name] += (e - s) * 1e-9
            for k in kernels:
                if k in instr:
                    kernel_s[k] += (e - s) * 1e-9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                holes.append((a, b))
    n = len(trace.devices)
    holes.sort(key=lambda h: h[0] - h[1])
    gaps = [(_label(spans, a, b), (b - a) * 1e-9) for a, b in holes[:n_gaps]]
    return Summary(
        window_s=(hi - lo) * 1e-9, busy_s=busy_total * 1e-9 / n,
        n_devices=n, op_s={k: v / n for k, v in op_s.items()},
        kernel_s={k: v / n for k, v in kernel_s.items()},
        gaps=gaps)


def _label(spans, a: int, b: int) -> str:
    """The host span covering most of [a, b); ``waiting`` if none."""
    best, cover = "waiting", 0
    for name, s, e in spans:
        c = min(b, e) - max(a, s)
        if c > cover:
            best, cover = name, c
    return best


def top_ops(summary: Summary, n: int = 10) -> List[List]:
    return [[k, v] for k, v in sorted(summary.op_s.items(),
                                      key=lambda kv: -kv[1])[:n]]
