"""Host spans of the serving path, on the profiler's clock.

Every span is a ``jax.profiler.TraceAnnotation`` (the scheduler tick a
``StepTraceAnnotation``), so a profiler trace holds the host's spans
beside the device's operations on one clock, and each idle stretch of
the device can be charged to the host work it waited on.  With no
profiler attached a span costs about a microsecond and emits nothing.

Names start with ``serve.`` and nest by layer (docs/async_scheduler.md
§Spans).  A span's arguments are values already on the host: a span
never fetches anything for itself.  Every blocking device->host
transfer on the serve path sits in a span of its own whose name ends
in ``.fetch``, so those spans count the host syncs.

A span also times itself (``seconds``, host wall time from enter to
exit): the scheduler's ``stage_busy`` and ``WindowStats.t_*`` are read
from it.  Those are host dispatch times, not device times; the device
side is in the profiler trace, under each jit's module name.
"""
from __future__ import annotations

import time

import jax


class span:
    """``with span("serve.x", windows=3) as sp: ...`` then ``sp.seconds``.

    ``sp.set(kept=...)`` adds arguments known only inside the span."""

    __slots__ = ("_me", "_t0", "seconds")
    _kind = jax.profiler.TraceAnnotation

    def __init__(self, name: str, **args):
        self._me = self._kind(name, **args)
        self.seconds = 0.0

    def __enter__(self) -> "span":
        self._me.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._me.__exit__(*exc)

    def set(self, **args) -> None:
        self._me.set_metadata(**args)


class step(span):
    """The scheduler tick: a profiler step (``step_num``), so trace
    viewers group the tick's host and device work under it."""

    __slots__ = ()
    _kind = jax.profiler.StepTraceAnnotation
