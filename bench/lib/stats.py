"""Arithmetic of the end-to-end metrics over one measured window.

The rate is all windows answered over all the window's seconds; the
tail is a percentile of every gap, never a median of per-chunk values.
"""
from __future__ import annotations

from collections import defaultdict
from typing import Dict, Iterable, List, Tuple

import numpy as np


def rate(n_done: int, t0: float, t1: float) -> float:
    return n_done / (t1 - t0)


def answer_gaps(answers: Iterable[Tuple[object, float]], t0: float,
                t1: float) -> List[float]:
    """Seconds between consecutive answers of one stream, both inside
    (t0, t1].  ``answers``: (stream, time) pairs in any order."""
    by_stream: Dict[object, List[float]] = defaultdict(list)
    for sid, t in answers:
        if t0 < t <= t1:
            by_stream[sid].append(t)
    gaps: List[float] = []
    for ts in by_stream.values():
        ts.sort()
        gaps.extend(np.diff(ts).tolist())
    return gaps


def percentile(values: List[float], q: float) -> float:
    """The q-th percentile (linear interpolation) of all values."""
    if not values:
        raise ValueError("no samples")
    return float(np.percentile(np.asarray(values, float), q))


def spread(values: List[float]) -> float:
    """Inter-quartile distance over the median, as the benchmark's
    bounds are set (``statistics.quantiles(values, n=4)``)."""
    import statistics
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2
