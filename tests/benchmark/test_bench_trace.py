"""Trace reduction on a small trace recorded on a TPU v5e: three calls of
a jitted Pallas kernel plus a reduction, inside ``bench.window`` with
``bench.step`` and ``bench.submit`` host spans."""
import pytest

from bench_helpers import DATA
from bench.lib import trace

TRACE = str(DATA / "probe_v5e.xplane.pb")


@pytest.fixture(scope="module")
def tr():
    return trace.load(TRACE)


def test_planes_and_spans(tr):
    assert list(tr.devices) == ["/device:TPU:0"]
    ops = tr.devices["/device:TPU:0"]
    assert [o.name for o in ops] == ["jit_f/f.1", "jit_f/convolution_reduce_fusion"] * 3
    names = [h[0] for h in tr.host]
    assert names.count("bench.window") == 1
    assert names.count("bench.step") == 3 and names.count("bench.submit") == 3


def test_busy_idle_and_kernel_time(tr):
    s = trace.summarize(tr, kernels=["f.1", "absent"])
    lo, hi = trace.window_bounds(tr)
    # the device clock reads ~1.5 ms behind the host's here, so the first
    # call's operations fall before the window's host span
    ops = [o for o in tr.devices["/device:TPU:0"] if o.start >= lo]
    assert len(ops) == 4
    busy = sum(o.end - o.start for o in ops) * 1e-9
    assert s.window_s == pytest.approx((hi - lo) * 1e-9)
    assert s.busy_s == pytest.approx(busy)          # no overlap
    assert 0 < s.busy_s < s.window_s
    assert s.idle_share == pytest.approx(1 - busy / s.window_s)
    kern = sum(o.end - o.start for o in ops if o.name.endswith("f.1")) * 1e-9
    assert s.kernel_s == {"f.1": pytest.approx(kern), "absent": 0.0}
    assert trace.top_ops(s, 1)[0][0] == "jit_f/f.1"


def test_gaps_are_longest_first_and_named_by_host_span(tr):
    s = trace.summarize(tr, n_gaps=3)
    assert len(s.gaps) == 3
    lens = [g[1] for g in s.gaps]
    assert lens == sorted(lens, reverse=True)
    # between steps the host sleeps inside bench.submit
    assert s.gaps[0][0] == "bench.submit"
    assert sum(g[1] for g in trace.summarize(tr, n_gaps=100).gaps) == \
        pytest.approx(s.window_s - s.busy_s)


def test_union_merges_overlaps():
    assert trace._union([(5, 9), (0, 2), (1, 3), (8, 10)]) == [(0, 3), (5, 10)]


def test_window_is_clipped(tr):
    ops = tr.devices["/device:TPU:0"]
    lo = ops[0].start + 100
    hi = ops[-1].end
    s = trace.summarize(tr, bounds=(lo, hi))
    full = sum(o.end - o.start for o in ops) * 1e-9
    assert s.busy_s == pytest.approx(full - 100e-9)
