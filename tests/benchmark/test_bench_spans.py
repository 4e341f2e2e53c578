"""The serving loop's spans against the device trace, on a short trace
recorded on a TPU v5e (``make_serve_probe.py``): four scheduler steps of
the tiny cell inside ``bench.window``, with the program's ``serve.``
spans, the benchmark's ``bench.`` spans and the device's programs under
their own names."""
import pytest

from bench_helpers import DATA, ROOT
from bench.lib import harness, spans, stages, trace

PROBE = str(DATA / "probe_serve_v5e.xplane.pb")
OLD = str(DATA / "probe_v5e.xplane.pb")


@pytest.fixture(scope="module")
def tr():
    return trace.load(PROBE)


@pytest.fixture(scope="module")
def sp():
    return spans.load(PROBE)


def test_probe_is_small():
    assert (DATA / "probe_serve_v5e.xplane.pb").stat().st_size < 1 << 20


def test_spans_carry_thread_and_arguments(sp):
    th = spans.scheduler_thread(sp)
    steps = [s for s in sp if s.name == "serve.step"]
    assert len(steps) == 4 and all(s.thread == th for s in steps)
    assert [s.args["step_num"] for s in steps] == \
        list(range(steps[0].args["step_num"],
                   steps[0].args["step_num"] + 4))
    slices = [s for s in sp if s.name == "serve.codec.slice"]
    assert slices and all(s.thread != th for s in slices)
    groups = [s for s in sp if s.name == "serve.encode.group"]
    assert groups and all({"windows", "fresh", "kept", "slots"} <= set(s.args)
                          for s in groups)


def _enclosing(sp, child):
    return {p.name for p in sp if p is not child and p.thread == child.thread
            and p.start <= child.start and child.end <= p.end}


@pytest.mark.parametrize("fetch,stage", [
    ("serve.vit.pack_plan.fetch", "serve.encode.group"),
    ("serve.vit.count.fetch", "serve.encode.group"),
    ("serve.prefill.valid.fetch", "serve.prefill.group"),
    ("serve.finalize.fetch", "serve.finalize.group"),
])
def test_each_fetch_nests_in_its_stage_and_step(sp, fetch, stage):
    found = [s for s in sp if s.name == fetch]
    assert found
    for s in found:
        assert {stage, "serve.step", "bench.step"} <= _enclosing(sp, s)


def test_old_probe_reads_as_before():
    """A trace with no ``serve.`` span: the gaps keep ``trace.py``'s
    labels and the span numbers find nothing to read."""
    old_tr, old_sp = trace.load(OLD), spans.load(OLD)
    want = trace.summarize(old_tr, n_gaps=3).gaps
    got = spans.gaps(old_tr, old_sp, 3)
    assert [g[0] for g in got] == [g[0] for g in want]
    assert [g[1] for g in got] == pytest.approx([g[1] for g in want])
    assert spans.fetches_per_window(old_sp) is None
    assert spans.codec_open_ms_per_frame(old_sp) is None
    assert spans.idle_in_host_work_share(old_tr, old_sp) is None


def test_gaps_are_named_by_serve_spans(tr, sp):
    s = trace.summarize(tr)
    g = spans.gaps(tr, sp, 10)
    assert [x[1] for x in g] == pytest.approx([x[1] for x in s.gaps])
    assert all(name.startswith("serve.") for name, _ in g)
    by = spans.idle_by_label(tr, sp)
    assert sum(by.values()) == pytest.approx(s.window_s - s.busy_s)


def test_stage_times_add_up_to_busy(tr):
    cell = harness.load_cell("ivl3-14b.cctv-sessions")
    s = trace.summarize(tr, harness.kernels(cell.block))
    split = stages.split(s.op_s)
    assert sum(split.values()) == pytest.approx(s.busy_s, rel=0.01)
    assert not any(k.startswith("jit__lambda") for k in s.op_s)
    for st in ("vit", "prefill", "decode"):
        assert split[st] > 0


def _view(tr, n_windows):
    cell = harness.load_cell("ivl3-14b.cctv-sessions")
    geo = harness.geometry(cell.conf, cell.mix.codec, cell.block)
    win = [{"tokens_refreshed": geo["total"]}] * n_windows
    return harness.RunView(cell, geo, 0.0, 1.0, win, win, [], 0,
                           trace.summarize(tr, harness.kernels(cell.block)),
                           {"peak": 1, "limit": 2}, None, "TPU v5 lite")


@pytest.mark.parametrize("name,stage", [
    ("vit.device_ms_per_window", "vit"),
    ("prefill.device_ms_per_window", "prefill"),
    ("decode.device_ms_per_step", "decode")])
def test_stage_readers(tr, sp, name, stage):
    n = spans.windows_answered(sp)
    view = _view(tr, n)
    got = harness._read_metric(ROOT / "bench", name)(view)
    assert got == pytest.approx(
        1e3 * stages.split(view.trace.op_s)[stage] / n)


def test_span_numbers(tr, sp):
    s = trace.summarize(tr)
    n = spans.windows_answered(sp)
    assert n > 0
    fetches = spans.fetches(sp)
    assert spans.fetches_per_window(sp) == pytest.approx(len(fetches) / n)
    assert spans.vit_fetch_ms_per_window(sp) > 0
    share = spans.idle_in_host_work_share(tr, sp)
    assert 0 < share <= 100 * s.idle_share + 1e-9
