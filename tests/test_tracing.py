"""Host spans of the serving loop (``serving/tracing.py``).

  * with no profiler attached a span emits nothing and never reads its
    arguments (so it cannot fetch them);
  * a profiler trace of the tiny scheduler holds the layer spans, each
    ``.fetch`` nested in its stage span inside ``serve.step``, with
    their arguments, and the serve path's programs under names of their
    own (no ``jit__lambda``);
  * every device->host transfer of the serve path happens inside a
    span whose name ends in ``.fetch``;
  * ``stage_busy`` and ``WindowStats.t_*`` are the spans' own seconds.
"""
import collections
import glob
import threading
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.base import CodecCfg, ModelCfg, ViTCfg
from repro.data.video import VideoSpec, generate_video
from repro.launch import serve
from repro.models import transformer as tfm
from repro.models import vit as vitm
from repro.models.init import ParamBuilder, split_tree
from repro.serving import (
    EngineCfg, KVCfg, Scheduler, SchedulerCfg, ServingPipeline,
    StreamRequest, WindowDone, tracing,
)

CODEC = CodecCfg(gop=4, block=16, search_radius=4, window_frames=8,
                 stride_frames=4, keep_ratio=0.4)
LM = ModelCfg(name="tiny-vlm", family="vlm", n_layers=2, d_model=64,
              n_heads=4, n_kv=2, d_ff=128, vocab=64, tied_embeddings=True)
VIT = ViTCfg(n_layers=2, d_model=64, n_heads=4, d_ff=128, patch=14,
             image=112, group=2)


@pytest.fixture(scope="module")
def pipe():
    params, _ = tfm.init_params(LM, jax.random.PRNGKey(0))
    vparams, _ = split_tree(
        vitm.init_vit(ParamBuilder(jax.random.PRNGKey(1)), VIT, LM.d_model))
    return ServingPipeline(LM, VIT, params, vparams, EngineCfg(
        mode="codecflow", codec=CODEC, kv=KVCfg(pool_streams=3)))


@pytest.fixture(scope="module")
def clips():
    return [generate_video(VideoSpec(n_frames=20, height=112, width=112,
                                     anomaly=bool(i % 2), seed=3 + i))[0]
            for i in range(3)]


def _serve(pipe, clips):
    sched = Scheduler(pipe, SchedulerCfg(max_concurrent=len(clips)))
    for i, f in enumerate(clips):
        sched.submit(StreamRequest(i, f))
    events = list(sched.events())
    return sched, events


class _Spy(tracing.span):
    """``tracing.span`` that also records, per thread, which spans are
    open, and each closed span's name and seconds."""

    local = threading.local()
    closed = []

    def __init__(self, name, **args):
        super().__init__(name, **args)
        self.name = name

    def __enter__(self):
        _Spy.open().append(self.name)
        return super().__enter__()

    def __exit__(self, *exc):
        super().__exit__(*exc)
        _Spy.open().pop()
        _Spy.closed.append((self.name, self.seconds))

    @classmethod
    def open(cls):
        if not hasattr(cls.local, "stack"):
            cls.local.stack = []
        return cls.local.stack


def test_span_times_itself():
    with tracing.span("serve.test", windows=2) as sp:
        time.sleep(0.01)
    assert 0.01 <= sp.seconds < 1.0


def test_span_without_profiler_emits_nothing_and_reads_no_argument(
        tmp_path):
    class Untouchable:
        def _no(self, *_):
            raise AssertionError("a span read its argument")
        __str__ = __repr__ = __int__ = __float__ = __index__ = _no

    assert not jax.profiler.TraceAnnotation.is_enabled()
    with tracing.span("serve.off", windows=Untouchable()) as sp:
        sp.set(kept=Untouchable())
    with tracing.step("serve.off_step", step_num=Untouchable()):
        pass
    # a trace started afterwards holds none of them
    with jax.profiler.trace(str(tmp_path)):
        jax.block_until_ready(jax.numpy.ones(2) + 1)
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)[0]
    names = {ev.name for pl in ProfileData.from_file(path).planes
             for ln in pl.lines for ev in ln.events}
    assert not any(n.startswith("serve.") for n in names)


def _host_lines(path):
    """Per host line (one per thread): its ``serve.`` spans as (name,
    start, end, args)."""
    out = []
    for pl in ProfileData.from_file(path).planes:
        if not pl.name.startswith("/host:"):
            continue
        for ln in pl.lines:
            evs = [(ev.name, int(ev.start_ns),
                    int(ev.start_ns + ev.duration_ns), dict(ev.stats))
                   for ev in ln.events if ev.name.startswith("serve.")]
            if evs:
                out.append(evs)
    return out


@pytest.fixture(scope="module")
def served_trace(tmp_path_factory):
    """A profiler trace of the launcher's serve loop (2 streams, fresh
    and incremental windows) and its report."""
    d = tmp_path_factory.mktemp("trace")
    rep = serve.serve("internvl3-14b-smoke", "codecflow", videos=2,
                      frames=16, window=8, stride=4, streams=2,
                      trace_dir=str(d))
    path = glob.glob(str(d / "**" / "*.xplane.pb"), recursive=True)[0]
    return _host_lines(path), rep


TABLE = (
    "serve.submit", "serve.codec.open", "serve.codec.encode",
    "serve.codec.decode", "serve.codec.decode.fetch", "serve.codec.slice",
    "serve.step", "serve.admit", "serve.prefill", "serve.encode",
    "serve.finalize", "serve.encode.group", "serve.encode.ingest_wait",
    "serve.vit.encode", "serve.vit.full", "serve.vit.motion_mask",
    "serve.vit.select", "serve.vit.pack_plan", "serve.vit.pack_plan.fetch",
    "serve.vit.packed", "serve.vit.count.fetch", "serve.prefill.group",
    "serve.prefill.state_concat", "serve.prefill.dispatch",
    "serve.prefill.fresh", "serve.prefill.reuse", "serve.prefill.select",
    "serve.prefill.selective", "serve.prefill.valid.fetch",
    "serve.decode.dispatch", "serve.prefill.state_split",
    "serve.finalize.group", "serve.finalize.fetch", "serve.finalize.stats",
)


def test_trace_holds_the_layer_spans(served_trace):
    lines, rep = served_trace
    assert rep["windows_total"] == 2 * 3
    names = {e[0] for evs in lines for e in evs if e[0].startswith("serve.")}
    assert set(TABLE) <= names, set(TABLE) - names
    # ingest slices windows on worker threads, the rest on one thread
    main = [evs for evs in lines if any(e[0] == "serve.step" for e in evs)]
    assert len(main) == 1
    assert {e[0] for e in main[0] if e[0].startswith("serve.")} \
        >= set(TABLE) - {"serve.codec.slice"}


def _parents(evs, child):
    """Names of the spans on the same thread that enclose ``child``."""
    _, s, e, _ = child
    return {p[0] for p in evs if p is not child and p[1] <= s and e <= p[2]}


@pytest.mark.parametrize("fetch,stage,step", [
    ("serve.vit.pack_plan.fetch", "serve.encode.group", "serve.step"),
    ("serve.vit.count.fetch", "serve.encode.group", "serve.step"),
    ("serve.prefill.valid.fetch", "serve.prefill.group", "serve.step"),
    ("serve.finalize.fetch", "serve.finalize.group", "serve.step"),
    ("serve.codec.decode.fetch", "serve.codec.decode", "serve.submit"),
])
def test_each_fetch_nests_in_its_stage(served_trace, fetch, stage, step):
    lines, _ = served_trace
    seen = 0
    for evs in lines:
        for ev in evs:
            if ev[0] == fetch:
                up = _parents(evs, ev)
                assert stage in up and step in up, (fetch, up)
                seen += 1
    assert seen


def test_span_arguments(served_trace):
    lines, _ = served_trace
    by = collections.defaultdict(list)
    for evs in lines:
        for name, _, _, args in evs:
            by[name].append(args)
    assert [a["step_num"] for a in by["serve.step"]] == \
        list(range(len(by["serve.step"])))
    assert all(a["frames"] == 16 for a in by["serve.codec.open"])
    assert all({"windows", "fresh", "kept", "slots"} <= set(a)
               for a in by["serve.encode.group"])
    assert sum(a["windows"] for a in by["serve.encode.group"]) == 6
    assert sum(a["windows"] for a in by["serve.finalize.group"]) == 6
    assert {a["fresh"] for a in by["serve.prefill.group"]} == {0, 1}
    assert all(a["refreshed"] > 0 for a in by["serve.prefill.group"])
    assert sorted(a["window"] for a in by["serve.codec.slice"]) == \
        [0, 0, 1, 1, 2, 2]


def test_transfers_happen_only_in_fetch_spans(pipe, clips, monkeypatch):
    """Every device->host transfer of the serve path (numpy on a device
    array, ``jax.device_get``, a Python scalar or list of one) happens
    with a ``.fetch`` span open on its thread."""
    import sys

    from jax._src import array

    outside, inside = [], collections.Counter()

    def note(what):
        stack = _Spy.open()
        if any(n.endswith(".fetch") for n in stack):
            inside[stack[-1]] += 1
        else:
            outside.append((what, list(stack)))

    def is_dev(x):
        # a concrete device array (a tracer, seen while a jit traces,
        # holds no data to transfer)
        if isinstance(x, (list, tuple)):
            return any(is_dev(y) for y in x)
        return (isinstance(x, jax.Array)
                and not isinstance(x, jax.core.Tracer))

    class NumpySpy:
        def __getattr__(self, name):
            attr = getattr(np, name)
            if not callable(attr) or isinstance(attr, type):
                return attr

            def call(*a, **k):
                if any(is_dev(x) for x in a):
                    note(f"np.{name}")
                return attr(*a, **k)
            return call

    for mod in list(sys.modules.values()):
        if (getattr(mod, "__name__", "").startswith("repro.")
                and getattr(mod, "np", None) is np):
            monkeypatch.setattr(mod, "np", NumpySpy())
    get = jax.device_get

    def device_get(x):
        if is_dev(jax.tree_util.tree_leaves(x)):
            note("jax.device_get")
        return get(x)
    monkeypatch.setattr(jax, "device_get", device_get)
    value = array.ArrayImpl._value

    def _value(self):
        if self._npy_value is None:
            note("ArrayImpl._value")
        return value.fget(self)
    monkeypatch.setattr(array.ArrayImpl, "_value", property(_value))
    monkeypatch.setattr(tracing, "span", _Spy)

    _, events = _serve(pipe, clips)
    assert sum(isinstance(e, WindowDone) for e in events) == 12
    assert outside == []
    assert set(inside) == {
        "serve.codec.decode.fetch", "serve.vit.pack_plan.fetch",
        "serve.vit.count.fetch", "serve.prefill.valid.fetch",
        "serve.finalize.fetch"}


def test_stage_busy_and_window_times_are_span_seconds(pipe, clips,
                                                      monkeypatch):
    _Spy.closed = []
    monkeypatch.setattr(tracing, "span", _Spy)
    sched, events = _serve(pipe, clips)
    secs = collections.defaultdict(float)
    for name, s in _Spy.closed:
        secs[name] += s
    busy = sched.stage_busy
    assert busy["ingest"] == pytest.approx(secs["serve.codec.slice"])
    assert busy["encode"] == pytest.approx(secs["serve.encode.group"])
    assert busy["prefill"] + busy["decode"] == pytest.approx(
        secs["serve.prefill.group"])
    assert busy["decode"] == pytest.approx(secs["serve.decode.dispatch"])
    assert busy["finalize"] == pytest.approx(secs["serve.finalize.group"])
    stats = [e.stats for e in events if isinstance(e, WindowDone)]
    assert sum(s.t_vit for s in stats) == pytest.approx(
        secs["serve.vit.encode"])
    assert sum(s.t_decode for s in stats) == pytest.approx(
        secs["serve.decode.dispatch"] + secs["serve.finalize.fetch"])
    assert sum(s.t_prefill for s in stats) == pytest.approx(
        secs["serve.prefill.dispatch"] - secs["serve.prefill.select"])
    occ = sched.stage_occupancy()
    assert occ["encode"] == pytest.approx(busy["encode"] / sched.t_serve)
