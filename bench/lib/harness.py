"""One benchmark run: set-up, warm-up, the measured window, the check.

Everything a cell needs is found by name: the workload entry in
``BENCHMARK.json``; its configuration ``bench/configs/<config>.json``;
its traffic ``bench/traffic/<traffic>.json``; its load
``bench/cells/<workload>.json`` (streams in flight); the language
model's block that the configuration names, ``bench/lm/<block>.py``
(``bench/lib/blocks.py``); and one reader per per-layer metric,
``bench/metrics/<metric>.py``.

The run drives the program's ``Scheduler`` itself: ``submit`` for each
camera segment or clip, ``step`` in a loop, a new segment submitted as
soon as one ends (closed loop, a fixed number of streams in flight).
"""
from __future__ import annotations

import dataclasses
import gc
import json
import math
import shutil
import sys
import time
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from . import blocks
from . import peaks as peakmod
from . import stats as statmod
from . import trace as tracemod
from . import traffic as trafficmod
from . import weights as weightmod
from .reference import QUERY_IDS, NO, YES, Layout, Reference

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
# warm-up runs at least one full period of the traffic and this many
# rounds of the fleet more, and stops once a whole period has passed
# with no new executable (the schedule is periodic: every group the
# window forms was formed in that period)
CLEAN_ROUNDS = 3
WARMUP_CAP_S = 900.0
DRAIN_S = 60.0


class NoAccelerator(RuntimeError):
    pass


# ======================================================================
# cells
# ======================================================================
@dataclasses.dataclass
class Cell:
    name: str
    entry: Dict[str, Any]
    conf: Dict[str, Any]
    mix: trafficmod.Mix
    load: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]
    bench_dir: Path
    block: ModuleType

    @property
    def streams(self) -> int:
        return int(self.load["streams"])

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, benchmark: Optional[Dict[str, Any]] = None,
              bench_dir: Path = BENCH) -> Cell:
    if benchmark is None:
        benchmark = json.loads((ROOT / "BENCHMARK.json").read_text())
    entries = {w["name"]: w for w in benchmark["workloads"]}
    if name not in entries:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    entry = entries[name]
    conf = json.loads(
        (bench_dir / "configs" / f"{entry['config']}.json").read_text())
    mix = trafficmod.load_mix(entry["traffic"], bench_dir / "traffic")
    load = json.loads((bench_dir / "cells" / f"{name}.json").read_text())
    return Cell(
        name=name, entry=entry, conf=conf, mix=mix, load=load,
        end_to_end=[m for m in benchmark["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in benchmark["per_layer"] if _applies(m, name)],
        bench_dir=bench_dir, block=blocks.load(conf["lm"], bench_dir))


def kernels(block: ModuleType) -> Tuple[str, ...]:
    """The kernels whose device time the trace sums: the LM block's and
    the vision tower's packed attention."""
    return tuple(block.KERNELS) + ("flash_packed",)


def vit_cfg(conf: Dict[str, Any]):
    from repro.configs import ViTCfg

    v = conf["vit"]
    return ViTCfg(**{k: v[k] for k in ("n_layers", "d_model", "n_heads",
                                       "d_ff", "patch", "image", "group")})


def program_cfg(conf: Dict[str, Any], block: ModuleType):
    """The program's (ModelCfg, ViTCfg) for a configuration file; the
    ModelCfg is the LM block's (``model_cfg``)."""
    vit = vit_cfg(conf)
    return block.model_cfg(conf, vit), vit


def geometry(conf: Dict[str, Any], codec: Dict[str, Any],
             block: ModuleType) -> Dict[str, Any]:
    """Static window geometry and the LM's block, for the work counts
    (``flops.window_work``)."""
    lay = Layout(codec, conf["vit"])
    v = conf["vit"]
    return {"total": lay.total, "refresh": lay.refresh,
            "frames_fresh": lay.window, "frames_inc": lay.stride,
            "n_patches": (v["image"] // v["patch"]) ** 2,
            "layout": lay,
            "block": block}


def attach_work(windows, segments, pool, cell: Cell, schedule) -> None:
    """Add to each window record the positions that hold a token
    (``valid``) and the patches per encoded frame (``kept``), from the
    benchmark's own codec and token selection over the window's frames
    (the same decisions the program makes: ``tests/benchmark``)."""
    from . import reference

    c, v = cell.mix.codec, cell.conf["vit"]
    lay = Layout(c, v)
    pp = v["image"] // v["patch"]
    g2 = v["group"] ** 2
    tables = {}
    for w in windows:
        seg = segments[w["sid"]]
        if seg.clip not in tables:
            _, mv = reference.codec(np.asarray(pool[seg.clip]), c["gop"],
                                    c["block"], c["search_radius"])
            _, gval = reference.select(np.asarray(mv), c["gop"], pp,
                                       v["group"], lay.k_tokens,
                                       c["mv_threshold"])
            tables[seg.clip] = gval
        gval = tables[seg.clip]
        f0 = (seg.first_window + w["window"]) * lay.stride
        frames = range(f0, f0 + lay.window)
        is_i = [f % lay.gop == 0 for f in frames]
        valid = [np.ones(lay.g_tokens, bool) if i else gval[f]
                 for f, i in zip(frames, is_i)]
        w["valid"] = np.concatenate(valid + [np.ones(len(QUERY_IDS), bool)])
        fresh = w["tokens_refreshed"] >= lay.total
        enc = frames if fresh else frames[lay.window - lay.stride:]
        w["kept"] = [lay.g_tokens * g2 if f % lay.gop == 0
                     else int(gval[f].sum()) * g2 for f in enc]


# ======================================================================
# compile counter
# ======================================================================
class CompileLog:
    """Times of new executables: XLA compiles and persistent-cache loads
    (``jax.monitoring`` events)."""

    COMPILE = "/jax/core/compile/backend_compile_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.times: List[float] = []
        self.compiled: List[float] = []     # seconds of each XLA compile

    def attach(self) -> "CompileLog":
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        return self

    def _on_duration(self, event, duration, **_):
        if event == self.COMPILE:
            self.times.append(time.perf_counter())
            self.compiled.append(float(duration))

    def _on_event(self, event, **_):
        if event == self.CACHE_HIT:
            self.times.append(time.perf_counter())

    def count(self, t0: float, t1: float) -> int:
        return sum(1 for t in self.times if t0 < t <= t1)


# ======================================================================
# closed-loop driver
# ======================================================================
class Driver:
    """Feeds the scheduler: one stream slot per camera (or clip slot),
    each resubmitted as soon as its stream ends."""

    def __init__(self, sched, schedule: trafficmod.Schedule,
                 pool: List[np.ndarray]):
        from repro.serving import StreamDone, StreamRequest, WindowDone

        self._req, self._done, self._win = StreamRequest, StreamDone, WindowDone
        self.sched = sched
        self.schedule = schedule
        self.pool = pool
        self.segments: Dict[int, trafficmod.Segment] = {}
        self.open: Dict[int, int] = {}        # sid -> windows answered
        self.finished: List[int] = []
        self.windows: List[Dict[str, Any]] = []
        self.submits: List[tuple] = []        # (t end, seconds, frames)
        self.steps: List[tuple] = []          # (t start, t end)
        self.resubmit = True

    def submit(self, seg: trafficmod.Segment) -> None:
        import jax.profiler as prof

        frames = self.schedule.frames(seg, self.pool)
        t0 = time.perf_counter()
        with prof.TraceAnnotation("bench.submit"):
            sid = self.sched.submit(self._req(seg.camera, frames))
        t1 = time.perf_counter()
        self.submits.append((t1, t1 - t0, len(frames)))
        self.segments[sid] = seg
        self.open[sid] = 0

    def start(self) -> None:
        for seg in self.schedule.first():
            self.submit(seg)

    def step(self) -> int:
        import jax.profiler as prof

        t0 = time.perf_counter()
        with prof.TraceAnnotation("bench.step"):
            evs = self.sched.step()
        t1 = time.perf_counter()
        self.steps.append((t0, t1))
        n = 0
        ends = []
        for ev in evs:
            if isinstance(ev, self._win):
                s = ev.result.stats
                yn = s.logits_yes_no
                rec = {f.name: getattr(s, f.name)
                       for f in dataclasses.fields(s)}
                rec.update({
                    "t": t1, "step": len(self.steps) - 1, "sid": ev.sid,
                    "window": ev.result.window,
                    "yes": yn[0], "no": yn[1],
                    "finite": bool(math.isfinite(yn[0])
                                   and math.isfinite(yn[1])),
                })
                self.windows.append(rec)
                self.open[ev.sid] = self.open.get(ev.sid, 0) + 1
                n += 1
            elif isinstance(ev, self._done):
                ends.append(ev.sid)
        for sid in ends:
            self.sched.close(sid)
            self.open.pop(sid, None)
            self.finished.append(sid)
            if self.resubmit:
                self.submit(self.schedule.next(self.segments[sid].camera))
        return n


# ======================================================================
# the run
# ======================================================================
def _device(require_tpu: bool, chips: int):
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoAccelerator(
            f"need {chips} TPU chip(s); JAX sees {len(devs)} "
            f"{devs[0].platform} device(s)")
    return devs


def _read_metric(bench_dir: Path, name: str):
    return blocks.load_module(bench_dir / "metrics" / f"{name}.py",
                              "bench_metric_").read


@dataclasses.dataclass
class RunView:
    """What a per-layer metric reader gets."""

    cell: Cell
    geometry: Dict[str, Any]
    t0: float
    t1: float
    windows: List[Dict[str, Any]]       # answered inside the window
    work_windows: List[Dict[str, Any]]  # whose device work lies inside
    submits: List[tuple]                # (t end, seconds, frames) inside
    compiles: int                       # new executables inside
    trace: Optional[tracemod.Summary]
    memory: Dict[str, int]
    peaks: Optional[Dict[str, Any]]
    device_kind: str

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def per_layer_metrics(cell: Cell, view: RunView) -> Dict[str, Any]:
    """Each per-layer metric's reader over the run; a reader that finds
    nothing to read returns None and its metric is left out."""
    out = {}
    for m in cell.per_layer:
        val = _read_metric(cell.bench_dir, m["name"])(view)
        if val is not None:
            out[m["name"]] = {"value": val, "unit": m["unit"]}
    return out


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        t_start: float, require_tpu: bool = True,
        scratch: Optional[Path] = None, log=None,
        compile_cache: bool = True, control: bool = False,
        records: Optional[List[Dict[str, Any]]] = None) -> Dict[str, Any]:
    """One run of ``cell``; returns the result line (a dict).  Every
    answered window's record, warm-up and drain included, is appended
    to ``records`` where it is given."""
    import jax

    def say(msg):
        print(msg, file=log or sys.stderr, flush=True)

    devs = _device(require_tpu, cell.chips)
    dev = devs[0]
    pk = peakmod.peaks(dev.device_kind) if require_tpu else None

    from repro.configs import CodecCfg
    from repro.launch import serve
    from repro.serving import (EngineCfg, KVCfg, Scheduler, SchedulerCfg,
                               ServingPipeline)

    if compile_cache:
        serve.enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    conf, mix, F = cell.conf, cell.mix, cell.streams
    cfg, vcfg = program_cfg(conf, cell.block)
    weightmod.check_layout(
        weightmod.shapes(conf["lm"], conf["vit"], cell.block),
        jax.eval_shape(lambda: serve.init_weights(cfg, vcfg, 0)))
    say(f"[{time.perf_counter() - t_start:.1f} s] device and config")
    params, vparams = jax.block_until_ready(
        weightmod.make_weights(conf["lm"], conf["vit"], seed, cell.block))
    say(f"[{time.perf_counter() - t_start:.1f} s] weights")
    pool = trafficmod.build_pool(mix, F)
    say(f"[{time.perf_counter() - t_start:.1f} s] traffic pool")
    schedule = trafficmod.Schedule(mix, F)
    codec = CodecCfg(**mix.codec)
    pipe = ServingPipeline(cfg, vcfg, params, vparams, EngineCfg(
        mode="codecflow", codec=codec, kv=KVCfg(pool_streams=F)))
    sched = Scheduler(pipe, SchedulerCfg(max_concurrent=F))
    clog = CompileLog().attach()
    drv = Driver(sched, schedule, pool)
    drv.start()

    # -- warm-up: the cell's own traffic until a whole period of it
    # compiles and loads nothing ----------------------------------------
    period = (mix.segment_windows if mix.kind == "sessions"
              else mix.pool_per_stream) * F
    t_w = time.perf_counter()
    while True:
        drv.step()
        n = len(drv.windows)
        last = clog.times[-1] if clog.times else t_w
        clean = sum(1 for w in drv.windows if w["t"] > last)
        if n >= period + CLEAN_ROUNDS * F and clean >= period:
            break
        if time.perf_counter() - t_w > WARMUP_CAP_S:
            say(f"warm-up cap reached with {n} windows")
            break
    n_warm = len(drv.windows)
    n_steps_warm = len(drv.steps)
    t0 = drv.steps[-1][1]
    setup_s = t0 - t_start
    at = [sum(1 for w in drv.windows if w["t"] < t) for t in clog.times]
    say(f"set-up {setup_s:.1f} s, warm-up {n_warm} windows in "
        f"{t0 - t_w:.1f} s; {len(at)} new executables "
        f"({len(clog.compiled)} compiled in {sum(clog.compiled):.1f} s, "
        f"the rest loaded), the last after {at[-1] if at else 0} windows")

    # -- the measured window ------------------------------------------
    tdir = None
    if trace:
        tdir = (scratch or ROOT / ".bench_tmp") / "trace"
        shutil.rmtree(tdir, ignore_errors=True)
        jax.profiler.start_trace(str(tdir))
    with jax.profiler.TraceAnnotation("bench.window"):
        while time.perf_counter() < t0 + seconds:
            drv.step()
    t1 = drv.steps[-1][1]
    if trace:
        jax.profiler.stop_trace()
    win = [w for w in drv.windows[n_warm:] if t0 < w["t"] <= t1]
    # device work of windows finalized from the third step on was
    # dispatched inside the window (encode runs one step ahead)
    first_step = n_steps_warm + 2
    work = [w for w in win if w["step"] >= first_step]

    # -- drain: every stream in flight at the close owes one window ----
    drv.resubmit = False
    due = {sid: k for sid, k in drv.open.items()}
    t_d = time.perf_counter()
    while due and time.perf_counter() - t_d < DRAIN_S:
        drv.step()
        due = {sid: k for sid, k in due.items()
               if sid in drv.open and drv.open[sid] == k}
    late = [w for w in drv.windows[n_warm:] if w["t"] > t1]
    attempted = len(win) + len(late) + len(due)
    failed = len(due) + sum(1 for w in win + late if not w["finite"])

    stats = dev.memory_stats() or {}
    memory = {"peak": int(stats.get("peak_bytes_in_use", 0)),
              "limit": int(stats.get("bytes_limit", 0))}

    # -- free the program's state before the reference runs ------------
    for sid in list(drv.open):
        sched.close(sid)
    finished = [(sid, drv.segments[sid]) for sid in drv.finished]
    by_sid: Dict[int, List[Dict[str, Any]]] = {}
    for w in drv.windows:
        by_sid.setdefault(w["sid"], []).append(w)
    summ = None
    if trace:
        attach_work(work, drv.segments, pool, cell, schedule)
        tr = tracemod.load(tracemod.find(str(tdir)))
        summ = tracemod.summarize(tr, kernels(cell.block))
        say(f"trace: busy {summ.busy_s:.3f} s of {summ.window_s:.3f} s, "
            f"kernels {summ.kernel_s}")
        shutil.rmtree(tdir, ignore_errors=True)
    windows_all = drv.windows
    if records is not None:
        records.extend(windows_all)
    submits = [s for s in drv.submits if t0 < s[0] <= t1]
    compiles = clog.count(t0, t1)
    del drv, sched, pipe
    gc.collect()

    # -- the check against the plain reference -------------------------
    t_r = time.perf_counter()
    checks, ok, ctl = check(cell, seed, params, vparams, pool, schedule,
                            finished, by_sid, say, control=control)
    say(f"reference {time.perf_counter() - t_r:.1f} s")

    geo = geometry(conf, mix.codec, cell.block)
    out: Dict[str, Any] = {"correct": bool(ok and failed == 0),
                           "attempted": attempted, "failed": failed}
    metrics: Dict[str, Any] = {}
    if not trace:
        vals = {
            "windows_per_s": statmod.rate(len(win), t0, t1),
            "setup_s": setup_s,
        }
        gaps = statmod.answer_gaps([(w["sid"], w["t"]) for w in windows_all],
                                   t0, t1)
        if gaps:
            vals["answer_gap_p90_s"] = statmod.percentile(gaps, 90)
        for m in cell.end_to_end:
            if m["name"] in vals:
                metrics[m["name"]] = {"value": vals[m["name"]],
                                      "unit": m["unit"]}
    else:
        metrics = per_layer_metrics(cell, RunView(
            cell, geo, t0, t1, win, work, submits, compiles, summ, memory,
            pk, dev.device_kind))
    out["metrics"] = metrics
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": memory["peak"]}
    if summ is not None:
        device["busy_s"] = summ.busy_s
        device["window_s"] = summ.window_s
        out["breakdown"] = {"device_ops": tracemod.top_ops(summ),
                            "idle_gaps": [[n, s] for n, s in summ.gaps]}
    out["device"] = device
    if ctl is not None:
        out["control"] = ctl
    out["checks"] = checks
    return out


# ======================================================================
# correctness
# ======================================================================
def sample(finished, seed: int, budget: int):
    """Finished streams to check: the longest (drawn from the seed among
    equals), then others in an order drawn from the seed, cameras not
    yet checked first, while the window count stays within ``budget``.
    Different cameras sit at different rows of the fused batches."""
    if not finished:
        return []
    rng = np.random.default_rng(trafficmod.clip_seed(seed, 1 << 20))
    order = [int(i) for i in rng.permutation(len(finished))]
    longest = max(order, key=lambda i: finished[i][1].windows)
    picked, total = [longest], finished[longest][1].windows
    cams = {finished[longest][1].camera}
    for i in sorted(order, key=lambda i: finished[i][1].camera in cams):
        seg = finished[i][1]
        if i != longest and total + seg.windows <= budget:
            picked.append(i)
            total += seg.windows
            cams.add(seg.camera)
    return [finished[i] for i in picked]


def deviations(answers: List[Dict[str, Any]], ref: List[np.ndarray]):
    """Per window, the yes and the no logit less the reference's."""
    return [(w["yes"] - float(r[YES]), w["no"] - float(r[NO]))
            for w, r in zip(answers, ref)]


def answer_gap(answers: List[Dict[str, Any]], ref: List[np.ndarray]):
    """Per window, in units of the reference's logit spread over the
    vocabulary, the gap by which the served answer's reference logit
    lies below the better of yes and no."""
    out = []
    for w, r in zip(answers, ref):
        ry, rn = float(r[YES]), float(r[NO])
        out.append((max(ry, rn) - (ry if w["answer"] else rn))
                   / float(np.std(r)))
    return out


def rms(devs) -> float:
    x = np.asarray(devs, np.float64)
    return float(np.sqrt(np.mean(x * x))) if x.size else float("nan")


def _stand_in(logits: List[np.ndarray]) -> List[Dict[str, Any]]:
    return [{"yes": float(x[YES]), "no": float(x[NO]),
             "answer": int(x[YES] > x[NO])} for x in logits]


def check(cell: Cell, seed: int, params, vparams, pool, schedule,
          finished, by_sid, say, control: bool = False):
    """Run the reference over a seeded sample of finished streams and
    hold the served answers to the configuration's limits.  Returns
    (readings {name: {value, limit}}, all within limits, control
    readings or None).

    The number held to a limit, ``logit_err_rel``, is the root mean
    square of the served yes and no logits' deviation from the float32
    reference over every checked window, over the same of the reference
    computed in bfloat16: how far the program strays, in units of what
    plain arithmetic in its own precision strays on these weights and
    windows.  (The drawn weights amplify rounding by a factor that
    differs tenfold from seed to seed; the ratio cancels it.)
    ``control`` also serves the sample with the fp8 control in the
    program's place and reads it the same way."""
    conf = cell.conf
    args = (conf["lm"], conf["vit"], cell.mix.codec, params, vparams,
            cell.block)
    ref = Reference(*args)
    low = Reference(*args, precision="bf16")
    ctl = Reference(*args, precision="fp8") if control else None
    d_prog, d_low, d_ctl, gaps, c_gaps = [], [], [], [], []
    per_window = []
    ok = True
    for sid, seg in sample(finished, seed, int(conf["reference_windows"])):
        prog = sorted(by_sid.get(sid, []), key=lambda w: w["window"])
        if [w["window"] for w in prog] != list(range(seg.windows)):
            say(f"stream {sid}: served windows {len(prog)} of {seg.windows}")
            ok = False
            continue
        if not all(w["finite"] for w in prog):
            ok = False
        frames = schedule.frames(seg, pool)
        logits = ref.serve(frames, seg.windows)
        dp = deviations(prog, logits)
        dl = deviations(_stand_in(low.serve(frames, seg.windows)), logits)
        d_prog += dp
        d_low += dl
        gaps += answer_gap(prog, logits)
        rows = [{"camera": seg.camera, "clip": seg.clip,
                 "first_window": seg.first_window, "window": w["window"],
                 "step": w["step"], "refreshed": w["tokens_refreshed"],
                 "dev": list(p), "dev_bf16": list(q),
                 "scale": float(np.std(r))}
                for w, p, q, r in zip(prog, dp, dl, logits)]
        if ctl is not None:
            c_ans = _stand_in(ctl.serve(frames, seg.windows))
            dc = deviations(c_ans, logits)
            d_ctl += dc
            c_gaps += answer_gap(c_ans, logits)
            for row, x in zip(rows, dc):
                row["dev_control"] = list(x)
        per_window += rows
    val = rms(d_prog) / rms(d_low) if d_low else float("nan")
    lim = conf["limits"]["logit_err_rel"]
    checks = {"logit_err_rel": {"value": val, "limit": lim}}
    ok = ok and val <= lim                      # NaN fails
    # for the record, not held to a limit: the served answer's gap (a
    # yes/no margin dwarfs rounding, so it reads 0) and the widest
    # deviation in units of the reference's logit spread
    widest = max((max(map(abs, r["dev"])) / r["scale"] for r in per_window),
                 default=float("nan"))
    say(f"windows checked: {len(per_window)}; answer_gap "
        f"{max(gaps, default=float('nan'))}; widest deviation {widest}; "
        f"rms deviation {rms(d_prog)}, of bfloat16 arithmetic {rms(d_low)}")
    for name, c in checks.items():
        say(f"check {name}: {c['value']} limit {c['limit']}")
    ctl_read = None
    if ctl is not None:
        ctl_read = {"logit_err_rel": rms(d_ctl) / rms(d_low),
                    "answer_gap": max(c_gaps, default=float("nan")),
                    "windows": per_window}
    return checks, ok and bool(per_window), ctl_read
