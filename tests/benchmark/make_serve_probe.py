"""Records ``data/probe_serve_v5e.xplane.pb`` on a TPU, and checks there
that every device->host transfer of the serve path sits in a ``.fetch``
span.

    python3 tests/benchmark/make_serve_probe.py [--out DIR]

Serves the tiny fixture cell (three streams, ``data/``) through the
benchmark's closed-loop driver on the chip, warms it up for two periods
of its traffic, then:

  1. traces four scheduler steps inside ``bench.window`` and writes the
     trace to ``DIR/probe_serve_v5e.xplane.pb``, cut to what the
     benchmark reads (``prune``): the ``serve.`` and ``bench.`` host
     spans, and the device's ``XLA Modules`` and ``XLA Ops`` lines with
     each operation's HLO text cut to its instruction name;
  2. serves one step with ``jax.transfer_guard_device_to_host("log")``,
     between two marker lines on standard error, so the log names every
     transfer of a warm step;
  3. serves a whole period with device->host transfers disallowed
     everywhere except inside spans whose name ends in ``.fetch``: a
     transfer anywhere else raises.

The last line of standard output is a JSON summary.
"""
import argparse
import glob
import json
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:0] = [str(HERE), str(ROOT), str(ROOT / "src")]

from bench_helpers import TINY, bench_dir, benchmark_with_tiny  # noqa: E402

SEED = 7
STEPS = 4


def _xplane_classes():
    """The few fields of ``xplane.proto`` that ``prune`` reads; every
    other field rides along as an unknown field, unchanged."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)

    T = descriptor_pb2.FieldDescriptorProto
    f = descriptor_pb2.FileDescriptorProto(name="xplane_cut.proto",
                                           package="xcut", syntax="proto3")

    def msg(name, *fields):
        m = f.message_type.add(name=name)
        for fname, num, typ, label, tname in fields:
            fd = m.field.add(name=fname, number=num, type=typ, label=label)
            if tname:
                fd.type_name = ".xcut." + tname

    one, many = T.LABEL_OPTIONAL, T.LABEL_REPEATED
    msg("Meta", ("id", 1, T.TYPE_INT64, one, None),
        ("name", 2, T.TYPE_STRING, one, None))
    msg("MetaEntry", ("key", 1, T.TYPE_INT64, one, None),       # map entry
        ("value", 2, T.TYPE_MESSAGE, one, "Meta"))
    msg("Event", ("metadata_id", 1, T.TYPE_INT64, one, None))
    msg("Line", ("name", 2, T.TYPE_STRING, one, None),
        ("events", 4, T.TYPE_MESSAGE, many, "Event"))
    msg("Plane", ("name", 2, T.TYPE_STRING, one, None),
        ("lines", 3, T.TYPE_MESSAGE, many, "Line"),
        ("event_metadata", 4, T.TYPE_MESSAGE, many, "MetaEntry"))
    msg("Space", ("planes", 1, T.TYPE_MESSAGE, many, "Plane"))
    pool = descriptor_pool.DescriptorPool()
    pool.Add(f)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("xcut.Space"))


def prune(src: Path, dst: Path) -> None:
    """Copy a trace keeping the host plane's ``serve.``/``bench.`` events
    and the device planes' module and operation lines (every line stays,
    so a host line's index still names its thread).  An operation's
    name is its HLO text, of which ``bench/lib/trace.py`` reads the
    instruction name before `` = ``: the rest, and the operation's
    other metadata, is cut."""
    space = _xplane_classes()()
    space.ParseFromString(src.read_bytes())
    for plane in space.planes:
        host = plane.name.startswith("/host:")
        if not (host or plane.name.startswith("/device:")):
            continue
        names = {e.key: e.value.name for e in plane.event_metadata}
        ops = set()
        for line in plane.lines:
            keep = [ev for ev in line.events
                    if (names.get(ev.metadata_id, "").startswith(
                        ("serve.", "bench.")) if host
                        else line.name in ("XLA Modules", "XLA Ops"))]
            del line.events[:]
            line.events.extend(keep)
            if line.name == "XLA Ops":
                ops |= {ev.metadata_id for ev in keep}
        used = {ev.metadata_id for line in plane.lines for ev in line.events}
        meta = [e for e in plane.event_metadata if e.key in used]
        for e in meta:
            if e.key in ops:
                name = e.value.name.split(" = ", 1)[0]
                e.value.Clear()                 # HLO text, source lines
                e.value.id, e.value.name = e.key, name
        del plane.event_metadata[:]
        plane.event_metadata.extend(meta)
    dst.write_bytes(space.SerializeToString())


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=str(ROOT / ".bench_tmp" / "probe"))
    args = ap.parse_args()

    import jax

    from bench.lib import harness, traffic, weights
    from repro.configs import CodecCfg
    from repro.serving import (EngineCfg, KVCfg, Scheduler, SchedulerCfg,
                               ServingPipeline, tracing)

    if jax.devices()[0].platform != "tpu":
        print("make_serve_probe: needs a TPU", file=sys.stderr)
        return 1
    tmp = Path(tempfile.mkdtemp())
    cell = harness.load_cell(TINY, benchmark_with_tiny(), bench_dir(tmp))
    F = cell.streams
    cfg, vcfg = harness.program_cfg(cell.conf, cell.block)
    params, vparams = weights.make_weights(cell.conf["lm"], cell.conf["vit"],
                                           SEED, cell.block)
    pool = traffic.build_pool(cell.mix, F)
    pipe = ServingPipeline(cfg, vcfg, params, vparams, EngineCfg(
        mode="codecflow", codec=CodecCfg(**cell.mix.codec),
        kv=KVCfg(pool_streams=F)))
    drv = harness.Driver(Scheduler(pipe, SchedulerCfg(max_concurrent=F)),
                         traffic.Schedule(cell.mix, F), pool)
    drv.start()
    period = cell.mix.segment_windows * F
    while len(drv.windows) < 2 * period:
        drv.step()

    # 1. the probe trace
    tdir = tmp / "trace"
    jax.profiler.start_trace(str(tdir))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(STEPS):
            drv.step()
    jax.profiler.stop_trace()
    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    probe = out / "probe_serve_v5e.xplane.pb"
    prune(Path(glob.glob(str(tdir / "**" / "*.xplane.pb"),
                         recursive=True)[0]), probe)

    # 2. the transfers of one warm step, as the guard logs them
    print("make_serve_probe: logged step begins", file=sys.stderr,
          flush=True)
    with jax.transfer_guard_device_to_host("log"):
        drv.step()
    print("make_serve_probe: logged step ends", file=sys.stderr, flush=True)

    # 3. a period with transfers allowed only inside .fetch spans
    fetched = {}

    class Fenced(tracing.span):
        def __init__(self, name, **kw):
            super().__init__(name, **kw)
            self.allow = (jax.transfer_guard_device_to_host("allow")
                          if name.endswith(".fetch") else None)
            if self.allow is not None:
                fetched[name] = fetched.get(name, 0) + 1

        def __enter__(self):
            if self.allow is not None:
                self.allow.__enter__()
            return super().__enter__()

        def __exit__(self, *exc):
            super().__exit__(*exc)
            if self.allow is not None:
                self.allow.__exit__(*exc)

    plain = tracing.span
    tracing.span = Fenced
    jax.config.update("jax_transfer_guard_device_to_host",
                      "disallow_explicit")
    try:
        n0 = len(drv.windows)
        while len(drv.windows) < n0 + period:
            drv.step()
    finally:
        jax.config.update("jax_transfer_guard_device_to_host", "allow")
        tracing.span = plain

    from bench.serve_trace import report
    summary = {"probe_bytes": probe.stat().st_size,
               "guarded_windows": len(drv.windows) - n0,
               "guarded_fetch_spans": fetched,
               "report": report(str(probe), harness.kernels(cell.block))}
    print(json.dumps(summary), flush=True)
    shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
