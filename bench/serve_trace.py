"""One traced run of a cell, its trace kept and read against the serving
loop's own spans.

    python3 bench/serve_trace.py --workload <cell> --seed <n> --seconds <s>

Runs the cell exactly as ``bench/run.py --trace 1`` does (same harness,
same result line first), keeps the window's ``.xplane.pb`` under
``--out``, and prints a second JSON line read from it with
``bench/lib/spans.py`` and ``bench/lib/stages.py``: device seconds by
stage against the busy seconds, the longest idle gaps and all idle time
named by the ``serve.`` span the scheduler thread was in, the blocking
fetches, and the span-based numbers (codec open time per frame, fetch
time under encode per window, fetches per window answered, idle share
inside host work).  Like ``bench/run.py`` it needs the chip.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def report(path: str, kernels) -> dict:
    """What the kept trace says about the serving loop's spans; the
    device time of ``kernels`` summed."""
    from bench.lib import spans, stages
    from bench.lib import trace as tracemod

    tr = tracemod.load(path)
    sp = spans.load(path)
    summ = tracemod.summarize(tr, kernels)
    split = stages.split(summ.op_s)
    fetches = spans.fetches(sp)
    by_fetch = {}
    for f in fetches:
        n, s = by_fetch.get(f.name, (0, 0.0))
        by_fetch[f.name] = (n + 1, s + f.seconds)
    return {
        "busy_s": summ.busy_s, "window_s": summ.window_s,
        "stage_s": split,
        "stage_sum_over_busy": sum(split.values()) / summ.busy_s,
        "other_top": stages.top_modules(summ.op_s, "other", 8),
        "gaps": spans.gaps(tr, sp, 10),
        "idle_by_label": dict(list(spans.idle_by_label(tr, sp).items())[:15]),
        "windows_answered": spans.windows_answered(sp),
        "fetches": {k: [n, s] for k, (n, s) in by_fetch.items()},
        "codec.open_ms_per_frame": spans.codec_open_ms_per_frame(sp),
        "vit.fetch_ms_per_window": spans.vit_fetch_ms_per_window(sp),
        "sched.fetches_per_window": spans.fetches_per_window(sp),
        "sched.idle_in_host_work_share": spans.idle_in_host_work_share(tr,
                                                                       sp),
        "host_s": dict(list(spans.seconds_by_name(
            sp, spans.scheduler_thread(sp)).items())[:30]),
        "counts": spans.counts_by_name(sp),
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=str(ROOT / ".bench_tmp" / "kept"))
    args = ap.parse_args()

    from bench.lib import harness
    from bench.lib import trace as tracemod

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    kept = out / "window.xplane.pb"
    load = tracemod.load

    def keep(path):
        # the harness reads the window's trace once and deletes it
        shutil.copy(path, kept)
        return load(path)

    tracemod.load = keep
    cell = harness.load_cell(args.workload)
    try:
        res = harness.run(cell, args.seed, args.seconds, True, T_START)
    except harness.NoAccelerator as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    finally:
        tracemod.load = load
    print(json.dumps(res), flush=True)
    print(json.dumps(report(str(kept), harness.kernels(cell.block))),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
