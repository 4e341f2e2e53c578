"""Readings for a cell's correctness limits: the program on many seeds
and the fp8 control on the same streams, in one process.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 --seconds 10

For each seed: one run of the cell as ``run.py`` makes it (set-up,
warm-up, a window of ``--seconds`` at the cell's load, the reference
over the seeded sample), and then the same sample served by the plain
reference in fp8 in the program's place.  One JSON line per seed:
the program's readings (``checks``), the control's (``control``), and
each checked window's reading with its stream (camera, pool clip,
first window), window index, scheduler step and refreshed tokens.
The benchmark's own runs never run the control.  Needs the chip.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()

    from bench.lib import harness

    cell = harness.load_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        try:
            out = harness.run(cell, seed, args.seconds, False, t0,
                              control=True)
        except harness.NoAccelerator as e:
            print(f"calibrate: {e}", file=sys.stderr)
            return 1
        print(json.dumps({
            "seed": seed,
            "program": {k: v["value"] for k, v in out["checks"].items()},
            "control": {k: v for k, v in out["control"].items()
                        if k != "windows"},
            "windows": out["control"]["windows"],
            "correct": out["correct"],
            "metrics": {k: v["value"] for k, v in out["metrics"].items()},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
