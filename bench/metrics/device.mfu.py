"""The whole serving step's share of the chip's bf16 peak, in %: the
FLOPs the answered windows require (vision tower over the patches
encoded, LM over the positions recomputed, the answer's head row, one
decode step; ``bench/lib/flops.py``), for the windows whose device work
lies inside the traced window, over its seconds times the peak."""
from bench.lib import flops


def read(run):
    if run.trace is None or run.peaks is None or not run.work_windows:
        return None
    lm, v = run.cell.conf["lm"], run.cell.conf["vit"]
    f = sum(flops.window_flops(w, run.geometry, lm, v)
            for w in run.work_windows)
    return 100.0 * f / (run.trace.window_s * run.peaks["bf16_flops"])
