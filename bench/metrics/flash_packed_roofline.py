"""Roofline share of the packed block-diagonal ViT attention kernel,
in %: the least time the chip needs for the kept P-frame patches'
attention within their frames (pairs >= (sum of kept)^2 / frames), over
the kernel's summed device time in the trace."""
from bench.lib import flops, peaks


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    t = run.trace.kernel_s.get("flash_packed", 0.0)
    if t <= 0 or not run.work_windows:
        return None
    lm, v = run.cell.conf["lm"], run.cell.conf["vit"]
    f = b = 0.0
    for w in run.work_windows:
        k = flops.window_work(w, run.geometry, lm, v)
        f += k["packed_attn_flops"]
        b += k["packed_attn_bytes"]
    if f <= 0:
        return None
    least, _ = peaks.roofline_s(f, b, run.device_kind)
    return 100.0 * least / t
