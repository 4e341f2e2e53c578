"""Roofline share of the paged flash-attention kernel, in %.

The least time the chip needs for the attention work of the windows
whose device work lies inside the traced window (fresh prefill,
selective refresh and the decode step: their (query, valid key) pairs,
a lower bound where validity per position is not reported; Q, O, K and
V moved once), over the kernel's summed device time in the trace.  The
work is set by operations at these lengths (``bench/lib/peaks.py``
names the bound)."""
from bench.lib import flops, peaks


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    t = run.trace.kernel_s.get("flash_refresh_paged", 0.0)
    if t <= 0 or not run.work_windows:
        return None
    lm, v = run.cell.conf["lm"], run.cell.conf["vit"]
    f = b = 0.0
    for w in run.work_windows:
        k = flops.window_work(w, run.geometry, lm, v)
        f += k["refresh_attn_flops"]
        b += k["refresh_attn_bytes"]
    least, _ = peaks.roofline_s(f, b, run.device_kind)
    return 100.0 * least / t
