"""Device milliseconds of the ViT encode stage per window: the traced
seconds of the ``vit`` programs (motion mask, token selection, full and
packed towers; ``bench/lib/stages.py``) over the windows whose device
work lies inside the traced window."""
from bench.lib import stages


def read(run):
    if run.trace is None or not run.work_windows:
        return None
    t = stages.split(run.trace.op_s)["vit"]
    if t <= 0:
        return None
    return 1e3 * t / len(run.work_windows)
