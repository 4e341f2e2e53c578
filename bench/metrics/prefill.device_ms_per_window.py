"""Device milliseconds of the LM context stage per window: the traced
seconds of the ``prefill`` programs (KV reuse with ``rope_shift``,
cold-page demotion, selective refresh, fresh prefill;
``bench/lib/stages.py``) over the windows whose device work lies inside
the traced window."""
from bench.lib import stages


def read(run):
    if run.trace is None or not run.work_windows:
        return None
    t = stages.split(run.trace.op_s)["prefill"]
    if t <= 0:
        return None
    return 1e3 * t / len(run.work_windows)
