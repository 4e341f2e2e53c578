"""Device milliseconds per decode step: the traced seconds of the
``decode`` programs (``bench/lib/stages.py``) over the decode steps of
the windows whose device work lies inside the traced window (one per
new token; the answer itself comes from the prefill logits)."""
from bench.lib import stages


def read(run):
    if run.trace is None or not run.work_windows:
        return None
    t = stages.split(run.trace.op_s)["decode"]
    if t <= 0:
        return None
    from repro.serving import EngineCfg     # the harness serves its default

    steps = len(run.work_windows) * EngineCfg().max_new_tokens
    return 1e3 * t / steps
