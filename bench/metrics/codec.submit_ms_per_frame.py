"""Milliseconds of ``Scheduler.submit`` per frame ingested, over the
submits inside the window (the benchmark's host span around the call:
codec encode, single-pass decode and the copy to the host).  Submit
blocks the scheduler loop, so every ms here is a ms no window moves."""


def read(run):
    frames = sum(s[2] for s in run.submits)
    if not frames:
        return None
    return 1e3 * sum(s[1] for s in run.submits) / frames
