"""Share of the LM's window positions recomputed, in %: tokens refreshed
over window length (fresh windows 100%; incremental windows the refresh
set, anchors + new stride + query)."""


def read(run):
    n = sum(w["tokens_refreshed"] for w in run.windows)
    total = run.geometry["total"] * len(run.windows)
    if not total:
        return None
    return 100.0 * n / total
