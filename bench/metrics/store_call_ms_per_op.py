"""Proxy and store: wall time inside the cluster's ``multi_*`` calls over
the ops they carried, for the windows issued in the traced sub-window
(host and device time together)."""


def read(run):
    if run.trace_bounds is None:
        return None
    t0, t1 = run.trace_bounds
    w = run.windows
    inside = (w[:, 0] >= t0) & (w[:, 0] < t1)
    ops = w[inside, 2].sum()
    return None if not ops else 1e3 * float(w[inside, 3].sum() / ops)
