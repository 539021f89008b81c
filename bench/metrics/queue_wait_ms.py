"""Client driver: mean gap from an op's due time to the issue of the
window that carries it, over the ops issued in the traced sub-window."""
import numpy as np


def read(run):
    if run.trace_bounds is None:
        return None
    t0, t1 = run.trace_bounds
    inside = (run.issue >= t0) & (run.issue < t1)
    if not inside.any():
        return None
    return 1e3 * float(np.mean(run.issue[inside] - run.due[inside]))
