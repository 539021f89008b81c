"""Coding engine: host time copying results back from the device
(``memec.engine.fetch``: ``np.asarray``) in the traced sub-window, per op
the traced window's calls carried."""
from bench import spans


def read(run):
    return spans.self_ms_per_op(run, "memec.engine.fetch")
