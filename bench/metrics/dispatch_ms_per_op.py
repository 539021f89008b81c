"""Kernel dispatch: host time enqueueing the jitted coding kernels
(``memec.kernel.call``) in the traced sub-window, per op the traced
window's calls carried."""
from bench import spans


def read(run):
    return spans.self_ms_per_op(run, "memec.kernel.call")
