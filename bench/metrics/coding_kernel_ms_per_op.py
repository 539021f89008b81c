"""Kernels: device time of the coding kernels' events in the traced
sub-window, per op the traced window's calls carried."""


def read(run):
    t = run.trace
    if t is None or not t.kernel_s or not t.ops:
        return None
    return 1e3 * t.kernel_s / t.ops
