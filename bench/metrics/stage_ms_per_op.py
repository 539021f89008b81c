"""Kernel dispatch: host time staging a coding call's operands to the
device (``memec.kernel.stage``: ``jnp.asarray``, pads, eager reshapes) in
the traced sub-window, per op the traced window's calls carried."""
from bench import spans


def read(run):
    return spans.self_ms_per_op(run, "memec.kernel.stage")
