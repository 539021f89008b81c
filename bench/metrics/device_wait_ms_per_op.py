"""Coding engine: host time blocked on the device
(``memec.engine.wait``: ``jax.block_until_ready``) in the traced
sub-window, per op the traced window's calls carried."""
from bench import spans


def read(run):
    return spans.self_ms_per_op(run, "memec.engine.wait")
