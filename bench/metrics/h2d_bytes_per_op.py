"""Coding engine: host bytes staged to the device (the ``bytes`` of every
``memec.kernel.stage`` span, as ``CodingEngine.h2d_bytes`` counts them) in
the traced sub-window, per op the traced window's calls carried."""
from bench import spans


def read(run):
    return spans.bytes_per_op(run, "memec.kernel.stage")
