"""Coding engine: bytes copied back from the device (the ``bytes`` of every
``memec.engine.fetch`` span, as ``CodingEngine.d2h_bytes`` counts them) in
the traced sub-window, per op the traced window's calls carried."""
from bench import spans


def read(run):
    return spans.bytes_per_op(run, "memec.engine.fetch")
