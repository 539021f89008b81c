"""Proxy and store: self time of the store's spans (``memec.store.*``: the
multi-key entries, the parity gather, the parity-delta log) in the traced
sub-window, per op the traced window's calls carried."""
from bench import spans


def read(run):
    return spans.self_ms_per_op(run, "memec.store")
