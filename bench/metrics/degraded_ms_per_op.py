"""Proxy and store: self time of the coordinated requests (``memec.store
.degraded``: the block of degraded GETs or UPDATEs that a ``multi_*``
call runs before its batch) in the traced sub-window, per op the traced
window's calls carried.  None where the store records no such span."""
from bench import spans


def read(run):
    return spans.self_ms_per_op(run, "memec.store.degraded")
