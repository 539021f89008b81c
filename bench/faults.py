"""Faults planted under the timed path, to show that ``correct`` fails.

Each fault wraps one entry point of the cluster or its coding engine in
place (instance attributes), so the harness and the store run unchanged
around it.  ``bench/control.py`` arms one on the chip at a cell's size;
``bench/tests/test_bench_faults.py`` arms each at a tiny size on the CPU.
"""
from __future__ import annotations

import numpy as np


def _stale_parity(cluster):
    """Each batched UPDATE's parity step returns the parity unchanged:
    the data chunk changes, its stripe's parity does not."""
    from repro.core.engine import EngineFuture

    def submit_apply_delta(parity, data_indices, xors):
        return EngineFuture.wrap(np.array(parity, np.uint8, copy=True))
    cluster.engine.submit_apply_delta = submit_apply_delta


def _half_batch(cluster):
    """``multi_update`` applies the first half of each batch and
    acknowledges all of it."""
    inner = cluster.multi_update

    def multi_update(items, proxy_id=0):
        items = list(items)
        h = (len(items) + 1) // 2
        return list(inner(items[:h], proxy_id=proxy_id)) + \
            [True] * (len(items) - h)
    cluster.multi_update = multi_update


def _wrong_answer(cluster):
    """The first GET answer after arming comes back with one byte
    altered."""
    inner = cluster.multi_get
    left = [1]

    def multi_get(keys, proxy_id=0):
        values = inner(keys, proxy_id=proxy_id)
        for i, v in enumerate(values):
            if left[0] and v:
                values[i] = bytes([v[0] ^ 0x01]) + v[1:]
                left[0] = 0
        return values
    cluster.multi_get = multi_get


def _bad_rebuild(cluster):
    """The first recovery decode after arming returns one chunk with one
    byte altered."""
    from repro.core.engine import EngineFuture
    engine = cluster.engine
    inner = engine.submit_decode
    left = [1]

    def submit_decode(available, wanted, chunk_size):
        out = inner(available, wanted, chunk_size).result()
        if left[0] and out:
            pos = wanted[0][0]
            out[0][pos] = out[0][pos].copy()
            out[0][pos][0] ^= 0x01
            left[0] = 0
        return EngineFuture.wrap(out)
    engine.submit_decode = submit_decode


FAULTS = {
    "stale_parity": _stale_parity,
    "half_batch": _half_batch,
    "wrong_answer": _wrong_answer,
    "bad_rebuild": _bad_rebuild,
}

# the control: the fault that breaks the configuration's parity guarantee,
# the shortcut a change to the update path would be tempted by
CONTROL = "stale_parity"


def arm(name: str, cluster) -> None:
    try:
        FAULTS[name](cluster)
    except KeyError:
        raise ValueError(f"unknown fault {name!r}; known: {sorted(FAULTS)}")
