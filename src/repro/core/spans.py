"""Wall-clock spans of the store, the coding engine and the kernel front
doors, on the device trace's clock.

Every span is a ``jax.profiler.TraceAnnotation`` named
``memec.<layer>.<what>``: it records only while a profiler trace runs,
on the same clock as the device's own events, and costs under a
microsecond when none does.  Spans sit at batch level, never inside a
per-key loop.  ``bench/spans.py`` reduces them to self time per span and
charges each device idle gap to the innermost span open during it.

Spans that carry ``bytes`` record the host bytes staged to the device
(``KERNEL_STAGE``) or fetched back (``ENGINE_FETCH``) by that call; the
engine keeps the same counts in ``CodingEngine.h2d_bytes`` /
``d2h_bytes``.
"""
from __future__ import annotations

import contextlib
import functools
import time

import numpy as np
from jax.profiler import TraceAnnotation as span

# proxy and store (core/store.py): the public multi-key entries (arg
# ``ops``), the block of coordinated (degraded) requests a ``multi_get``
# or ``multi_update`` runs before its batch (arg ``ops``), the parity
# gather before the engine call of a sealed UPDATE/DELETE round (batched
# or a single key's batch of one), and the parity-delta log after it
STORE_MULTI_GET = "memec.store.multi_get"
STORE_MULTI_UPDATE = "memec.store.multi_update"
STORE_MULTI_SET = "memec.store.multi_set"
STORE_DEGRADED = "memec.store.degraded"
STORE_PARITY_GATHER = "memec.store.parity_gather"
STORE_DELTA_LOG = "memec.store.delta_log"

# network model (core/netsim.py): host time spent modelling the network
NETSIM = "memec.netsim"

# coding engine (core/engine.py): gamma / matrix / decode-plan
# preparation on the host, the wait for the computation, the wait for
# the rest of the copy back (started at dispatch)
ENGINE_PREPARE = "memec.engine.prepare"
ENGINE_WAIT = "memec.engine.wait"
ENGINE_FETCH = "memec.engine.fetch"

# kernel dispatch (kernels/delta_update.py, kernels/gf256_matmul.py,
# kernels/xla_gf256.py): the work before the jitted call (host-side only
# in delta_apply_batched and gf256_matmul_per_item_batched; the
# operands' copies to the device and their pads in the other front
# doors), then the jitted call, with the argument transfers it makes
# itself
KERNEL_STAGE = "memec.kernel.stage"
KERNEL_CALL = "memec.kernel.call"

# recovery (MemECCluster.fail_server): the state transition, gathering
# the surviving chunks, the batched decode, installing the rebuilt chunks
RECOVER_TRANSITION = "memec.recover.transition"
RECOVER_GATHER = "memec.recover.gather"
RECOVER_DECODE = "memec.recover.decode"
RECOVER_INSTALL = "memec.recover.install"


def host_bytes(*arrays) -> int:
    """Bytes of the host (numpy) arrays among ``arrays``: what staging
    them copies to the device.  Device arrays and None count 0."""
    return sum(a.nbytes for a in arrays if isinstance(a, np.ndarray))


def spanned(name: str):
    """Decorator: every call of the function runs inside span ``name``."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return call
    return wrap


@contextlib.contextmanager
def timed(name: str, totals: dict, key: str):
    """Span ``name`` whose wall seconds also accumulate into
    ``totals[key]``, for work that runs while no profiler records."""
    t0 = time.perf_counter()
    try:
        with span(name):
            yield
    finally:
        totals[key] += time.perf_counter() - t0
