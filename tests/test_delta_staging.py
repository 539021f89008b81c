"""The batched RS parity-delta front door stages nothing on the device.

``delta_apply_batched`` hands the caller's operands unchanged to one
jitted program, which casts, pads and lays them out itself; no device
array exists before that call.  The same front door still takes device
arrays and tracers, and every spelling is byte-equal to the numpy
GF(2^8) oracle.  Interpret mode runs the Pallas kernels on the CPU.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gf256
from repro.core.codes import RSCode
from repro.kernels.delta_update import delta_apply_batched

# the module itself: as an attribute, ``repro.kernels.delta_update`` is
# the single-row kernel function the package re-exports
KERNELS = importlib.import_module("repro.kernels.delta_update")

A = np.asarray(RSCode(n=10, k=8).parity_matrix, np.uint8)
JITTED = ("_delta_apply_batched_call", "_delta_only_batched_call")


def _operands(rng, B, C, fold):
    """Host operands as the engine passes them: int32 gammas (B, m),
    uint8 xor rows (B, C) and, when ``fold``, uint8 parity (B, m, C)."""
    gammas = A[:, rng.integers(0, A.shape[1], B)].T.astype(np.int32)
    xors = rng.integers(0, 256, (B, C), dtype=np.uint8)
    parity = (rng.integers(0, 256, (B, A.shape[0], C), dtype=np.uint8)
              if fold else None)
    return parity, gammas, xors


def _oracle(parity, gammas, xors):
    delta = gf256.gf_mul_np(gammas[:, :, None], xors[:, None, :])
    return delta if parity is None else parity ^ delta


@pytest.fixture
def calls(monkeypatch):
    """Record each jitted program's operands and the live device arrays
    at the moment it is called."""
    seen = []
    for name in JITTED:
        def spy(*args, _real=getattr(KERNELS, name), _name=name, **kw):
            seen.append((_name, args, jax.live_arrays()))
            return _real(*args, **kw)
        monkeypatch.setattr(KERNELS, name, spy)
    return seen


@pytest.mark.parametrize("C", (300, 4096))
@pytest.mark.parametrize("B", (1, 3, 64))
@pytest.mark.parametrize("fold", (True, False), ids=("parity", "delta-only"))
def test_numpy_operands_go_straight_to_one_jit(calls, fold, B, C, rng):
    parity, gammas, xors = _operands(rng, B, C, fold)
    before = jax.live_arrays()          # held, so no id is reused
    got = delta_apply_batched(parity, gammas, xors, interpret=True)
    (name, args, live), = calls
    operands = (gammas, xors) if parity is None else (parity, gammas, xors)
    assert name == JITTED[parity is None]
    assert len(args) == len(operands)
    assert all(a is b for a, b in zip(args, operands))
    made = {id(a) for a in live} - {id(a) for a in before}
    assert not made, "device arrays created before the jitted call"
    assert got.shape == (B, A.shape[0], C)
    assert np.array_equal(np.asarray(got), _oracle(parity, gammas, xors))


@pytest.mark.parametrize("fold", (True, False), ids=("parity", "delta-only"))
@pytest.mark.parametrize("spelling", ("device-arrays", "tracers"))
def test_device_arrays_and_tracers_give_the_same_bytes(fold, spelling, rng):
    parity, gammas, xors = _operands(rng, 5, 300, fold)
    want = _oracle(parity, gammas, xors)
    host = np.asarray(delta_apply_batched(parity, gammas, xors,
                                          interpret=True))
    assert np.array_equal(host, want)

    def front_door(p, g, x):
        return delta_apply_batched(p, g, x, interpret=True)

    if spelling == "tracers":
        front_door = jax.jit(front_door)
    dev = [None if a is None else jnp.asarray(a)
           for a in (parity, gammas, xors)]
    got = front_door(*dev)
    assert isinstance(got, jax.Array)
    assert np.array_equal(np.asarray(got), want)
