"""The per-item (RDP) parity-delta front door stages nothing on the device.

``gf256_matmul_per_item_batched`` hands the caller's operands unchanged to
one jitted program, which widens ``Ms``, casts and pads the rest and
slices the result itself; no device array exists before that call.  The
same front door still takes device arrays and tracers, and every
spelling is byte-equal to the numpy GF(2^8) oracle.  Interpret mode runs
the Pallas kernels on the CPU.
"""
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gf256
from repro.core.codes import RSCode, make_code
from repro.core.engine import block_rep
from repro.kernels.gf256_matmul import gf256_matmul_per_item_batched

# the module itself: as an attribute, ``repro.kernels.gf256_matmul`` is
# the single-matrix kernel function the package re-exports
KERNELS = importlib.import_module("repro.kernels.gf256_matmul")

JITTED = ("_per_item_fold_call", "_per_item_call")

RS = RSCode(n=10, k=8)
RDP = make_code("rdp", 10, 8)
REP = block_rep(RDP)
R = REP.r
# RDP's encode matrix as (m*r, k, r): column block j is data chunk j's
# (m*r, r) delta system, 0/1 entries
E4 = np.asarray(REP.encode, np.uint8).reshape(RDP.m * R, RDP.k, R)


def _rdp_ms(rng, B):
    """RDP UPDATE systems: each item's (m*r, r) column block (0/1)."""
    return np.ascontiguousarray(
        E4[:, rng.integers(0, RDP.k, B), :].transpose(1, 0, 2))


def _rs_ms(rng, B):
    """RS seal-fold systems: one (1, 1) parity coefficient per item."""
    A = np.asarray(RS.parity_matrix, np.uint8)
    return A.reshape(-1)[rng.integers(0, A.size, B)].reshape(B, 1, 1)


# (Ms maker, strategy): RDP's 0/1 body, an RS gf body (bit planes), and
# the bit-plane body forced on a 0/1 matrix
SYSTEMS = {"rdp-01": (_rdp_ms, None), "rs-gf": (_rs_ms, None),
           "rdp-cols": (_rdp_ms, "cols")}


def _operands(rng, system, B, C, fold):
    """Host operands as the engine passes them: uint8 ``Ms`` (B, O, J),
    blocks (B, J, C) and, when ``fold``, parity (B, O, C)."""
    Ms = SYSTEMS[system][0](rng, B)
    _, O, J = Ms.shape
    blocks = rng.integers(0, 256, (B, J, C), dtype=np.uint8)
    parity = (rng.integers(0, 256, (B, O, C), dtype=np.uint8)
              if fold else None)
    return Ms, blocks, parity


def _oracle(Ms, blocks, parity):
    out = np.stack([gf256.gf_matmul_np(M, b) for M, b in zip(Ms, blocks)])
    return out if parity is None else parity ^ out


@pytest.fixture
def calls(monkeypatch):
    """Record each jitted program's operands and the live device arrays
    at the moment it is called."""
    seen = []
    for name in JITTED:
        def spy(*args, _real=getattr(KERNELS, name), _name=name, **kw):
            seen.append((_name, args, kw, jax.live_arrays()))
            return _real(*args, **kw)
        monkeypatch.setattr(KERNELS, name, spy)
    return seen


@pytest.mark.parametrize("system", tuple(SYSTEMS))
@pytest.mark.parametrize("C", (256, 300))
@pytest.mark.parametrize("B", (1, 3, 64))
@pytest.mark.parametrize("fold", (True, False), ids=("parity", "delta-only"))
def test_numpy_operands_go_straight_to_one_jit(calls, fold, B, C, system,
                                               rng):
    Ms, blocks, parity = _operands(rng, system, B, C, fold)
    strategy = SYSTEMS[system][1]
    before = jax.live_arrays()          # held, so no id is reused
    got = gf256_matmul_per_item_batched(Ms, blocks, parity,
                                        strategy=strategy, interpret=True)
    (name, args, kw, live), = calls
    operands = (Ms, blocks) if parity is None else (Ms, parity, blocks)
    assert name == JITTED[parity is None]
    assert len(args) == len(operands)
    assert all(a is b for a, b in zip(args, operands))
    assert kw["is01"] == (system == "rdp-01")
    made = {id(a) for a in live} - {id(a) for a in before}
    assert not made, "device arrays created before the jitted call"
    assert got.shape == (B, Ms.shape[1], C)
    assert np.array_equal(np.asarray(got), _oracle(Ms, blocks, parity))


@pytest.mark.parametrize("system", ("rdp-01", "rs-gf"))
@pytest.mark.parametrize("fold", (True, False), ids=("parity", "delta-only"))
@pytest.mark.parametrize("spelling", ("device-arrays", "tracers"))
def test_device_arrays_and_tracers_give_the_same_bytes(fold, spelling,
                                                       system, rng):
    Ms, blocks, parity = _operands(rng, system, 5, 300, fold)
    want = _oracle(Ms, blocks, parity)
    host = np.asarray(gf256_matmul_per_item_batched(Ms, blocks, parity,
                                                    interpret=True))
    assert np.array_equal(host, want)

    def front_door(b, p):
        return gf256_matmul_per_item_batched(Ms, b, p, interpret=True)

    if spelling == "tracers":
        front_door = jax.jit(front_door)
    dev = [None if a is None else jnp.asarray(a) for a in (blocks, parity)]
    got = front_door(*dev)
    assert isinstance(got, jax.Array)
    assert np.array_equal(np.asarray(got), want)
