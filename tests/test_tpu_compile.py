"""The main-path coding kernels compile for a TPU v5e.

Each test lowers a public kernel entry point with ``interpret=False``
inside ``jax.jit`` over ``ShapeDtypeStruct``s placed on one chip of a
described ``v5e:2x2`` topology, and compiles it with the TPU compiler.
No chip is needed: this catches what interpret mode cannot, such as
block shapes the TPU lowering refuses.  Shapes are the paper's: RS(10,8)
and RDP(10,8) at p=17 (16 sub-blocks of 256 bytes), 4 KB chunks,
batches of 64.
"""
import base64
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import gf256
from repro.core.codes import RSCode, make_code
from repro.core.engine import NumpyEngine, block_rep
from repro.kernels import dispatch
from repro.kernels.delta_update import (delta_apply_batched,
                                        delta_apply_per_item_batched)
from repro.kernels.gf256_matmul import gf256_matmul_batched

C = 4096
B = 64


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache, so keep it out of the cache entirely
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


U8, I32 = jnp.uint8, jnp.int32
RS = RSCode(n=10, k=8)
RDP = make_code("rdp", 10, 8)
REP = block_rep(RDP)                     # (m*r, k*r) = (32, 128), r = 16
R = REP.r
E4 = np.asarray(REP.encode).reshape(RDP.m * R, RDP.k, R)


def _fused_decode_matrix(code, wanted):
    """The batched-recovery matrix for ``wanted`` lost positions: the
    decode inverse, plus the re-encode rows of lost parity positions."""
    avail = [p for p in range(code.n) if p not in wanted]
    (g,) = NumpyEngine(code).plan_decode([avail], [wanted], C).groups
    if g.par_rows is None:
        return g.inv
    return np.concatenate([g.inv, gf256.gf_matmul_np(g.par_rows, g.inv)])


@pytest.mark.parametrize("name, A, width", [
    ("rs-encode", RS.parity_matrix, C),                          # 2 x 8
    ("rs-fused-decode", _fused_decode_matrix(RS, [8, 9]), C),    # 10 x 8
    ("rdp-encode", REP.encode, C // R),                          # 32 x 128
    ("rdp-fused-decode", _fused_decode_matrix(RDP, [8]), C // R),  # 144 x 128
    # 512-byte chunks give 32-byte sub-blocks, padded to one 128-lane tile
    ("rdp-encode-512B-chunks", REP.encode, 512 // R),
])
def test_gf256_matmul_batched_compiles(one_chip, name, A, width):
    A = np.asarray(A, np.uint8)
    _compile(lambda d: gf256_matmul_batched(A, d, interpret=False),
             one_chip, ((B, A.shape[1], width), U8))


@pytest.mark.parametrize("name, Ms, width", [
    # RS seal fold: one (1, 1) parity-matrix coefficient per item
    ("rs-seal-fold",
     RS.parity_matrix.reshape(-1)[np.arange(B) % RS.parity_matrix.size]
     .reshape(B, 1, 1), C),
    # RDP update: the (m*r, r) column block of the block matrix per item
    ("rdp-delta", np.broadcast_to(E4[:, 0, :], (B, RDP.m * R, R)), C // R),
    # RDP seal fold: one parity row's (r, r) system per item
    ("rdp-seal-fold", np.broadcast_to(E4[:R, 0, :], (B, R, R)), C // R),
])
def test_delta_apply_per_item_batched_compiles(one_chip, name, Ms, width):
    Ms = np.ascontiguousarray(Ms)
    _, O, J = Ms.shape
    _compile(lambda p, x: delta_apply_per_item_batched(p, Ms, x,
                                                       interpret=False),
             one_chip, ((B, O, width), U8), ((B, J, width), U8))


@pytest.mark.parametrize("with_parity", [True, False])
def test_rdp_delta_cols_compiles(one_chip, with_parity):
    """RDP's update shape forced onto the bit-plane body (``is01`` false),
    as a ``strategy="cols"`` tuning entry steers it."""
    Ms = np.ascontiguousarray(np.broadcast_to(E4[:, 0, :], (B, RDP.m * R, R)))
    x = ((B, R, C // R), U8)
    if with_parity:
        _compile(lambda p, d: delta_apply_per_item_batched(
            p, Ms, d, strategy="cols", interpret=False),
            one_chip, ((B, RDP.m * R, C // R), U8), x)
    else:
        _compile(lambda d: delta_apply_per_item_batched(
            None, Ms, d, strategy="cols", interpret=False), one_chip, x)


@pytest.mark.parametrize("lost, wanted", [
    ((2, 3), (3,)),        # two data chunks lost, one rebuilt: 128 x 128
    ((3, 8), (8,)),        # data and row parity lost, parity: 144 x 128
])
@pytest.mark.parametrize("batch", [1, 5])
def test_rdp_two_loss_decode_compiles(one_chip, lost, wanted, batch):
    """Recovery with two servers down: each chunk decodes from the eight
    survivors of its stripe, one small group per loss pattern."""
    avail = [p for p in range(RDP.n) if p not in lost]
    (g,) = NumpyEngine(RDP).plan_decode([avail], [wanted], C).groups
    A = np.asarray(g.inv if g.par_rows is None else np.concatenate(
        [g.inv, gf256.gf_matmul_np(g.par_rows, g.inv)]), np.uint8)
    _compile(lambda d: gf256_matmul_batched(A, d, interpret=False),
             one_chip, ((batch, A.shape[1], C // R), U8))


def test_rdp_single_key_delta_compiles(one_chip):
    """A degraded RDP UPDATE's parity delta: one item, no parity operand
    (the delta-only program)."""
    Ms = np.ascontiguousarray(E4[None, :, 3, :])          # (1, m*r, r)
    _compile(lambda x: delta_apply_per_item_batched(None, Ms, x,
                                                    interpret=False),
             one_chip, ((1, R, C // R), U8))


@pytest.mark.parametrize("with_parity", [True, False])
def test_delta_apply_batched_compiles(one_chip, with_parity):
    """The RS batched UPDATE kernels at B=64 (refused by the TPU lowering
    while gammas and xor were laid out (B, m) and (B, C))."""
    m = RS.m
    if with_parity:
        _compile(lambda p, g, x: delta_apply_batched(p, g, x,
                                                     interpret=False),
                 one_chip, ((B, m, C), U8), ((B, m), I32), ((B, C), U8))
    else:
        _compile(lambda g, x: delta_apply_batched(None, g, x,
                                                  interpret=False),
                 one_chip, ((B, m), I32), ((B, C), U8))


def _kernel_bodies(fn, sharding, *shapes) -> list[bytes]:
    """The serialized Mosaic bodies of the lowered ``tpu_custom_call``s."""
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).as_text()
    found = re.findall(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', text)
    assert found
    return [base64.b64decode(b) for b in found]


def test_kernel_body_drops_checkout_path(one_chip):
    """With ``enable_compile_cache``'s source-name rule, a kernel's body
    (part of its persistent-cache key) names its source relative to the
    repository, so another checkout of the same tree hits the cache."""
    root = str(dispatch.REPO_ROOT).encode()
    shapes = (((B, RS.m, C), U8), ((B, RS.m), I32), ((B, C), U8))

    def lower():
        return _kernel_bodies(
            lambda p, g, x: delta_apply_batched(p, g, x, interpret=False),
            one_chip, *shapes)

    assert any(root in b for b in lower())
    key = "jax_hlo_source_file_canonicalization_regex"
    before = getattr(jax.config, key)
    jax.config.update(key, dispatch.SOURCE_PREFIX_RE)
    try:
        bodies = lower()
    finally:
        jax.config.update(key, before)
    assert not any(root in b for b in bodies)
    assert any(b"src/repro/kernels/delta_update.py" in b for b in bodies)
