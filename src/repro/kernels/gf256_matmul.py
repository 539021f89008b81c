"""Pallas TPU kernel: GF(2^8) matrix multiply (stripe encode/decode).

TPU adaptation (DESIGN.md §4): the CPU path (ISA-L) gathers 16-entry PSHUFB
tables per product — byte gathers don't vectorize on the TPU VPU.  Instead
we use the bit-plane decomposition

    gamma * x  =  XOR_{b : bit b of x set}  (gamma * 2^b)

so a stripe encode P[m,C] = A[m,k] (*) D[k,C] becomes, per C-tile:

    P[r] = XOR_{i<k, b<8}  ((D[i] >> b) & 1) * APOW[r,i,b]

where APOW[r,i,b] = A[r,i] * 2^b in GF(2^8) is a tiny host-precomputed
table.  The kernel body is pure shift/and/multiply/xor on int32 lanes —
fully VPU-vectorizable, no gathers, no MXU.  m*k*8 fused ops per tile
(e.g. 128 for (n,k)=(10,8)): the op is HBM-bandwidth-bound by design.

Tiling: grid over the byte axis; D tile (k, BC) and P tile (m, BC) live in
VMEM; APOW (m,k,8 int32) is broadcast to every grid step.  BC=2048 keeps
the working set (k+m)*BC + 32*m*k ~ 20-40 KB, far under the ~16 MB VMEM
budget, and 2048 = 16 lanes * 128 keeps the last dim lane-aligned.

Large matrices (PR 5): fully unrolling the (m, k, 8) product is only
sane for small dense parity shapes; the RDP *block* representation is
(m*r, k*r) — e.g. (32, 128) for (10,8) at p=17 — and its decode inverse
is (k*r, k*r).  Above ``MAX_UNROLL_OPS`` the batched entry point
switches to column-loop kernels whose body is O(k) vector steps over
(m, BC) lanes; pure-XOR 0/1 matrices (RDP blocks, XOR, and their decode
inverses — GF(2) systems stay 0/1 under inversion) additionally drop
the bit-plane loop, since gamma ∈ {0,1} makes gamma·x a select.  This
is what lets the engine route RDP through the batched Pallas grid
natively instead of falling back to the jnp path.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import gf256, spans
from repro.core.spans import span
from repro.kernels import dispatch

DEFAULT_BLOCK_C = 2048

# heuristic fallback when no tuning entry covers the shape: beyond this
# many fused ops (m*k*8) the per-element unrolled kernel body becomes
# pathological and the column-loop variants take over.  The tuner
# (kernels/tune.py) overrides this per (k, m, chunk, batch) key.
MAX_UNROLL_OPS = 1024

# Pallas-path strategy names (the tuner's vocabulary; the XLA CPU path
# has its own set in xla_gf256.STRATEGIES)
PALLAS_STRATEGIES = ("unroll", "cols", "gf01")


def build_apow(A: np.ndarray) -> np.ndarray:
    """APOW[r,i,b] = A[r,i] * 2^b over GF(2^8), int32 (m,k,8)."""
    A = np.asarray(A, dtype=np.uint8)
    pow2 = np.array([1 << b for b in range(8)], dtype=np.uint8)
    return gf256.MUL_TABLE[A[..., None], pow2[None, None, :]].astype(np.int32)


def _gf_matmul_kernel(apow_ref, d_ref, o_ref, *, m: int, k: int):
    d = d_ref[...].astype(jnp.int32)                      # (k, BC)
    acc = [jnp.zeros(d.shape[1:], jnp.int32) for _ in range(m)]
    for i in range(k):
        di = d[i]
        for b in range(8):
            bit = (di >> b) & 1                           # (BC,) 0/1
            for r in range(m):
                acc[r] = acc[r] ^ (bit * apow_ref[r, i, b])
    o_ref[...] = jnp.stack(acc).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("m", "k", "block_c", "interpret"))
def _gf_matmul_call(apow, data, *, m, k, block_c, interpret):
    C = data.shape[1]
    grid = (C // block_c,)
    return pl.pallas_call(
        functools.partial(_gf_matmul_kernel, m=m, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, k, 8), lambda c: (0, 0, 0)),
            pl.BlockSpec((k, block_c), lambda c: (0, c)),
        ],
        out_specs=pl.BlockSpec((m, block_c), lambda c: (0, c)),
        out_shape=jax.ShapeDtypeStruct((m, C), jnp.uint8),
        interpret=interpret,
    )(apow, data)


def _gf_matmul_batched_kernel(apow_ref, d_ref, o_ref, *, m: int, k: int):
    d = d_ref[0].astype(jnp.int32)                        # (k, BC)
    acc = [jnp.zeros(d.shape[1:], jnp.int32) for _ in range(m)]
    for i in range(k):
        di = d[i]
        for b in range(8):
            bit = (di >> b) & 1
            for r in range(m):
                acc[r] = acc[r] ^ (bit * apow_ref[r, i, b])
    o_ref[0] = jnp.stack(acc).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("m", "k", "block_c", "interpret"))
def _gf_matmul_batched_call(apow, data, *, m, k, block_c, interpret):
    B, _, C = data.shape
    grid = (B, C // block_c)
    return pl.pallas_call(
        functools.partial(_gf_matmul_batched_kernel, m=m, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, k, 8), lambda b, c: (0, 0, 0)),
            pl.BlockSpec((1, k, block_c), lambda b, c: (b, 0, c)),
        ],
        out_specs=pl.BlockSpec((1, m, block_c), lambda b, c: (b, 0, c)),
        out_shape=jax.ShapeDtypeStruct((B, m, C), jnp.uint8),
        interpret=interpret,
    )(apow, data)


def _gf_matmul_cols_kernel(apow_ref, d_ref, o_ref, *, m: int, k: int):
    """Column-loop body for large matrices: k*8 vectorized (m, BC)
    accumulation steps instead of m*k*8 scalar-coefficient ops."""
    d = d_ref[0].astype(jnp.int32)                        # (k, BC)
    acc = jnp.zeros((m, d.shape[1]), jnp.int32)
    for j in range(k):
        dj = d[j]
        for b in range(8):
            bit = (dj >> b) & 1                           # (BC,)
            acc = acc ^ (bit[None, :] * apow_ref[:, j, b][:, None])
    o_ref[0] = acc.astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("m", "k", "block_c", "interpret"))
def _gf_matmul_cols_call(apow, data, *, m, k, block_c, interpret):
    B, _, C = data.shape
    grid = (B, C // block_c)
    return pl.pallas_call(
        functools.partial(_gf_matmul_cols_kernel, m=m, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, k, 8), lambda b, c: (0, 0, 0)),
            pl.BlockSpec((1, k, block_c), lambda b, c: (b, 0, c)),
        ],
        out_specs=pl.BlockSpec((1, m, block_c), lambda b, c: (b, 0, c)),
        out_shape=jax.ShapeDtypeStruct((B, m, C), jnp.uint8),
        interpret=interpret,
    )(apow, data)


def _gf01_matmul_kernel(a_ref, d_ref, o_ref, *, m: int, k: int):
    """0/1 matrices (pure-XOR codes): gamma·x is a select, so the
    bit-plane loop vanishes — k XOR-select steps over (m, BC) lanes."""
    d = d_ref[0].astype(jnp.int32)                        # (k, BC)
    acc = jnp.zeros((m, d.shape[1]), jnp.int32)
    for j in range(k):
        acc = acc ^ (a_ref[:, j][:, None] * d[j][None, :])
    o_ref[0] = acc.astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("m", "k", "block_c", "interpret"))
def _gf01_matmul_call(a01, data, *, m, k, block_c, interpret):
    B, _, C = data.shape
    grid = (B, C // block_c)
    return pl.pallas_call(
        functools.partial(_gf01_matmul_kernel, m=m, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, k), lambda b, c: (0, 0)),
            pl.BlockSpec((1, k, block_c), lambda b, c: (b, 0, c)),
        ],
        out_specs=pl.BlockSpec((1, m, block_c), lambda b, c: (b, 0, c)),
        out_shape=jax.ShapeDtypeStruct((B, m, C), jnp.uint8),
        interpret=interpret,
    )(a01, data)


def gf256_matmul_batched(A: np.ndarray, data: jax.Array, *,
                         block_c: int | None = None,
                         strategy: str | None = None,
                         interpret: bool | None = None) -> jax.Array:
    """Batched A (*) data over GF(2^8): one matrix, a whole batch of stripes.

    A: (m, k) uint8 shared across the batch; data: (B, k, C) uint8 ->
    (B, m, C).  The grid runs (batch, C-tiles) so every stripe's tiles are
    independent grid steps — the batched analogue of `gf256_matmul`.

    Dispatch: the path comes from ``kernels.dispatch`` (compiled Pallas
    on TPU/GPU, the XLA-jitted ``xla_gf256`` formulations on CPU,
    interpret only when forced).  ``strategy``/``block_c`` default to the
    tuning cache for this (path, shape) key, then to the MAX_UNROLL_OPS
    heuristic: small dense matrices (RS/XOR parity shapes) take the
    fully-unrolled kernel; larger ones — the RDP block representation and
    its decode inverses — take the column-loop kernels, with 0/1 matrices
    on the bit-plane-free XOR-select body.
    """
    from repro.kernels import tune, xla_gf256
    A = np.asarray(A, dtype=np.uint8)
    m, k = A.shape
    B, kd, C = np.shape(data)
    assert kd == k, (np.shape(data), k)
    if B == 0 or m == 0:
        return jnp.zeros((B, m, C), jnp.uint8)
    dec = dispatch.decide(interpret)
    cls = "01" if int(A.max(initial=0)) <= 1 else "gf"
    if strategy is None or block_c is None:
        entry = tune.lookup("matmul", dec.path, k=k, m=m, chunk=C,
                            batch=B, cls=cls)
        if entry:
            strategy = strategy or entry.get("strategy")
            if block_c is None and entry.get("block_c"):
                block_c = entry["block_c"]
    if dec.path == dispatch.XLA:
        s = strategy if strategy in xla_gf256.STRATEGIES else None
        return xla_gf256.matmul_batched(A, data, strategy=s)
    block_c = min(block_c or DEFAULT_BLOCK_C, _round_up(C, 128))
    Cp = _round_up(C, block_c)
    if strategy not in PALLAS_STRATEGIES:
        strategy = ("unroll" if m * k * 8 <= MAX_UNROLL_OPS
                    else "gf01" if cls == "01" else "cols")
    if strategy == "gf01" and cls != "01":
        strategy = "cols"
    with span(spans.KERNEL_STAGE, bytes=spans.host_bytes(A, data)):
        data = jnp.asarray(data, dtype=jnp.uint8)
        if Cp != C:
            data = jnp.pad(data, ((0, 0), (0, 0), (0, Cp - C)))
        mat = jnp.asarray(A.astype(np.int32) if strategy == "gf01"
                          else build_apow(A))
    with span(spans.KERNEL_CALL):
        call = {"unroll": _gf_matmul_batched_call, "gf01": _gf01_matmul_call,
                "cols": _gf_matmul_cols_call}[strategy]
        out = call(mat, data, m=m, k=k, block_c=block_c,
                   interpret=dec.interpret)
        return out[:, :, :C]


def gf256_matmul(A: np.ndarray, data: jax.Array, *,
                 block_c: int | None = None,
                 interpret: bool | None = None) -> jax.Array:
    """Compute A (*) data over GF(2^8).

    A: (m, k) uint8 host matrix (encode parity matrix or decode inverse);
    data: (k, C) uint8.  C is padded to a multiple of block_c internally.
    Dispatches like ``gf256_matmul_batched`` (the XLA CPU path runs it as
    a batch of one).
    """
    from repro.kernels import tune, xla_gf256
    A = np.asarray(A, dtype=np.uint8)
    m, k = A.shape
    data = xla_gf256._as_u8(data)
    assert data.shape[0] == k, (data.shape, k)
    C = data.shape[1]
    dec = dispatch.decide(interpret)
    if dec.path == dispatch.XLA:
        ent = tune.lookup("matmul", dec.path, k=k, m=m, chunk=C, batch=1,
                          cls=tune.matrix_cls(A))
        s = ent.get("strategy") if ent else None
        return xla_gf256.matmul(
            A, data, strategy=s if s in xla_gf256.STRATEGIES else None)
    block_c = min(block_c or DEFAULT_BLOCK_C, _round_up(C, 128))
    Cp = _round_up(C, block_c)
    if Cp != C:
        data = jnp.pad(data, ((0, 0), (0, Cp - C)))
    apow = jnp.asarray(build_apow(A))
    out = _gf_matmul_call(apow, data, m=m, k=k, block_c=block_c,
                          interpret=dec.interpret)
    return out[:, :C]


def _per_item_acc(m_ref, d, o: int, j: int, is01: bool):
    """Accumulate M_b (*) D_b for one grid step's (O, J) matrix tile.

    Coefficients are traced (each batch item carries its own matrix), so
    gamma powers come from in-kernel xtime steps like delta_update's —
    no host APOW table.  0/1 matrices skip the bit-plane loop entirely.
    """
    acc = jnp.zeros((o, d.shape[1]), jnp.int32)
    for jj in range(j):
        x = d[jj]                                         # (BC,)
        if is01:
            acc = acc ^ (m_ref[0, :, jj][:, None] * x[None, :])
        else:
            g = m_ref[0, :, jj].astype(jnp.int32)         # (O,)
            for b in range(8):
                acc = acc ^ (((x >> b) & 1)[None, :] * g[:, None])
                g = ((g << 1) ^ jnp.where((g & 0x80) != 0, 0x11D, 0)) & 0xFF
    return acc


def _per_item_kernel(m_ref, d_ref, o_ref, *, o: int, j: int, is01: bool):
    d = d_ref[0].astype(jnp.int32)                        # (J, BC)
    o_ref[0] = _per_item_acc(m_ref, d, o, j, is01).astype(jnp.uint8)


def _per_item_fold_kernel(m_ref, p_ref, d_ref, o_ref, *, o: int, j: int,
                          is01: bool):
    d = d_ref[0].astype(jnp.int32)
    o_ref[0] = p_ref[0] ^ _per_item_acc(m_ref, d, o, j, is01).astype(jnp.uint8)


@functools.partial(jax.jit,
                   static_argnames=("o", "j", "block_c", "interpret", "is01"))
def _per_item_call(Ms, data, *, o, j, block_c, interpret, is01):
    B, _, C = data.shape
    grid = (B, C // block_c)
    return pl.pallas_call(
        functools.partial(_per_item_kernel, o=o, j=j, is01=is01),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, o, j), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((1, j, block_c), lambda b, c: (b, 0, c)),
        ],
        out_specs=pl.BlockSpec((1, o, block_c), lambda b, c: (b, 0, c)),
        out_shape=jax.ShapeDtypeStruct((B, o, C), jnp.uint8),
        interpret=interpret,
    )(Ms, data)


@functools.partial(jax.jit,
                   static_argnames=("o", "j", "block_c", "interpret", "is01"))
def _per_item_fold_call(Ms, parity, data, *, o, j, block_c, interpret, is01):
    B, _, C = data.shape
    grid = (B, C // block_c)
    return pl.pallas_call(
        functools.partial(_per_item_fold_kernel, o=o, j=j, is01=is01),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, o, j), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((1, o, block_c), lambda b, c: (b, 0, c)),
            pl.BlockSpec((1, j, block_c), lambda b, c: (b, 0, c)),
        ],
        out_specs=pl.BlockSpec((1, o, block_c), lambda b, c: (b, 0, c)),
        out_shape=jax.ShapeDtypeStruct((B, o, C), jnp.uint8),
        interpret=interpret,
    )(Ms, parity, data)


def gf256_matmul_per_item_batched(Ms, blocks, parity=None, *,
                                  block_c: int | None = None,
                                  strategy: str | None = None,
                                  interpret: bool | None = None):
    """Per-item matrices: (B, O, J) (*) (B, J, C) -> (B, O, C).

    Each batch item multiplies by its *own* matrix — the r > 1 (RDP)
    delta shape, where every update folds a (r, r)-per-parity-row system,
    and the fused seal-fold path.  ``parity`` (B, O, C), when given, is
    XORed into the product inside the same kernel (one read stream more,
    one device round trip fewer).  Grid = (batch, C-tiles), like
    ``gf256_matmul_batched``; 0/1 matrices drop the bit-plane loop.
    """
    from repro.kernels import xla_gf256
    Ms = np.asarray(Ms, dtype=np.uint8)
    B, O, J = Ms.shape
    *lead, C = np.shape(blocks)
    assert tuple(lead) == (B, J), (Ms.shape, np.shape(blocks))
    if B == 0 or O == 0:
        return (jnp.asarray(parity, jnp.uint8) if parity is not None
                else jnp.zeros((B, O, C), jnp.uint8))
    dec = dispatch.decide(interpret)
    if dec.path == dispatch.XLA:
        s = strategy if strategy in xla_gf256.STRATEGIES else None
        return xla_gf256.matmul_per_item(Ms, blocks, parity, strategy=s)
    is01 = int(Ms.max(initial=0)) <= 1 and strategy != "cols"
    block_c = min(block_c or DEFAULT_BLOCK_C, _round_up(C, 128))
    Cp = _round_up(C, block_c)
    with span(spans.KERNEL_STAGE,
              bytes=spans.host_bytes(Ms, blocks, parity)):
        blocks = jnp.asarray(blocks, dtype=jnp.uint8)
        if Cp != C:
            blocks = jnp.pad(blocks, ((0, 0), (0, 0), (0, Cp - C)))
        Ms_dev = jnp.asarray(Ms.astype(np.int32))
        if parity is not None:
            parity = jnp.asarray(parity, dtype=jnp.uint8)
            if Cp != C:
                parity = jnp.pad(parity, ((0, 0), (0, 0), (0, Cp - C)))
    with span(spans.KERNEL_CALL):
        if parity is None:
            out = _per_item_call(Ms_dev, blocks, o=O, j=J, block_c=block_c,
                                 interpret=dec.interpret, is01=is01)
        else:
            out = _per_item_fold_call(Ms_dev, parity, blocks, o=O, j=J,
                                      block_c=block_c,
                                      interpret=dec.interpret, is01=is01)
        return out[:, :, :C]


def _round_up(x: int, mult: int) -> int:
    return -(-x // mult) * mult
