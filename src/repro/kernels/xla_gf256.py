"""XLA-jitted GF(2^8) data plane — the compiled CPU path.

CPU has no Pallas lowering (interpret mode only), so the dispatch policy
routes CPU calls here: the same GF(2^8) formulations as the Pallas
kernels, expressed in jnp and compiled by XLA.  Three strategies, all
byte-identical (cross-checked against the numpy oracle in
``tests/test_dispatch_tune.py``); ``kernels/tune.py`` picks per shape:

* ``bitplane32`` — the bit-plane decomposition packed four bytes per
  uint32 lane: coefficients are < 256, so ``((x >> b) & 0x01010101) * c``
  scales all four byte lanes with no carry between them.  8 fused
  shift/and/mul/xor steps per input row; the default for the small dense
  parity shapes (RS/XOR) where it beats the numpy table path ~5x.
* ``select32`` — 0/1 matrices (RDP blocks and their GF(2) inverses):
  gamma ∈ {0,1} makes gamma·x a select, one masked XOR per input row on
  the same packed uint32 lanes.
* ``table`` — the classic log/exp-gather formulation, one gather row per
  input column; wins when the matrix is large and dense enough that
  8-step bit-plane unrolling dominates.

Entry points mirror the Pallas batched kernels (shared-matrix matmul,
per-item-matrix matmul, per-item-gamma delta) and return *device* arrays
without blocking, so ``submit_*`` engine calls keep their dispatch-at-
submit semantics.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import gf256, spans
from repro.core.spans import span

_LANES = np.uint32(0x01010101)  # bit b of each packed byte after >> b

# strategy names (the tuner's vocabulary for this path)
BITPLANE32 = "bitplane32"
SELECT32 = "select32"
TABLE = "table"
STRATEGIES = (BITPLANE32, SELECT32, TABLE)


def _as_u8(x) -> jax.Array:
    """uint8 device array; skips ``jnp.asarray`` when already one (the
    conversion machinery costs ~65us/call on CPU — real money against a
    ~50us kernel)."""
    if isinstance(x, jax.Array) and x.dtype == jnp.uint8:
        return x
    return jnp.asarray(x, dtype=jnp.uint8)


def default_strategy(A: np.ndarray) -> str:
    """Heuristic when no tuning entry exists: 0/1 matrices select, dense
    ones run the packed bit-plane (it beat the table path at every CI
    shape we measured — the tuner can still override per key)."""
    return SELECT32 if int(A.max(initial=0)) <= 1 else BITPLANE32


@functools.lru_cache(maxsize=256)
def _mat_dev(kind: str, shape: tuple, buf: bytes) -> jax.Array:
    """Device-resident matrix constants, cached by value: encode/decode
    matrices are few and reused every call, so don't re-transfer (or
    rebuild APOW) per encode."""
    A = np.frombuffer(buf, dtype=np.uint8).reshape(shape)
    if kind == "apow":
        from .gf256_matmul import build_apow
        return jnp.asarray(build_apow(A).astype(np.uint32))
    if kind == "u32":
        return jnp.asarray(A.astype(np.uint32))
    return jnp.asarray(A)


def _pack32(x: jax.Array) -> jax.Array:
    """(..., C) uint8 -> (..., C//4) uint32 byte-lane view (C % 4 == 0)."""
    return jax.lax.bitcast_convert_type(
        x.reshape(x.shape[:-1] + (x.shape[-1] // 4, 4)), jnp.uint32)


def _unpack32(x: jax.Array, C: int) -> jax.Array:
    """Inverse of ``_pack32``."""
    return jax.lax.bitcast_convert_type(x, jnp.uint8).reshape(
        x.shape[:-1] + (C,))


def _xtime_powers(g: jax.Array) -> jax.Array:
    """(..., ) uint32 gamma -> (..., 8) uint32 with out[..., b] = g * 2^b
    over GF(2^8)/0x11D — traced-friendly (no host table)."""
    outs = []
    for _ in range(8):
        outs.append(g)
        g = ((g << 1) ^ jnp.where((g & 0x80) != 0, np.uint32(0x11D),
                                  np.uint32(0))) & np.uint32(0xFF)
    return jnp.stack(outs, axis=-1)


def _pad4(x: jax.Array) -> tuple[jax.Array, int]:
    """Pad the trailing byte axis to a multiple of 4 for the packed
    strategies; returns (padded, original C)."""
    C = x.shape[-1]
    pad = (-C) % 4
    if pad:
        cfg = [(0, 0)] * (x.ndim - 1) + [(0, pad)]
        x = jnp.pad(x, cfg)
    return x, C


# ---------------------------------------------------------------------------
# shared-matrix batched matmul: (m, k) x (B, k, C) -> (B, m, C)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("m", "k"))
def _matmul_bitplane32(apow, data, *, m, k):
    B, _, C = data.shape
    d = _pack32(data)                                     # (B, k, C/4)
    acc = jnp.zeros((B, m, C // 4), jnp.uint32)
    for j in range(k):
        dj = d[:, j]
        for b in range(8):
            bit = (dj >> b) & _LANES                      # (B, C/4)
            acc = acc ^ bit[:, None, :] * apow[None, :, j, b, None]
    return _unpack32(acc, C)


@functools.partial(jax.jit, static_argnames=("m", "k"))
def _matmul_select32(a01, data, *, m, k):
    B, _, C = data.shape
    d = _pack32(data)
    acc = jnp.zeros((B, m, C // 4), jnp.uint32)
    for j in range(k):
        acc = acc ^ a01[None, :, j, None] * d[:, j][:, None, :]
    return _unpack32(acc, C)


@functools.partial(jax.jit, static_argnames=("m", "k"))
def _matmul_table(A, data, *, m, k):
    exp, log, _ = gf256._device_tables()
    la = log[A.astype(jnp.int32)]                         # (m, k)
    B, _, C = data.shape
    acc = jnp.zeros((B, m, C), jnp.uint8)
    for j in range(k):
        dj = data[:, j]                                   # (B, C)
        prod = exp[(la[:, j][None, :, None]
                    + log[dj.astype(jnp.int32)][:, None, :]) % 255]
        prod = jnp.where((A[:, j] == 0)[None, :, None]
                         | (dj == 0)[:, None, :], jnp.uint8(0), prod)
        acc = acc ^ prod
    return acc


def matmul_batched(A: np.ndarray, data, *, strategy: str | None = None):
    """XLA twin of ``gf256_matmul_batched``: (B, k, C) -> (B, m, C)."""
    A = np.asarray(A, dtype=np.uint8)
    m, k = A.shape
    B, kd, C = np.shape(data)
    assert kd == k, (np.shape(data), k)
    if B == 0 or m == 0:
        return jnp.zeros((B, m, C), jnp.uint8)
    if strategy is None or (strategy == SELECT32 and int(A.max()) > 1):
        strategy = default_strategy(A)
    kind = {TABLE: "u8", SELECT32: "u32"}.get(strategy, "apow")
    with span(spans.KERNEL_STAGE, bytes=spans.host_bytes(A, data)):
        data = _as_u8(data)
        if strategy != TABLE:
            data, C = _pad4(data)
        mat = _mat_dev(kind, A.shape, A.tobytes())
    with span(spans.KERNEL_CALL):
        if strategy == TABLE:
            return _matmul_table(mat, data, m=m, k=k)
        fn = _matmul_select32 if strategy == SELECT32 else _matmul_bitplane32
        out = fn(mat, data, m=m, k=k)
        return out if out.shape[-1] == C else out[:, :, :C]


# ---------------------------------------------------------------------------
# single-stripe 2D matmul: (m, k) x (k, C) -> (m, C)
# Dedicated jits: the batched entry at B=1 pays an eager expand/squeeze
# per call, which dominates at paper chunk sizes on the CPU dispatcher.
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("m", "k"))
def _matmul2d_bitplane32(apow, data, *, m, k):
    C = data.shape[-1]
    d = _pack32(data)                                     # (k, C/4)
    acc = jnp.zeros((m, C // 4), jnp.uint32)
    for j in range(k):
        dj = d[j]
        for b in range(8):
            bit = (dj >> b) & _LANES                      # (C/4,)
            acc = acc ^ bit[None, :] * apow[:, j, b, None]
    return _unpack32(acc, C)


@functools.partial(jax.jit, static_argnames=("m", "k"))
def _matmul2d_select32(a01, data, *, m, k):
    C = data.shape[-1]
    d = _pack32(data)
    acc = jnp.zeros((m, C // 4), jnp.uint32)
    for j in range(k):
        acc = acc ^ a01[:, j, None] * d[j][None, :]
    return _unpack32(acc, C)


@functools.partial(jax.jit, static_argnames=("m", "k"))
def _matmul2d_table(A, data, *, m, k):
    exp, log, _ = gf256._device_tables()
    la = log[A.astype(jnp.int32)]                         # (m, k)
    C = data.shape[-1]
    acc = jnp.zeros((m, C), jnp.uint8)
    for j in range(k):
        dj = data[j]                                      # (C,)
        prod = exp[(la[:, j][:, None]
                    + log[dj.astype(jnp.int32)][None, :]) % 255]
        prod = jnp.where((A[:, j] == 0)[:, None]
                         | (dj == 0)[None, :], jnp.uint8(0), prod)
        acc = acc ^ prod
    return acc


def matmul(A: np.ndarray, data, *, strategy: str | None = None):
    """XLA twin of ``gf256_matmul``: (m, k) x (k, C) -> (m, C)."""
    A = np.asarray(A, dtype=np.uint8)
    m, k = A.shape
    data = _as_u8(data)
    C = data.shape[-1]
    if m == 0:
        return jnp.zeros((m, C), jnp.uint8)
    if strategy is None or (strategy == SELECT32 and int(A.max()) > 1):
        strategy = default_strategy(A)
    if strategy == TABLE:
        return _matmul2d_table(_mat_dev("u8", A.shape, A.tobytes()), data,
                               m=m, k=k)
    data, C = _pad4(data)
    if strategy == SELECT32:
        out = _matmul2d_select32(_mat_dev("u32", A.shape, A.tobytes()),
                                 data, m=m, k=k)
    else:
        out = _matmul2d_bitplane32(_mat_dev("apow", A.shape, A.tobytes()),
                                   data, m=m, k=k)
    return out if out.shape[-1] == C else out[:, :C]


# ---------------------------------------------------------------------------
# per-item-matrix batched matmul: (B, O, J) x (B, J, C) -> (B, O, C)
# (r > 1 delta matrices, fused parity folds)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("o", "j", "fold"))
def _per_item_bitplane32(Ms, data, parity, *, o, j, fold):
    B, _, C = data.shape
    d = _pack32(data)
    apow = _xtime_powers(Ms.astype(jnp.uint32))           # (B, O, J, 8)
    acc = jnp.zeros((B, o, C // 4), jnp.uint32)
    for jj in range(j):
        dj = d[:, jj]
        for b in range(8):
            bit = (dj >> b) & _LANES
            acc = acc ^ bit[:, None, :] * apow[:, :, jj, b, None]
    out = _unpack32(acc, C)
    return parity ^ out if fold else out


@functools.partial(jax.jit, static_argnames=("o", "j", "fold"))
def _per_item_select32(Ms, data, parity, *, o, j, fold):
    B, _, C = data.shape
    d = _pack32(data)
    m01 = Ms.astype(jnp.uint32)
    acc = jnp.zeros((B, o, C // 4), jnp.uint32)
    for jj in range(j):
        acc = acc ^ m01[:, :, jj, None] * d[:, jj][:, None, :]
    out = _unpack32(acc, C)
    return parity ^ out if fold else out


@functools.partial(jax.jit, static_argnames=("o", "j", "fold"))
def _per_item_table(Ms, data, parity, *, o, j, fold):
    exp, log, _ = gf256._device_tables()
    B, _, C = data.shape
    lm = log[Ms.astype(jnp.int32)]                        # (B, O, J)
    acc = jnp.zeros((B, o, C), jnp.uint8)
    for jj in range(j):
        dj = data[:, jj]
        prod = exp[(lm[:, :, jj, None]
                    + log[dj.astype(jnp.int32)][:, None, :]) % 255]
        prod = jnp.where((Ms[:, :, jj, None] == 0)
                         | (dj == 0)[:, None, :], jnp.uint8(0), prod)
        acc = acc ^ prod
    return parity ^ acc if fold else acc


def matmul_per_item(Ms, blocks, parity=None, *, strategy: str | None = None):
    """Per-item matrices: (B, O, J) ∘ (B, J, C) -> (B, O, C).

    ``parity`` (B, O, C), when given, is XORed in inside the same jit —
    the fused delta-apply / seal-fold path (no separate device round
    trip for the fold)."""
    Ms = np.asarray(Ms, dtype=np.uint8) if isinstance(Ms, np.ndarray) \
        else jnp.asarray(Ms, dtype=jnp.uint8)
    B, O, J = Ms.shape
    C = np.shape(blocks)[2]
    if B == 0 or O == 0:
        return jnp.zeros((B, O, C), jnp.uint8)
    if strategy is None or (strategy == SELECT32
                            and int(np.asarray(Ms).max()) > 1):
        strategy = default_strategy(np.asarray(Ms))
    fold = parity is not None
    with span(spans.KERNEL_STAGE,
              bytes=spans.host_bytes(Ms, blocks, parity)):
        blocks = _as_u8(blocks)
        par = (_as_u8(parity) if fold
               else jnp.zeros((), jnp.uint8))
        if strategy != TABLE:
            blocks, C = _pad4(blocks)
            if fold:
                par, _ = _pad4(par)
        Ms = jnp.asarray(Ms)
    with span(spans.KERNEL_CALL):
        if strategy == TABLE:
            return _per_item_table(Ms, blocks, par, o=O, j=J, fold=fold)
        fn = (_per_item_select32 if strategy == SELECT32
              else _per_item_bitplane32)
        out = fn(Ms, blocks, par, o=O, j=J, fold=fold)
        return out[:, :, :C]


# ---------------------------------------------------------------------------
# per-item-gamma delta: gammas (B, m), xor (B, C) -> (B, m, C)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("m", "fold"))
def _delta_bitplane32(gammas, xor, parity, *, m, fold):
    B, C = xor.shape
    x = _pack32(xor)                                      # (B, C/4)
    gpow = _xtime_powers(gammas.astype(jnp.uint32))       # (B, m, 8)
    acc = jnp.zeros((B, m, C // 4), jnp.uint32)
    for b in range(8):
        bit = (x >> b) & _LANES
        acc = acc ^ bit[:, None, :] * gpow[:, :, b, None]
    out = _unpack32(acc, C)
    return parity ^ out if fold else out


@functools.partial(jax.jit, static_argnames=("m",))
def _delta2d_bitplane32(gammas, old, new, parity, *, m):
    x = _pack32(old ^ new)                                # (C/4,)
    gpow = _xtime_powers(gammas.astype(jnp.uint32))       # (m, 8)
    C = old.shape[-1]
    acc = jnp.zeros((m, C // 4), jnp.uint32)
    for b in range(8):
        bit = (x >> b) & _LANES
        acc = acc ^ bit[None, :] * gpow[:, b, None]
    return parity ^ _unpack32(acc, C)


def delta_single(parity, gammas, old, new):
    """Single-row fused P' = P ^ gamma (old ^ new): the XOR and the fold
    both happen inside one jit (no eager expand/squeeze at B=1)."""
    parity = _as_u8(parity)
    m = parity.shape[0]
    C = parity.shape[-1]
    if m == 0:
        return parity
    old, _ = _pad4(_as_u8(old))
    new, _ = _pad4(_as_u8(new))
    par, _ = _pad4(parity)
    out = _delta2d_bitplane32(jnp.asarray(gammas, dtype=jnp.uint32),
                              old, new, par, m=m)
    return out if out.shape[-1] == C else out[:, :C]


def delta_batched(gammas, xors, parity=None):
    """XLA twin of ``delta_apply_batched``: per-item gamma rows, 8 packed
    bit-plane steps; ``parity`` folds in-jit when given."""
    B, m = np.shape(gammas)
    C = np.shape(xors)[1]
    if B == 0 or m == 0:
        return jnp.zeros((B, m, C), jnp.uint8)
    fold = parity is not None
    with span(spans.KERNEL_STAGE,
              bytes=spans.host_bytes(parity, gammas, xors)):
        gammas = jnp.asarray(gammas, dtype=jnp.uint32)
        xors, C = _pad4(_as_u8(xors))
        par = jnp.zeros((), jnp.uint8)
        if fold:
            par, _ = _pad4(_as_u8(parity))
    with span(spans.KERNEL_CALL):
        out = _delta_bitplane32(gammas, xors, par, m=m, fold=fold)
        return out[:, :, :C]
