"""Pallas TPU kernel: fused delta parity update  P' = P ⊕ gamma·(D ⊕ D').

This is the paper's UPDATE hot path (§2/§4.2) and the inner loop of the
EC-checkpoint maintenance in training: every step the optimizer's byte
delta is folded into the m parity rows.  Fusing XOR + GF-scale + XOR into
one kernel reads old/new/parity once from HBM and writes parity once —
3 reads + 1 write per byte, the bandwidth floor for this op.

gamma powers (gamma * 2^b) are computed *in-kernel* from the scalar gamma
via 8 xtime steps (shift + conditional reduction by the field polynomial
0x11D), so the kernel accepts traced per-row coefficients — no host table
needed, which matters when the stripe position (and hence gamma) is picked
dynamically by the stripe mapper.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import spans
from repro.core.spans import span
from repro.kernels import dispatch
from repro.kernels.gf256_matmul import (_pad_last, _round_up,
                                        gf256_matmul_per_item_batched)

DEFAULT_BLOCK_C = 2048


def _delta_kernel(g_ref, p_ref, old_ref, new_ref, o_ref, *, m: int):
    x = (old_ref[...] ^ new_ref[...]).astype(jnp.int32)       # (BC,)
    outs = []
    for r in range(m):
        g = g_ref[r].astype(jnp.int32)                        # scalar gamma
        acc = jnp.zeros_like(x)
        for b in range(8):
            acc = acc ^ (((x >> b) & 1) * g)
            # xtime: g <- g*2 in GF(2^8) / 0x11D
            g = ((g << 1) ^ jnp.where((g & 0x80) != 0, 0x11D, 0)) & 0xFF
        outs.append(p_ref[r] ^ acc.astype(jnp.uint8))
    o_ref[...] = jnp.stack(outs)


@functools.partial(jax.jit, static_argnames=("m", "block_c", "interpret"))
def _delta_call(gammas, parity, old, new, *, m, block_c, interpret):
    C = parity.shape[1]
    grid = (C // block_c,)
    return pl.pallas_call(
        functools.partial(_delta_kernel, m=m),
        grid=grid,
        in_specs=[
            pl.BlockSpec((m,), lambda c: (0,)),
            pl.BlockSpec((m, block_c), lambda c: (0, c)),
            pl.BlockSpec((block_c,), lambda c: (c,)),
            pl.BlockSpec((block_c,), lambda c: (c,)),
        ],
        out_specs=pl.BlockSpec((m, block_c), lambda c: (0, c)),
        out_shape=jax.ShapeDtypeStruct((m, C), jnp.uint8),
        interpret=interpret,
    )(gammas, parity, old, new)


def _scaled_rows(g_ref, x, m: int):
    """rows[r] = gamma_r * x over GF(2^8) via in-kernel xtime powers."""
    rows = []
    for r in range(m):
        g = g_ref[0, 0, r].astype(jnp.int32)
        acc = jnp.zeros_like(x)
        for b in range(8):
            acc = acc ^ (((x >> b) & 1) * g)
            g = ((g << 1) ^ jnp.where((g & 0x80) != 0, 0x11D, 0)) & 0xFF
        rows.append(acc.astype(jnp.uint8))
    return rows


def _delta_apply_batched_kernel(g_ref, p_ref, x_ref, o_ref, *, m: int):
    x = x_ref[0, 0].astype(jnp.int32)                     # (BC,)
    rows = _scaled_rows(g_ref, x, m)
    o_ref[0] = jnp.stack([p_ref[0, r] ^ rows[r] for r in range(m)])


def _delta_only_batched_kernel(g_ref, x_ref, o_ref, *, m: int):
    x = x_ref[0, 0].astype(jnp.int32)                     # (BC,)
    o_ref[0] = jnp.stack(_scaled_rows(g_ref, x, m))


def _batched_layout(gammas, xor, block_c: int):
    """Inside the jit: int32 gammas laid out (B, 1, m) and uint8 xor
    padded to whole ``block_c`` tiles, laid out (B, 1, Cp) — every
    block's last two dims are then (1, m) / (1, block_c) over full
    (1, m) / (1, Cp) extents, since the TPU lowering refuses a (1, m)
    block over a (B, m) array once B > 1.  Returns them with the
    effective ``block_c`` and ``Cp``."""
    B, m = gammas.shape
    C = xor.shape[1]
    block_c = min(block_c, _round_up(C, 128))
    Cp = _round_up(C, block_c)
    xor = _pad_last(xor.astype(jnp.uint8), Cp)
    return (gammas.astype(jnp.int32).reshape(B, 1, m),
            xor.reshape(B, 1, Cp), block_c, Cp)


# Each front-door call is ONE jitted program: the casts, pads and
# layouts above, the kernel and the trailing slice all run inside it, so
# host numpy operands reach the device through the jit's own argument
# transfer with no eager device op before it.
@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def _delta_apply_batched_call(parity, gammas, xor, *, block_c, interpret):
    B, m = gammas.shape
    C = xor.shape[1]
    gammas, xor, block_c, Cp = _batched_layout(gammas, xor, block_c)
    parity = _pad_last(parity.astype(jnp.uint8), Cp)
    out = pl.pallas_call(
        functools.partial(_delta_apply_batched_kernel, m=m),
        grid=(B, Cp // block_c),
        in_specs=[
            pl.BlockSpec((1, 1, m), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((1, m, block_c), lambda b, c: (b, 0, c)),
            pl.BlockSpec((1, 1, block_c), lambda b, c: (b, 0, c)),
        ],
        out_specs=pl.BlockSpec((1, m, block_c), lambda b, c: (b, 0, c)),
        out_shape=jax.ShapeDtypeStruct((B, m, Cp), jnp.uint8),
        interpret=interpret,
    )(gammas, parity, xor)
    return out[:, :, :C]


@functools.partial(jax.jit, static_argnames=("block_c", "interpret"))
def _delta_only_batched_call(gammas, xor, *, block_c, interpret):
    B, m = gammas.shape
    C = xor.shape[1]
    gammas, xor, block_c, Cp = _batched_layout(gammas, xor, block_c)
    out = pl.pallas_call(
        functools.partial(_delta_only_batched_kernel, m=m),
        grid=(B, Cp // block_c),
        in_specs=[
            pl.BlockSpec((1, 1, m), lambda b, c: (b, 0, 0)),
            pl.BlockSpec((1, 1, block_c), lambda b, c: (b, 0, c)),
        ],
        out_specs=pl.BlockSpec((1, m, block_c), lambda b, c: (b, 0, c)),
        out_shape=jax.ShapeDtypeStruct((B, m, Cp), jnp.uint8),
        interpret=interpret,
    )(gammas, xor)
    return out[:, :, :C]


def delta_apply_batched(parity: jax.Array | None, gammas: jax.Array,
                        xor: jax.Array, *, block_c: int = DEFAULT_BLOCK_C,
                        interpret: bool | None = None) -> jax.Array:
    """Batched fused delta fold with per-item coefficients.

    parity: (B, m, C); gammas: (B, m) — each batch item may update a
    different stripe position, hence per-item gamma rows; xor: (B, C) is
    D ⊕ D' per item.  Returns (B, m, C) updated parity.  This is the
    batched analogue of `delta_update` (grid = batch x C-tiles).

    ``parity=None`` returns the bare deltas gamma_r·xor — same kernel
    minus the parity read/write streams, for callers that fold the delta
    into host-side buffers themselves.

    On the Pallas path the operands go unchanged to one jitted program
    (host numpy arrays, device arrays or tracers alike), which casts,
    pads and lays them out itself.
    """
    dec = dispatch.decide(interpret)
    if dec.path == dispatch.XLA:
        from repro.kernels import xla_gf256
        return xla_gf256.delta_batched(gammas, xor, parity)
    with span(spans.KERNEL_STAGE,
              bytes=spans.host_bytes(parity, gammas, xor)):
        B, m = gammas.shape
        C = xor.shape[1]
        if B == 0 or m == 0:
            return jnp.zeros((B, m, C), jnp.uint8)
    with span(spans.KERNEL_CALL):
        if parity is None:
            return _delta_only_batched_call(gammas, xor, block_c=block_c,
                                            interpret=dec.interpret)
        return _delta_apply_batched_call(parity, gammas, xor,
                                         block_c=block_c,
                                         interpret=dec.interpret)


def delta_apply_per_item_batched(parity: jax.Array | None, Ms, blocks, *,
                                 block_c: int | None = None,
                                 strategy: str | None = None,
                                 interpret: bool | None = None) -> jax.Array:
    """Per-item-matrix delta fold — the r > 1 (RDP) update shape.

    ``Ms`` (B, O, J): one sub-block system per item (O = m*r rows,
    J = r columns for a single-chunk mutation); ``blocks`` (B, J, Cb)
    the xor sub-blocks; ``parity`` (B, O, Cb), when given, is folded in
    the same kernel.  This is the dispatch-routed, tune-aware front door
    for ``gf256_matmul_per_item_batched`` — the engines' r > 1 delta
    path goes through here so RDP updates hit the compiled per-item
    grid (Pallas on TPU/GPU, the ``xla_gf256`` twin on CPU) instead of
    the jnp per-item matmul, and the ``(op=delta_per_item, ...)`` tuning
    entries steer strategy × block_c when the caller doesn't.
    """
    from repro.kernels import tune
    import numpy as np
    Ms = np.asarray(Ms, dtype=np.uint8)
    B, O, J = Ms.shape
    C = blocks.shape[2]
    if strategy is None and block_c is None and B and O:
        dec = dispatch.decide(interpret)
        tuned = tune.lookup("delta_per_item", dec.path, k=J, m=O, chunk=C,
                            batch=B, cls=tune.matrix_cls(Ms))
        if tuned is not None:
            strategy = tuned.get("strategy")
            block_c = tuned.get("block_c") or None
    return gf256_matmul_per_item_batched(Ms, blocks, parity,
                                         block_c=block_c, strategy=strategy,
                                         interpret=interpret)


def delta_update(parity: jax.Array, gammas: jax.Array, old: jax.Array,
                 new: jax.Array, *, block_c: int = DEFAULT_BLOCK_C,
                 interpret: bool | None = None) -> jax.Array:
    """parity (m,C), gammas (m,), old/new (C,) -> new parity (m,C)."""
    dec = dispatch.decide(interpret)
    if dec.path == dispatch.XLA:
        from repro.kernels import xla_gf256
        return xla_gf256.delta_single(parity, gammas, old, new)
    interpret = dec.interpret
    parity = jnp.asarray(parity, dtype=jnp.uint8)
    old = jnp.asarray(old, dtype=jnp.uint8)
    new = jnp.asarray(new, dtype=jnp.uint8)
    gammas = jnp.asarray(gammas, dtype=jnp.int32)
    m, C = parity.shape
    block_c = min(block_c, _round_up(C, 128))
    Cp = _round_up(C, block_c)
    if Cp != C:
        parity = jnp.pad(parity, ((0, 0), (0, Cp - C)))
        old = jnp.pad(old, (0, Cp - C))
        new = jnp.pad(new, (0, Cp - C))
    out = _delta_call(gammas, parity, old, new, m=m, block_c=block_c,
                      interpret=interpret)
    return out[:, :C]

