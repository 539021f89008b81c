"""Collective building blocks: GF(2^8) scaling, XOR rings, compressed psum.

These run inside `shard_map` bodies.  GF(2^8) scaling by a *static*
coefficient uses the same bit-plane identity as the Pallas kernels
(gamma*x = XOR_b bit_b(x) * (gamma*2^b)) so it is pure shift/and/mul/xor —
VPU-friendly and fusible with the surrounding XORs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gf256


@functools.lru_cache(maxsize=None)
def _gamma_pows(gamma: int) -> tuple:
    return tuple(int(gf256.MUL_TABLE[gamma, 1 << b]) for b in range(8))


def gf_scale_static(gamma: int, x: jax.Array) -> jax.Array:
    """gamma * x over GF(2^8) for a static gamma; x uint8."""
    if gamma == 0:
        return jnp.zeros_like(x)
    if gamma == 1:
        return x
    xi = x.astype(jnp.int32)
    acc = jnp.zeros_like(xi)
    for b, g in enumerate(_gamma_pows(gamma)):
        acc = acc ^ (((xi >> b) & 1) * g)
    return acc.astype(jnp.uint8)


def ring_shift(x: jax.Array, axis_name: str, shift: int) -> jax.Array:
    """Send x to (rank + shift) mod A; receive from (rank - shift)."""
    A = jax.lax.axis_size(axis_name)
    perm = [(i, (i + shift) % A) for i in range(A)]
    return jax.lax.ppermute(x, axis_name, perm)


def ring_xor_reduce(x: jax.Array, axis_name: str) -> jax.Array:
    """XOR-reduce across the axis; result replicated on every member.

    (A-1) ppermute steps; used on the rare recovery path, where the
    masked-contribution + reduce pattern mirrors the paper's decode-from-k.
    """
    A = jax.lax.axis_size(axis_name)
    acc = x
    buf = x

    def body(i, carry):
        acc, buf = carry
        buf = ring_shift(buf, axis_name, 1)
        return acc ^ buf, buf

    acc, _ = jax.lax.fori_loop(0, A - 1, body, (acc, buf))
    return acc


def compressed_psum(x: jax.Array, axis_name: str, *, block: int = 256
                    ) -> jax.Array:
    """int8-quantized sum across an axis (cross-pod gradient compression).

    Per-block absmax scaling; only the int8 payload (+tiny fp32 scales)
    crosses the slow cross-pod links (4x less traffic than fp32 psum).
    Each member's payload keeps its own scale, so the weighted sum is
    exact w.r.t. the quantized values.  The caller owns error feedback
    (see train_step's compression residual).
    """
    shape = x.shape
    flat = x.reshape(-1)
    n = flat.shape[0]
    pad = (-n) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, block)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    qg = jax.lax.all_gather(q, axis_name)              # (A, nb, block) int8
    sg = jax.lax.all_gather(scale, axis_name)          # (A, nb, 1) fp32
    out = jnp.sum(qg.astype(jnp.float32) * sg, axis=0)  # (nb, block)
    out = out.reshape(-1)[:n].reshape(shape)
    return out
