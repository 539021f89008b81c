"""EC in-memory state store: the paper's architecture over the mesh.

MemEC's roles map onto the training mesh's **data axis** (A devices per
model column).  Stripe lists (paper §4.3) become *rotationally symmetric*:

    list l (l = 0..A-1):  data members  (l, l+1, ..., l+k-1) mod A
                          parity row r on device (l+k+r) mod A

On a homogeneous TPU ring the rotation achieves exactly the write-load
balance the paper's greedy generator optimizes for (every device: data
role in k lists, parity role in m lists -> identical load), and it turns
the paper's point-to-point delta unicast into *uniform* `ppermute`
collectives — the TPU-native form of "data server ships gamma*delta to
each parity server" (§2, §4.2).

Layout per device (inside shard_map, fully manual over the mesh):
    local state bytes -> pages (P, page_size) uint8,
    page p: class j = p mod k, stripe s = p div k, list (d - j) mod A;
    parity buffer (m, P//k, page): row r protects list (d - k - r) mod A.

Per train step the optimizer delta (old XOR new) feeds
``parity_delta_update`` — the paper's  P' = P ⊕ gamma (D ⊕ D')  —
with m*k gamma-scaled ppermutes.  Reconstruction of a failed device's
pages is decode-from-k with masked contributions + an XOR-reduce ring
(paper §5.4 degraded GET, at page granularity).

Storage overhead: m/k (25 % for RS(10,8)) vs 100 %+ for replication —
the all-encoding win at fleet scale, since index state (the pytree
structure) is derivable and needs no redundancy (paper §3.2).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.core import gf256
from repro.core.codes import RSCode

from .collectives import gf_scale_static, ring_shift, ring_xor_reduce


@dataclasses.dataclass(frozen=True)
class ECConfig:
    k: int = 8
    m: int = 2
    page_size: int = 4096
    axis: str = "data"

    @property
    def n(self) -> int:
        return self.k + self.m

    @property
    def code(self) -> RSCode:
        return RSCode(n=self.n, k=self.k)

    @property
    def gamma(self) -> np.ndarray:
        return self.code.parity_matrix  # (m, k)


# ---------------------------------------------------------------------------
# page packing (local, inside shard_map)
# ---------------------------------------------------------------------------

def bytes_of_tree(tree) -> jax.Array:
    """Flatten a pytree's local shards into one uint8 vector."""
    leaves = jax.tree.leaves(tree)
    parts = [jax.lax.bitcast_convert_type(
        x.reshape(-1, 1) if x.dtype == jnp.uint8 else x.reshape(-1),
        jnp.uint8).reshape(-1) for x in leaves]
    return jnp.concatenate(parts) if parts else jnp.zeros((0,), jnp.uint8)


def to_pages(flat: jax.Array, cfg: ECConfig) -> jax.Array:
    unit = cfg.k * cfg.page_size
    n = flat.shape[0]
    pad = (-n) % unit
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, cfg.page_size)  # (P, page)


def tree_xor_pages(old_tree, new_tree, cfg: ECConfig) -> jax.Array:
    """(old ⊕ new) as pages — the data delta of the paper's UPDATE."""
    return to_pages(bytes_of_tree(old_tree) ^ bytes_of_tree(new_tree), cfg)


# ---------------------------------------------------------------------------
# core EC ops (inside shard_map; collectives over cfg.axis)
# ---------------------------------------------------------------------------

def parity_delta_update(xor_pages: jax.Array, parity: jax.Array,
                        cfg: ECConfig) -> jax.Array:
    """P' = P ⊕ gamma·(D ⊕ D') routed to rotated parity owners.

    xor_pages: (P, page) local delta; parity: (m, P//k, page) local parity
    buffer.  m*k gamma-scaled ppermutes (shift = (k + r - j) mod A).
    """
    A = jax.lax.axis_size(cfg.axis)
    Pn, page = xor_pages.shape
    S = Pn // cfg.k
    cls = xor_pages.reshape(S, cfg.k, page)
    gamma = cfg.gamma
    rows = []
    for r in range(cfg.m):
        acc = jnp.zeros((S, page), jnp.uint8)
        for j in range(cfg.k):
            contrib = gf_scale_static(int(gamma[r, j]), cls[:, j])
            shift = (cfg.k + r - j) % A
            acc = acc ^ ring_shift(contrib, cfg.axis, shift)
        rows.append(parity[r] ^ acc)
    return jnp.stack(rows)


def parity_delta_update_chain(xor_pages: jax.Array, parity: jax.Array,
                              cfg: ECConfig) -> jax.Array:
    """Systolic variant of `parity_delta_update` (§Perf hillclimb).

    The baseline ships each gamma-scaled contribution directly with a
    shift-(k+r-j) ppermute: on a torus that occupies (k+r-j) links, so the
    per-link traffic is sum_{r,j} (k+r-j) * S pages (= 80*S for RS(10,8)).
    Here partial parities accumulate along a shift-1 ring: at step t every
    device XORs gamma[r,t] * (its class-t delta) into the m bundles passing
    through it, then forwards one hop.  After k steps the row-0 bundle sits
    on its owner; row r forwards r more hops.  Per-link traffic:
    (k + r) hops * m bundles * S pages ≈ 18*S — a 4.4x reduction for
    RS(10,8), at the cost of serializing k+m-1 neighbor hops.
    """
    Pn, page = xor_pages.shape
    S = Pn // cfg.k
    cls = xor_pages.reshape(S, cfg.k, page)
    gamma = cfg.gamma
    bundles = [jnp.zeros((S, page), jnp.uint8) for _ in range(cfg.m)]
    for t in range(cfg.k):
        for r in range(cfg.m):
            bundles[r] = bundles[r] ^ gf_scale_static(int(gamma[r, t]),
                                                      cls[:, t])
        bundles = [ring_shift(b, cfg.axis, 1) for b in bundles]
    # row r travels r extra hops to its owner (l + k + r)
    rows = []
    for r in range(cfg.m):
        b = bundles[r]
        for _ in range(r):
            b = ring_shift(b, cfg.axis, 1)
        rows.append(parity[r] ^ b)
    return jnp.stack(rows)


def encode_parity(pages: jax.Array, cfg: ECConfig) -> jax.Array:
    """Full encode = delta update from an all-zero state."""
    Pn = pages.shape[0]
    parity0 = jnp.zeros((cfg.m, Pn // cfg.k, cfg.page_size), jnp.uint8)
    return parity_delta_update(pages, parity0, cfg)


@functools.lru_cache(maxsize=None)
def _decode_coeffs(k: int, m: int, failed_class: int) -> tuple:
    """Coefficients reconstructing data chunk `failed_class` from the
    surviving k-1 data chunks + parity row 0 (single-device loss)."""
    code = RSCode(n=k + m, k=k)
    avail = [i for i in range(k) if i != failed_class] + [k]
    inv, idx = code.decode_matrix(avail)
    # data = inv @ chunks[idx]; we want row `failed_class`
    coeffs = {pos: int(inv[failed_class, i]) for i, pos in enumerate(idx)}
    return tuple(sorted(coeffs.items()))


@functools.lru_cache(maxsize=None)
def _decode_coeffs_pair(k: int, m: int, want: int, other: int,
                        rows: tuple) -> tuple:
    """Coefficients for data position `want` when data positions
    {want, other} are erased (other = -1 if the second failure holds no
    data chunk in this stripe) using parity rows `rows`."""
    code = RSCode(n=k + m, k=k)
    missing = {want} | ({other} if other >= 0 else set())
    avail = [i for i in range(k) if i not in missing] + \
        [k + r for r in rows]
    inv, idx = code.decode_matrix(avail)
    coeffs = {pos: int(inv[want, i]) for i, pos in enumerate(idx)}
    return tuple(sorted((p, c) for p, c in coeffs.items() if c != 0))


def reconstruct_failed(pages: jax.Array, parity: jax.Array, failed: jax.Array,
                       cfg: ECConfig) -> jax.Array:
    """Rebuild the pages of device `failed` (traced int32 axis index).

    Every device contributes its coefficient-scaled chunk for each stripe
    class, masked to the survivors the decode uses; an XOR ring reduces
    them so the result lands everywhere (the caller slices/uses it on the
    replacement device).  This is degraded GET at page granularity (§5.4).
    """
    A = jax.lax.axis_size(cfg.axis)
    d = jax.lax.axis_index(cfg.axis)
    Pn, page = pages.shape
    S = Pn // cfg.k
    cls = pages.reshape(S, cfg.k, page)
    out = []
    for j in range(cfg.k):
        # list of the failed page-class: l = failed - j
        # this device's data position in that list:
        my_pos = (d - (failed - j)) % A
        coeffs = dict(_decode_coeffs(cfg.k, cfg.m, j))
        contrib = jnp.zeros((S, page), jnp.uint8)
        for pos, coeff in coeffs.items():
            if pos < cfg.k:
                # survivor data member `pos` contributes its class-`pos`
                # pages (its page in list l is its class-(my_pos) slot)
                sel = (my_pos == pos)
                scaled = gf_scale_static(coeff, cls[:, pos])
            else:
                # parity row 0 of list l lives on device l + k
                sel = (my_pos == cfg.k)
                scaled = gf_scale_static(coeff, parity[0])
            contrib = jnp.where(sel, contrib ^ scaled, contrib)
        out.append(ring_xor_reduce(contrib, cfg.axis))
    # out[j]: (S, page) = failed device's class-j pages
    return jnp.stack(out, axis=1).reshape(Pn, page)


def reconstruct_failed_pair(pages: jax.Array, parity: jax.Array,
                            f1: int, f2: int, axis_size: int,
                            cfg: ECConfig) -> jax.Array:
    """Rebuild device f1's pages when devices {f1, f2} are BOTH lost
    (m >= 2 tolerance — the paper's RS(10,8) double failure at fleet
    level).  f1/f2/axis_size are static ints (recovery is a concrete
    coordinator event).  Call twice (swapping f1/f2) to rebuild both.

    Positions are relative to list l = f1 - j: f1 sits at data position
    j, f2 at pos2 = (f2 - f1 + j) mod A (a data member iff pos2 < k),
    parity row r's owner at (k + r) mod A.  Surviving contributions are
    coefficient-scaled, masked, and XOR-ring-reduced (decode-from-k, as
    in the single-failure path).
    """
    A = axis_size
    d = jax.lax.axis_index(cfg.axis)
    Pn, page = pages.shape
    S = Pn // cfg.k
    cls = pages.reshape(S, cfg.k, page)
    out = []
    for j in range(cfg.k):
        pos2 = (f2 - f1 + j) % A
        data_missing = [j] + ([pos2] if pos2 < cfg.k else [])
        failed_pos = {j, pos2}
        rows_avail = [r for r in range(cfg.m)
                      if (cfg.k + r) % A not in failed_pos]
        if len(rows_avail) < len(data_missing):
            raise ValueError(
                f"class {j}: not enough surviving parity rows "
                f"(RS({cfg.n},{cfg.k}) over axis {A}) — stripe "
                "undecodable for this failure pair")
        rows = tuple(rows_avail[: len(data_missing)])
        other = pos2 if pos2 < cfg.k else -1
        coeffs = dict(_decode_coeffs_pair(cfg.k, cfg.m, j, other, rows))
        my_pos = (d - (f1 - j)) % A
        contrib = jnp.zeros((S, page), jnp.uint8)
        for pos, coeff in coeffs.items():
            if pos < cfg.k:
                sel = (my_pos == pos)
                scaled = gf_scale_static(coeff, cls[:, pos])
            else:
                r = pos - cfg.k
                sel = (my_pos == (cfg.k + r) % A)
                scaled = gf_scale_static(coeff, parity[r])
            contrib = jnp.where(sel, contrib ^ scaled, contrib)
        out.append(ring_xor_reduce(contrib, cfg.axis))
    return jnp.stack(out, axis=1).reshape(Pn, page)


# ---------------------------------------------------------------------------
# pytree-level wrappers (build the shard_map around the ops)
# ---------------------------------------------------------------------------

def _flat_specs(tree_specs):
    return tree_specs


class ECStateStore:
    """Erasure-coded in-memory protection of a sharded state pytree.

    Wraps the shard_map plumbing: callers pass auto-sharded pytrees (the
    same ones jit'd train steps use); parity lives as a (A_data, ...)
    device-sharded buffer.
    """

    def __init__(self, mesh: Mesh, state_specs, cfg: ECConfig | None = None):
        self.mesh = mesh
        self.cfg = cfg or ECConfig()
        self.state_specs = state_specs
        axes = mesh.axis_names
        self.extra_axes = [a for a in axes if a != self.cfg.axis]

    def _parity_out_spec(self):
        # parity: (A_data, m, S, page) sharded on the data axis; identical
        # across model/pod columns? No — state differs per model column, so
        # parity carries the model axis too: (A_data, A_model, m, S, page).
        return P(self.cfg.axis, *self.extra_axes)

    def _wrap(self, fn, in_specs, out_specs):
        return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)

    def local_pages(self, state) -> jax.Array:
        """(A_data, A_other..., P, page) global view of state pages."""
        cfg = self.cfg

        def f(st):
            pages = to_pages(bytes_of_tree(st), cfg)
            shape = (1,) * len(self.mesh.axis_names) + pages.shape
            return pages.reshape(shape)

        out_spec = P(*self.mesh.axis_names, None, None)
        return self._wrap(f, (self.state_specs,), out_spec)(state)

    def encode(self, state) -> jax.Array:
        cfg = self.cfg

        def f(st):
            pages = to_pages(bytes_of_tree(st), cfg)
            par = encode_parity(pages, cfg)
            return par.reshape((1,) * len(self.mesh.axis_names) + par.shape)

        out_spec = P(*self.mesh.axis_names, None, None, None)
        return jax.jit(self._wrap(f, (self.state_specs,), out_spec))(state)

    def delta_update(self, old_state, new_state, parity) -> jax.Array:
        cfg = self.cfg
        axes = self.mesh.axis_names

        def f(old, new, par):
            xor = tree_xor_pages(old, new, cfg)
            par = par.reshape(par.shape[len(axes):])
            out = parity_delta_update(xor, par, cfg)
            return out.reshape((1,) * len(axes) + out.shape)

        spec = P(*axes, None, None, None)
        return jax.jit(self._wrap(
            f, (self.state_specs, self.state_specs, spec), spec))(
                old_state, new_state, parity)

    def reconstruct(self, state, parity, failed_index: int) -> jax.Array:
        """Pages of the failed data-axis position (replicated result)."""
        cfg = self.cfg
        axes = self.mesh.axis_names

        def f(st, par):
            pages = to_pages(bytes_of_tree(st), cfg)
            par = par.reshape(par.shape[len(axes):])
            rec = reconstruct_failed(pages, par,
                                     jnp.int32(failed_index), cfg)
            return rec.reshape((1,) * len(axes) + rec.shape)

        pspec = P(*axes, None, None, None)
        out_spec = P(*axes, None, None)
        return jax.jit(self._wrap(f, (self.state_specs, pspec), out_spec))(
            state, parity)
