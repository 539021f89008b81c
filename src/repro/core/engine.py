"""Unified batched coding data plane: one pluggable engine, kernels → cluster.

Every layer of the reproduction used to drive coding through one-chunk-at-
a-time ``codes.Code`` calls while the Pallas kernels sat in benchmarks.
``CodingEngine`` is the single seam they now share:

    encode_batch((B, k, C))                 -> (B, m, C) parity
    decode_batch([avail...], [wanted...])   -> [{pos: chunk}, ...]
    delta_batch((B,), (B, C))               -> (B, m, C) parity deltas
    apply_delta_batch((B, m, C), ...)       -> (B, m, C) updated parity

Backends (all byte-identical, cross-validated in ``tests/test_engine.py``):

* ``NumpyEngine``  — wraps the ``codes.Code`` classes one item at a time;
  the reference oracle and the default for the CPU-only simulation.
* ``JaxEngine``    — pure-jnp batched path (``kernels/ref.py`` idiom).
* ``PallasEngine`` — batched Pallas grids over ``gf256_matmul`` /
  ``delta_update``; block-structured codes (RDP) route through the same
  ``gf256_matmul_batched`` entry natively (column-loop kernels, with
  0/1 matrices on a bit-plane-free XOR-select body).

The device backends share a *block-linear representation* of the code: any
systematic code here (RS, RDP, XOR, none) is GF(2^8)-linear over sub-block
rows — a chunk is ``r`` sub-blocks (r=1 for RS/XOR, r=p-1 for RDP) and
encode is one (m*r, k*r) matrix over GF(2^8), probed generically from the
numpy oracle with basis vectors.  Decode inverts k available chunk-row
groups of the systematic generator (host-side, cached per erasure
pattern); deltas are column slices of the encode matrix.

Selection: ``make_engine(name, code)``; ``name=None`` reads the
``MEMEC_ENGINE`` env var (``numpy`` | ``jax`` | ``pallas``), defaulting to
``numpy``.  ``configs/memec.py`` carries the same knob for the cluster.

Async submission (PR 4): ``submit_encode`` / ``submit_decode`` /
``submit_delta`` return lightweight ``EngineFuture`` handles so the
cluster can issue coding work while the same shard's netsim legs are
modeled in flight (``async_engine=True`` / ``$MEMEC_ASYNC``).  The numpy
backend resolves lazily (the work runs at ``result()``); the jax and
pallas backends *dispatch* on-device at submit time — XLA's async
dispatch does the real overlapping — queue each result's copy to the
host behind it at once, and call ``jax.block_until_ready`` only at
resolution.  Every future carries a deterministic ``work_bytes``
figure (GF(2^8) multiply-accumulate bytes) that ``CostModel.coding_s``
turns into modeled time; results are byte-identical to the blocking
calls by construction.

Plan/execute decode (PR 5): decode is split into a ``DecodePlan`` built
at submit time from host *metadata only* — erasure-pattern signatures,
the cached ``(k*r, k*r)`` inversions (a bounded LRU, ``inv_cache_size``
/ ``$MEMEC_INV_CACHE``), per-pattern group layout, and the output
scatter map — and an execute stage that issues ONE batched device
matmul per pattern group (plus one for re-encoded parity rows).  On the
device backends ``submit_decode`` therefore dispatches at submit like
encode/delta, instead of deferring the group-by to ``result()``; the
``device_dispatches`` counter is the probe the tests assert this with.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from collections import OrderedDict

import numpy as np

from . import gf256, spans
from .codes import Code, RDPCode
from .spans import span


# ---------------------------------------------------------------------------
# Block-linear representation (shared by the device backends)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BlockRep:
    """A code as one GF(2^8) matrix over sub-block rows.

    ``r`` sub-blocks per chunk; ``encode``: (m*r, k*r) uint8 with
    parity_blocks = encode ∘ data_blocks, where chunk (C,) reshapes to
    (r, C//r) sub-block rows.
    """
    r: int
    encode: np.ndarray  # (m*r, k*r) uint8, read-only

    @property
    def generator(self) -> np.ndarray:
        """(n*r, k*r) systematic generator [I ; encode]."""
        kr = self.encode.shape[1]
        return np.concatenate([np.eye(kr, dtype=np.uint8), self.encode])


@functools.lru_cache(maxsize=None)
def block_rep(code: Code) -> BlockRep:
    """The code's block-linear matrix, analytic where available.

    Codes exposing ``block_matrix()`` (RDP) hand over their matrix
    directly; anything else is probed from the numpy oracle with basis
    vectors — all codes here are XOR-linear maps with GF(2^8)
    coefficients, so k*r single-byte probes at chunk width r fully
    determine the encode matrix (``tests/test_codes.py`` cross-checks
    the analytic form against the probe).
    """
    r = (code.p - 1) if isinstance(code, RDPCode) else 1
    k, m = code.k, code.m
    if hasattr(code, "block_matrix"):
        E = np.asarray(code.block_matrix(), dtype=np.uint8)
        assert E.shape == (m * r, k * r), (E.shape, m, k, r)
    else:
        E = np.zeros((m * r, k * r), dtype=np.uint8)
        for j in range(k * r):
            probe = np.zeros((k, r), dtype=np.uint8)
            probe[j // r, j % r] = 1
            E[:, j] = code.encode(probe).reshape(m * r)
    E.setflags(write=False)
    return BlockRep(r=r, encode=E)


# ---------------------------------------------------------------------------
# Decode plan (host metadata only — no chunk bytes)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DecodeGroup:
    """One erasure-pattern group of a batched decode.

    ``idxs``: batch items sharing the pattern; ``use``: the chunk
    positions feeding the inverse (sorted availability, first k);
    ``inv``: the cached (k*r, k*r) inverse; ``need_par``/``par_rows``:
    parity positions to re-encode and their generator rows.
    """
    idxs: tuple[int, ...]
    use: tuple[int, ...]
    inv: np.ndarray
    wanted: tuple[int, ...]
    need_par: tuple[int, ...]
    par_rows: np.ndarray | None


@dataclasses.dataclass(frozen=True)
class DecodePlan:
    """Everything a decode needs besides the chunk bytes: the pattern
    group-by, per-group inverses, and the output scatter map.  Built
    from host metadata at submit time so device backends can dispatch
    the per-group matmuls immediately."""
    n_items: int
    chunk_size: int
    groups: tuple[DecodeGroup, ...]


# ---------------------------------------------------------------------------
# Async submission handles
# ---------------------------------------------------------------------------

class EngineFuture:
    """Handle to a submitted coding op.

    ``result()`` returns host numpy arrays, computing (numpy backend) or
    blocking on the already-dispatched device work (jax/pallas) on first
    call; resolution is idempotent.  ``work_bytes`` is the deterministic
    modeled-cost input for ``CostModel.coding_s`` — identical whether the
    op ran sync or async, so latency accounting can't drift between the
    two modes.
    """

    __slots__ = ("_thunk", "_value", "_done", "work_bytes", "kind")

    def __init__(self, thunk, work_bytes: int = 0, kind: str = ""):
        self._thunk = thunk
        self._value = None
        self._done = False
        self.work_bytes = work_bytes
        self.kind = kind

    @classmethod
    def wrap(cls, value, work_bytes: int = 0, kind: str = "") -> "EngineFuture":
        """An already-resolved future (empty batches, degenerate codes)."""
        fut = cls(None, work_bytes, kind)
        fut._value = value
        fut._done = True
        return fut

    @property
    def done(self) -> bool:
        return self._done

    def result(self):
        if not self._done:
            self._value = self._thunk()
            self._done = True
            self._thunk = None
        return self._value


# ---------------------------------------------------------------------------
# Engine interface
# ---------------------------------------------------------------------------

class CodingEngine:
    """Batched encode/decode/delta over a fixed ``Code``.

    All arrays are host numpy uint8 at the interface (the cluster
    simulation lives on host); device backends convert internally.
    """

    name = "base"

    #: default bound for the decode-inverse LRU (see ``inv_cache_size``)
    DEFAULT_INV_CACHE = 256

    def __init__(self, code: Code, inv_cache_size: int | None = None):
        self.code = code
        self.rep = block_rep(code)
        # decode-matrix cache: erasure patterns recur per failed server,
        # but rolling failures across many patterns must not grow it
        # without bound — bounded LRU (knob: ctor arg or $MEMEC_INV_CACHE)
        if inv_cache_size is None:
            inv_cache_size = int(os.environ.get("MEMEC_INV_CACHE",
                                                self.DEFAULT_INV_CACHE))
        self.inv_cache_size = max(1, int(inv_cache_size))
        self._inv_cache: OrderedDict[tuple[int, ...],
                                     tuple[tuple[int, ...], np.ndarray]] = \
            OrderedDict()
        # fused decode matrices: [inv ; par_rows ∘ inv] per (use, need_par)
        # — lets the execute stage issue ONE matmul per pattern group
        # instead of matmul + re-encode pass (same LRU bound as _inv_cache)
        self._fused_cache: OrderedDict[tuple, np.ndarray] = OrderedDict()
        # device-dispatch probe: device backends bump this every time a
        # kernel/jit call is issued — tests assert submit_* dispatches
        # at submit (counter moves before result()), numpy stays at 0
        self.device_dispatches = 0
        # cumulative modeled engine-busy seconds (CostModel.coding_s of
        # every call merged into a request); the sharded scatter planner
        # sorts shard groups by this clock to drain idle engines first
        self.modeled_busy_s = 0.0
        # bytes of the host arrays handed to device calls, and of the
        # results copied back (spans.KERNEL_STAGE / ENGINE_FETCH carry
        # the same counts per call)
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        # device results whose copy to the host started at dispatch, and
        # device results resolved: equal once every future has resolved
        self.copies_at_dispatch = 0
        self.results_fetched = 0
        # per-op dispatch provenance: every device hook records which
        # path actually ran it ("pallas-compiled" / "xla-compiled" /
        # "interpret" / "jnp-fallback") — a silent jnp fallback used to
        # be invisible in describe(), which claimed the dispatch path
        # unconditionally; tests now assert on this map
        self.op_paths: dict[str, str] = {}

    def note_modeled_busy(self, coding_s: float):
        """Charge modeled busy seconds against this engine's clock."""
        if coding_s > 0.0:
            self.modeled_busy_s += coding_s

    def _dispatch(self, op: str, path: str, *operands) -> None:
        """Note one device call of ``op``: the path it runs on and the
        host bytes it is handed."""
        self.device_dispatches += 1
        self.op_paths[op] = path
        self.h2d_bytes += spans.host_bytes(*operands)

    # -- core batched ops (implemented by backends) ---------------------
    def encode_batch(self, data: np.ndarray) -> np.ndarray:
        """(B, k, C) data chunks -> (B, m, C) parity chunks."""
        raise NotImplementedError

    def decode_batch(self, available, wanted, chunk_size: int) -> list[dict]:
        """Reconstruct stripe positions for a batch of stripes.

        ``available``: sequence of {position: chunk (C,)} dicts;
        ``wanted``: sequence of position lists.  Returns one
        {position: chunk} dict per stripe.  Items sharing an erasure
        pattern are decoded together (one matrix inversion + one batched
        matmul per pattern).
        """
        raise NotImplementedError

    def delta_batch(self, data_indices, xors: np.ndarray) -> np.ndarray:
        """Parity deltas for B independent chunk mutations.

        ``data_indices``: (B,) stripe data positions; ``xors``: (B, C)
        full-chunk D ⊕ D' per item.  Returns (B, m, C); apply with
        ``parity ^= delta``.
        """
        raise NotImplementedError

    def apply_delta_batch(self, parity: np.ndarray, data_indices,
                          xors: np.ndarray) -> np.ndarray:
        """(B, m, C) parity ⊕ delta_batch(data_indices, xors)."""
        parity = np.asarray(parity, dtype=np.uint8)
        if parity.shape[1] == 0 or parity.shape[0] == 0:
            return parity.copy()
        return parity ^ self.delta_batch(data_indices, xors)

    # -- introspection ---------------------------------------------------
    def describe(self) -> dict:
        """Engine identity + the kernel dispatch path actually in use —
        the answer to "did I actually compile?" (base: host numpy)."""
        return {
            "engine": self.name,
            "code": type(self.code).__name__,
            "n": self.code.n, "k": self.code.k, "r": self.rep.r,
            "backend": "host",
            "path": "numpy-host",
            "op_paths": dict(self.op_paths),
        }

    def stats(self) -> dict:
        """Run counters: device dispatches, bytes each way, device
        results fetched (and how many had their copy started at
        dispatch) and plan-cache occupancy."""
        return {
            "path": self.describe()["path"],
            "op_paths": dict(self.op_paths),
            "device_dispatches": self.device_dispatches,
            "h2d_bytes": self.h2d_bytes,
            "d2h_bytes": self.d2h_bytes,
            "copies_at_dispatch": self.copies_at_dispatch,
            "results_fetched": self.results_fetched,
            "inv_cache": len(self._inv_cache),
            "fused_cache": len(self._fused_cache),
            "modeled_busy_s": self.modeled_busy_s,
        }

    # -- modeled work (GF(2^8) multiply-accumulate bytes per batch) -----
    def encode_work_bytes(self, batch: int, chunk_size: int) -> int:
        """(m*r, k*r) matrix times (k*r, C/r) blocks, B times."""
        return batch * self.code.m * self.code.k * self.rep.r * chunk_size

    def decode_work_bytes(self, batch: int, chunk_size: int) -> int:
        """(k*r, k*r) inverse times the available blocks, B times (the
        per-pattern inversion amortizes across the batch)."""
        return batch * self.code.k * self.code.k * self.rep.r * chunk_size

    def delta_work_bytes(self, batch: int, chunk_size: int) -> int:
        """m*r parity rows from one chunk's xor, B times."""
        return batch * self.code.m * self.rep.r * chunk_size

    # -- async submission (overridden by device backends to dispatch
    # eagerly; the base implementation defers the work to result()) -----
    def submit_encode(self, data: np.ndarray) -> EngineFuture:
        data = np.asarray(data, dtype=np.uint8)
        B, _, C = data.shape
        return EngineFuture(lambda: self.encode_batch(data),
                            self.encode_work_bytes(B, C), "encode")

    def submit_decode(self, available, wanted, chunk_size: int) -> EngineFuture:
        available = [dict(a) for a in available]
        wanted = [list(w) for w in wanted]
        return EngineFuture(
            lambda: self.decode_batch(available, wanted, chunk_size),
            self.decode_work_bytes(len(available), chunk_size), "decode")

    def submit_delta(self, data_indices, xors: np.ndarray) -> EngineFuture:
        xors = np.asarray(xors, dtype=np.uint8)
        B, C = xors.shape
        return EngineFuture(lambda: self.delta_batch(data_indices, xors),
                            self.delta_work_bytes(B, C), "delta")

    def submit_fold_rows(self, data_indices, xors: np.ndarray, row_indices,
                         parity_rows: np.ndarray) -> EngineFuture:
        """Fused encode + seal-fold: per item, one parity *row*.

        Item i mutates the data chunk at stripe position
        ``data_indices[i]`` by ``xors[i]`` (B, C) and folds the resulting
        delta for parity row ``row_indices[i]`` into ``parity_rows[i]``
        (B, C) — the ``Server.submit_fold_seals`` shape, where each
        parity server folds only its own row.  Returns (B, C) updated
        rows.  Base implementation is the two-call composition (full
        delta, then row pick) the fused device kernels are byte-checked
        against; work models the single row actually produced.
        """
        xors = np.asarray(xors, dtype=np.uint8)
        parity_rows = np.asarray(parity_rows, dtype=np.uint8)
        B, C = xors.shape
        wb = B * self.rep.r * C
        if B == 0 or self.code.m == 0:
            return EngineFuture.wrap(parity_rows.copy(), wb, "fold")
        rows = np.asarray(row_indices, dtype=np.int64)
        idxs = list(data_indices)

        def thunk():
            delta = self.delta_batch(idxs, xors)          # (B, m, C)
            return parity_rows ^ delta[np.arange(B), rows]
        return EngineFuture(thunk, wb, "fold")

    def submit_apply_delta(self, parity: np.ndarray, data_indices,
                           xors: np.ndarray) -> EngineFuture:
        """Fused delta + parity apply: (B, m, C) updated parity.

        The async spelling of ``apply_delta_batch`` — device backends
        fold the delta into the parity inside one kernel instead of
        materializing (B, m, C) deltas and XORing on the host.
        """
        parity = np.asarray(parity, dtype=np.uint8)
        xors = np.asarray(xors, dtype=np.uint8)
        B, C = xors.shape
        wb = self.delta_work_bytes(B, C)
        if B == 0 or parity.shape[1] == 0:
            return EngineFuture.wrap(parity.copy(), wb, "apply_delta")
        idxs = list(data_indices)
        return EngineFuture(
            lambda: self.apply_delta_batch(parity, idxs, xors),
            wb, "apply_delta")

    def collapse_work_bytes(self, versions, chunk_size: int) -> int:
        """Modeled cost of a version-collapse flush: one delta round
        plus the XOR pass over every buffered version's bytes.  Shared
        by all backends so hot-tier latency accounting can't drift."""
        return (self.delta_work_bytes(len(versions), chunk_size)
                + sum(int(np.asarray(v).size) for v in versions))

    def submit_delta_collapse(self, parity: np.ndarray, data_indices,
                              version_xors) -> EngineFuture:
        """Fold V buffered versions per item into parity in ONE round.

        ``version_xors``: per item, a (V_i, C) uint8 array of successive
        version deltas (each XOR against the then-current chunk bytes);
        their XOR-fold is the collapsed base→latest delta, so N buffered
        updates to a hot key cost one parity round instead of N.
        ``parity`` (B, m, C); returns a future of updated parity.  The
        collapse is pure XOR (associative, byte-exact), so every backend
        is byte-identical to applying the versions one at a time.
        """
        parity = np.asarray(parity, dtype=np.uint8)
        versions = [np.asarray(v, dtype=np.uint8) for v in version_xors]
        B, C = len(versions), parity.shape[2]
        wb = self.collapse_work_bytes(versions, C)
        if B == 0 or parity.shape[1] == 0:
            return EngineFuture.wrap(parity.copy(), wb, "delta_collapse")
        idxs = list(data_indices)

        def thunk():
            collapsed = np.stack(
                [np.bitwise_xor.reduce(v, axis=0) for v in versions])
            return self.apply_delta_batch(parity, idxs, collapsed)
        return EngineFuture(thunk, wb, "delta_collapse")

    # -- shared decode plumbing -----------------------------------------
    def _decode_inverse(self, avail_sig: tuple[int, ...]
                        ) -> tuple[tuple[int, ...], np.ndarray]:
        """(positions used, (k*r, k*r) inverse) for an availability set.

        Mirrors ``RSCode.decode_matrix``: sorted positions, first k.  For
        an MDS code, restricting to any k available chunks is equivalent
        to erasing the rest — within tolerance, hence invertible.
        """
        hit = self._inv_cache.get(avail_sig)
        if hit is not None:
            self._inv_cache.move_to_end(avail_sig)
            return hit
        k, r = self.code.k, self.rep.r
        if len(avail_sig) < k:
            raise ValueError(
                f"need {k} chunks, got {len(avail_sig)} — beyond erasure "
                f"tolerance of {type(self.code).__name__}"
                f"({self.code.n},{k})")
        use = avail_sig[:k]
        G = self.rep.generator
        rows = np.concatenate([G[p * r:(p + 1) * r] for p in use])
        inv = gf256.gf_mat_inv(rows)
        self._inv_cache[avail_sig] = (use, inv)
        while len(self._inv_cache) > self.inv_cache_size:
            self._inv_cache.popitem(last=False)
        return use, inv

    def plan_decode(self, avail_sigs, wanted, chunk_size: int) -> DecodePlan:
        """Build a ``DecodePlan`` from host metadata only.

        ``avail_sigs``: per item, the available stripe positions (any
        iterable — sorted here); ``wanted``: per item, the positions to
        reconstruct.  Items sharing (pattern, wanted) decode together:
        one cached inversion, one batched matmul, one scatter group.
        """
        k, r = self.code.k, self.rep.r
        G = self.rep.generator
        sigs = [tuple(sorted(s)) for s in avail_sigs]
        wsigs = [tuple(w) for w in wanted]
        by_pattern: dict[tuple, list[int]] = {}
        for i, key in enumerate(zip(sigs, wsigs)):
            by_pattern.setdefault(key, []).append(i)
        groups = []
        for (sig, wsig), idxs in by_pattern.items():
            use, inv = self._decode_inverse(sig)
            need_par = tuple(w for w in wsig if w >= k)
            par_rows = None
            if need_par:
                par_rows = np.concatenate(
                    [G[p * r:(p + 1) * r] for p in need_par])
            groups.append(DecodeGroup(tuple(idxs), use, inv, wsig,
                                      need_par, par_rows))
        return DecodePlan(len(sigs), chunk_size, tuple(groups))

    def _fused_decode_matrix(self, g: DecodeGroup) -> np.ndarray:
        """[inv ; par_rows ∘ inv] — one matrix so a group's data recovery
        AND parity re-encode are a single device matmul instead of two
        chained ones.  The composition runs on host once per (use,
        need_par) pattern and is LRU-cached like the inversions."""
        key = (g.use, g.need_par)
        hit = self._fused_cache.get(key)
        if hit is not None:
            self._fused_cache.move_to_end(key)
            return hit
        M = g.inv if g.par_rows is None else np.concatenate(
            [g.inv, gf256.gf_matmul_np(g.par_rows, g.inv)])
        self._fused_cache[key] = M
        while len(self._fused_cache) > self.inv_cache_size:
            self._fused_cache.popitem(last=False)
        return M


class NumpyEngine(CodingEngine):
    """Reference oracle: loops the host ``codes.Code`` implementation."""

    name = "numpy"

    def encode_batch(self, data):
        data = np.asarray(data, dtype=np.uint8)
        B, k, C = data.shape
        if B == 0:
            return np.zeros((0, self.code.m, C), np.uint8)
        return np.stack([self.code.encode(d) for d in data])

    def decode_batch(self, available, wanted, chunk_size):
        return [self.code.decode(dict(a), list(w), chunk_size)
                for a, w in zip(available, wanted)]

    def delta_batch(self, data_indices, xors):
        xors = np.asarray(xors, dtype=np.uint8)
        B, C = xors.shape
        if B == 0:
            return np.zeros((0, self.code.m, C), np.uint8)
        return np.stack([self.code.xor_delta(int(i), x)
                         for i, x in zip(data_indices, xors)])


# ---------------------------------------------------------------------------
# Device backends
# ---------------------------------------------------------------------------

def _jax():
    import jax
    import jax.numpy as jnp
    return jax, jnp


@functools.lru_cache(maxsize=None)
def _jnp_block_matmuls():
    """jit'd (O,J)x(B,J,Cb) and per-item (B,O,J)x(B,J,Cb) GF(2^8) matmuls,
    plus the parity-folding per-item variant (fused delta + apply)."""
    jax, jnp = _jax()
    from repro.kernels import ref as kref

    @jax.jit
    def shared(M, D):
        prod = kref.gf256_mul_ref(M[None, :, :, None], D[:, None, :, :])
        return jax.lax.reduce(prod, np.uint8(0), jax.lax.bitwise_xor, (2,))

    @jax.jit
    def per_item(Ms, D):
        prod = kref.gf256_mul_ref(Ms[..., None], D[:, None, :, :])
        return jax.lax.reduce(prod, np.uint8(0), jax.lax.bitwise_xor, (2,))

    @jax.jit
    def per_item_fold(Ms, D, P):
        prod = kref.gf256_mul_ref(Ms[..., None], D[:, None, :, :])
        return P ^ jax.lax.reduce(prod, np.uint8(0), jax.lax.bitwise_xor,
                                  (2,))

    return shared, per_item, per_item_fold


@functools.lru_cache(maxsize=None)
def _jnp_xor_collapse():
    """jit'd (B, V, C) -> (B, C) XOR-fold over the version axis (the
    device half of ``submit_delta_collapse`` on the jax/pallas paths)."""
    jax, _ = _jax()

    @jax.jit
    def collapse(stacked):
        return jax.lax.reduce(stacked, np.uint8(0), jax.lax.bitwise_xor,
                              (1,))
    return collapse


class JaxEngine(CodingEngine):
    """Pure-jnp batched backend over the block-linear representation."""

    name = "jax"

    # -- device matmul hooks (PallasEngine overrides the dense case).
    # The `_dev` variants return device arrays without blocking — XLA
    # dispatches asynchronously, so submit_* can issue work and only
    # synchronize at EngineFuture.result().
    def _matmul_dev(self, M: np.ndarray, blocks: np.ndarray):
        """(O, J) ∘ (B, J, Cb) -> (B, O, Cb) over GF(2^8), device-side."""
        _, jnp = _jax()
        shared, _, _ = _jnp_block_matmuls()
        self._dispatch("matmul", "jnp-fallback", M, blocks)
        return shared(jnp.asarray(M), jnp.asarray(blocks))

    def _matmul_per_item_dev(self, Ms: np.ndarray, blocks: np.ndarray,
                             parity: np.ndarray | None = None):
        """(B, O, J) ∘ (B, J, Cb) -> (B, O, Cb), one matrix per item;
        ``parity`` (B, O, Cb), when given, is folded in the same jit."""
        _, jnp = _jax()
        _, per_item, per_item_fold = _jnp_block_matmuls()
        self._dispatch("delta_per_item", "jnp-fallback", Ms, blocks, parity)
        if parity is None:
            return per_item(jnp.asarray(Ms), jnp.asarray(blocks))
        return per_item_fold(jnp.asarray(Ms), jnp.asarray(blocks),
                             jnp.asarray(parity))

    def describe(self) -> dict:
        from repro.kernels import dispatch
        d = super().describe()
        d.update(backend=dispatch.backend(), path=dispatch.XLA,
                 interpret_forced=dispatch.interpret_forced())
        return d

    def _device_future(self, dev, shape, work_bytes: int = 0,
                       kind: str = "") -> EngineFuture:
        """The future of a dispatched device array, the one way a result
        comes home: its copy to the host is queued behind the computation
        now, so ``result()`` finds it under way or landed."""
        dev.copy_to_host_async()
        self.copies_at_dispatch += 1
        return EngineFuture(lambda: self._resolve_dev(dev, shape),
                            work_bytes, kind)

    def _resolve_dev(self, dev, shape):
        """Blocking resolution of a dispatched device array (the only
        place the async path waits on the device): wait for the
        computation, then for what is left of the copy started at
        dispatch."""
        jax, _ = _jax()
        with span(spans.ENGINE_WAIT):
            jax.block_until_ready(dev)
        with span(spans.ENGINE_FETCH, bytes=dev.nbytes):
            out = np.asarray(dev).reshape(shape)
        self.d2h_bytes += out.nbytes
        self.results_fetched += 1
        return out

    def submit_encode(self, data):
        data = np.asarray(data, dtype=np.uint8)
        B, k, C = data.shape
        m = self.code.m
        wb = self.encode_work_bytes(B, C)
        if B == 0 or m == 0:
            return EngineFuture.wrap(np.zeros((B, m, C), np.uint8), wb,
                                     "encode")
        dev = self._matmul_dev(self.rep.encode, self._blocks(data))
        return self._device_future(dev, (B, m, C), wb, "encode")

    def submit_delta(self, data_indices, xors):
        xors = np.asarray(xors, dtype=np.uint8)
        B, C = xors.shape
        m, r = self.code.m, self.rep.r
        wb = self.delta_work_bytes(B, C)
        if B == 0 or m == 0:
            return EngineFuture.wrap(np.zeros((B, m, C), np.uint8), wb,
                                     "delta")
        with span(spans.ENGINE_PREPARE):
            Ms = self._delta_matrices(data_indices)
        dev = self._matmul_per_item_dev(Ms, xors.reshape(B, r, C // r))
        return self._device_future(dev, (B, m, C), wb, "delta")

    def submit_fold_rows(self, data_indices, xors, row_indices, parity_rows):
        """Fused: per item, the (r, r) sub-system for ONE parity row is
        multiplied against the xor blocks and folded into the row inside
        a single device call — m× less delta work than ``submit_delta``
        and no host-side XOR pass."""
        xors = np.asarray(xors, dtype=np.uint8)
        parity_rows = np.asarray(parity_rows, dtype=np.uint8)
        B, C = xors.shape
        m, k, r = self.code.m, self.code.k, self.rep.r
        wb = B * r * C
        if B == 0 or m == 0:
            return EngineFuture.wrap(parity_rows.copy(), wb, "fold")
        with span(spans.ENGINE_PREPARE):
            idx = np.asarray(data_indices, dtype=np.int64)
            rows = np.asarray(row_indices, dtype=np.int64)
            # E reshaped (m, r, k, r): item i's system is
            # E4[row_i, :, pos_i, :]
            E4 = self.rep.encode.reshape(m, r, k, r)
            Ms = np.ascontiguousarray(E4[rows, :, idx, :])    # (B, r, r)
        dev = self._matmul_per_item_dev(Ms, xors.reshape(B, r, C // r),
                                        parity_rows.reshape(B, r, C // r))
        return self._device_future(dev, (B, C), wb, "fold")

    def submit_apply_delta(self, parity, data_indices, xors):
        """Fused delta + parity apply in one per-item device call (the
        old path materialized (B, m, C) deltas, round-tripped them to
        host, and XORed there)."""
        parity = np.asarray(parity, dtype=np.uint8)
        xors = np.asarray(xors, dtype=np.uint8)
        B, C = xors.shape
        m, r = self.code.m, self.rep.r
        wb = self.delta_work_bytes(B, C)
        if B == 0 or m == 0:
            return EngineFuture.wrap(parity.copy(), wb, "apply_delta")
        with span(spans.ENGINE_PREPARE):
            Ms = self._delta_matrices(data_indices)
        dev = self._matmul_per_item_dev(Ms, xors.reshape(B, r, C // r),
                                        parity.reshape(B, m * r, C // r))
        return self._device_future(dev, (B, m, C), wb, "apply_delta")

    def apply_delta_batch(self, parity, data_indices, xors):
        return self.submit_apply_delta(parity, data_indices, xors).result()

    def submit_delta_collapse(self, parity, data_indices, version_xors):
        """Device-side collapse: pad-stack the versions (B, Vmax, C)
        (zeros are XOR-identity), XOR-reduce on device, and feed the
        fused per-item delta+apply — dispatched at submit like the other
        device ops.  Byte-identical to the host collapse by XOR
        associativity."""
        parity = np.asarray(parity, dtype=np.uint8)
        versions = [np.asarray(v, dtype=np.uint8) for v in version_xors]
        B, C = len(versions), parity.shape[2]
        m, r = self.code.m, self.rep.r
        wb = self.collapse_work_bytes(versions, C)
        if B == 0 or m == 0:
            return EngineFuture.wrap(parity.copy(), wb, "delta_collapse")
        _, jnp = _jax()
        with span(spans.ENGINE_PREPARE):
            vmax = max(v.shape[0] for v in versions)
            stacked = np.zeros((B, vmax, C), dtype=np.uint8)
            for i, v in enumerate(versions):
                stacked[i, :v.shape[0]] = v
            Ms = self._delta_matrices(data_indices)
        self.device_dispatches += 1
        self.h2d_bytes += stacked.nbytes
        with span(spans.KERNEL_STAGE, bytes=stacked.nbytes):
            stacked = jnp.asarray(stacked)
        collapsed = _jnp_xor_collapse()(stacked)                   # (B, C)
        dev = self._matmul_per_item_dev(
            Ms, collapsed.reshape(B, r, C // r),
            parity.reshape(B, m * r, C // r))
        return self._device_future(dev, (B, m, C), wb, "delta_collapse")

    def _delta_matrices(self, data_indices) -> np.ndarray:
        """(B, m*r, r) per item: the encode matrix's columns of its
        stripe data position."""
        m, k, r = self.code.m, self.code.k, self.rep.r
        idx = np.asarray(data_indices, dtype=np.int64)
        cols = self.rep.encode.reshape(m * r, k, r)[:, idx, :]
        return np.ascontiguousarray(np.transpose(cols, (1, 0, 2)))

    def _blocks(self, chunks: np.ndarray) -> np.ndarray:
        """(B, x, C) -> (B, x*r, C//r) sub-block rows."""
        B, x, C = chunks.shape
        r = self.rep.r
        if C % r:
            raise ValueError(f"chunk size {C} not divisible by r={r}")
        return chunks.reshape(B, x * r, C // r)

    def encode_batch(self, data):
        # the blocking call IS the submitted future resolved on the spot
        # — one dispatch body for both paths keeps sync/async
        # byte-identity true by construction
        return self.submit_encode(data).result()

    def submit_decode(self, available, wanted, chunk_size):
        """Plan on host metadata, dispatch the per-group matmuls NOW.

        The plan's group-by and cached inversions need no chunk bytes,
        so the device work is issued at submit — like encode/delta —
        and ``result()`` only blocks on it and scatters the output."""
        available = [dict(a) for a in available]
        wb = self.decode_work_bytes(len(available), chunk_size)
        if not available:
            return EngineFuture.wrap([], wb, "decode")
        with span(spans.ENGINE_PREPARE):
            plan = self.plan_decode([a.keys() for a in available], wanted,
                                    chunk_size)
        futs = self._execute_decode_dev(plan, available)
        return EngineFuture(lambda: self._scatter_decode(plan, futs),
                            wb, "decode")

    def _execute_decode_dev(self, plan: DecodePlan,
                            available) -> list[EngineFuture]:
        """Execute stage: ONE batched device matmul per pattern group,
        each group's output (Bg, k + n_par, C) behind its own future.

        The group's inverse and its re-encoded-parity rows are fused into
        a single host-composed matrix (``_fused_decode_matrix``), so the
        old matmul -> parity-re-encode chain collapses to one kernel —
        byte-checked against the two-call composition in
        ``tests/test_dispatch_tune.py``."""
        k, C = self.code.k, plan.chunk_size
        futs = []
        for g in plan.groups:
            with span(spans.ENGINE_PREPARE):
                stacked = np.stack(
                    [np.stack([np.asarray(available[i][p], np.uint8)
                               for p in g.use]) for i in g.idxs])  # (Bg, k, C)
                M = self._fused_decode_matrix(g)
            dev = self._matmul_dev(M, self._blocks(stacked))
            futs.append(self._device_future(
                dev, (len(g.idxs), k + len(g.need_par), C)))
        return futs

    def _scatter_decode(self, plan: DecodePlan, futs) -> list[dict]:
        """Resolution: resolve the groups' futures and scatter each
        item's wanted positions back into per-stripe dicts.  The fused
        matmul output is (Bg, k + n_par, C): data rows then the
        re-encoded parity rows."""
        k = self.code.k
        results: list[dict | None] = [None] * plan.n_items
        for g, fut in zip(plan.groups, futs):
            out = fut.result()
            for bi, i in enumerate(g.idxs):
                results[i] = {w: (out[bi, w] if w < k
                                  else out[bi, k + g.need_par.index(w)])
                              for w in g.wanted}
        return results

    def decode_batch(self, available, wanted, chunk_size):
        # same plan/execute body as the submitted path, resolved on the
        # spot — sync/async byte-identity true by construction
        return self.submit_decode(available, wanted, chunk_size).result()

    def delta_batch(self, data_indices, xors):
        return self.submit_delta(data_indices, xors).result()


class PallasEngine(JaxEngine):
    """Batched Pallas grids for every block-linear code.

    Dense codes (RS, XOR; r == 1) hit the fully-unrolled
    `gf256_matmul`/`delta_update` kernel bodies with a (batch, C-tile)
    grid.  Block-structured codes (RDP; r = p-1) route through the SAME
    `gf256_matmul_batched` entry point natively: its column-loop kernels
    handle the (m*r, k*r) block matrix — pure-XOR 0/1 matrices drop the
    bit-plane loop entirely — so RDP encode/decode no longer falls back
    to the jnp path (ROADMAP "batching RDP natively in Pallas").
    Per-item delta matrices (r > 1) run `gf256_matmul_per_item_batched`
    — the same batched grid with one matrix tile per item — so RDP
    updates no longer drop to the jnp per-item matmul either.

    How the kernels actually run comes from ``kernels.dispatch``:
    compiled Pallas on TPU/GPU, the XLA-jitted ``xla_gf256`` twins on
    CPU, interpret mode only under ``$MEMEC_INTERPRET=1`` —
    ``describe()`` reports the resolved path.
    """

    name = "pallas"

    def _matmul_dev(self, M, blocks):
        from repro.kernels import dispatch
        from repro.kernels.gf256_matmul import gf256_matmul_batched
        self._dispatch("matmul", dispatch.decide().path, M, blocks)
        return gf256_matmul_batched(M, blocks)

    def _matmul_per_item_dev(self, Ms, blocks, parity=None):
        from repro.kernels import dispatch
        from repro.kernels.delta_update import delta_apply_per_item_batched
        self._dispatch("delta_per_item", dispatch.decide().path, Ms, blocks,
                       parity)
        return delta_apply_per_item_batched(parity, Ms, blocks)

    def describe(self) -> dict:
        from repro.kernels import dispatch
        d = CodingEngine.describe(self)
        d.update(dispatch.describe())
        return d

    def _gammas(self, data_indices) -> np.ndarray:
        idx = np.asarray(data_indices, dtype=np.int64)
        return np.ascontiguousarray(
            self.rep.encode[:, idx].T).astype(np.int32)   # (B, m)

    def submit_delta(self, data_indices, xors):
        if self.rep.r != 1 or self.code.m == 0:
            return super().submit_delta(data_indices, xors)
        xors = np.asarray(xors, dtype=np.uint8)
        B, C = xors.shape
        wb = self.delta_work_bytes(B, C)
        if B == 0:
            return EngineFuture.wrap(np.zeros((B, self.code.m, C), np.uint8),
                                     wb, "delta")
        from repro.kernels import dispatch
        from repro.kernels.delta_update import delta_apply_batched
        with span(spans.ENGINE_PREPARE):
            gammas = self._gammas(data_indices)
        # parity=None: delta-only kernel — no dead parity streams
        self._dispatch("delta", dispatch.decide().path, gammas, xors)
        dev = delta_apply_batched(None, gammas, xors)
        return self._device_future(dev, (B, self.code.m, C), wb, "delta")

    def submit_apply_delta(self, parity, data_indices, xors):
        if self.rep.r != 1 or self.code.m == 0:
            # r > 1: the per-item Pallas grid with in-kernel parity fold
            return super().submit_apply_delta(parity, data_indices, xors)
        parity = np.asarray(parity, dtype=np.uint8)
        xors = np.asarray(xors, dtype=np.uint8)
        B, C = xors.shape
        wb = self.delta_work_bytes(B, C)
        if B == 0 or parity.shape[1] == 0:
            return EngineFuture.wrap(parity.copy(), wb, "apply_delta")
        from repro.kernels import dispatch
        from repro.kernels.delta_update import delta_apply_batched
        with span(spans.ENGINE_PREPARE):
            gammas = self._gammas(data_indices)
        self._dispatch("delta", dispatch.decide().path, parity, gammas, xors)
        dev = delta_apply_batched(parity, gammas, xors)
        return self._device_future(dev, parity.shape, wb, "apply_delta")


# ---------------------------------------------------------------------------
# Selection
# ---------------------------------------------------------------------------

ENGINES = {
    "numpy": NumpyEngine,
    "jax": JaxEngine,
    "pallas": PallasEngine,
}


def make_engine(name: str | None, code: Code) -> CodingEngine:
    """Build a backend for ``code``.

    ``name=None`` falls back to ``$MEMEC_ENGINE`` then ``"numpy"``.  A
    comma-separated list (the per-shard spelling, e.g. ``pallas,numpy``)
    collapses to its first entry when a single engine is requested.
    """
    if isinstance(name, CodingEngine):
        return name
    name = (name or os.environ.get("MEMEC_ENGINE") or "numpy").lower()
    if "," in name:
        name = name.split(",")[0].strip()
    try:
        cls = ENGINES[name]
    except KeyError:
        raise ValueError(
            f"unknown coding engine {name!r}; pick from {sorted(ENGINES)}")
    return cls(code)


def resolve_async(async_engine=None) -> bool:
    """Async-pipeline knob: the argument, else ``$MEMEC_ASYNC`` (truthy
    spellings: 1/true/yes/on), defaulting to the synchronous pipeline."""
    if async_engine is None:
        return os.environ.get("MEMEC_ASYNC", "").strip().lower() in (
            "1", "true", "yes", "on")
    return bool(async_engine)


def engine_specs(spec, num_shards: int) -> list:
    """Expand an engine spec into one entry per shard.

    ``spec`` may be None (defer to ``$MEMEC_ENGINE``, itself possibly a
    comma list), a single backend name, a comma-separated string, a
    list/tuple of names, or a ``CodingEngine`` instance; shorter lists
    cycle (e.g. ``"pallas,numpy"`` over 4 shards -> pallas/numpy/pallas/
    numpy — pallas for hot shards, numpy elsewhere)."""
    if spec is None:
        spec = os.environ.get("MEMEC_ENGINE")
    if isinstance(spec, str) and "," in spec:
        spec = [s.strip() for s in spec.split(",") if s.strip()]
    if isinstance(spec, (list, tuple)):
        if not spec:
            raise ValueError("empty engine spec list")
        return [spec[i % len(spec)] for i in range(num_shards)]
    return [spec] * num_shards
