"""Compiled data plane: dispatch policy, cross-strategy byte equivalence,
fused-kernel compositions, and the tuning cache.

Everything the dispatch seam can pick (XLA bit-plane / select / table,
Pallas unroll / cols / gf01, single-stripe 2D jits, per-item matrices,
fused folds) must be byte-identical to the numpy GF(2^8) oracle, and a
missing or corrupt tuning cache must degrade to heuristics — never
crash.
"""
import json

import numpy as np
import pytest

from repro.core import gf256
from repro.core.codes import RSCode, make_code
from repro.core.engine import block_rep, make_engine
from repro.kernels import dispatch, tune, xla_gf256
from repro.kernels.delta_update import delta_apply_batched, delta_update
from repro.kernels.gf256_matmul import (PALLAS_STRATEGIES, gf256_matmul,
                                        gf256_matmul_batched,
                                        gf256_matmul_per_item_batched)

CPU = dispatch.backend() == "cpu"


@pytest.fixture(autouse=True)
def _fresh_tune_cache():
    """Tests monkeypatch $MEMEC_TUNE_CACHE; make sure the module cache is
    re-resolved both on entry and after the env is restored."""
    tune.load_cache(reload=True)
    yield
    tune.load_cache(reload=True)


# ---------------------------------------------------------------------------
# dispatch policy
# ---------------------------------------------------------------------------

def test_decide_explicit_overrides():
    assert dispatch.decide(True).path == dispatch.INTERPRET
    assert dispatch.decide(True).interpret is True
    assert dispatch.decide(False).path == dispatch.PALLAS
    assert dispatch.decide(False).interpret is False
    assert dispatch.decide(False).compiled is True


@pytest.mark.skipif(not CPU, reason="CPU-policy test")
def test_decide_cpu_defaults_to_xla(monkeypatch):
    monkeypatch.delenv("MEMEC_INTERPRET", raising=False)
    assert dispatch.decide().path == dispatch.XLA
    assert dispatch.decide().compiled is True
    # kernels with no XLA twin fall back to interpret on CPU
    assert dispatch.decide(xla_ok=False).path == dispatch.INTERPRET


def test_interpret_env_forces_interpret(monkeypatch):
    for val in ("1", "true", "YES", " on "):
        monkeypatch.setenv("MEMEC_INTERPRET", val)
        assert dispatch.interpret_forced(), val
        assert dispatch.decide().path == dispatch.INTERPRET
    for val in ("", "0", "no", "off"):
        monkeypatch.setenv("MEMEC_INTERPRET", val)
        assert not dispatch.interpret_forced(), val
        assert dispatch.decide().path != dispatch.INTERPRET
    # the env hatch loses to an explicit per-call interpret=False
    monkeypatch.setenv("MEMEC_INTERPRET", "1")
    assert dispatch.decide(False).path == dispatch.PALLAS


def test_describe_snapshot(monkeypatch):
    monkeypatch.delenv("MEMEC_INTERPRET", raising=False)
    d = dispatch.describe()
    assert d["backend"] == dispatch.backend()
    assert d["path"] == dispatch.decide().path
    assert d["interpret_forced"] is False


# ---------------------------------------------------------------------------
# cross-strategy byte equivalence vs the numpy oracle
# ---------------------------------------------------------------------------

def _matrices():
    rs = RSCode(n=10, k=8)
    # a small 0/1 matrix stands in for the RDP block class (the real
    # (m*r, k*r) block matrices are strategy-equivalent but too wide to
    # unroll in interpret mode; test_engine covers them end to end)
    rng01 = np.random.default_rng(7)
    A01 = rng01.integers(0, 2, (3, 6), dtype=np.uint8)
    A01[:, 0] = 1
    return [
        ("rs-parity", np.asarray(rs.parity_matrix, np.uint8)),
        ("block-01", A01),
    ]


@pytest.mark.parametrize("name,A", _matrices())
@pytest.mark.parametrize("C", (37, 129, 256))
@pytest.mark.parametrize("B", (0, 1, 3))
def test_all_strategies_match_oracle(name, A, C, B, rng):
    data = rng.integers(0, 256, (B, A.shape[1], C), dtype=np.uint8)
    want = np.stack([gf256.gf_matmul_np(A, d) for d in data]) if B else \
        np.zeros((0, A.shape[0], C), np.uint8)
    # XLA strategies (select32 demotes itself on dense matrices)
    for s in xla_gf256.STRATEGIES:
        got = np.asarray(xla_gf256.matmul_batched(A, data, strategy=s))
        assert np.array_equal(got, want), (name, s, C, B)
    # Pallas strategies in interpret mode, incl. a block_c that does not
    # divide C (forces the pad/slice path)
    for s in PALLAS_STRATEGIES:
        got = np.asarray(gf256_matmul_batched(
            A, data, strategy=s, block_c=128, interpret=True))
        assert np.array_equal(got, want), (name, s, C, B)
    # the dispatch default (whatever the policy + tune cache picked)
    got = np.asarray(gf256_matmul_batched(A, data))
    assert np.array_equal(got, want), (name, "default", C, B)


@pytest.mark.parametrize("C", (37, 208))
def test_single_stripe_matches_oracle(C, rng):
    A = np.asarray(RSCode(n=10, k=8).parity_matrix, np.uint8)
    d = rng.integers(0, 256, (A.shape[1], C), dtype=np.uint8)
    want = gf256.gf_matmul_np(A, d)
    assert np.array_equal(np.asarray(gf256_matmul(A, d)), want)
    assert np.array_equal(np.asarray(gf256_matmul(A, d, interpret=True)),
                          want)
    for s in xla_gf256.STRATEGIES:
        assert np.array_equal(
            np.asarray(xla_gf256.matmul(A, d, strategy=s)), want), s


def test_empty_matrix_rows(rng):
    A = np.zeros((0, 4), np.uint8)
    data = rng.integers(0, 256, (2, 4, 64), dtype=np.uint8)
    assert gf256_matmul_batched(A, data).shape == (2, 0, 64)


# ---------------------------------------------------------------------------
# per-item-matrix kernels (r > 1 deltas, fused folds)
# ---------------------------------------------------------------------------

def _per_item_oracle(Ms, blocks, parity=None):
    out = np.stack([gf256.gf_matmul_np(M, d) for M, d in zip(Ms, blocks)]) \
        if len(Ms) else np.zeros((0, Ms.shape[1], blocks.shape[2]), np.uint8)
    return out if parity is None else parity ^ out


@pytest.mark.parametrize("dense", (True, False))
@pytest.mark.parametrize("C", (37, 128))
@pytest.mark.parametrize("B", (0, 1, 3))
@pytest.mark.parametrize("fold", (False, True))
def test_per_item_matmul_matches_oracle(dense, C, B, fold, rng):
    O, J = 3, 4
    Ms = rng.integers(0, 256 if dense else 2, (B, O, J), dtype=np.uint8)
    blocks = rng.integers(0, 256, (B, J, C), dtype=np.uint8)
    parity = rng.integers(0, 256, (B, O, C), dtype=np.uint8) if fold else None
    want = _per_item_oracle(Ms, blocks, parity)
    got = np.asarray(gf256_matmul_per_item_batched(Ms, blocks, parity))
    assert np.array_equal(got, want), ("dispatch", dense, C, B, fold)
    got = np.asarray(gf256_matmul_per_item_batched(
        Ms, blocks, parity, block_c=128, interpret=True))
    assert np.array_equal(got, want), ("interpret", dense, C, B, fold)
    for s in xla_gf256.STRATEGIES:
        got = np.asarray(xla_gf256.matmul_per_item(
            Ms, blocks, parity, strategy=s))
        assert np.array_equal(got, want), (s, dense, C, B, fold)


@pytest.mark.parametrize("C", (37, 200))
def test_delta_kernels_match_oracle(C, rng):
    A = np.asarray(RSCode(n=10, k=8).parity_matrix, np.uint8)
    B, m = 3, A.shape[0]
    idxs = rng.integers(0, A.shape[1], B)
    gammas = A[:, idxs].T.astype(np.uint32)               # (B, m)
    xors = rng.integers(0, 256, (B, C), dtype=np.uint8)
    parity = rng.integers(0, 256, (B, m, C), dtype=np.uint8)
    want = parity ^ np.stack(
        [np.stack([gf256.gf_mul_np(np.full(C, g, np.uint8), x)
                   for g in gam]) for gam, x in zip(gammas, xors)])
    got = np.asarray(delta_apply_batched(parity, gammas, xors))
    assert np.array_equal(got, want)
    got = np.asarray(delta_apply_batched(parity, gammas, xors,
                                         interpret=True))
    assert np.array_equal(got, want)
    # single-row fused spelling
    old = rng.integers(0, 256, C, dtype=np.uint8)
    new = old ^ xors[0]
    want0 = parity[0] ^ np.stack(
        [gf256.gf_mul_np(np.full(C, g, np.uint8), xors[0])
         for g in gammas[0]])
    got0 = np.asarray(delta_update(parity[0], gammas[0].astype(np.int32),
                                   old, new))
    assert np.array_equal(got0, want0)


@pytest.mark.parametrize("interpret", [True, None])
def test_delta_only_batched_matches_oracle(interpret, rng):
    """parity=None (bare deltas) at B > 1, with gammas and xor laid out
    (B, 1, m) / (B, 1, C) inside the kernel call."""
    A = np.asarray(RSCode(n=10, k=8).parity_matrix, np.uint8)
    B, C = 5, 300
    gammas = A[:, rng.integers(0, A.shape[1], B)].T.astype(np.int32)
    xors = rng.integers(0, 256, (B, C), dtype=np.uint8)
    want = np.stack([np.stack([gf256.gf_mul_np(np.full(C, g, np.uint8), x)
                               for g in gam])
                     for gam, x in zip(gammas, xors)])
    got = np.asarray(delta_apply_batched(None, gammas, xors,
                                         interpret=interpret))
    assert np.array_equal(got, want)


# ---------------------------------------------------------------------------
# fused engine ops == their two-call compositions
# ---------------------------------------------------------------------------

BACKENDS = ("numpy", "jax", "pallas")


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("scheme,n,k", (("rs", 10, 8), ("rdp", 10, 8)))
def test_submit_fold_rows_equals_delta_then_pick(backend, scheme, n, k, rng):
    code = make_code(scheme, n, k)
    eng = make_engine(backend, code)
    oracle = make_engine("numpy", code)
    B, C = 5, 128
    idxs = rng.integers(0, code.k, B)
    xors = rng.integers(0, 256, (B, C), dtype=np.uint8)
    rows = rng.integers(0, code.m, B)
    parity_rows = rng.integers(0, 256, (B, C), dtype=np.uint8)
    want = parity_rows ^ oracle.delta_batch(idxs, xors)[np.arange(B), rows]
    got = eng.submit_fold_rows(idxs, xors, rows, parity_rows).result()
    assert np.array_equal(got, want), backend
    # empty batch: rows pass through untouched
    empty = eng.submit_fold_rows(np.zeros(0, int),
                                 np.zeros((0, C), np.uint8),
                                 np.zeros(0, int),
                                 np.zeros((0, C), np.uint8)).result()
    assert empty.shape == (0, C)


@pytest.mark.parametrize("backend", BACKENDS)
@pytest.mark.parametrize("scheme,n,k", (("rs", 10, 8), ("rdp", 10, 8)))
def test_submit_apply_delta_equals_delta_then_xor(backend, scheme, n, k, rng):
    code = make_code(scheme, n, k)
    eng = make_engine(backend, code)
    oracle = make_engine("numpy", code)
    B, C = 4, 128
    idxs = rng.integers(0, code.k, B)
    xors = rng.integers(0, 256, (B, C), dtype=np.uint8)
    parity = rng.integers(0, 256, (B, code.m, C), dtype=np.uint8)
    want = parity ^ oracle.delta_batch(idxs, xors)
    got = eng.submit_apply_delta(parity, idxs, xors).result()
    assert np.array_equal(got, want), backend


@pytest.mark.parametrize("backend", BACKENDS)
def test_fused_decode_matrix_equals_two_pass(backend, rng):
    """[inv ; G∘inv] applied once == decode matmul + re-encode pass."""
    code = make_code("rs", 10, 8)
    eng = make_engine(backend, code)
    C = 96
    data = rng.integers(0, 256, (code.k, C), dtype=np.uint8)
    parity = code.encode(data)
    stripe = np.concatenate([data, parity])
    erased = (0, 9)
    avail = {i: stripe[i] for i in range(code.n) if i not in erased}
    plan = eng.plan_decode([tuple(sorted(avail))], [list(erased)], C)
    (g,) = plan.groups
    M = eng._fused_decode_matrix(g)
    stacked = np.stack([avail[i] for i in g.use])
    fused = gf256.gf_matmul_np(M, stacked)
    inv_out = gf256.gf_matmul_np(g.inv, stacked)
    two_pass = np.concatenate(
        [inv_out, gf256.gf_matmul_np(g.par_rows, inv_out)])
    assert np.array_equal(fused, two_pass)
    # and end to end: the decoded positions match the original stripe
    out = eng.decode_batch([avail], [list(erased)], C)[0]
    for w in erased:
        assert np.array_equal(out[w], stripe[w]), (backend, w)


@pytest.mark.skipif(not CPU, reason="CPU dispatch surface")
def test_engine_describe_exposes_dispatch_path(monkeypatch):
    monkeypatch.delenv("MEMEC_INTERPRET", raising=False)
    code = make_code("rs", 10, 8)
    d = make_engine("pallas", code).describe()
    assert d["path"] == dispatch.XLA
    assert d["backend"] == "cpu"
    assert d["interpret_forced"] is False
    assert make_engine("numpy", code).describe()["path"] == "numpy-host"
    monkeypatch.setenv("MEMEC_INTERPRET", "1")
    assert make_engine("pallas", code).describe()["path"] == \
        dispatch.INTERPRET


def test_engine_stats_counts_device_dispatches(rng):
    code = make_code("rs", 10, 8)
    eng = make_engine("pallas", code)
    assert eng.stats()["device_dispatches"] == 0
    data = rng.integers(0, 256, (2, code.k, 64), dtype=np.uint8)
    eng.encode_batch(data)
    s = eng.stats()
    assert s["device_dispatches"] > 0
    assert s["path"] == eng.describe()["path"]


# ---------------------------------------------------------------------------
# tuning cache
# ---------------------------------------------------------------------------

def test_tune_cache_round_trip(tmp_path, monkeypatch):
    path = tmp_path / "tune.json"
    monkeypatch.setenv("MEMEC_TUNE_CACHE", str(path))
    # a pointed-at-but-missing cache warns once and degrades to empty
    with pytest.warns(UserWarning, match="not found"):
        assert tune.load_cache(reload=True) == {}
    A = np.asarray(RSCode(n=6, k=4).parity_matrix, np.uint8)
    best = tune.autotune_matmul(A, chunk=64, batch=2, reps=1)
    assert best["strategy"]
    assert tune.save() == str(path)
    tune.load_cache(reload=True)
    ent = tune.lookup("matmul", dispatch.decide().path, k=4, m=2,
                      chunk=64, batch=2, cls=tune.matrix_cls(A))
    assert ent is not None and ent["strategy"] == best["strategy"]
    # the persisted JSON is the versioned {entries: ...} shape
    raw = json.loads(path.read_text())
    assert raw["version"] == 1 and raw["entries"]


@pytest.mark.parametrize("content", (None, "not json {", '{"entries": 3}',
                                     '["wrong shape"]'))
def test_corrupt_or_missing_cache_falls_back(tmp_path, monkeypatch, content,
                                             rng):
    path = tmp_path / "tune.json"
    if content is not None:
        path.write_text(content)
    monkeypatch.setenv("MEMEC_TUNE_CACHE", str(path))
    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        cache = tune.load_cache(reload=True)
    assert cache == {}
    # dispatch still answers correctly with heuristics only
    A = np.asarray(RSCode(n=10, k=8).parity_matrix, np.uint8)
    data = rng.integers(0, 256, (2, 8, 100), dtype=np.uint8)
    want = np.stack([gf256.gf_matmul_np(A, d) for d in data])
    assert np.array_equal(np.asarray(gf256_matmul_batched(A, data)), want)


def test_malformed_entries_are_filtered(tmp_path, monkeypatch):
    path = tmp_path / "tune.json"
    key = tune.key("matmul", dispatch.XLA, k=8, m=2, chunk=64, batch=1)
    path.write_text(json.dumps({"entries": {
        key: {"strategy": "bitplane32", "block_c": 0},
        "bad/one": {"block_c": 9},                      # no strategy
        "worse/one": "not a dict",
    }}))
    monkeypatch.setenv("MEMEC_TUNE_CACHE", str(path))
    cache = tune.load_cache(reload=True)
    assert list(cache) == [key]


def test_committed_defaults_parse():
    """The checked-in tune_defaults.json must always load cleanly."""
    raw = json.loads(open(tune.DEFAULTS_PATH).read())
    assert raw["entries"], "committed tune defaults are empty"
    for k, v in raw["entries"].items():
        assert "strategy" in v and "block_c" in v, k


@pytest.mark.parametrize("scheme", ["rs", "rdp"])
def test_served_path_never_autotunes(scheme, monkeypatch):
    """The store only looks tuning entries up: the autotuner, whose
    ``except`` treats a failing candidate as data, is never reached from
    SET, UPDATE, recovery or degraded reads."""
    from repro.core import MemECCluster

    def unreachable(*a, **k):
        raise AssertionError("autotuner reached from the served path")
    for name in ("autotune_matmul", "autotune_delta_per_item", "_time_call"):
        monkeypatch.setattr(tune, name, unreachable)
    cl = MemECCluster(scheme=scheme, chunk_size=512, max_unsealed=1,
                      engine="pallas")
    items = [(b"k%06d" % i, bytes([i % 251]) * (8 + 24 * (i % 2)))
             for i in range(2000)]
    for i in range(0, len(items), 64):
        assert all(cl.multi_set(items[i:i + 64]))
    ups = [(k, bytes(len(v))) for k, v in items[::3]]
    assert all(cl.multi_update(ups))
    cl.fail_server(0)
    want = dict(items) | dict(ups)
    keys = list(want)[:300]
    assert cl.multi_get(keys) == [want[k] for k in keys]
    assert {"matmul", "delta_per_item"} <= set(cl.engine.op_paths)
