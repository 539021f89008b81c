"""CodingEngine cross-backend properties: numpy / jax / pallas backends
must agree byte-for-byte with the numpy ``Code`` oracle for every scheme,
batch size, and (odd) chunk width."""
import numpy as np
import pytest

from repro.core.codes import make_code
from repro.core.engine import (ENGINES, JaxEngine, NumpyEngine, PallasEngine,
                               block_rep, make_engine)

# (scheme, n, k) x chunk widths.  RDP views chunks as (p-1)=16 sub-blocks,
# so its widths must be multiples of 16 (non-powers-of-two still exercise
# padding); the dense codes get genuinely odd widths.
SCHEMES = {
    ("rs", 10, 8): (37, 129),
    ("xor", 9, 8): (41, 160),
    ("rdp", 10, 8): (64, 208),
    ("none", 10, 10): (33,),
}
BATCHES = (1, 3, 16)
BACKENDS = ("numpy", "jax", "pallas")


def _cases():
    for (scheme, n, k), widths in SCHEMES.items():
        for C in widths:
            yield scheme, n, k, C


@pytest.fixture(scope="module")
def engines():
    cache = {}

    def get(scheme, n, k):
        if (scheme, n, k) not in cache:
            code = make_code(scheme, n, k)
            cache[(scheme, n, k)] = {b: make_engine(b, code)
                                     for b in BACKENDS}
        return cache[(scheme, n, k)]

    return get


@pytest.mark.parametrize("scheme,n,k,C", _cases())
@pytest.mark.parametrize("B", BATCHES)
def test_encode_batch_matches_oracle(scheme, n, k, C, B, engines, rng):
    code = make_code(scheme, n, k)
    data = rng.integers(0, 256, (B, code.k, C), dtype=np.uint8)
    want = np.stack([code.encode(d) for d in data])
    for backend, eng in engines(scheme, n, k).items():
        got = eng.encode_batch(data)
        assert got.shape == (B, code.m, C), backend
        assert np.array_equal(got, want), (backend, scheme, C, B)


@pytest.mark.parametrize("scheme,n,k,C", _cases())
@pytest.mark.parametrize("B", BATCHES)
def test_decode_batch_matches_oracle(scheme, n, k, C, B, engines, rng):
    code = make_code(scheme, n, k)
    if code.m == 0:
        pytest.skip("nothing to erase under NoCode")
    data = rng.integers(0, 256, (B, code.k, C), dtype=np.uint8)
    stripes = np.concatenate(
        [data, np.stack([code.encode(d) for d in data])], axis=1)
    avail, wanted = [], []
    for b in range(B):  # erasure patterns deliberately vary across items
        n_erase = int(rng.integers(1, code.m + 1))
        erased = set(rng.choice(code.n, size=n_erase, replace=False).tolist())
        avail.append({i: stripes[b, i] for i in range(code.n)
                      if i not in erased})
        wanted.append(sorted(erased))
    want = [code.decode(a, w, C) for a, w in zip(avail, wanted)]
    for backend, eng in engines(scheme, n, k).items():
        got = eng.decode_batch(avail, wanted, C)
        for b in range(B):
            for w in wanted[b]:
                assert np.array_equal(got[b][w], want[b][w]), \
                    (backend, scheme, C, B, b, w)


@pytest.mark.parametrize("scheme,n,k,C", _cases())
@pytest.mark.parametrize("B", BATCHES)
def test_apply_delta_batch_matches_oracle(scheme, n, k, C, B, engines, rng):
    code = make_code(scheme, n, k)
    data = rng.integers(0, 256, (B, code.k, C), dtype=np.uint8)
    parity = np.stack([code.encode(d) for d in data])
    idx = rng.integers(0, code.k, B)
    xors = np.zeros((B, C), np.uint8)
    for b in range(B):  # sparse spans, like real object updates
        span = int(rng.integers(1, C + 1))
        off = int(rng.integers(0, C - span + 1))
        xors[b, off: off + span] = rng.integers(0, 256, span, dtype=np.uint8)
    want_delta = np.stack([code.xor_delta(int(i), x)
                           for i, x in zip(idx, xors)])
    for backend, eng in engines(scheme, n, k).items():
        got = eng.delta_batch(idx, xors)
        assert np.array_equal(got, want_delta), (backend, scheme, C, B)
        applied = eng.apply_delta_batch(parity, idx, xors)
        assert np.array_equal(applied, parity ^ want_delta), \
            (backend, scheme, C, B)


@pytest.mark.parametrize("scheme,n,k,C", _cases())
def test_delta_equals_reencode_through_engine(scheme, n, k, C, engines, rng):
    """Linearity end-to-end: applying engine deltas == re-encoding."""
    code = make_code(scheme, n, k)
    if code.m == 0:
        pytest.skip("no parity under NoCode")
    B = 4
    data = rng.integers(0, 256, (B, code.k, C), dtype=np.uint8)
    idx = rng.integers(0, code.k, B)
    new = data.copy()
    for b in range(B):
        new[b, idx[b], : C // 2] ^= rng.integers(
            0, 256, C // 2, dtype=np.uint8)
    xors = np.stack([data[b, idx[b]] ^ new[b, idx[b]] for b in range(B)])
    for backend, eng in engines(scheme, n, k).items():
        parity = eng.encode_batch(data)
        updated = eng.apply_delta_batch(parity, idx, xors)
        assert np.array_equal(updated, eng.encode_batch(new)), backend


def test_block_rep_matches_rs_parity_matrix():
    code = make_code("rs", 10, 8)
    rep = block_rep(code)
    assert rep.r == 1
    assert np.array_equal(rep.encode, code.parity_matrix)


def test_decode_beyond_tolerance_raises(rng):
    code = make_code("rs", 10, 8)
    data = rng.integers(0, 256, (1, 8, 64), dtype=np.uint8)
    stripe = np.concatenate([data[0], code.encode(data[0])])
    avail = [{i: stripe[i] for i in range(7)}]  # 7 < k
    for backend in BACKENDS:
        with pytest.raises(ValueError):
            make_engine(backend, code).decode_batch(avail, [[8]], 64)


# ---------------------------------------------------------------------------
# full cross-backend parity grid: numpy / jax / pallas must be pairwise
# byte-identical for encode, decode, AND delta over every scheme at
# several chunk sizes and batch shapes — one parametrized matrix, the
# regression gate for any new backend or kernel change.
# ---------------------------------------------------------------------------
GRID_SCHEMES = {
    ("rs", 10, 8): (37, 64, 129),
    ("xor", 9, 8): (41, 96),
    ("rdp", 10, 8): (64, 160),
    ("none", 10, 10): (33, 64),
}
GRID_BATCHES = (1, 5)


def _grid_cases():
    for (scheme, n, k), widths in GRID_SCHEMES.items():
        for C in widths:
            for B in GRID_BATCHES:
                yield scheme, n, k, C, B


@pytest.mark.parametrize("scheme,n,k,C,B", _grid_cases())
def test_backends_pairwise_identical_grid(scheme, n, k, C, B, engines, rng):
    code = make_code(scheme, n, k)
    engs = engines(scheme, n, k)
    data = rng.integers(0, 256, (B, code.k, C), dtype=np.uint8)

    encoded = {b: e.encode_batch(data) for b, e in engs.items()}
    ref = encoded["numpy"]
    for b, got in encoded.items():
        assert got.dtype == np.uint8 and got.shape == (B, code.m, C), b
        assert np.array_equal(got, ref), ("encode", b, scheme, C, B)

    if code.m:
        idx = rng.integers(0, code.k, B)
        xors = rng.integers(0, 256, (B, C), dtype=np.uint8)
        deltas = {b: e.delta_batch(idx, xors) for b, e in engs.items()}
        applied = {b: e.apply_delta_batch(ref, idx, xors)
                   for b, e in engs.items()}
        for b in engs:
            assert np.array_equal(deltas[b], deltas["numpy"]), \
                ("delta", b, scheme, C, B)
            assert np.array_equal(applied[b], applied["numpy"]), \
                ("apply", b, scheme, C, B)

        stripes = np.concatenate([data, ref], axis=1)
        erased = sorted(rng.choice(code.n, size=code.m,
                                   replace=False).tolist())
        avail = [{i: stripes[b2, i] for i in range(code.n)
                  if i not in erased} for b2 in range(B)]
        wanted = [list(erased)] * B
        decoded = {b: e.decode_batch(avail, wanted, C)
                   for b, e in engs.items()}
        for b in engs:
            for b2 in range(B):
                for w in erased:
                    assert np.array_equal(decoded[b][b2][w],
                                          decoded["numpy"][b2][w]), \
                        ("decode", b, scheme, C, B, b2, w)


def test_make_engine_selection(monkeypatch):
    code = make_code("rs", 10, 8)
    assert isinstance(make_engine("numpy", code), NumpyEngine)
    assert isinstance(make_engine("jax", code), JaxEngine)
    assert isinstance(make_engine("pallas", code), PallasEngine)
    monkeypatch.setenv("MEMEC_ENGINE", "jax")
    assert isinstance(make_engine(None, code), JaxEngine)
    monkeypatch.delenv("MEMEC_ENGINE")
    assert isinstance(make_engine(None, code), NumpyEngine)
    # per-shard comma lists collapse to their first entry here
    assert isinstance(make_engine("jax,numpy", code), JaxEngine)
    with pytest.raises(ValueError):
        make_engine("isal", code)
    assert set(ENGINES) == {"numpy", "jax", "pallas"}


# ---------------------------------------------------------------------------
# copy to the host at dispatch: every device result a submit path hands
# back has its copy started at submit, and is fetched once at result()
# ---------------------------------------------------------------------------
SUBMIT_C = 64


def _submit_case(path, rng):
    """(scheme, call) for one submit path; ``call(eng)`` submits it."""
    B, C = 3, SUBMIT_C
    idx = [0, 3, 7]
    xors = rng.integers(0, 256, (B, C), dtype=np.uint8)
    parity = rng.integers(0, 256, (B, 2, C), dtype=np.uint8)
    if path == "encode":
        data = rng.integers(0, 256, (B, 8, C), dtype=np.uint8)
        return "rs", lambda eng: eng.submit_encode(data)
    if path in ("rs_delta", "rdp_delta"):
        return path[:-6], lambda eng: eng.submit_delta(idx, xors)
    if path in ("rs_apply_delta", "rdp_apply_delta"):
        return path[:-12], lambda eng: eng.submit_apply_delta(parity, idx,
                                                              xors)
    if path == "fold_rows":
        rows = rng.integers(0, 256, (B, C), dtype=np.uint8)
        return "rdp", lambda eng: eng.submit_fold_rows(idx, xors, [0, 1, 1],
                                                       rows)
    if path == "delta_collapse":
        versions = [rng.integers(0, 256, (v, C), dtype=np.uint8)
                    for v in (1, 3, 2)]
        return "rs", lambda eng: eng.submit_delta_collapse(parity, idx,
                                                           versions)
    assert path == "decode"
    code = make_code("rs", 10, 8)
    data = rng.integers(0, 256, (4, 8, C), dtype=np.uint8)
    stripes = np.concatenate(
        [data, np.stack([code.encode(d) for d in data])], axis=1)
    # two erasure patterns: {2} for stripes 0-1, {5, 9} for stripes 2-3
    erased = [[2], [2], [5, 9], [5, 9]]
    avail = [{p: s[p] for p in range(10) if p not in e}
             for s, e in zip(stripes, erased)]
    return "rs", lambda eng: eng.submit_decode(avail, erased, C)


@pytest.mark.parametrize("backend", ("jax", "pallas"))
@pytest.mark.parametrize("path", ("encode", "rs_delta", "rs_apply_delta",
                                  "rdp_delta", "rdp_apply_delta",
                                  "fold_rows", "delta_collapse", "decode"))
def test_submit_starts_the_copy_home_at_dispatch(path, backend):
    scheme, call = _submit_case(path, np.random.default_rng(5))
    code = make_code(scheme, 10, 8)
    ref_eng = NumpyEngine(code)
    want = call(ref_eng).result()
    assert (ref_eng.copies_at_dispatch, ref_eng.results_fetched) == (0, 0)

    eng = make_engine(backend, code)
    fut = call(eng)
    groups = 2 if path == "decode" else 1
    assert eng.copies_at_dispatch == groups       # at submit
    assert eng.results_fetched == 0
    got = fut.result()
    assert eng.results_fetched == eng.copies_at_dispatch == groups
    assert eng.stats()["copies_at_dispatch"] == groups
    assert eng.stats()["results_fetched"] == groups
    if path == "decode":
        assert [sorted(g) for g in got] == [sorted(w) for w in want]
        for g, w in zip(got, want):
            for pos in w:
                assert g[pos].tobytes() == w[pos].tobytes(), pos
    else:
        assert got.dtype == want.dtype and got.shape == want.shape
        assert got.tobytes() == want.tobytes()
