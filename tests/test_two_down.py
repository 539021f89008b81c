"""Two servers down at once, the double failure RS(10,8) and RDP(10,8)
tolerate (n - k = 2): the pair that shares the most stripe lists fails,
seeded windows of ``multi_get`` / ``multi_update`` run against a dict of
acknowledged writes, every rebuilt chunk is compared with the bytes its
server held and with what the numpy oracle rebuilds, and after restore
every stripe's parity with the plain codes of ``bench/codes/``."""
import contextlib
import itertools

import numpy as np
import pytest

from bench import reference
from repro.core import MemECCluster, spans
from repro.core import store as store_mod

SCHEMES = ["rs", "rdp"]
C = 512


def _cfg(scheme):
    return {"scheme": scheme, "n": 10, "k": 8, "chunk_size": C,
            "rdp_p": 17}


def _cluster(scheme, engine="numpy"):
    return MemECCluster(num_servers=16, num_proxies=4, scheme=scheme,
                        n=10, k=8, chunk_size=C, max_unsealed=1,
                        engine=engine)


def _load(clusters, n=1200, seed=0):
    rng = np.random.default_rng(seed)
    acked = {b"user%019d" % i: bytes(rng.integers(0, 256, (8, 32)[i % 2],
                                                  dtype=np.uint8))
             for i in range(n)}
    items = list(acked.items())
    for cl in clusters:
        for s in range(0, n, 64):
            assert all(cl.multi_set(items[s: s + 64], (s // 64) % 4))
    return acked


def _pair(cl):
    lists = [set(sl.servers) for sl in cl.stripe_lists]
    return max(itertools.combinations(range(len(cl.servers)), 2),
               key=lambda p: sum(set(p) <= sl for sl in lists))


def _windows(clusters, acked, seed, n=60):
    """Seeded windows of GETs or UPDATEs (with repeated keys) on every
    cluster alike; each GET answer must equal the acknowledged value."""
    rng = np.random.default_rng(seed)
    keys = list(acked)
    for w in range(n):
        ks = [keys[i] for i in rng.integers(0, len(keys),
                                            int(rng.integers(1, 20)))]
        ks += ks[:2]
        if w % 2:
            for cl in clusters:
                assert cl.multi_get(ks, w % 4) == [acked[k] for k in ks]
        else:
            items = [(k, bytes(rng.integers(0, 256, len(acked[k]),
                                            dtype=np.uint8))) for k in ks]
            for cl in clusters:
                assert all(cl.multi_update(items, w % 4))
            acked.update(items)


def _recon(cl):
    return {key: rc.buf.copy() for sid in sorted(cl.redirect)
            for key, rc in cl.redirect[sid].recon.items()}


@pytest.mark.parametrize("scheme", SCHEMES)
def test_two_down_reads_rebuilds_and_restores_exactly(scheme):
    dev, oracle = _cluster(scheme, "pallas"), _cluster(scheme)
    acked = _load([dev, oracle])
    pair = _pair(dev)
    shared = sum(set(pair) <= set(sl.servers) for sl in dev.stripe_lists)
    assert shared > len(dev.stripe_lists) // 2
    _windows([dev, oracle], acked, seed=1, n=10)
    for sid in pair:
        for cl in (dev, oracle):
            cl.fail_server(sid, recover=True)
        snap = reference.snapshot_recovery(dev, sid, acked)
        assert snap and reference.check_recovered(snap) == (len(snap), 0)
    rebuilt = _recon(dev)
    assert rebuilt.keys() == _recon(oracle).keys()
    assert all(np.array_equal(rebuilt[k], v)
               for k, v in _recon(oracle).items())
    assert dev.stats["two_loss_rebuilds"] > 0
    for name in ("reconstructions", "two_loss_rebuilds",
                 "degraded_requests"):
        assert dev.stats[name] == oracle.stats[name]

    _windows([dev, oracle], acked, seed=2)
    for sid in pair:
        dev.restore_server(sid)
    keys = list(acked)
    assert dev.multi_get(keys) == [acked[k] for k in keys]
    checked, bad = reference.check_parity(dev, _cfg(scheme))
    assert checked > 0 and bad == 0


@pytest.mark.parametrize("scheme", SCHEMES)
def test_on_demand_rebuilds_count_two_losses(scheme):
    cl = _cluster(scheme)
    acked = _load([cl])
    pair = _pair(cl)
    for sid in pair:
        cl.fail_server(sid, recover=False)
    assert cl.stats["two_loss_rebuilds"] == 0
    _windows([cl], acked, seed=3)
    assert 0 < cl.stats["two_loss_rebuilds"] <= cl.stats["reconstructions"]
    for sid in pair:
        cl.restore_server(sid)
    checked, bad = reference.check_parity(cl, _cfg(scheme))
    assert checked > 0 and bad == 0


def test_one_server_down_rebuilds_no_two_loss_chunk():
    cl = _cluster("rdp")
    _load([cl])
    assert cl.fail_server(_pair(cl)[0], recover=True)["recovered_chunks"]
    assert cl.stats["two_loss_rebuilds"] == 0


def _keys_by_path(cl, acked, pair):
    """A key whose UPDATE is degraded and one that batches."""
    def involved(k):
        sl, ds = cl.mapper.data_server_for(k)
        return {ds, *sl.parity_servers}
    deg = next(k for k in acked if involved(k) & set(pair))
    ok = next(k for k in acked if not involved(k) & set(pair))
    return deg, ok


@pytest.mark.parametrize("scheme", SCHEMES)
def test_window_ends_on_the_last_write_of_each_key(scheme):
    cl, seq = _cluster(scheme), _cluster(scheme)
    acked = _load([cl, seq])
    pair = _pair(cl)
    for c in (cl, seq):
        for sid in pair:
            c.fail_server(sid, recover=True)
    deg, ok = _keys_by_path(cl, acked, pair)
    items = [(k, bytes([j + 1]) * len(acked[k]))
             for j, k in enumerate([deg, ok, deg, ok, deg])]
    assert all(cl.multi_update(items))
    for k, v in items:
        assert seq.update(k, v)
    assert cl.multi_get([deg, ok]) == [items[4][1], items[3][1]]
    assert cl.get(deg) == seq.get(deg) and cl.get(ok) == seq.get(ok)
    assert (cl.stats["degraded_requests"] == seq.stats["degraded_requests"])


@pytest.mark.parametrize("kind", ["get", "update"])
def test_degraded_requests_run_as_one_spanned_block(kind, monkeypatch):
    cl = _cluster("rdp")
    acked = _load([cl])
    pair = _pair(cl)
    deg, ok = _keys_by_path(cl, acked, pair)
    opened = []

    @contextlib.contextmanager
    def span(name, **kw):
        opened.append((name, kw))
        yield
    monkeypatch.setattr(store_mod, "span", span)

    def call(keys):
        opened.clear()
        if kind == "get":
            cl.multi_get(keys)
        else:
            cl.multi_update([(k, acked[k]) for k in keys])
        return [kw for name, kw in opened if name == spans.STORE_DEGRADED]

    assert call([deg, ok, deg]) == []                  # nothing is down
    for sid in pair:
        cl.fail_server(sid, recover=True)
    if kind == "get":   # a GET is degraded only through its data server
        deg = next(k for k in acked
                   if cl.mapper.data_server_for(k)[1] in pair)
    assert call([deg, ok, deg]) == [{"ops": 2}]
    assert call([ok]) == []
