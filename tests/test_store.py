"""MemEC cluster end-to-end behaviour: normal mode, seals, degraded mode,
transitions, consistency resolution, redundancy accounting."""
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st  # hypothesis or fallback

from repro.core import MemECCluster, PartialFailure, ServerState
from repro.core.invariants import parity_invariant


def make_cluster(**kw):
    defaults = dict(num_servers=16, scheme="rs", n=10, k=8, c=16,
                    chunk_size=512, max_unsealed=2, verify_rebuild=True)
    defaults.update(kw)
    return MemECCluster(**defaults)


def load(cl, n, seed=0, vsizes=(8, 32)):
    rng = np.random.default_rng(seed)
    kv = {}
    for i in range(n):
        key = b"key%08d" % i
        val = bytes(rng.integers(0, 256, vsizes[i % len(vsizes)],
                                 dtype=np.uint8))
        cl.set(key, val, proxy_id=i % 4)
        kv[key] = val
    return kv, rng


def check_all(cl, kv):
    return sum(1 for k, v in kv.items() if cl.get(k) != v)


class TestNormalMode:
    def test_set_get_update_delete(self):
        cl = make_cluster()
        kv, rng = load(cl, 4000)
        assert check_all(cl, kv) == 0
        for k in list(kv)[::3]:
            nv = bytes(rng.integers(0, 256, len(kv[k]), dtype=np.uint8))
            assert cl.update(k, nv)
            kv[k] = nv
        for k in list(kv)[::7]:
            assert cl.delete(k)
            del kv[k]
            assert cl.get(k) is None
        assert check_all(cl, kv) == 0
        checked, bad = parity_invariant(cl)
        assert checked > 0 and bad == 0

    def test_get_missing_returns_none(self):
        cl = make_cluster()
        assert cl.get(b"nothing") is None
        assert not cl.update(b"nothing", b"xx")
        assert not cl.delete(b"nothing")

    def test_upsert_same_key_never_duplicates(self):
        cl = make_cluster()
        cl.set(b"dup", b"AAAA")
        cl.set(b"dup", b"BBBB")           # same size -> update path
        assert cl.get(b"dup") == b"BBBB"
        cl.set(b"dup", b"C" * 10)         # different size -> delete+set
        assert cl.get(b"dup") == b"C" * 10
        _, bad = parity_invariant(cl)
        assert bad == 0

    def test_update_size_change_rejected(self):
        cl = make_cluster()
        cl.set(b"k", b"12345678")
        with pytest.raises(ValueError):
            cl.update(b"k", b"123")

    def test_large_objects(self):
        cl = make_cluster(chunk_size=512)
        big = bytes(range(256)) * 9       # 2304 bytes > chunk
        cl.set(b"bigkey", big)
        assert cl.get(b"bigkey") == big
        big2 = bytes(reversed(big))
        cl.update(b"bigkey", big2)
        assert cl.get(b"bigkey") == big2
        cl.delete(b"bigkey")
        assert cl.get(b"bigkey") is None

    def test_seal_message_carries_keys_only(self):
        cl = make_cluster()
        load(cl, 3000)
        seal_bytes = cl.net.bytes_by_kind.get("seal", 0)
        seals = sum(s.seals for s in cl.servers)
        assert seals > 0
        # keys are 11 bytes (+1 len +24 header): far below chunk size
        assert seal_bytes / seals < cl.chunk_size


class TestCodingSchemes:
    @pytest.mark.parametrize("scheme,n,k", [("rs", 10, 8), ("rdp", 10, 8),
                                            ("xor", 9, 8), ("none", 10, 10)])
    def test_scheme_end_to_end(self, scheme, n, k):
        cl = make_cluster(scheme=scheme, n=n, k=k)
        kv, rng = load(cl, 800)
        for key in list(kv)[::5]:
            nv = bytes(rng.integers(0, 256, len(kv[key]), dtype=np.uint8))
            cl.update(key, nv)
            kv[key] = nv
        assert check_all(cl, kv) == 0
        if scheme != "none":
            _, bad = parity_invariant(cl)
            assert bad == 0


class TestDegradedMode:
    def test_single_failure_cycle(self):
        cl = make_cluster()
        kv, rng = load(cl, 2500)
        t = cl.fail_server(3)
        assert t["T_N_to_D"] > 0
        assert cl.coordinator.state_of(3) == ServerState.DEGRADED
        assert check_all(cl, kv) == 0
        assert cl.stats["degraded_requests"] > 0
        # degraded mutations
        for k in list(kv)[:400]:
            nv = bytes(rng.integers(0, 256, len(kv[k]), dtype=np.uint8))
            assert cl.update(k, nv)
            kv[k] = nv
        for i in range(100):
            key = b"newkey%05d" % i
            val = bytes(rng.integers(0, 256, 16, dtype=np.uint8))
            cl.set(key, val)
            kv[key] = val
        for k in list(kv)[::17][:40]:
            cl.delete(k)
            del kv[k]
        assert check_all(cl, kv) == 0
        t2 = cl.restore_server(3)
        assert t2["T_D_to_N"] > 0
        assert cl.coordinator.state_of(3) == ServerState.NORMAL
        assert check_all(cl, kv) == 0
        _, bad = parity_invariant(cl)
        assert bad == 0

    def test_double_failure_after_churn(self):
        cl = make_cluster()
        kv, rng = load(cl, 2000)
        cl.fail_server(2)
        for k in list(kv)[:300]:
            nv = bytes(rng.integers(0, 256, len(kv[k]), dtype=np.uint8))
            cl.update(k, nv)
            kv[k] = nv
        cl.restore_server(2)
        for k in list(kv)[100:400]:
            nv = bytes(rng.integers(0, 256, len(kv[k]), dtype=np.uint8))
            cl.update(k, nv)
            kv[k] = nv
        cl.fail_server(5)
        cl.fail_server(11)
        assert check_all(cl, kv) == 0
        for k in list(kv)[:200]:
            nv = bytes(rng.integers(0, 256, len(kv[k]), dtype=np.uint8))
            assert cl.update(k, nv)
            kv[k] = nv
        assert check_all(cl, kv) == 0
        cl.restore_server(5)
        cl.restore_server(11)
        assert check_all(cl, kv) == 0
        _, bad = parity_invariant(cl)
        assert bad == 0

    def test_degraded_disabled_still_serves(self):
        cl = make_cluster(degraded_enabled=False)
        kv, _ = load(cl, 500)
        cl.fail_server(3)
        assert check_all(cl, kv) == 0       # slow (netem) but correct
        lat = cl.net.latencies["GET"]
        assert max(lat) > cl.net.cost.failed_delay_s

    def test_reconstruction_amortized_at_chunk_granularity(self):
        """Paper §5.4: later GETs to the same reconstructed chunk are free."""
        cl = make_cluster()
        kv, _ = load(cl, 2500)
        cl.fail_server(3)
        for k in kv:
            cl.get(k)
        assert cl.stats["recon_chunk_hits"] >= cl.stats["reconstructions"] * 0

        recons_after_one_pass = cl.stats["reconstructions"]
        for k in kv:
            cl.get(k)
        # second pass reconstructs nothing new
        assert cl.stats["reconstructions"] == recons_after_one_pass


class TestConsistencyResolution:
    def test_partial_update_revert_and_replay(self):
        """§5.3: a request interrupted mid-parity-fanout is reverted from
        the delta buffers and replayed as a degraded request."""
        cl = make_cluster()
        kv, rng = load(cl, 4000)
        # choose a key in a sealed chunk
        target = None
        for k in kv:
            sl, ds = cl.mapper.data_server_for(k)
            ref = cl.servers[ds].lookup(k)
            if ref is not None and cl.servers[ds].sealed[ref.chunk_local_idx]:
                target = (k, ds)
                break
        assert target is not None
        key, ds = target
        newval = bytes(rng.integers(0, 256, len(kv[key]), dtype=np.uint8))
        cl.crash_hook = ("update", key, 1)   # crash after 1 of 2 parity legs
        with pytest.raises(PartialFailure):
            cl.update(key, newval)
        # proxy still holds the request; now the data server fails
        assert any(p.pending for p in cl.proxies)
        cl.fail_server(ds)
        assert cl.stats["reverted_deltas"] >= 1
        # replayed as degraded update: new value visible
        assert cl.get(key) == newval
        kv[key] = newval
        cl.restore_server(ds)
        assert cl.get(key) == newval
        _, bad = parity_invariant(cl)
        assert bad == 0


    def test_partial_delete_revert_and_replay(self):
        """§5.3 for DELETE: a sealed object's DELETE interrupted after one
        of its two parity legs is reverted and replayed as a degraded
        DELETE; the tombstone survives the restore."""
        cl = make_cluster()
        kv, _ = load(cl, 4000)
        target = None
        for k in kv:
            sl, ds = cl.mapper.data_server_for(k)
            ref = cl.servers[ds].lookup(k)
            if ref is not None and cl.servers[ds].sealed[ref.chunk_local_idx]:
                target = (k, ds)
                break
        assert target is not None
        key, ds = target
        cl.crash_hook = ("delete", key, 1)
        with pytest.raises(PartialFailure):
            cl.delete(key)
        assert cl.crash_hook is None
        assert any(p.pending for p in cl.proxies)
        cl.fail_server(ds)
        assert cl.stats["reverted_deltas"] >= 1
        assert cl.get(key) is None
        cl.restore_server(ds)
        assert cl.get(key) is None
        del kv[key]
        assert check_all(cl, kv) == 0
        _, bad = parity_invariant(cl)
        assert bad == 0


class TestRedundancyAccounting:
    def test_measured_redundancy_tracks_formula(self):
        """Loaded-store byte accounting approaches the §3.3 analysis."""
        from repro.core.analysis import AnalysisParams, redundancy_all_encoding
        cl = make_cluster(chunk_size=4096, max_unsealed=1, c=16)
        K, V = 24, 32
        n_obj = 12000
        rng = np.random.default_rng(0)
        for i in range(n_obj):
            cl.set(b"%023d!" % i, bytes(rng.integers(0, 256, V,
                                                     dtype=np.uint8)))
        sealed = sum(1 for s in cl.servers for i, c in enumerate(s.chunk_ids)
                     if c is not None and s.sealed[i] and c.position < cl.k)
        assert sealed > 50
        # count chunk bytes of sealed data + their parity (m/k ratio)
        payload = n_obj * (K + V + 4)
        chunk_bytes = sum(len(s.region) * cl.chunk_size for s in cl.servers)
        measured = chunk_bytes / payload
        formula = redundancy_all_encoding(
            AnalysisParams(K=K, V=V, n=10, k=8))
        # unsealed slack + index overhead keep measured within ~40%
        assert measured == pytest.approx(formula, rel=0.4)


class TestStateTransitions:
    def test_transition_timings_shape(self):
        """Exp 5 shape: T_N->D with pending requests > without; both < 1s."""
        cl = make_cluster()
        kv, rng = load(cl, 1500)
        t_idle = cl.fail_server(3)["T_N_to_D"]
        cl.restore_server(3)
        # leave an unacknowledged request hanging, then fail
        key = next(iter(kv))
        cl.crash_hook = ("update", key, 1)
        try:
            cl.update(key, bytes(rng.integers(0, 256, len(kv[key]),
                                              dtype=np.uint8)))
        except PartialFailure:
            pass
        sl, ds = cl.mapper.data_server_for(key)
        t_busy = cl.fail_server(ds)["T_N_to_D"]
        assert t_idle < 1.0 and t_busy < 1.0
        assert t_busy >= t_idle * 0.5  # busy path includes revert work


    def test_transition_timings_shape_delete(self):
        """As above, with the hanging request a DELETE of an unsealed
        object: its replica deltas are reverted, and the DELETE replays."""
        cl = make_cluster()
        kv, _ = load(cl, 1500)
        t_idle = cl.fail_server(3)["T_N_to_D"]
        cl.restore_server(3)

        def unsealed(k):
            srv = cl.servers[cl.mapper.data_server_for(k)[1]]
            return not srv.sealed[srv.lookup(k).chunk_local_idx]
        key = next(k for k in kv if unsealed(k))
        cl.crash_hook = ("delete", key, 1)
        with pytest.raises(PartialFailure):
            cl.delete(key)
        sl, ds = cl.mapper.data_server_for(key)
        t_busy = cl.fail_server(ds)["T_N_to_D"]
        assert t_idle < 1.0 and t_busy < 1.0
        assert t_busy >= t_idle * 0.5  # busy path includes revert work
        assert cl.stats["reverted_deltas"] >= 1
        assert cl.get(key) is None


class TestReSetInstanceHardening:
    """Delete-then-re-SET churn (heavy under shard migration, but
    reachable with plain requests): the superseded instance's tombstone
    may still sit in an unsealed chunk when the key is re-added, so
    parity replicas and recovery mappings must be matched by *instance*,
    not by key alone."""

    def _churn_reset(self, cl, kv, rng, frac=3):
        """Delete then immediately re-SET every frac-th key."""
        for i, key in enumerate(list(kv)):
            if i % frac:
                continue
            assert cl.delete(key)
            nv = bytes(rng.integers(0, 256, len(kv[key]), dtype=np.uint8))
            assert cl.set(key, nv)
            kv[key] = nv

    def test_zombie_seal_uses_tombstoned_replica(self):
        """Sealing a chunk that holds a superseded tombstone must consume
        that instance's frozen replica (verify_rebuild cross-checks the
        rebuilt bytes), leaving the live instance's replica intact."""
        cl = make_cluster(chunk_size=256)
        kv, rng = load(cl, 60)
        self._churn_reset(cl, kv, rng)
        # force every chunk to seal by appending filler traffic
        filler, _ = load(cl, 400, seed=7)
        kv.update(filler)
        assert check_all(cl, kv) == 0
        _, bad = parity_invariant(cl)
        assert bad == 0
        # updating/deleting re-set keys still finds their live replicas
        for i, key in enumerate(list(kv)[:30]):
            nv = bytes(rng.integers(0, 256, len(kv[key]), dtype=np.uint8))
            assert cl.update(key, nv)
            kv[key] = nv
        assert check_all(cl, kv) == 0

    def test_degraded_reads_resolve_newest_instance(self):
        """Multiple proxies buffer mappings for different instances of a
        re-SET key; the failure-time merge must resolve the newest one,
        whatever order the proxies push in."""
        cl = make_cluster(chunk_size=256)
        kv, rng = load(cl, 300)
        # rotate proxies so old/new instances land in different buffers
        for i, key in enumerate(list(kv)[:80]):
            assert cl.delete(key, proxy_id=i % 4)
            nv = bytes(rng.integers(0, 256, len(kv[key]), dtype=np.uint8))
            assert cl.set(key, nv, proxy_id=(i + 1) % 4)
            kv[key] = nv
        for sid in (2, 9):
            cl.fail_server(sid)
            assert check_all(cl, kv) == 0, \
                f"stale instance served after fail({sid})"
            cl.restore_server(sid)
        assert check_all(cl, kv) == 0

    def test_restore_keeps_reset_keys(self):
        """A pre-failure tombstone in a dirty reconstructed chunk must not
        evict the re-SET instance's index entry at restore time."""
        cl = make_cluster(chunk_size=256)
        kv, rng = load(cl, 300)
        self._churn_reset(cl, kv, rng, frac=4)
        sl, ds = cl.mapper.data_server_for(next(iter(kv)))
        cl.fail_server(ds)
        # degraded churn dirties reconstructed chunks
        for key in list(kv)[:40]:
            nv = bytes(rng.integers(0, 256, len(kv[key]), dtype=np.uint8))
            assert cl.update(key, nv)
            kv[key] = nv
        assert check_all(cl, kv) == 0
        cl.restore_server(ds)
        assert check_all(cl, kv) == 0
        _, bad = parity_invariant(cl)
        assert bad == 0

    def test_shadowed_delete_survives_parity_outage_seal(self):
        """Delete (and delete/re-SET) of unsealed objects while a parity
        server is down: the shadow must preserve the tombstone's value
        extent and its instance, so chunks sealing after the restore
        rebuild byte-identically (verify_rebuild asserts it)."""
        cl = make_cluster(chunk_size=256)
        kv, rng = load(cl, 120)
        # pick a parity server of some unsealed object and fail it
        key0 = next(iter(kv))
        sl, ds = cl.mapper.data_server_for(key0)
        parity = sl.parity_servers[0]
        cl.fail_server(parity)
        dropped, reset = [], []
        for i, key in enumerate(list(kv)):
            sl2, _ = cl.mapper.data_server_for(key)
            if parity not in sl2.parity_servers:
                continue
            if i % 2:
                assert cl.delete(key)      # shadowed tombstone
                kv[key] = None
                dropped.append(key)
            else:                           # delete + re-SET: new instance
                assert cl.delete(key)
                nv = bytes(rng.integers(0, 256, 8, dtype=np.uint8))
                assert cl.set(key, nv)
                kv[key] = nv
                reset.append(key)
        assert dropped and reset
        cl.restore_server(parity)
        # filler traffic forces every touched chunk to seal + rebuild
        filler, _ = load(cl, 500, seed=11)
        kv.update(filler)
        assert sum(1 for k, v in kv.items() if cl.get(k) != v) == 0
        _, bad = parity_invariant(cl)
        assert bad == 0


class TestLargeObjectUpsert:
    def test_small_over_large_removes_fragments(self):
        """SET of a small value over an existing large object must tear
        the old fragments down, not just overwrite the manifest head."""
        cl = make_cluster(chunk_size=256)
        key = b"biggie"
        rng = np.random.default_rng(1)
        big = bytes(rng.integers(0, 256, 900, dtype=np.uint8))
        assert cl.set(key, big)
        assert cl.get(key) == big
        frag_keys = [k for s in cl.servers for k in s.object_index.keys()
                     if k.startswith(key) and k != key]
        assert frag_keys   # fragments exist
        small = b"tiny"
        assert cl.set(key, small)
        assert cl.get(key) == small
        for fk in frag_keys:   # no orphaned fragment survives
            assert all(s.lookup(fk) is None for s in cl.servers)

    def test_large_over_large_shrink(self):
        """Re-SET of a large object with fewer fragments must not leave
        stale tail fragments that a later read or migration could see."""
        cl = make_cluster(chunk_size=256)
        key = b"shrinker"
        rng = np.random.default_rng(2)
        big = bytes(rng.integers(0, 256, 1200, dtype=np.uint8))
        smaller = bytes(rng.integers(0, 256, 400, dtype=np.uint8))
        assert cl.set(key, big)
        assert cl.set(key, smaller)
        assert cl.get(key) == smaller
        live_frags = [k for s in cl.servers for k in s.object_index.keys()
                      if k.startswith(key) and k != key]
        from repro.core.chunk import fragment_count
        assert len(live_frags) == fragment_count(len(smaller), len(key),
                                                 cl.chunk_size)

    def test_small_over_large_during_data_server_outage(self):
        """Upsert teardown must resolve the manifest through the degraded
        view: a large object SET while its data server is down lives in
        the redirect store, not the frozen server memory."""
        cl = make_cluster(chunk_size=256)
        kv, rng = load(cl, 60)
        key = b"deg-big"
        sl, ds = cl.mapper.data_server_for(key)
        cl.fail_server(ds)
        big = bytes(rng.integers(0, 256, 700, dtype=np.uint8))
        assert cl.set(key, big)            # degraded large SET
        assert cl.get(key) == big
        assert cl.set(key, b"tiny")        # upsert over it, still degraded
        assert cl.get(key) == b"tiny"
        cl.restore_server(ds)
        assert cl.get(key) == b"tiny"
        # no orphaned fragment keys survive anywhere
        for s in cl.servers:
            assert not [k for k in s.object_index.keys()
                        if k.startswith(key) and k != key]
        assert sum(1 for k, v in kv.items() if cl.get(k) != v) == 0
