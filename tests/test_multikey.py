"""Batched multi-key requests + batched recovery: the multi_* API must be
semantically identical to sequential single-key requests, in normal AND
degraded mode, and `fail_server` must recover every lost chunk in one
batched decode."""
import numpy as np
import pytest

from repro.core import MemECCluster, ServerState
from repro.core.invariants import parity_invariant
from repro.data.ycsb import YCSBConfig, run_workload


def make_cluster(**kw):
    defaults = dict(num_servers=16, scheme="rs", n=10, k=8, c=16,
                    chunk_size=512, max_unsealed=2, verify_rebuild=True)
    defaults.update(kw)
    return MemECCluster(**defaults)


def batch_load(cl, n, batch=16, seed=0, vsizes=(8, 32)):
    rng = np.random.default_rng(seed)
    items = [(b"bk%08d" % i,
              bytes(rng.integers(0, 256, vsizes[i % len(vsizes)],
                                 dtype=np.uint8)))
             for i in range(n)]
    for i in range(0, n, batch):
        ok = cl.multi_set(items[i:i + batch], proxy_id=(i // batch) % 4)
        assert all(ok)
    return dict(items), rng


class TestMultiKeyNormalMode:
    def test_multi_set_get_roundtrip(self):
        cl = make_cluster()
        kv, _ = batch_load(cl, 3000)
        keys = list(kv)
        for i in range(0, len(keys), 16):
            got = cl.multi_get(keys[i:i + 16])
            assert got == [kv[k] for k in keys[i:i + 16]]
        checked, bad = parity_invariant(cl)
        assert checked > 0 and bad == 0

    def test_multi_matches_sequential(self):
        """Batched and per-key execution must leave identical contents."""
        cl_b, cl_s = make_cluster(), make_cluster()
        rng = np.random.default_rng(7)
        items = [(b"eq%07d" % i,
                  bytes(rng.integers(0, 256, 16, dtype=np.uint8)))
                 for i in range(600)]
        cl_b.multi_set(items)
        for k, v in items:
            cl_s.set(k, v)
        upd = [(k, bytes(rng.integers(0, 256, 16, dtype=np.uint8)))
               for k, _ in items[::5]]
        cl_b.multi_update(upd)
        for k, v in upd:
            cl_s.update(k, v)
        keys = [k for k, _ in items]
        assert cl_b.multi_get(keys) == [cl_s.get(k) for k in keys]

    def test_multi_set_duplicates_and_upserts(self):
        cl = make_cluster()
        cl.set(b"old", b"XXXX")
        ok = cl.multi_set([(b"dup", b"AAAA"), (b"dup", b"BBBB"),
                           (b"old", b"YYYY"), (b"new", b"ZZZZ")])
        assert all(ok)
        assert cl.get(b"dup") == b"BBBB"     # last write wins
        assert cl.get(b"old") == b"YYYY"     # upsert through fallback
        assert cl.get(b"new") == b"ZZZZ"
        _, bad = parity_invariant(cl)
        assert bad == 0

    def test_multi_get_missing_and_update_missing(self):
        cl = make_cluster()
        cl.multi_set([(b"a", b"1234")])
        assert cl.multi_get([b"a", b"nope"]) == [b"1234", None]
        assert cl.multi_update([(b"a", b"5678"), (b"nope", b"0000")]) == \
            [True, False]
        assert cl.get(b"a") == b"5678"

    def test_multi_set_large_object_fallback(self):
        cl = make_cluster(chunk_size=512)
        big = bytes(range(256)) * 9
        ok = cl.multi_set([(b"small", b"abcd"), (b"bigkey", big)])
        assert all(ok)
        assert cl.get(b"bigkey") == big
        assert cl.multi_get([b"bigkey", b"small"]) == [big, b"abcd"]

    def test_batched_seal_identical_to_sequential(self):
        """Seal fan-out through fold_seal_batch must rebuild the exact
        chunk bytes (verify_rebuild asserts parity-side equality)."""
        cl = make_cluster(verify_rebuild=True, max_unsealed=1)
        batch_load(cl, 2000, batch=64)
        assert sum(s.seals for s in cl.servers) > 0
        _, bad = parity_invariant(cl)
        assert bad == 0


    def test_crash_hook_fires_in_multi_update(self):
        """Fault injection must behave exactly as in sequential mode."""
        from repro.core import PartialFailure
        cl = make_cluster(max_unsealed=1)
        kv, rng = batch_load(cl, 1500)
        target = None
        for k in kv:
            _, ds = cl.mapper.data_server_for(k)
            ref = cl.servers[ds].lookup(k)
            if ref is not None and cl.servers[ds].sealed[ref.chunk_local_idx]:
                target = k
                break
        assert target is not None
        cl.crash_hook = ("update", target, 1)
        with pytest.raises(PartialFailure):
            cl.multi_update([(target, bytes(len(kv[target])))])

    def test_crash_hook_mid_batch_matches_sequential_order(self):
        """Items before the crashing key complete; items after do not."""
        from repro.core import PartialFailure
        cl = make_cluster(max_unsealed=1)
        kv, rng = batch_load(cl, 1500)
        sealed = []
        for k in kv:
            _, ds = cl.mapper.data_server_for(k)
            ref = cl.servers[ds].lookup(k)
            if ref is not None and cl.servers[ds].sealed[ref.chunk_local_idx]:
                sealed.append(k)
            if len(sealed) == 3:
                break
        assert len(sealed) == 3
        before, target, after = sealed
        newvals = {k: bytes(rng.integers(0, 256, len(kv[k]),
                                         dtype=np.uint8)) for k in sealed}
        cl.crash_hook = ("update", target, 1)
        with pytest.raises(PartialFailure):
            cl.multi_update([(k, newvals[k]) for k in sealed])
        assert cl.get(before) == newvals[before]   # ran before the crash
        assert cl.get(after) == kv[after]          # never executed


class TestMultiKeyDegradedMode:
    def test_degraded_multi_roundtrip(self):
        cl = make_cluster()
        kv, rng = batch_load(cl, 2500)
        cl.fail_server(3)
        assert cl.coordinator.state_of(3) == ServerState.DEGRADED
        keys = list(kv)
        for i in range(0, len(keys), 16):
            got = cl.multi_get(keys[i:i + 16])
            assert got == [kv[k] for k in keys[i:i + 16]]
        upd = [(k, bytes(rng.integers(0, 256, len(kv[k]), dtype=np.uint8)))
               for k in keys[:300]]
        for i in range(0, len(upd), 16):
            assert all(cl.multi_update(upd[i:i + 16]))
        kv.update(dict(upd))
        new = [(b"deg%05d" % i, bytes(rng.integers(0, 256, 16,
                                                   dtype=np.uint8)))
               for i in range(80)]
        for i in range(0, len(new), 16):
            assert all(cl.multi_set(new[i:i + 16]))
        kv.update(dict(new))
        for i in range(0, len(keys), 16):
            got = cl.multi_get(keys[i:i + 16])
            assert got == [kv[k] for k in keys[i:i + 16]]
        cl.restore_server(3)
        assert all(cl.get(k) == v for k, v in kv.items())
        _, bad = parity_invariant(cl)
        assert bad == 0


class TestBatchedRecovery:
    def test_fail_server_recovers_all_chunks_in_one_decode(self):
        cl = make_cluster()
        kv, _ = batch_load(cl, 3000)
        sealed_owned = sum(
            1 for idx, cid in enumerate(cl.servers[3].chunk_ids)
            if cid is not None and cl.servers[3].sealed[idx])
        t = cl.fail_server(3)
        assert t["recovered_chunks"] == sealed_owned > 0
        assert t["T_recovery"] > 0
        assert cl.stats["batch_recovered_chunks"] == sealed_owned
        # every sealed chunk is already reconstructed: a full GET sweep
        # must not trigger a single further per-chunk decode
        before = cl.stats["reconstructions"]
        for k in kv:
            cl.get(k)
        assert cl.stats["reconstructions"] == before
        assert cl.stats["recon_chunk_hits"] > 0
        cl.restore_server(3)
        assert all(cl.get(k) == v for k, v in kv.items())

    def test_recovery_timing_separate_from_transition(self):
        cl = make_cluster()
        batch_load(cl, 1500)
        t = cl.fail_server(5)
        assert set(t) >= {"T_N_to_D", "T_recovery", "recovered_chunks"}
        assert t["T_N_to_D"] < 1.0    # paper Exp 5: transitions stay fast
        cl.restore_server(5)

    def test_recovery_time_scales_with_volume(self):
        """T_recovery models link-serialized fetches per redirected
        server — more lost chunks must cost more modeled time."""
        times = {}
        for n_obj in (600, 4800):
            cl = make_cluster(max_unsealed=1)
            batch_load(cl, n_obj, batch=32)
            t = cl.fail_server(3)
            times[n_obj] = (t["recovered_chunks"], t["T_recovery"])
            cl.restore_server(3)
        assert times[4800][0] > times[600][0]
        assert times[4800][1] > times[600][1]

    def test_nocode_recovery_is_noop(self):
        cl = make_cluster(scheme="none", n=10, k=10)
        batch_load(cl, 500)
        t = cl.fail_server(2)
        assert t["recovered_chunks"] == 0
        cl.restore_server(2)


class TestBatchedYCSB:
    @pytest.mark.parametrize("fail", [False, True])
    def test_ycsb_batched_roundtrip(self, fail):
        """multi_get/multi_set round-trip YCSB in normal AND degraded mode:
        the batched driver must leave the store byte-identical with what a
        sequential verification sweep reads back."""
        cl = make_cluster()
        cfg = YCSBConfig(num_objects=800)
        ops, w = run_workload(cl, "load", 0, cfg, batch_size=16)
        assert ops == 800
        if fail:
            cl.fail_server(4)
        ops, _ = run_workload(cl, "A", 1200, cfg, batch_size=16)
        assert ops == 1200
        assert cl.net.ops_by_kind.get("MGET", 0) > 0
        assert cl.net.ops_by_kind.get("MUPDATE", 0) > 0 or fail
        if fail:
            cl.restore_server(4)
        # verify every object readable (updates in workload A move values;
        # GET correctness is checked against a sequentially-driven twin)
        cl2 = make_cluster()
        run_workload(cl2, "load", 0, cfg, batch_size=1)
        ops2, _ = run_workload(cl2, "A", 1200, cfg, batch_size=1)
        for i in range(cfg.num_objects):
            key = w.key(i)
            assert cl.get(key) == cl2.get(key), (i, fail)
        _, bad = parity_invariant(cl)
        assert bad == 0
