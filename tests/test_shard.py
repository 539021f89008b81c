"""ShardedCluster: hash routing, S=1/S>1 byte-identity, pipelined
cross-shard batches, shard-scoped failures, and seeded fault injection on
the batched multi-key paths (degraded fallback must hit exactly the
affected keys)."""
import numpy as np
import pytest

from repro.core import (MemECCluster, ShardedCluster, engine_specs,
                        make_cluster, resolve_shards, shard_for_key)
from repro.core.engine import JaxEngine, NumpyEngine
from repro.data.ycsb import YCSBConfig, YCSBWorkload, run_workload
from repro.core.invariants import parity_invariant

KW = dict(num_servers=10, num_proxies=2, scheme="rs", n=4, k=2, c=8,
          chunk_size=256, max_unsealed=2)


def sharded(shards=3, **kw):
    merged = dict(KW)
    merged.update(kw)
    return ShardedCluster(shards=shards, **merged)


def seeded_items(n, seed=0, sizes=(8, 32)):
    rng = np.random.default_rng(seed)
    return [(b"sk%06d" % i,
             bytes(rng.integers(0, 256, sizes[i % len(sizes)],
                                dtype=np.uint8)))
            for i in range(n)]


class TestConstructionAndRouting:
    def test_make_cluster_s1_is_plain_memec(self):
        cl = make_cluster(shards=1, **KW)
        assert isinstance(cl, MemECCluster)
        cl = make_cluster(shards=3, **KW)
        assert isinstance(cl, ShardedCluster) and cl.num_shards == 3

    def test_memec_shards_env(self, monkeypatch):
        monkeypatch.setenv("MEMEC_SHARDS", "4")
        assert resolve_shards(None) == 4
        cl = make_cluster(**KW)
        assert isinstance(cl, ShardedCluster) and cl.num_shards == 4
        monkeypatch.delenv("MEMEC_SHARDS")
        assert resolve_shards(None) == 1
        with pytest.raises(ValueError):
            resolve_shards(0)

    def test_shard_routing_deterministic_and_spread(self):
        keys = [b"rk%05d" % i for i in range(2000)]
        assign = [shard_for_key(k, 4) for k in keys]
        assert assign == [shard_for_key(k, 4) for k in keys]  # stable
        counts = np.bincount(assign, minlength=4)
        assert (counts > 0).all()          # every shard gets traffic
        assert counts.max() < 2 * counts.min()  # roughly uniform
        assert all(shard_for_key(k, 1) == 0 for k in keys[:10])

    def test_routing_flows_through_placement(self):
        """No caller may hard-code FNV-mod: shard_of/locate/_plan all go
        through the pluggable Placement (default: the historical mod)."""
        from repro.core import RingPlacement
        cl = sharded(shards=3)
        assert cl.placement.kind == "mod"
        keys = [b"pk%05d" % i for i in range(300)]
        assert [cl.shard_of(k) for k in keys] == \
            [shard_for_key(k, 3) for k in keys]        # default unchanged
        ring = sharded(shards=3, placement="ring")
        assert isinstance(ring.placement, RingPlacement)
        for k in keys[:50]:
            si, sl, ds = ring.locate(k)
            assert si == ring.placement.shard_for(k)
        groups = ring._plan(keys)
        for si, idxs in groups.items():
            assert all(ring.placement.shard_for(keys[i]) == si
                       for i in idxs)

    def test_mixed_engines_per_shard(self):
        assert engine_specs("pallas,numpy", 4) == \
            ["pallas", "numpy", "pallas", "numpy"]
        assert engine_specs(["jax"], 3) == ["jax", "jax", "jax"]
        cl = sharded(shards=3, engine="numpy,jax")
        assert isinstance(cl.shards[0].engine, NumpyEngine)
        assert isinstance(cl.shards[1].engine, JaxEngine)
        assert isinstance(cl.shards[2].engine, NumpyEngine)
        # every shard still serves the same data plane
        items = seeded_items(120, seed=5)
        assert all(cl.multi_set(items))
        assert cl.multi_get([k for k, _ in items]) == [v for _, v in items]


class TestShardEquivalence:
    def test_s3_matches_s1_seeded_workload(self):
        """Byte-identity: the sharded cluster must serve exactly what the
        unsharded one serves for the same seeded batched workload."""
        cl3, cl1 = sharded(shards=3), make_cluster(shards=1, **KW)
        items = seeded_items(900, seed=1)
        keys = [k for k, _ in items]
        for i in range(0, len(items), 32):
            assert all(cl3.multi_set(items[i:i + 32]))
            assert all(cl1.multi_set(items[i:i + 32]))
        rng = np.random.default_rng(2)
        upd = [(k, bytes(rng.integers(0, 256, len(v), dtype=np.uint8)))
               for k, v in items[::4]]
        assert all(cl3.multi_update(upd)) == all(cl1.multi_update(upd))
        assert cl3.multi_get(keys) == cl1.multi_get(keys)
        for sh in cl3.shards:
            checked, bad = parity_invariant(sh)
            assert bad == 0 and checked > 0

    def test_degraded_decode_matches_s1(self):
        """Decode byte-identity: degraded reads (reconstructed chunks) in
        every shard must equal the unsharded cluster's contents."""
        cl3, cl1 = sharded(shards=3), make_cluster(shards=1, **KW)
        items = seeded_items(600, seed=3)
        keys = [k for k, _ in items]
        for cl in (cl3, cl1):
            for i in range(0, len(items), 32):
                assert all(cl.multi_set(items[i:i + 32]))
        for si in range(cl3.num_shards):   # one failure per shard
            cl3.fail_server(cl3.global_sid(si, 1))
        assert cl3.multi_get(keys) == cl1.multi_get(keys)
        assert cl3.stats["degraded_requests"] > 0
        for si in range(cl3.num_shards):
            cl3.restore_server(cl3.global_sid(si, 1))
        assert cl3.multi_get(keys) == cl1.multi_get(keys)

    def test_ycsb_driver_sharded_matches_unsharded(self):
        cfg = YCSBConfig(num_objects=500)
        cl3, cl1 = sharded(shards=3), make_cluster(shards=1, **KW)
        for cl in (cl3, cl1):
            run_workload(cl, "load", 0, cfg, batch_size=16)
            run_workload(cl, "A", 800, cfg, batch_size=16)
        w = YCSBWorkload(cfg)
        keys = [w.key(i) for i in range(cfg.num_objects)]
        assert cl3.multi_get(keys) == cl1.multi_get(keys)
        assert cl3.net.ops_by_kind.get("MGET", 0) > 0


class TestPipelinedBatches:
    def test_overlap_saves_modeled_time(self):
        cl = sharded(shards=4)
        items = seeded_items(400, seed=7)
        for i in range(0, len(items), 64):
            cl.multi_set(items[i:i + 64])
        saved_after_load = cl.stats["pipeline_overlap_saved_s"]
        assert cl.stats["pipelined_batches"] > 0
        assert saved_after_load > 0
        cl.multi_get([k for k, _ in items[:128]])
        assert cl.stats["pipeline_overlap_saved_s"] > saved_after_load

    def test_merged_latency_is_slowest_shard(self):
        cl = sharded(shards=3, pipeline=True)
        items = seeded_items(96, seed=8)
        cl.multi_set(items)
        shard_t = [sh.net.latencies["MSET"][-1] for sh in cl.shards
                   if sh.net.latencies.get("MSET")]
        assert cl.net.local.latencies["MSET"][-1] == \
            pytest.approx(max(shard_t))

    def test_pipeline_off_is_byte_identical(self):
        cl_p = sharded(shards=3, pipeline=True)
        cl_s = sharded(shards=3, pipeline=False)
        items = seeded_items(300, seed=9)
        assert cl_p.multi_set(items) == cl_s.multi_set(items)
        keys = [k for k, _ in items]
        assert cl_p.multi_get(keys) == cl_s.multi_get(keys)
        assert cl_p.stats["degraded_requests"] == 0

    def test_planner_routes_through_per_shard_proxies(self):
        cl = sharded(shards=3)
        items = seeded_items(240, seed=13)
        for pid in range(cl.num_proxies):
            for i in range(0, len(items), 48):
                cl.multi_set(items[i:i + 48], proxy_id=pid)
        for sh in cl.shards:   # every shard's proxies carried requests
            assert sum(p.requests_begun for p in sh.proxies) > 0
        assert cl.multi_get([k for k, _ in items]) == \
            [v for _, v in items]

    def test_aggregate_net_view(self):
        cl = sharded(shards=2)
        items = seeded_items(64, seed=10)
        cl.multi_set(items)
        cl.multi_get([k for k, _ in items])
        lat = cl.net.latencies
        assert lat["MGET"] and lat["MSET"]
        # facade-merged entries, not per-shard duplicates
        assert len(lat["MGET"]) == cl.net.local.ops_by_kind["MGET"]
        eps = cl.net.bytes_by_endpoint
        assert any(ep.startswith("sh0:s") for ep in eps)
        assert any(ep.startswith("sh1:s") for ep in eps)
        assert set(cl.server_endpoint_names()) <= \
            {f"sh{i}:s{j}" for i in range(2) for j in range(10)}
        assert cl.net.total_bytes() > 0
        cl.net.reset()
        assert cl.net.latencies == {} and cl.net.total_bytes() == 0


class TestShardScopedFailures:
    def test_failure_isolated_to_owning_shard(self):
        cl = sharded(shards=3)
        items = seeded_items(600, seed=11)
        for i in range(0, len(items), 32):
            cl.multi_set(items[i:i + 32])
        t = cl.fail_server(cl.global_sid(1, 2))
        assert t["shard"] == 1 and t["recovered_chunks"] >= 0
        assert cl.failed == {cl.global_sid(1, 2)}
        keys = [k for k, _ in items]
        assert cl.multi_get(keys) == [v for _, v in items]
        assert cl.shards[1].stats["degraded_requests"] > 0
        assert cl.shards[0].stats["degraded_requests"] == 0
        assert cl.shards[2].stats["degraded_requests"] == 0
        # unaffected shards never left NORMAL: no coordinated traffic
        assert not cl.shards[0].coordinator.any_failure()
        assert not cl.shards[2].coordinator.any_failure()
        t = cl.restore_server(cl.global_sid(1, 2))
        assert t["shard"] == 1
        assert cl.failed == set()
        assert cl.multi_get(keys) == [v for _, v in items]

    def test_explicit_shard_kwarg(self):
        cl = sharded(shards=2)
        cl.multi_set(seeded_items(50, seed=12))
        t = cl.fail_server(3, shard=1)
        assert t["shard"] == 1 and cl.failed == {cl.global_sid(1, 3)}
        cl.restore_server(3, shard=1)
        with pytest.raises(ValueError):
            cl.fail_server(0, shard=5)


class TestSeededFaultInjectionBatched:
    """PR-1 fallback logic regression guards: batched requests with a
    failure in *some* shards degrade exactly the affected keys."""

    def _loaded(self, shards=2, n_items=500, seed=21):
        cl = sharded(shards=shards)
        items = seeded_items(n_items, seed=seed)
        for i in range(0, n_items, 32):
            assert all(cl.multi_set(items[i:i + 32]))
        return cl, items

    def test_multi_get_degrades_exactly_affected_keys(self):
        cl, items = self._loaded()
        fsid, fshard = 2, 0
        cl.fail_server(cl.global_sid(fshard, fsid))
        affected = [k for k, _ in items
                    if cl.shard_of(k) == fshard
                    and cl.locate(k)[2] == fsid]
        assert affected   # seed must actually hit the failed server
        base = cl.stats["degraded_requests"]
        got = cl.multi_get([k for k, _ in items])
        assert got == [v for _, v in items]
        assert cl.stats["degraded_requests"] - base == len(affected)
        assert cl.shards[1].stats["degraded_requests"] == 0
        cl.restore_server(cl.global_sid(fshard, fsid))

    def test_multi_update_degrades_exactly_affected_keys(self):
        cl, items = self._loaded(seed=22)
        fsid, fshard = 1, 1
        cl.fail_server(cl.global_sid(fshard, fsid))
        rng = np.random.default_rng(99)
        upd = [(k, bytes(rng.integers(0, 256, len(v), dtype=np.uint8)))
               for k, v in items]
        expected = 0
        for k, _ in upd:
            si, sl, ds = cl.locate(k)
            if si != fshard:
                continue
            if ds == fsid:
                expected += 2   # degraded head-probe GET + degraded UPDATE
            elif fsid in sl.parity_servers:
                expected += 1   # degraded UPDATE only
        assert expected > 0
        base = cl.stats["degraded_requests"]
        assert all(cl.multi_update(upd))
        assert cl.stats["degraded_requests"] - base == expected
        assert cl.shards[0].stats["degraded_requests"] == 0
        cl.restore_server(cl.global_sid(fshard, fsid))
        kv = dict(upd)
        assert cl.multi_get([k for k, _ in items]) == \
            [kv[k] for k, _ in items]
        for sh in cl.shards:
            _, bad = parity_invariant(sh)
            assert bad == 0

    def test_multi_set_degrades_only_affected_shard(self):
        cl, _ = self._loaded(seed=23)
        cl.fail_server(cl.global_sid(0, 4))
        fresh = seeded_items(120, seed=24)
        fresh = [(b"new" + k, v) for k, v in fresh]
        assert all(cl.multi_set(fresh))
        assert cl.multi_get([k for k, _ in fresh]) == [v for _, v in fresh]
        assert cl.shards[1].stats["degraded_requests"] == 0
        cl.restore_server(cl.global_sid(0, 4))
        assert cl.multi_get([k for k, _ in fresh]) == [v for _, v in fresh]
