"""Elastic ShardedCluster: live stripe migration (add/remove shard),
skew-aware rebalancing, forwarding-table routing, and migration x failure
interleavings — no key may ever be unreadable mid-rebalance."""
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st  # hypothesis or fallback
from repro.core import Rebalancer, make_cluster
from repro.data.ycsb import YCSBConfig, YCSBWorkload, hot_shard_id_map, \
    run_workload
from repro.core.invariants import parity_invariant

KW = dict(num_servers=10, num_proxies=2, scheme="rs", n=4, k=2, c=8,
          chunk_size=256, max_unsealed=2)


def ring_cluster(shards=3, **kw):
    merged = dict(KW)
    merged.update(kw)
    return make_cluster(shards=shards, placement="ring", **merged)


def seeded_items(n, seed=0, sizes=(8, 32)):
    rng = np.random.default_rng(seed)
    return [(b"ek%06d" % i,
             bytes(rng.integers(0, 256, sizes[i % len(sizes)],
                                dtype=np.uint8)))
            for i in range(n)]


def load(cl, items, batch=32):
    for i in range(0, len(items), batch):
        assert all(cl.multi_set(items[i:i + batch]))


class TestScaleOut:
    def test_add_shard_minimal_movement_and_equivalence(self):
        cl = ring_cluster(shards=3)
        items = seeded_items(900, seed=1)
        load(cl, items)
        resident = cl.stored_payload_bytes()
        rep = cl.add_shard()
        assert rep["shard"] == 3 and cl.num_shards == 4
        # consistent hashing: ~1/(S+1) of resident bytes move, with slack
        assert rep["moved_bytes"] / resident <= 1 / 4 + 0.08
        assert rep["pending_left"] == 0
        keys = [k for k, _ in items]
        assert cl.multi_get(keys) == [v for _, v in items]
        # the new shard actually serves data, routed through the placement
        assert len(cl.shards[3].resident_keys()) == rep["moved_keys"] > 0
        assert all(cl.shard_of(k) == 3
                   for k in cl.shards[3].resident_keys())
        for sh in cl.shards:
            _, bad = parity_invariant(sh)
            assert bad == 0

    def test_sealed_objects_move_chunk_wise(self):
        cl = ring_cluster(shards=2)
        items = seeded_items(600, seed=2)
        load(cl, items)
        rep = cl.add_shard()
        # far fewer chunk fetches than moved keys: each source chunk is
        # fetched once and its movers extracted from the chunk bytes
        assert 0 < rep["chunks_fetched"] < rep["moved_keys"]
        assert rep["chunk_fetch_bytes"] == \
            rep["chunks_fetched"] * cl.chunk_size
        # migration traffic is accounted on the merged netsim view
        kinds = cl.net.bytes_by_kind
        assert kinds.get("mig_chunk", 0) == rep["chunk_fetch_bytes"] + \
            cl.net.cost.header_bytes * rep["chunks_fetched"]
        assert kinds.get("mig_obj", 0) > 0
        assert cl.net.latencies.get("MIGRATE")
        assert cl.stats["migration_bytes"] == rep["moved_bytes"]
        assert cl.stats["migrated_keys"] == rep["moved_keys"]

    def test_add_shard_without_migration_forwards(self):
        """migrate=False leaves data in place but must still install the
        forwarding table — the new placement already routes ~1/S of keys
        to the empty shard.  Nothing is ever unreadable in between."""
        cl = ring_cluster(shards=2)
        items = seeded_items(400, seed=3)
        load(cl, items)
        rep = cl.add_shard(migrate=False)
        assert rep["moved_keys"] == 0
        assert rep["pending_left"] == rep["mismatched"] == len(cl._pending) > 0
        keys = [k for k, _ in items]
        assert cl.multi_get(keys) == [v for _, v in items]  # forwarded
        # writes land at the forwarded location too, then migrate later
        assert cl.update(keys[0], items[0][1])
        rb = Rebalancer(cl)
        plan = rb.plan()
        assert plan.mismatched == rep["mismatched"]
        rep2 = rb.execute(plan)
        assert rep2["moved_keys"] == plan.mismatched
        assert rep2["pending_left"] == 0
        assert cl.multi_get(keys) == [v for _, v in items]


class TestScaleIn:
    def test_remove_shard_drains_fully(self):
        cl = ring_cluster(shards=3)
        items = seeded_items(700, seed=4)
        load(cl, items)
        rep = cl.remove_shard(1)
        assert rep["shard"] == 1 and rep["pending_left"] == 0
        assert cl.shards[1].resident_keys() == []
        assert 1 not in cl.placement.shard_ids and 1 in cl.retired
        keys = [k for k, _ in items]
        assert cl.multi_get(keys) == [v for _, v in items]
        assert all(cl.shard_of(k) != 1 for k in keys)
        with pytest.raises(ValueError):
            cl.remove_shard(1)   # already retired

    def test_scale_out_then_back_in(self):
        """Add a shard, then retire it again: the round trip must not
        lose or resurrect anything (the drain is physical)."""
        cl = ring_cluster(shards=2)
        items = seeded_items(500, seed=5)
        load(cl, items)
        dead = items[3][0]
        assert cl.delete(dead)
        cl.add_shard()
        cl.remove_shard(2)
        keys = [k for k, _ in items]
        got = cl.multi_get(keys)
        for (k, v), g in zip(items, got):
            assert g == (None if k == dead else v)


class TestLiveMigration:
    def test_requests_succeed_mid_migration(self):
        cl = ring_cluster(shards=2)
        items = seeded_items(600, seed=6)
        load(cl, items)
        state = dict(items)
        rng = np.random.default_rng(60)
        steps = 0

        def cb(p):
            nonlocal steps
            steps += 1
            probe = [k for k, _ in items[::5]]
            assert cl.multi_get(probe) == [state[k] for k in probe]
            # writes + deletes keep landing wherever the key lives now
            k_upd = items[(7 * p["batch"]) % len(items)][0]
            if state.get(k_upd) is not None:
                nv = bytes(rng.integers(0, 256, len(state[k_upd]),
                                        dtype=np.uint8))
                assert cl.update(k_upd, nv)
                state[k_upd] = nv
            k_new = b"live%05d" % p["batch"]
            v_new = bytes(rng.integers(0, 256, 16, dtype=np.uint8))
            assert cl.set(k_new, v_new)
            state[k_new] = v_new
            k_del = items[(11 * p["batch"]) % len(items)][0]
            if state.get(k_del) is not None:
                assert cl.delete(k_del)
                state[k_del] = None

        rep = cl.add_shard(batch_size=48, step_cb=cb)
        assert steps >= 2 and rep["moved_keys"] > 0
        for key, want in state.items():
            assert cl.get(key) == want
        for sh in cl.shards:
            _, bad = parity_invariant(sh)
            assert bad == 0

    def test_max_moves_cap_and_followup(self):
        cl = ring_cluster(shards=2)
        items = seeded_items(500, seed=7)
        load(cl, items)
        rep = cl.add_shard(max_moves=60)
        assert rep["moved_keys"] == 60
        assert rep["pending_left"] == rep["mismatched"] - 60 > 0
        keys = [k for k, _ in items]
        # uncapped remainder stays forwarded — everything readable
        assert cl.multi_get(keys) == [v for _, v in items]
        rep2 = Rebalancer(cl).run()
        assert rep2["pending_left"] == 0
        assert rep2["moved_keys"] == rep["mismatched"] - 60
        assert cl.multi_get(keys) == [v for _, v in items]

    def test_large_objects_move_logically(self):
        cl = ring_cluster(shards=2, chunk_size=256)
        items = seeded_items(150, seed=8)
        load(cl, items)
        rng = np.random.default_rng(80)
        big = {b"big%04d" % i: bytes(rng.integers(0, 256, 700,
                                                  dtype=np.uint8))
               for i in range(6)}
        for k, v in big.items():
            assert cl.set(k, v)
        cl.add_shard()
        Rebalancer(cl).run()   # idempotent follow-up: nothing mismatched
        for k, v in {**dict(items), **big}.items():
            assert cl.get(k) == v
        # fragments live with their manifest's shard, never alone
        for k in big:
            si = cl.shard_of(k)
            assert cl.shards[si].get(k) == big[k]


class TestMigrationFailureInterleaving:
    def test_seeded_failure_mid_migration(self):
        """The satellite scenario: fail_server lands in the middle of a
        live migration; movers on the lost server resolve through the
        batched-decode reconstruction cache and every key stays readable
        at every step."""
        cl = ring_cluster(shards=2)
        items = seeded_items(600, seed=9)
        load(cl, items)
        keys = [k for k, _ in items]
        expect = [v for _, v in items]
        events = []

        def cb(p):
            if p["batch"] == 1:
                # fail the source server with the most sealed chunks
                victim = max(range(cl.servers_per_shard),
                             key=lambda s: sum(cl.shards[0].servers[s].sealed))
                cl.fail_server(victim, shard=0)
                events.append(("fail", victim))
            if p["batch"] == 3 and events:
                cl.restore_server(events[0][1], shard=0)
                events.append(("restore",))
            assert cl.multi_get(keys) == expect, \
                f"key unreadable mid-rebalance at step {p}"

        rep = cl.add_shard(batch_size=24, step_cb=cb)
        assert [e[0] for e in events] == ["fail", "restore"]
        assert rep["moved_keys"] > 0 and rep["pending_left"] == 0
        assert cl.multi_get(keys) == expect
        assert cl.failed == set()

    def test_failure_in_destination_shard(self):
        cl = ring_cluster(shards=2)
        items = seeded_items(400, seed=10)
        load(cl, items)
        keys = [k for k, _ in items]
        expect = [v for _, v in items]

        def cb(p):
            if p["batch"] == 1:
                cl.fail_server(1, shard=2)   # new shard degraded mid-move
            assert cl.multi_get(keys) == expect

        rep = cl.add_shard(batch_size=32, step_cb=cb)
        assert rep["pending_left"] == 0
        assert cl.multi_get(keys) == expect
        cl.restore_server(1, shard=2)
        assert cl.multi_get(keys) == expect

    def test_migration_of_already_degraded_shard(self):
        """fail first, migrate second: movers come out of the redirected
        server's recon cache (batched decode at fail time)."""
        cl = ring_cluster(shards=2)
        items = seeded_items(500, seed=11)
        load(cl, items)
        victim = max(range(cl.servers_per_shard),
                     key=lambda s: sum(cl.shards[0].servers[s].sealed))
        t = cl.fail_server(victim, shard=0)
        assert t["recovered_chunks"] > 0
        rep = cl.add_shard()
        keys = [k for k, _ in items]
        assert cl.multi_get(keys) == [v for _, v in items]
        assert rep["pending_left"] == 0
        cl.restore_server(victim, shard=0)
        assert cl.multi_get(keys) == [v for _, v in items]

    @settings(max_examples=5, deadline=None)
    @given(st.data())
    def test_interleaving_property(self, data):
        """Property: random interleavings of {fail, restore, update, add
        traffic} with migration batches never make a key unreadable."""
        cl = ring_cluster(shards=2)
        items = seeded_items(300, seed=12)
        load(cl, items)
        state = dict(items)
        rng = np.random.default_rng(120)
        failed = []

        def cb(p):
            act = data.draw(st.sampled_from(
                ["none", "fail", "restore", "update"]), label="act")
            if act == "fail" and not failed:
                sh = data.draw(st.integers(min_value=0, max_value=2),
                               label="shard")
                if sh < cl.num_shards:
                    sid = data.draw(st.integers(
                        min_value=0, max_value=cl.servers_per_shard - 1),
                        label="sid")
                    cl.fail_server(sid, shard=sh)
                    failed.append((sh, sid))
            elif act == "restore" and failed:
                sh, sid = failed.pop()
                cl.restore_server(sid, shard=sh)
            elif act == "update":
                k = items[data.draw(st.integers(
                    min_value=0, max_value=len(items) - 1), label="i")][0]
                nv = bytes(rng.integers(0, 256, len(state[k]),
                                        dtype=np.uint8))
                assert cl.update(k, nv)
                state[k] = nv
            probe = [k for k, _ in items[::9]]
            assert cl.multi_get(probe) == [state[k] for k in probe], \
                "key unreadable mid-rebalance"

        cl.add_shard(batch_size=40, step_cb=cb)
        while failed:
            sh, sid = failed.pop()
            cl.restore_server(sid, shard=sh)
        assert cl.multi_get([k for k, _ in items]) == \
            [state[k] for k, _ in items]


class TestSkewRebalance:
    def _hot_loaded(self, seed=13):
        cl = ring_cluster(shards=3)
        cfg = YCSBConfig(num_objects=900, seed=seed)
        run_workload(cl, "load", 0, cfg, batch_size=16)
        return cl, cfg

    def test_skew_metric_and_snapshot(self):
        cl, cfg = self._hot_loaded()
        cl.reset_load()
        assert cl.load_skew() == 1.0   # no traffic -> neutral
        run_workload(cl, "B", 400, cfg, batch_size=16, hot_shard=0)
        snap = cl.net.snapshot()
        assert snap["shard_ops"] == cl.shard_ops
        assert snap["load_skew"] == cl.load_skew() == \
            cl.stats["load_skew"] > 1.0
        assert max(cl.shard_ops) == cl.shard_ops[0]

    def test_rebalance_reduces_skew(self):
        cl, cfg = self._hot_loaded(seed=14)
        id_map = hot_shard_id_map(cl, cfg, hot_shard=1)
        cl.reset_load()
        run_workload(cl, "B", 500, cfg, batch_size=16, id_map=id_map)
        before = cl.load_skew()
        assert before > 1.25
        rep = cl.rebalance(skew_threshold=1.25)
        assert rep["moved_keys"] > 0
        assert rep["weights"][1] < 1.0   # hot shard shed arcs
        run_workload(cl, "B", 500, cfg, batch_size=16, id_map=id_map)
        assert cl.load_skew() < before
        w = YCSBWorkload(cfg)
        keys = [w.key(i) for i in range(cfg.num_objects)]
        assert all(v is not None for v in cl.multi_get(keys))

    def test_rebalance_below_threshold_is_noop(self):
        cl, _ = self._hot_loaded(seed=15)
        cl.reset_load()
        rep = cl.rebalance(skew_threshold=1.25)
        assert rep["moved_keys"] == 0 and "skipped" in rep

    def test_mod_placement_reports_unsupported(self):
        cl = make_cluster(shards=2, placement="mod", **KW)
        items = seeded_items(200, seed=16)
        load(cl, items)
        hot = [k for k, _ in items if cl.shard_of(k) == 0]
        cl.reset_load()
        for _ in range(10):
            cl.multi_get(hot)
        rep = cl.rebalance(skew_threshold=1.1)
        assert rep["moved_keys"] == 0
        assert "does not support" in rep["skipped"]
        assert cl.multi_get(hot) == [dict(items)[k] for k in hot]


class TestDriverIntegration:
    def test_ycsb_under_scaling_matches_reference(self):
        """The verify.sh smoke's core: scale S=2 -> 3 under a running
        YCSB window; final contents byte-identical to an unscaled
        reference serving the same stream."""
        cfg = YCSBConfig(num_objects=500, seed=17)
        ref = ring_cluster(shards=2)
        cl = ring_cluster(shards=2)
        for c in (ref, cl):
            run_workload(c, "load", 0, cfg, batch_size=16)
            run_workload(c, "A", 400, cfg, batch_size=16)

        def cb(p):
            # the window keeps running against both clusters mid-move
            for c in (ref, cl):
                run_workload(c, "C", 60, YCSBConfig(num_objects=500,
                                                    seed=17 + p["batch"]),
                             batch_size=16)

        cl.add_shard(batch_size=32, step_cb=cb)
        w = YCSBWorkload(cfg)
        keys = [w.key(i) for i in range(cfg.num_objects)]
        assert cl.multi_get(keys) == ref.multi_get(keys)

    @pytest.mark.slow
    def test_soak_scale_out_in_under_churn(self):
        """Long soak: repeated add/remove/rebalance under workload A
        churn with a failure window, asserting byte-identity against an
        inelastic reference throughout."""
        cfg = YCSBConfig(num_objects=1200, seed=18)
        ref = ring_cluster(shards=2)
        cl = ring_cluster(shards=2)
        for c in (ref, cl):
            run_workload(c, "load", 0, cfg, batch_size=16)
        w = YCSBWorkload(cfg)
        keys = [w.key(i) for i in range(cfg.num_objects)]

        def churn(c, seed):
            run_workload(c, "A", 300, YCSBConfig(num_objects=1200,
                                                 seed=seed), batch_size=16)

        for round_i in range(3):
            for c in (ref, cl):
                churn(c, 100 + round_i)
            cl.add_shard(step_cb=lambda p: None)
            assert cl.multi_get(keys) == ref.multi_get(keys)
            cl.fail_server(2, shard=round_i % cl.num_shards)
            for c in (ref, cl):
                churn(c, 200 + round_i)
            cl.restore_server(2, shard=round_i % cl.num_shards)
            cl.remove_shard(cl.num_shards - 1)
            assert cl.multi_get(keys) == ref.multi_get(keys)
            rep = cl.rebalance(skew_threshold=1.05, max_moves=150)
            for c in (ref, cl):
                churn(c, 300 + round_i)
            assert cl.multi_get(keys) == ref.multi_get(keys)
        for sh in cl.shards:
            _, bad = parity_invariant(sh)
            assert bad == 0
