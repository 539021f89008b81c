"""A single-key request is a batch of one.

Each normal-mode operation has one body in the store (``_get_batch``,
``_set_batch``, ``_mutate_batch``).  Driving a key through the single-key
API and its twin cluster through the ``multi_*`` API with that one key
must leave the same answers, data and parity bytes, revert logs, proxy
sequence numbers, counters and device dispatches — on RS and RDP, for a
key in an unsealed chunk, in a sealed chunk, and a missing key.
"""
import hashlib

import numpy as np
import pytest

from repro.core import MemECCluster
from repro.core.invariants import parity_invariant

KW = dict(num_servers=16, n=10, k=8, c=4, chunk_size=512, max_unsealed=1,
          verify_rebuild=True, engine="pallas", async_engine=False,
          redundant_reads=0)


def make(scheme, hot=0.0):
    return MemECCluster(scheme=scheme, hot_key_threshold=hot, **KW)


def load(cl, n=400, seed=3):
    rng = np.random.default_rng(seed)
    items = [(b"k%07d" % i, bytes(rng.integers(0, 256, (8, 32)[i % 2],
                                               dtype=np.uint8)))
             for i in range(n)]
    for i in range(0, n, 16):
        assert all(cl.multi_set(items[i:i + 16], proxy_id=0))
    return dict(items)


def pick(cl, kv, state):
    """A loaded key whose chunk is sealed / unsealed, or a missing key."""
    if state == "missing":
        return b"nokey000", None
    for key, value in kv.items():
        _, ds = cl.mapper.data_server_for(key)
        ref = cl.servers[ds].lookup(key)
        if cl.servers[ds].sealed[ref.chunk_local_idx] == (state == "sealed"):
            return key, value
    raise AssertionError(f"no {state} key loaded")


def state(cl):
    """Everything a normal-mode request may change, bar modeled time
    (recorded under different kinds: GET vs MGET)."""
    servers = []
    for s in cl.servers:
        servers.append((
            [bytes(r) for r in s.region], list(s.chunk_ids), list(s.sealed),
            sorted(s.object_index.keys()),
            sorted(s.temp_replicas.items()), sorted(s.replica_iseq.items()),
            sorted((pid, [(r.seq, r.local_idx, r.offset, bytes(r.applied),
                           r.key, r.old_value, r.old_deleted) for r in recs])
                   for pid, recs in s.delta_buffer.items())))
    stats = {k: v for k, v in cl.stats.items() if k != "latency"}
    return dict(servers=servers, stats=stats,
                proxies=[(p.seq, p.ack_watermark, sorted(p.pending),
                          p.requests_begun) for p in cl.proxies],
                dispatches=cl.engine.device_dispatches,
                invariant=parity_invariant(cl))


def delete_batch_of_one(cl, key):
    """There is no ``multi_delete``: drive the batch core the way the
    ``multi_*`` API does — the batched head probe, then one entry."""
    cl.multi_get([key])
    sl, ds = cl.mapper.data_server_for(key)
    ok, t = cl._mutate_batch("delete", cl.proxies[0], [(key, None, sl, ds)])
    cl.net.record("MDELETE", t)
    return ok[0]


def run_single(cl, op, key, value):
    if op == "get":
        return cl.get(key)
    if op == "set":
        return cl.set(key, value)
    if op == "update":
        return cl.update(key, value)
    return cl.delete(key)


def run_batch(cl, op, key, value):
    if op == "get":
        return cl.multi_get([key])[0]
    if op == "set":
        return cl.multi_set([(key, value)])[0]
    if op == "update":
        return cl.multi_update([(key, value)])[0]
    return delete_batch_of_one(cl, key)


CASES = [(scheme, op, st)
         for scheme in ("rs", "rdp")
         for op in ("get", "set", "set_resize", "update", "delete")
         for st in ("unsealed", "sealed", "missing")
         if not (op == "set_resize" and st == "missing")]
CASES += [(scheme, "update_hot", "sealed") for scheme in ("rs", "rdp")]


@pytest.mark.parametrize("scheme,op,key_state", CASES)
def test_batch_of_one_matches_single_key(scheme, op, key_state):
    hot = op == "update_hot"
    single, batch = (make(scheme, hot=1.5 if hot else 0.0)
                     for _ in range(2))
    kv = load(single)
    assert load(batch) == kv
    key, old = pick(single, kv, key_state)
    rng = np.random.default_rng(11)
    size = len(old) if old is not None else 16
    if op == "set_resize":
        size = 40 - size
    # a hot key's UPDATEs buffer once it is hot: send several
    rounds = 6 if hot else 1
    values = [bytes(rng.integers(0, 256, size, dtype=np.uint8))
              for _ in range(rounds)]
    base = "update" if hot else op.replace("_resize", "")
    answers = []
    for cl, run in ((single, run_single), (batch, run_batch)):
        answers.append([run(cl, base, key, v) for v in values])
        answers[-1].append(cl.get(key))   # read back through one path
    assert answers[0] == answers[1]
    if hot:
        assert single.stats["hot_tier"]["buffered_updates"] > 0
        for cl in (single, batch):
            cl.flush_hot_buffers()
    s, b = state(single), state(batch)
    assert s["invariant"][1] == 0
    for field in ("servers", "proxies", "stats", "dispatches", "invariant"):
        assert s[field] == b[field], field


def mixed_run(cl, n_ops=400, seed=5):
    """Seeded single-key traffic over loaded keys: GET (hits and
    misses), UPDATE, DELETE, upserts of the same and of another size."""
    rng = np.random.default_rng(seed)
    keys = [b"m%07d" % i for i in range(300)]
    sizes = {}
    for i, key in enumerate(keys):
        sizes[key] = (8, 32)[i % 2]
        cl.set(key, bytes(rng.integers(0, 256, sizes[key], dtype=np.uint8)),
               proxy_id=i % 4)
    live = set(keys)
    for step in range(n_ops):
        key = keys[int(rng.integers(0, len(keys)))]
        pid = int(rng.integers(0, 4))
        r = int(rng.integers(0, 10))
        if r < 3:
            cl.get(key if r else b"absent%d" % step, pid)
        elif key not in live:
            cl.set(key, bytes(sizes[key]), pid)
            live.add(key)
        elif r < 7:
            cl.update(key, bytes(rng.integers(0, 256, sizes[key],
                                              dtype=np.uint8)), pid)
        elif r == 7:
            cl.delete(key, pid)
            live.discard(key)
        else:
            if r == 9:
                sizes[key] = 40 - sizes[key]
            cl.set(key, bytes(rng.integers(0, 256, sizes[key],
                                           dtype=np.uint8)), pid)


# latency_summary() of mixed_run — (count, mean, p50, p99, p999) in
# modeled seconds — as the store gave it before its single-key requests
# became batches of one
PINNED = {
    "rs": {
        "DELETE": (89, 0.0006069439280898876, 0.0006067679999999999,
                   0.0006094416, 0.0006094416),
        "GET": (298, 0.0004045863892617449, 0.000404512,
                0.000404704, 0.000404704),
        "SET": (365, 0.00040758117260273973, 0.000404672,
                0.0006103408000000001, 0.000610458592),
        "UPDATE": (185, 0.0006072704691891892, 0.00060728,
                   0.0006096256, 0.0006096256),
    },
    "rdp": {
        "DELETE": (89, 0.0006073581303370786, 0.0006067679999999999,
                   0.0006155856000000001, 0.0006155856000000001),
        "GET": (298, 0.0004045863892617449, 0.000404512,
                0.000404704, 0.000404704),
        "SET": (365, 0.0004076232547945205, 0.000404672,
                0.0006134128, 0.000613530592),
        "UPDATE": (185, 0.0006077022097297297, 0.00060728,
                   0.0006157695999999999, 0.0006157695999999999),
    },
}


def digest(cl):
    """sha256 over every server's chunk bytes and revert log and every
    proxy's sequence number and ack watermark."""
    h = hashlib.sha256()
    for s in cl.servers:
        for r in s.region:
            h.update(bytes(r))
        for pid in sorted(s.delta_buffer):
            for r in s.delta_buffer[pid]:
                h.update(repr((pid, r.seq, r.local_idx, r.offset, r.key,
                               r.old_value, r.old_deleted)).encode())
                h.update(bytes(r.applied))
    for p in cl.proxies:
        h.update(repr((p.seq, p.ack_watermark)).encode())
    return h.hexdigest()[:16]


# digest() after mixed_run, from the same store
PINNED_DIGEST = {"rs": "cffdeede30c0ebcd", "rdp": "b9dfc8bf6a12f632"}


@pytest.mark.parametrize("scheme", ["rs", "rdp"])
def test_single_key_run_pinned(scheme):
    cl = MemECCluster(scheme=scheme, **dict(KW, engine="numpy"))
    mixed_run(cl)
    assert digest(cl) == PINNED_DIGEST[scheme]
    assert parity_invariant(cl)[1] == 0
    got = cl.net.latency_summary()
    assert sorted(got) == sorted(PINNED[scheme])
    for kind, (count, *secs) in PINNED[scheme].items():
        assert got[kind]["count"] == count, kind
        assert [got[kind][q] for q in ("mean_s", "p50_s", "p99_s", "p999_s")
                ] == pytest.approx(secs, rel=1e-12, abs=0), kind


@pytest.mark.parametrize("kind", ["update", "delete"])
def test_missing_key_mutation_takes_ack_leg(kind):
    """A single-key UPDATE or DELETE of a missing key is charged its
    request leg and its ack leg, exactly as in a batch."""
    cl = MemECCluster(scheme="rs", **dict(KW, engine="numpy"))
    value = b"abcd" if kind == "update" else None
    key = b"nothing!"
    ok = (cl.update(key, value) if kind == "update" else cl.delete(key))
    assert ok is False
    cost = cl.net.cost
    req = cost.leg(len(key) + (len(value) if value else 0))
    assert cl.net.latencies[kind.upper()] == [req + cost.leg(8)]
    assert all(p.seq == 0 for p in cl.proxies[1:])
    assert cl.proxies[0].ack_watermark == cl.proxies[0].seq == 1
