"""What decides ``correct``: the store's answers and bytes against a plain
reference.

- Reads: every GET answer equals the last acknowledged write of its key
  (``driver.CheckedClient`` holds the dict of acknowledged writes).
- Parity: every stripe's stored parity equals the configuration's code
  (``bench/codes/<scheme>.py``) applied to the stripe's stored data chunks,
  unsealed chunks counting as zeros.
- Recovery: every chunk a failed server's recovery rebuilt equals the
  bytes the server held, and each object in a rebuilt data chunk equals
  its acknowledged value.
- Paths: every coding op ran on the expected dispatch path, and every op
  the cell must exercise ran.

Each comparison is exact: its limit is 0.
"""
from __future__ import annotations

import numpy as np

from bench import spec


def stripes(cluster) -> list[tuple[int, int]]:
    """Every (stripe list, stripe) holding a sealed data or parity chunk."""
    out = set()
    for srv in cluster.servers:
        for idx, cid in enumerate(srv.chunk_ids):
            if cid is not None and srv.sealed[idx]:
                out.add((cid.stripe_list_id, cid.stripe_id))
    return sorted(out)


def check_parity(cluster, cfg: dict) -> tuple[int, int]:
    """(stripes checked, stripes whose parity differs from the code's)."""
    from repro.core.chunk import ChunkId
    code = spec.code(cfg["scheme"])
    k, n, C = cfg["k"], cfg["n"], cfg["chunk_size"]
    zeros = np.zeros(C, np.uint8)

    def chunk(sid: int, cid) -> np.ndarray:
        c = cluster.servers[sid].get_sealed_chunk(cid)
        return zeros if c is None else c

    checked = bad = 0
    for lid, st in stripes(cluster):
        sl = cluster.stripe_lists[lid]
        data = np.stack([chunk(sl.data_servers[i], ChunkId(lid, st, i))
                         for i in range(k)])
        par = np.stack([chunk(sl.parity_servers[j], ChunkId(lid, st, k + j))
                        for j in range(n - k)])
        checked += 1
        bad += not np.array_equal(code.parity(data, cfg), par)
    return checked, bad


def sealed_chunks(cluster, sid: int) -> list:
    """(chunk id, slot) of every sealed chunk server ``sid`` holds."""
    srv = cluster.servers[sid]
    return [(cid, idx) for idx, cid in enumerate(srv.chunk_ids)
            if cid is not None and srv.sealed[idx]]


def snapshot_recovery(cluster, sid: int, acked: dict) -> list[tuple]:
    """After ``fail_server(sid)``: for each sealed chunk of ``sid``, the
    bytes it held, the rebuilt bytes, and each live object of the rebuilt
    chunk with its value offset and the value acknowledged at this moment
    (copies, compared once the window has closed).  A chunk that was not
    rebuilt appears with ``None``."""
    srv = cluster.servers[sid]
    out = []
    for cid, idx in sealed_chunks(cluster, sid):
        sl = cluster.stripe_lists[cid.stripe_list_id]
        r = cluster.coordinator.redirected_server(sl, sid)
        rs = cluster.redirect.get(r)
        rc = rs.recon.get(cid.key()) if rs is not None else None
        if rc is None:
            out.append((srv.region[idx].copy(), None, []))
            continue
        objects = [(off + 4 + ksz, vsz, acked.get(key))
                   for key, (off, ksz, vsz, deleted)
                   in (rc.objects or {}).items() if not deleted]
        out.append((srv.region[idx].copy(), rc.buf.copy(), objects))
    return out


def check_recovered(snapshots: list[tuple]) -> tuple[int, int]:
    """(chunks checked, chunks rebuilt wrong or not at all)."""
    bad = 0
    for held, rebuilt, objects in snapshots:
        if rebuilt is None or not np.array_equal(held, rebuilt) or any(
                want is not None and rebuilt[vo: vo + vsz].tobytes() != want
                for vo, vsz, want in objects):
            bad += 1
    return len(snapshots), bad


def check_paths(op_paths: dict, expected: str, required) -> tuple[int, int]:
    """(ops off the expected path, required ops never run)."""
    off = sum(1 for p in op_paths.values() if p != expected)
    return off, len(set(required) - set(op_paths))


def read_back(client, keys: list[bytes], num_proxies: int,
              batch: int = 256) -> int:
    """GET every key in ``keys`` through the client (which counts wrong
    answers); returns the number read."""
    for s in range(0, len(keys), batch):
        client.multi_get(keys[s: s + batch], (s // batch) % num_proxies)
    return len(keys)
