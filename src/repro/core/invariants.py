"""Whole-cluster parity check: every sealed data chunk must decode from
the rest of its stripe.

The decode runs through the numpy ``Code`` classes, never through the
cluster's coding engine, so it checks a device engine's parity against
the reference implementation.
"""
from __future__ import annotations

import numpy as np

from .chunk import ChunkId


def parity_invariant(cluster, sample: int | None = None,
                     seed: int = 0) -> tuple[int, int]:
    """Decode sealed data chunks from their stripes; returns (checked, bad).

    ``sample`` caps the check at that many chunks, drawn without
    replacement (``seed``); None checks every sealed data chunk.  Chunks
    of a stripe that are not sealed count as zeros, as they do in parity.
    """
    cs = cluster.chunk_size
    chunks = [(s, idx, cid) for s in cluster.servers
              for idx, cid in enumerate(s.chunk_ids)
              if cid is not None and s.sealed[idx]
              and cid.position < cluster.k]
    if sample is not None and sample < len(chunks):
        pick = np.random.default_rng(seed).choice(len(chunks), sample,
                                                  replace=False)
        chunks = [chunks[i] for i in sorted(pick)]
    bad = 0
    for s, idx, cid in chunks:
        sl = cluster.stripe_lists[cid.stripe_list_id]
        avail = {}
        for i in range(cluster.n):
            if i == cid.position:
                continue
            c = cluster.servers[sl.servers[i]].get_sealed_chunk(
                ChunkId(cid.stripe_list_id, cid.stripe_id, i))
            avail[i] = c if c is not None else np.zeros(cs, np.uint8)
        rec = cluster.code.decode(avail, [cid.position], cs)[cid.position]
        bad += 0 if np.array_equal(rec, s.region[idx]) else 1
    return len(chunks), bad
