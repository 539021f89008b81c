"""Row-Diagonal Parity (Corbett et al., FAST 2004) over a prime p: each
chunk is p - 1 sub-blocks; row parity XORs a sub-row across the k data
chunks, and diagonal d XORs sub-block s of virtual column i (data 0..k-1,
row parity at k) wherever (i + s) mod p == d, for d in 0..p-2."""
from __future__ import annotations

import numpy as np


def parity(data: np.ndarray, cfg: dict) -> np.ndarray:
    """(k, C) data chunks -> (2, C): row parity, then diagonal parity."""
    p = cfg["rdp_p"]
    k, C = data.shape
    r = p - 1
    blocks = data.reshape(k, r, C // r)
    row = np.bitwise_xor.reduce(blocks, axis=0)
    diag = np.zeros_like(row)
    for i, col in enumerate(list(blocks) + [row]):
        for s in range(r):
            d = (i + s) % p
            if d != p - 1:
                diag[d] ^= col[s]
    return np.stack([row.reshape(C), diag.reshape(C)])
