"""GF(2^8) over the polynomial x^8 + x^4 + x^3 + x^2 + 1 (0x11D), the
field RS deployments (ISA-L, jerasure) use, built from scratch."""
from __future__ import annotations

import numpy as np

POLY = 0x11D


def _tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(512, np.int64)
    log = np.zeros(256, np.int64)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    exp[255:510] = exp[:255]
    return exp, log


EXP, LOG = _tables()
MUL = np.zeros((256, 256), np.uint8)
MUL[1:, 1:] = EXP[(LOG[1:, None] + LOG[None, 1:]) % 255]


def inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    return int(EXP[255 - LOG[a]])


def matmul(A: np.ndarray, D: np.ndarray) -> np.ndarray:
    """(m, k) x (k, C) over GF(2^8), one table lookup per product."""
    out = np.zeros((A.shape[0], D.shape[1]), np.uint8)
    for j in range(A.shape[0]):
        for i in range(A.shape[1]):
            if A[j, i]:
                out[j] ^= MUL[A[j, i]][D[i]]
    return out
