"""Systematic Reed-Solomon over GF(2^8) with a Cauchy parity matrix:
parity row j, data column i holds 1 / ((k + j) XOR i)."""
from __future__ import annotations

import numpy as np

from bench.codes import gf256


def parity_matrix(n: int, k: int) -> np.ndarray:
    return np.array([[gf256.inv((k + j) ^ i) for i in range(k)]
                     for j in range(n - k)], np.uint8)


def parity(data: np.ndarray, cfg: dict) -> np.ndarray:
    """(k, C) data chunks -> (n - k, C) parity chunks."""
    return gf256.matmul(parity_matrix(cfg["n"], cfg["k"]), data)
