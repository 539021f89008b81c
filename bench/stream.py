"""Open-loop YCSB op streams, generated from a seed before a window opens.

The Zipf sampler is copied from the repository's YCSB driver
(``src/repro/data/ycsb.py``) so that a change to the program cannot move
the yardstick; a traffic file gives its mix as shares of ``get``,
``update`` and ``set`` (YCSB-A: 0.5 / 0.5, YCSB-B: 0.95 / 0.05).  Two
departures from YCSB make runs of different seeds do the same amount of
work: a window of ``seconds`` at ``rate`` holds exactly
``round(rate * seconds)`` ops (a Poisson process conditioned on its
count: sorted uniform due times), and each kind's share of the ops is
exact, in a seeded order.
"""
from __future__ import annotations

import dataclasses

import numpy as np

KINDS = ("get", "update", "set")
GET, UPDATE, SET = range(3)


class ZipfGenerator:
    """Classic YCSB zeta-based Zipfian over [0, n)."""

    def __init__(self, n: int, theta: float, rng: np.random.Generator):
        self.n = n
        self.theta = theta
        self.rng = rng
        self.zetan = np.sum(1.0 / np.power(np.arange(1, n + 1), theta))
        self.alpha = 1.0 / (1.0 - theta)
        zeta2 = np.sum(1.0 / np.power(np.arange(1, 3), theta))
        self.eta = (1 - (2.0 / n) ** (1 - theta)) / (1 - zeta2 / self.zetan)

    def sample(self, size: int) -> np.ndarray:
        u = self.rng.random(size)
        uz = u * self.zetan
        out = np.empty(size, dtype=np.int64)
        cut1 = uz < 1.0
        cut2 = (~cut1) & (uz < 1.0 + 0.5 ** self.theta)
        out[cut1] = 0
        out[cut2] = 1
        rest = ~(cut1 | cut2)
        out[rest] = (self.n * np.power(self.eta * u[rest] - self.eta + 1,
                                       self.alpha)).astype(np.int64)
        return np.clip(out, 0, self.n - 1)


def key(i: int) -> bytes:
    return b"user%019d" % i   # 24 bytes, YCSB-style


def value_size(cfg: dict, i: int) -> int:
    sizes = cfg["value_sizes"]
    return sizes[i % len(sizes)]


@dataclasses.dataclass
class Stream:
    """``due`` seconds from the window's start, one entry per op."""
    due: np.ndarray          # float64, sorted
    kind: np.ndarray         # int8: GET | UPDATE | SET
    ids: np.ndarray          # int64 object ids
    values: list             # bytes for writes, None for reads

    def __len__(self) -> int:
        return len(self.due)


def rng_for(seed: int, purpose: int) -> np.random.Generator:
    """Independent streams of one seed: 0 data, 1 warm-up, 2 window,
    3 sweep.  ``seed`` may exceed 32 bits."""
    return np.random.default_rng([int(seed), purpose])


def load_values(cfg: dict, rng: np.random.Generator) -> list[bytes]:
    """The value of every object the load phase writes."""
    n = cfg["objects"]
    blob = rng.bytes(n * max(cfg["value_sizes"]))
    w = max(cfg["value_sizes"])
    return [blob[i * w: i * w + value_size(cfg, i)] for i in range(n)]


def make_stream(cfg: dict, traffic: dict, rate: float, seconds: float,
                rng: np.random.Generator) -> Stream:
    """``round(rate * seconds)`` ops of the traffic's mix over
    ``seconds``, keys drawn Zipf(theta) over the loaded objects."""
    n = int(round(rate * seconds))
    mix = traffic["mix"]
    counts = [int(round(mix.get(k, 0.0) * n)) for k in KINDS]
    counts[KINDS.index(max(mix, key=mix.get))] += n - sum(counts)
    kind = rng.permutation(np.repeat(np.arange(3, dtype=np.int8), counts))
    due = np.sort(rng.random(n)) * seconds
    ids = ZipfGenerator(cfg["objects"], traffic["zipf_theta"],
                        rng).sample(n)
    w = max(cfg["value_sizes"])
    blob = rng.bytes(n * w)
    values: list = [None] * n
    for t in np.flatnonzero(kind != GET):
        values[t] = blob[t * w: t * w + value_size(cfg, int(ids[t]))]
    return Stream(due, kind, ids, values)
