"""Sizes drawn from a seed so that every seed gets the same multiset and
only their order changes: the i-th of ``n`` values is the distribution's
quantile at ``(i + 0.5) / n``, and the seed permutes them."""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.Generator(np.random.PCG64([int(seed) & (2**64 - 1), stream]))


def lognormal(spec: dict, n: int) -> np.ndarray:
    """``n`` integer lengths: log-normal with ``median`` and ``sigma``,
    clipped to ``[min, max]``."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


class Cycle:
    """Endless draws from a stratified pool: each pass over the ``n``
    values is a fresh permutation from ``gen``."""

    def __init__(self, values: np.ndarray, gen: np.random.Generator):
        self.values, self.gen, self.order, self.i = values, gen, [], 0

    def next(self):
        if self.i == len(self.order):
            self.order, self.i = self.gen.permutation(self.values), 0
        self.i += 1
        return self.order[self.i - 1]


def prompt_tokens(gen: np.random.Generator, n: int, vocab: int) -> list[int]:
    return gen.integers(0, vocab, size=int(n)).tolist()
