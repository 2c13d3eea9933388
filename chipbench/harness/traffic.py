"""Shared pieces of the traffic generators: fixed pools of lengths and gaps.

Every seed gets the same pool of sizes (quantiles of the stated
distribution) in an order the seed chooses, so runs with different seeds do
the same work in another order and the seed moves only the order and the
token ids.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np


@dataclass
class Spec:
    """One request the generator hands the harness."""

    due_s: float | None        # scheduled arrival, seconds after the origin
    prompt: np.ndarray         # (S,) int32 token ids
    max_new: int


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_pool(p: dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of a lognormal with the
    given median and sigma, clipped to [min, max] and rounded up to a
    multiple of ``bucket`` (1 when absent)."""
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    x = p["median"] * np.exp(p["sigma"] * z)
    x = np.clip(np.ceil(x), p["min"], p["max"])
    b = int(p.get("bucket", 1))
    return (np.ceil(x / b) * b).astype(np.int64)


def exponential_pool(rate: float, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps at evenly spaced quantiles of an exponential
    distribution with the given rate (mean gap 1/rate)."""
    return np.array([-math.log(1.0 - q) / rate for q in _quantiles(n)])


def buckets(p: dict) -> list[int]:
    """Every prompt length the pool can produce."""
    b = int(p.get("bucket", 1))
    lo = -(-int(p["min"]) // b) * b
    return list(range(lo, int(p["max"]) + 1, b))


class Cycle:
    """Endless draws from a fixed pool, each pass in a new seeded order."""

    def __init__(self, pool: np.ndarray, rng: np.random.Generator):
        self.pool, self.rng = pool, rng
        self.order: np.ndarray = np.empty(0, np.int64)
        self.i = 0

    def next(self):
        if self.i >= len(self.order):
            self.order = self.rng.permutation(len(self.pool))
            self.i = 0
        v = self.pool[self.order[self.i]]
        self.i += 1
        return v


class Lengths:
    """Seeded (prompt, output) pairs with prompt + output <= ``limit``, so
    no request is cut at the context limit.

    The first ``in_progress`` pairs stand for requests caught in the middle
    of their generation, as a steady state holds them, so that a run can
    start its window without a long warm-up: a lane holds a request for as
    long as it generates, so their outputs are evenly spaced quantiles of
    the output pool weighted by length; each is a fraction ``u`` of the way
    through (evenly spaced fractions, paired in a seeded order), its ``u``
    generated tokens are counted into its prompt, and it generates the
    rest.  Their prompts are rounded up to the bucket, at most ``cap()``.
    """

    def __init__(self, mix: dict, limit: int, rng: np.random.Generator,
                 in_progress: int = 0):
        n = int(mix["pool"])
        self.prompts = Cycle(lognormal_pool(mix["prompt"], n), rng)
        self.outputs = Cycle(lognormal_pool(mix["output"], n), rng)
        self.limit = limit
        self.bucket = int(mix["prompt"].get("bucket", 1))
        self.rng = rng
        self.in_progress = in_progress
        self.first = self._in_progress(in_progress)

    def cap(self) -> int:
        """The longest prompt an in-progress request may carry: one token
        of room below the limit, in whole buckets."""
        return (self.limit - 1) // self.bucket * self.bucket

    def _in_progress(self, n: int) -> list[tuple[int, int]]:
        if n <= 0:
            return []
        outs = np.sort(self.outputs.pool)
        weight = np.cumsum(outs) / outs.sum()
        q = _quantiles(n)
        full = outs[np.minimum(np.searchsorted(weight, q), len(outs) - 1)]
        frac = self.rng.permutation(q)
        pairs = []
        for total, f in zip(full, frac):
            u = int(f * total)
            s = -(-(int(self.prompts.next()) + u) // self.bucket) * self.bucket
            s = min(s, self.cap())
            pairs.append((s, max(1, min(int(total) - u, self.limit - s))))
        return pairs

    def next(self, vocab: int) -> tuple[np.ndarray, int]:
        if self.first:
            s, out = self.first.pop(0)
        else:
            s = int(self.prompts.next())
            out = int(min(self.outputs.next(), self.limit - s))
        prompt = self.rng.integers(0, vocab, size=s, dtype=np.int32)
        return prompt, out

    def prompt_lengths(self, p: dict) -> list[int]:
        """Every prompt length this can produce: the pool's buckets, and
        with requests in progress every bucket up to ``cap()``."""
        if not self.in_progress:
            return buckets(p)
        return buckets(dict(p, max=max(int(p["max"]), self.cap())))
