"""Backlog traffic: the engine's queue never runs dry.

Whenever the engine holds fewer than ``lanes + queued_beyond_lanes``
requests, new ones are submitted at once; their due time is the moment of
submission.  The first ``lanes`` requests are caught in the middle of
their generation (``Lengths``' in-progress draw), so every lane starts
with a steady-state context and they leave at staggered times; warm-up
runs until each of them has its first token, and the window starts there.
"""
from __future__ import annotations

import numpy as np

from chipbench.harness.traffic import Lengths, Spec


class Backlog:
    scheduled = False          # submitted by the decode loop, by load

    def __init__(self, mix: dict, lanes: int, max_len: int, vocab: int,
                 seed: int):
        self.mix, self.lanes, self.vocab = mix, lanes, vocab
        self.lengths = Lengths(mix, max_len - 2, np.random.default_rng(seed),
                               in_progress=lanes)
        self.target = lanes + int(mix["queued_beyond_lanes"])

    def prompt_lengths(self) -> list[int]:
        return self.lengths.prompt_lengths(self.mix["prompt"])

    def due(self, elapsed_s: float, load: int) -> list[Spec]:
        out = []
        for _ in range(max(0, self.target - load)):
            prompt, n = self.lengths.next(self.vocab)
            out.append(Spec(None, prompt, n))
        return out

    def next_due_s(self) -> float | None:
        return None

    def warm_done(self, elapsed_s: float, started: int) -> bool:
        return started >= self.lanes


def make(mix: dict, lanes: int, max_len: int, vocab: int, seed: int) -> Backlog:
    return Backlog(mix, lanes, max_len, vocab, seed)
