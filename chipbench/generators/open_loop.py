"""Open-loop traffic: Poisson arrivals at a fixed rate.

Arrivals follow a schedule fixed before the run, whatever the engine does;
the harness hands each request to the engine at its due time from a thread
of its own, as a client would, so a stall makes later requests wait and
the wait is counted from each request's due time.  At time 0 the schedule
holds ``in_progress`` requests caught in the middle of their generation
(``Lengths``' in-progress draw), about the number a steady state keeps in
flight at this rate (Little's law), so the run needs no warm-up of a whole
request lifetime.  Warm-up lasts ``warm_s`` seconds and until each of them
has its first token; the window goes on with the same schedule.
"""
from __future__ import annotations

import numpy as np

from chipbench.harness.traffic import Cycle, Lengths, Spec, exponential_pool


class OpenLoop:
    scheduled = True           # submitted at due times, by a thread

    def __init__(self, mix: dict, lanes: int, max_len: int, vocab: int,
                 seed: int):
        self.mix, self.vocab = mix, vocab
        rng = np.random.default_rng(seed)
        self.n_first = int(mix["in_progress"])
        self.lengths = Lengths(mix, max_len - 2, rng, in_progress=self.n_first)
        self.gaps = Cycle(exponential_pool(float(mix["rate_per_s"]),
                                           int(mix["pool"])), rng)
        self.pending_first = self.n_first
        self.next_s = 0.0 if self.n_first else float(self.gaps.next())

    def prompt_lengths(self) -> list[int]:
        return self.lengths.prompt_lengths(self.mix["prompt"])

    def due(self, elapsed_s: float, load: int) -> list[Spec]:
        out = []
        while self.next_s <= elapsed_s:
            prompt, n = self.lengths.next(self.vocab)
            out.append(Spec(self.next_s, prompt, n))
            if self.pending_first:
                self.pending_first -= 1
                if self.pending_first:
                    continue
            self.next_s += float(self.gaps.next())
        return out

    def next_due_s(self) -> float | None:
        return self.next_s

    def warm_done(self, elapsed_s: float, started: int) -> bool:
        return elapsed_s >= float(self.mix["warm_s"]) and started >= self.n_first


def make(mix: dict, lanes: int, max_len: int, vocab: int, seed: int) -> OpenLoop:
    return OpenLoop(mix, lanes, max_len, vocab, seed)
