"""The generators: the same requests for the same seed, the same pool of
sizes for every seed, the length bounds and the 64-token buckets."""
import collections

import numpy as np

from chipbench.harness import registry
from chipbench.harness.traffic import buckets, lognormal_pool


def _draw(kind, seed, n=300, elapsed=1e9):
    mix = registry.mix(kind)
    gen = registry.generator(mix["kind"]).make(mix, 64, 2048, 151936, seed)
    if mix["kind"] == "backlog":
        out = []
        while len(out) < n:
            out += gen.due(0.0, 0)
        return mix, out[:n]
    return mix, gen.due(n / mix["rate_per_s"], 0)


def test_same_seed_same_requests():
    for kind in ("batch", "chat"):
        _, a = _draw(kind, 2**33 + 5)
        _, b = _draw(kind, 2**33 + 5)
        assert len(a) == len(b)
        for x, y in zip(a, b):
            assert x.due_s == y.due_s and x.max_new == y.max_new
            assert np.array_equal(x.prompt, y.prompt)


def _one_pass(cycle):
    """The next whole pass through the pool, after the one under way."""
    while cycle.i < len(cycle.order):
        cycle.next()
    return [cycle.next() for _ in range(len(cycle.pool))]


def test_seeds_share_the_pool_of_sizes():
    for kind in ("batch", "chat"):
        mix = registry.mix(kind)
        gen1 = registry.generator(mix["kind"]).make(mix, 64, 2048, 1000, 1)
        gen2 = registry.generator(mix["kind"]).make(mix, 64, 2048, 1000, 2)
        p1, p2 = _one_pass(gen1.lengths.prompts), _one_pass(gen2.lengths.prompts)
        assert p1 != p2
        assert collections.Counter(p1) == collections.Counter(p2)


def _in_progress(mix):
    return 64 if mix["kind"] == "backlog" else int(mix["in_progress"])


def test_lengths_bounds_and_buckets():
    for kind in ("batch", "chat"):
        mix, reqs = _draw(kind, 11)
        p, o = mix["prompt"], mix["output"]
        for r in reqs[_in_progress(mix):]:
            s = len(r.prompt)
            assert s % 64 == 0 and p["min"] <= s <= p["max"]
            assert o["min"] <= r.max_new <= o["max"] or s + r.max_new == 2046
            assert s + r.max_new <= 2046
        assert set(len(r.prompt) for r in reqs[_in_progress(mix):]) <= set(buckets(p))


def test_pool_median():
    p = registry.mix("batch")["prompt"]
    pool = lognormal_pool(dict(p, bucket=1, min=1, max=10**6), 4001)
    assert abs(np.median(pool) - p["median"]) <= 1


def test_open_loop_rate():
    mix, reqs = _draw("chat", 3, n=2000)
    due = [r.due_s for r in reqs]
    assert due == sorted(due)
    rate = len(due) / due[-1]
    assert abs(rate / mix["rate_per_s"] - 1) < 0.05


def test_in_progress_start():
    """The first requests stand for a steady state caught mid-generation:
    bucketed prompts that hold the tokens already generated, every one
    within the context limit, the same sizes for every seed."""
    for kind in ("batch", "chat"):
        mix = registry.mix(kind)
        n = _in_progress(mix)
        sizes = []
        for seed in (5, 2**33 + 6):
            _, reqs = _draw(kind, seed)
            first = reqs[:n]
            if mix["kind"] == "open_loop":
                assert all(r.due_s == 0.0 for r in first)
                assert reqs[n].due_s > 0.0
            for r in first:
                assert len(r.prompt) % 64 == 0 and len(r.prompt) <= 1984
                assert 1 <= r.max_new and len(r.prompt) + r.max_new <= 2046
            sizes.append(sum(len(r.prompt) + r.max_new for r in first))
            mean_left = np.mean([r.max_new for r in first])
            assert mean_left < np.mean(lognormal_pool(mix["output"], 64))
        assert abs(sizes[0] / sizes[1] - 1) < 0.1
        gen = registry.generator(mix["kind"]).make(mix, 64, 2048, 1000, 1)
        assert max(gen.prompt_lengths()) == 1984
