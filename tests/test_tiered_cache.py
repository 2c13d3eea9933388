"""Tiered paged-KV cache: host-DRAM offload + swap-vs-recompute preemption.

The load-bearing invariants:

* ``preempt_policy='swap'`` reproduces ``'recompute'`` (and the gather
  oracle) token-for-token on attention, MLA, SSD, and RG-LRU configs under
  forced preemption — a swap round-trips page bytes exactly, so the only
  way identity could break is a bookkeeping bug;
* a swap captures and restores the victim lane's recurrent state (SSD
  state / RG-LRU h / conv rings) bit-exactly;
* double-preempting the same request reuses the clean host-page prefix
  (pages that were full at first swap are never re-copied);
* host-tier exhaustion (or an adverse cost model) falls back to recompute,
  and both tiers' free lists round-trip to full.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import build_model
from repro.models.common import AxisRules, DEFAULT_RULES
from repro.serve import (
    EngineConfig,
    PagedKVCache,
    Request,
    RequestState,
    Scheduler,
    SchedulerConfig,
    ServeEngine,
)

RULES = AxisRules(DEFAULT_RULES)

# the forced-preemption cell: 3 lanes on a 7-page pool of page_size 4 —
# every request reserves 2 pages and grows past it, so the pool runs dry
# mid-decode and the preempt-longest-running policy must fire
PRESSURE = dict(batch_slots=3, max_len=32, page_size=4, n_pages=7)

PAGED_FAMILIES = ["qwen2.5-3b", "deepseek-v3-671b", "mamba2-130m",
                  "recurrentgemma-9b", "granite-4.0-h-micro"]


def _family_model(arch):
    cfg = get_arch(arch).reduced()
    model = build_model(dataclasses.replace(cfg, decode_unroll_layers=False))
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _reqs(cfg, n=3, plen=7, max_new=12, seed=7):
    rng = np.random.default_rng(seed)
    return [
        Request(uid=i,
                prompt=rng.integers(0, cfg.vocab_size,
                                    size=(plen,)).astype(np.int32),
                max_new_tokens=max_new)
        for i in range(n)
    ]


def _serve(model, params, ecfg, reqs):
    eng = ServeEngine(model, params, ecfg, RULES)
    for r in reqs:
        eng.submit(r)
    eng.run()
    return {r.uid: r.out_tokens for r in reqs}, eng


# ---------------------------------------------------------------------------
# The acceptance bar: swap == recompute == gather oracle, per family
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", PAGED_FAMILIES)
def test_swap_matches_recompute_and_gather_under_pressure(arch):
    cfg, model, params = _family_model(arch)
    want, e_rec = _serve(model, params,
                         EngineConfig(**PRESSURE,
                                      preempt_policy="recompute"),
                         _reqs(cfg))
    got, e_swp = _serve(model, params,
                        EngineConfig(**PRESSURE, preempt_policy="swap",
                                     swap_token_cost=0.0),
                        _reqs(cfg))
    oracle, e_gat = _serve(model, params,
                           EngineConfig(**PRESSURE, preempt_policy="swap",
                                        swap_token_cost=0.0,
                                        decode_path="gather"),
                           _reqs(cfg))
    assert e_rec.sched.n_recompute_preemptions > 0
    assert e_swp.sched.n_swap_preemptions > 0
    assert e_swp.sched.n_recompute_preemptions == 0
    assert e_gat.sched.n_swap_preemptions > 0
    assert want == got == oracle
    for eng in (e_rec, e_swp, e_gat):
        assert eng.cache.allocator.n_free == eng.cache.n_pages
    # every host page came back on retire
    assert e_swp.cache.host.allocator.n_free == e_swp.cache.host.n_pages
    # swap preemption never re-runs prefill: exactly the 3 submitted 7-token
    # prompts are prefilled once each, while recompute re-prefills victims
    assert e_swp.stats["prefill_tokens"] == 3 * 7
    assert e_rec.stats["prefill_tokens"] > e_swp.stats["prefill_tokens"]


def test_unpressured_baseline_matches_swap():
    cfg, model, params = _family_model("qwen2.5-3b")
    base, e0 = _serve(model, params,
                      EngineConfig(batch_slots=1, max_len=32, page_size=4,
                                   n_pages=16),
                      _reqs(cfg))
    assert e0.sched.n_preemptions == 0
    got, _ = _serve(model, params,
                    EngineConfig(**PRESSURE, preempt_policy="swap",
                                 swap_token_cost=0.0),
                    _reqs(cfg))
    assert base == got


# ---------------------------------------------------------------------------
# Recurrent lane state (SSD / RG-LRU) rides the swap
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["mamba2-130m", "recurrentgemma-9b"])
def test_swap_roundtrips_recurrent_lane_state_bitexact(arch):
    cfg, model, params = _family_model(arch)
    cache = PagedKVCache(model, lanes=2, n_pages=8, page_size=4,
                         max_len=32, host_pages=8)
    assert cache.has_state_leaves()
    prompt = np.asarray([5, 9, 2, 7, 11], np.int32)
    _, pc = model.prefill(params, jnp.asarray(prompt)[None], RULES)
    pages = cache.acquire(len(prompt) + 1)
    cache.write_prefill(pages, pc, lane=0)
    cache.assign_lane(0, pages)
    before = jax.tree.map(np.asarray, cache.pools)

    handle = cache.swap_out(pages, lane=0, length=len(prompt))
    assert handle is not None and handle.state is not None
    # scramble the freed pages and the lane row (as a new tenant would)
    cache.pools = jax.tree.map(lambda x: x + 1.0 if x.dtype.kind == "f"
                               else x, cache.pools)
    cache.allocator.release(pages)
    cache.clear_lane(0)

    new_pages = cache.allocator.acquire(len(handle.host_pages))
    state = cache.swap_in(handle, new_pages)
    assert state is not None
    cache.assign_lane(1, new_pages)
    cache.write_state(1, state)
    after = jax.tree.map(np.asarray, cache.pools)

    def check(path, b, a):
        from repro.serve.paged_cache import _is_seq
        if _is_seq(path):
            for lp, pp in zip(pages, new_pages):
                assert np.array_equal(b[:, lp], a[:, pp]), path
        else:
            assert np.array_equal(b[:, 0], a[:, 1]), path   # lane 0 → lane 1

    jax.tree_util.tree_map_with_path(check, before, after)


# ---------------------------------------------------------------------------
# Dirty-page bookkeeping: double preemption of the same request
# ---------------------------------------------------------------------------


def test_double_preemption_reuses_clean_host_pages():
    cfg, model, params = _family_model("qwen2.5-3b")
    want, _ = _serve(model, params,
                     EngineConfig(**PRESSURE, preempt_policy="recompute"),
                     _reqs(cfg))
    got, eng = _serve(model, params,
                      EngineConfig(**PRESSURE, preempt_policy="swap",
                                   swap_token_cost=0.0),
                      _reqs(cfg))
    assert want == got
    # at least one request was preempted twice... (the per-uid counters are
    # cleared on retire so long-lived engines don't grow a dict entry per
    # request — the high-water mark is what survives)
    assert eng.sched.preemptions_by_uid == {}
    assert eng.sched.max_preemptions_per_request >= 2
    assert eng.telemetry()["max_request_preemptions"] >= 2
    # ...and its second swap-out skipped the still-clean full pages
    assert eng.cache.host.stats["dirty_pages_skipped"] > 0
    # clean-prefix reuse means strictly fewer pages copied out than in
    # (every swap-in restores the full page list)
    assert (eng.cache.host.stats["pages_out"]
            < eng.cache.host.stats["pages_in"])


# ---------------------------------------------------------------------------
# Fallbacks: host-tier exhaustion and the cost model
# ---------------------------------------------------------------------------


def test_host_tier_exhaustion_falls_back_to_recompute():
    cfg, model, params = _family_model("qwen2.5-3b")
    want, _ = _serve(model, params,
                     EngineConfig(**PRESSURE, preempt_policy="recompute"),
                     _reqs(cfg))
    got, eng = _serve(model, params,
                      EngineConfig(**PRESSURE, preempt_policy="swap",
                                   swap_token_cost=0.0, host_pages=1),
                      _reqs(cfg))
    assert want == got
    assert eng.sched.n_swap_preemptions == 0
    assert eng.sched.n_recompute_preemptions > 0
    assert eng.cache.host.stats["exhausted_fallbacks"] > 0
    # a failed swap holds no host pages
    assert eng.cache.host.allocator.n_free == eng.cache.host.n_pages


def test_adverse_cost_model_prefers_recompute():
    cfg, model, params = _family_model("qwen2.5-3b")
    want, _ = _serve(model, params,
                     EngineConfig(**PRESSURE, preempt_policy="recompute"),
                     _reqs(cfg))
    got, eng = _serve(model, params,
                      EngineConfig(**PRESSURE, preempt_policy="swap",
                                   swap_token_cost=1e9),
                      _reqs(cfg))
    assert want == got
    assert eng.sched.n_swap_preemptions == 0
    assert eng.sched.n_recompute_preemptions > 0


def test_recompute_policy_allocates_no_host_tier():
    cfg, model, params = _family_model("qwen2.5-3b")
    eng = ServeEngine(model, params,
                      EngineConfig(batch_slots=1, max_len=32,
                                   preempt_policy="recompute"), RULES)
    assert eng.cache.host is None
    assert eng.telemetry()["host_page_occupancy"] == 0.0


# ---------------------------------------------------------------------------
# Cost-model unit (no jax)
# ---------------------------------------------------------------------------


class _StubCache:
    page_size = 4


def _running_state(plen, out_tokens, n_pages, clean=0):
    req = Request(uid=0, prompt=np.zeros(plen, np.int32))
    req.out_tokens = list(range(out_tokens))
    st = RequestState(req=req, resume_tokens=np.zeros(plen, np.int32),
                      pages=list(range(n_pages)), lane=0)
    if clean:
        from repro.serve import SwapHandle
        st.swap_handle = SwapHandle(host_pages=list(range(n_pages)),
                                    clean_pages=clean)
    return st


def test_cost_model_pages_vs_tokens():
    s = Scheduler(SchedulerConfig(swap_token_cost=0.25))
    cache = _StubCache()
    # long request, few pages: 4 pages * 4 slots * 2 moves * 0.25 = 8 token-
    # equivalents < 30 tokens to recompute → swap
    assert s.swap_beats_recompute(_running_state(16, 15, 4), cache)
    # short request: 2 pages * 4 * 2 * 0.25 = 4 > 5 - ... recompute cost is
    # plen + out - 1 = 3 < 4 → recompute
    assert not s.swap_beats_recompute(_running_state(2, 2, 2), cache)
    # a clean host prefix shrinks the move cost: same request, 3 of 4 pages
    # clean → (1 + 4) * 4 * 0.25 = 5 < 30
    dirty = s.swap_beats_recompute(_running_state(16, 15, 4, clean=3), cache)
    assert dirty
    # swap_token_cost=0 always swaps
    s0 = Scheduler(SchedulerConfig(swap_token_cost=0.0))
    assert s0.swap_beats_recompute(_running_state(2, 2, 2), cache)


def test_scheduler_rejects_unknown_preempt_policy():
    with pytest.raises(ValueError):
        Scheduler(SchedulerConfig(preempt_policy="discard"))


def test_engine_rejects_unknown_preempt_policy():
    cfg, model, params = _family_model("qwen2.5-3b")
    with pytest.raises(ValueError):
        ServeEngine(model, params,
                    EngineConfig(batch_slots=1, max_len=32,
                                 preempt_policy="discard"), RULES)


# ---------------------------------------------------------------------------
# Host-tier sharding: unsharded / replicated leaves
# ---------------------------------------------------------------------------


def test_host_tier_shardings_replicated():
    from jax.sharding import PartitionSpec

    from repro.dist.sharding import (
        cube_rules,
        host_cache_axes,
        host_tier_shardings,
        tree_shardings,
    )

    cfg, model, params = _family_model("qwen2.5-3b")
    specs = model.cache_page_specs(lanes=2, n_pages=8, page_size=8)
    axes = host_cache_axes(specs)
    for s, ax in zip(jax.tree.leaves(specs),
                     jax.tree.leaves(axes,
                                     is_leaf=lambda x: isinstance(x, tuple))):
        assert ax == (None,) * len(s.shape)
    mesh = jax.make_mesh((1,), ("pod",))
    # resolving the all-None axes through the cube rule table and the direct
    # replicated tree agree: host-tier leaves never shard
    via_axes = tree_shardings(mesh, specs, axes, cube_rules(mesh))
    direct = host_tier_shardings(mesh, specs)
    for a, b in zip(jax.tree.leaves(via_axes), jax.tree.leaves(direct)):
        assert all(entry is None for entry in a.spec)   # fully replicated
        assert b.spec == PartitionSpec()


def test_swap_in_through_replicated_shardings():
    """PagedKVCache(host_shardings=...) stages restored pages through an
    explicit replicated NamedSharding tree — same bytes, placed."""
    from repro.dist.sharding import host_tier_shardings

    cfg, model, params = _family_model("qwen2.5-3b")
    mesh = jax.make_mesh((1,), ("pod",))
    cache = PagedKVCache(model, lanes=1, n_pages=4, page_size=4, max_len=16,
                         host_pages=4)
    cache.host_shardings = host_tier_shardings(mesh, cache.pools)
    prompt = np.asarray([3, 1, 4, 1, 5], np.int32)
    _, pc = model.prefill(params, jnp.asarray(prompt)[None], RULES)
    pages = cache.acquire(len(prompt) + 1)
    cache.write_prefill(pages, pc, lane=0)
    cache.assign_lane(0, pages)
    before = jax.tree.map(np.asarray, cache.pools)
    handle = cache.swap_out(pages, lane=0, length=len(prompt))
    cache.allocator.release(pages)
    new_pages = cache.allocator.acquire(len(handle.host_pages))
    cache.swap_in(handle, new_pages)
    after = jax.tree.map(np.asarray, cache.pools)
    for b, a in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        for lp, np_ in zip(pages, new_pages):
            assert np.array_equal(b[:, lp], a[:, np_])
