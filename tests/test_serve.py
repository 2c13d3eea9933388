"""Serve v2: paged-KV cache, scheduler, router, and engine equivalence.

The load-bearing invariants:

* page-table gather reproduces the dense per-slot cache bit-exactly, and the
  decode logits over the gathered view equal the dense-cache logits
  bit-exactly (old engine vs new engine, same seed);
* the paged engine's greedy tokens equal the dense slot engine's on the same
  workload, and a ragged batch equals sequential single-request serving;
* alloc/free round-trips leave the free list full; preemption + recompute-
  resume reproduces identical tokens; early-EOS requests release their slot.
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
    AdmissionConfig,
    CacheConfig,
    CubeRouter,
    DenseSlotEngine,
    EngineConfig,
    PageAllocator,
    PagedKVCache,
    Request,
    Scheduler,
    SchedulerConfig,
    ServeEngine,
)
from repro.serve import router as router_mod
from repro.serve.paged_cache import gather_views

RULES = AxisRules(DEFAULT_RULES)


@pytest.fixture(scope="module")
def served():
    cfg = get_arch("qwen2.5-3b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


def _reqs(cfg, n=5, plen=6, max_new=4, ragged=False, seed=0):
    rng = np.random.default_rng(seed)
    return [
        Request(
            uid=i,
            prompt=rng.integers(
                0, cfg.vocab_size, size=(plen + (3 * i if ragged else 0),)
            ).astype(np.int32),
            max_new_tokens=max_new,
        )
        for i in range(n)
    ]


def _serve(engine_cls, model, params, ecfg, reqs):
    eng = engine_cls(model, params, ecfg, RULES)
    for r in reqs:
        eng.submit(r)
    eng.run()
    return {r.uid: r.out_tokens for r in reqs}, eng


# ---------------------------------------------------------------------------
# Free-list invariants
# ---------------------------------------------------------------------------


def test_free_list_roundtrip():
    alloc = PageAllocator(16)
    a = alloc.acquire(5)
    b = alloc.acquire(11)
    assert alloc.n_free == 0
    assert sorted(a + b) == list(range(16))          # every page handed once
    assert alloc.acquire(1) is None                  # dry pool: no side effect
    assert alloc.n_free == 0
    assert sorted(alloc.release(b)) == sorted(b)     # sole owner → all freed
    alloc.release(a)
    assert alloc.n_free == 16                        # round trip → full again
    assert sorted(alloc.acquire(16)) == list(range(16))


def test_refcount_share_release_fork():
    alloc = PageAllocator(8)
    (p,) = alloc.acquire(1)
    alloc.share([p])
    assert alloc.refcount(p) == 2
    assert alloc.fork_for_write(p) != p              # shared → fresh copy
    assert alloc.refcount(p) == 1                    # fork dropped one owner
    assert alloc.fork_for_write(p) == p              # sole owner writes in place
    assert alloc.release([p]) == [p]
    alloc.check_invariant()


def test_absorb_decode_inactive_lane_writes_nothing():
    """Regression: the inactive-lane scatter sentinel must be out of bounds
    ABOVE the pool (a -1 index is normalized to n_pages-1 before mode='drop'
    applies and would corrupt the last physical page)."""
    from repro.serve.paged_cache import absorb_decode

    pool = {"k": jnp.zeros((1, 4, 2, 1, 1), jnp.float32)}   # 4 pages of 2
    view = {"k": jnp.full((1, 2, 4, 1, 1), -5.0, jnp.float32)}
    bt = jnp.asarray([[0, 1], [-1, -1]], jnp.int32)
    out = absorb_decode(
        pool, view, bt, positions=jnp.asarray([1, 0], jnp.int32),
        active=jnp.asarray([True, False]), page_size=2,
    )
    got = np.array(out["k"])
    assert got[0, 0, 1, 0, 0] == -5.0          # active lane 0 wrote page 0
    got[0, 0, 1, 0, 0] = 0.0
    assert np.all(got == 0.0)                  # inactive lane wrote nowhere


def test_engine_rejects_oversize_prompt(served):
    cfg, model, params = served
    eng = ServeEngine(model, params,
                      EngineConfig(batch_slots=1, max_len=32), RULES)
    with pytest.raises(ValueError):
        eng.submit(Request(uid=0, prompt=np.zeros(40, np.int32)))


# ---------------------------------------------------------------------------
# Page-table gather == dense cache, bit-exactly (old vs new engine layout)
# ---------------------------------------------------------------------------


def test_gather_matches_dense_cache_and_logits_bitexact(served):
    cfg, model, params = served
    model = build_model(dataclasses.replace(cfg, decode_unroll_layers=False))
    max_len, ps = 32, 8
    prompts = [np.asarray([5, 9, 2, 7, 11], np.int32),
               np.asarray([3, 1, 4, 1, 5], np.int32)]

    # dense per-slot cache, packed exactly as the dense slot engine packs it
    dense = jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), model.cache_specs(2, max_len)
    )
    paged = PagedKVCache(model, lanes=2, n_pages=8, page_size=ps,
                         max_len=max_len)
    for slot, prompt in enumerate(prompts):
        _, pc = model.prefill(params, jnp.asarray(prompt)[None], RULES)

        def pack(big, small, _slot=slot):
            if big.ndim >= 3 and small.shape[2:3] != big.shape[2:3]:
                pad = [(0, 0)] * small.ndim
                pad[2] = (0, big.shape[2] - small.shape[2])
                small = jnp.pad(small, pad)
            return big.at[:, _slot: _slot + 1].set(small.astype(big.dtype))

        dense = jax.tree.map(pack, dense, pc)
        pages = paged.acquire(len(prompt) + 1)
        paged.write_prefill(pages, pc, lane=slot)
        paged.assign_lane(slot, pages)

    view = gather_views(paged.pools, jnp.asarray(paged.block_tables))
    for dv, pv in zip(jax.tree.leaves(dense), jax.tree.leaves(view)):
        assert dv.shape == pv.shape
        assert np.array_equal(np.asarray(dv), np.asarray(pv))

    # decode over the gathered view == decode over the dense cache, bit-exact
    toks = jnp.asarray([[5], [3]], jnp.int32)
    ld, _ = model.decode_step(params, dense, toks, jnp.asarray(5, jnp.int32),
                              RULES)
    lp, _ = model.decode_step(params, view, toks, jnp.asarray(5, jnp.int32),
                              RULES)
    assert np.array_equal(np.asarray(ld), np.asarray(lp))
    # and the per-lane-position decode agrees bit-exactly with the scalar one
    lv, _ = model.decode_step(params, view, toks,
                              jnp.asarray([5, 5], jnp.int32), RULES)
    assert np.array_equal(np.asarray(ld), np.asarray(lv))


# ---------------------------------------------------------------------------
# Engine-level greedy equivalence (the acceptance bar)
# ---------------------------------------------------------------------------


def test_paged_engine_matches_dense_engine_greedy(served):
    """Same-length prompts (the dense engine's shared-max-position stepping
    is only exact there), more requests than slots → queueing + refill."""
    cfg, model, params = served
    ecfg = EngineConfig(batch_slots=2, max_len=64)
    want, _ = _serve(DenseSlotEngine, model, params, ecfg, _reqs(cfg))
    got, eng = _serve(ServeEngine, model, params, ecfg, _reqs(cfg))
    assert want == got
    assert eng.cache.allocator.n_free == eng.cache.n_pages   # all pages back


def test_ragged_batch_matches_sequential(served):
    """Per-lane positions: a ragged batch reproduces single-request serving
    (which the dense engine's shared-position step cannot guarantee)."""
    cfg, model, params = served
    seq = ServeEngine(model, params,
                      EngineConfig(batch_slots=1, max_len=64), RULES)
    base = {}
    for r in _reqs(cfg, n=4, ragged=True):
        seq.submit(r)
        seq.run()
        base[r.uid] = r.out_tokens
    got, _ = _serve(ServeEngine, model, params,
                    EngineConfig(batch_slots=3, max_len=64),
                    _reqs(cfg, n=4, ragged=True))
    assert base == got


def test_chunked_prefill_matches_whole_prompt(served):
    cfg, model, params = served
    whole, _ = _serve(ServeEngine, model, params,
                      EngineConfig(batch_slots=2, max_len=64),
                      _reqs(cfg, n=3, plen=11))
    chunked, eng = _serve(ServeEngine, model, params,
                          EngineConfig(batch_slots=2, max_len=64,
                                       prefill_chunk=4, max_step_tokens=12),
                          _reqs(cfg, n=3, plen=11))
    assert whole == chunked
    assert eng.stats["prefill_tokens"] == 3 * 11


def test_preemption_then_resume_reproduces_tokens(served):
    cfg, model, params = served
    reqs = lambda: _reqs(cfg, n=3, plen=7, max_new=10, seed=7)  # noqa: E731
    base, _ = _serve(ServeEngine, model, params,
                     EngineConfig(batch_slots=1, max_len=32, page_size=4),
                     reqs())
    # 3 lanes on a 7-page pool: each request reserves 2 pages and grows to 5
    # → the pool runs dry mid-decode and must preempt
    got, eng = _serve(ServeEngine, model, params,
                      EngineConfig(batch_slots=3, max_len=32, page_size=4,
                                   n_pages=7),
                      reqs())
    assert eng.sched.n_preemptions > 0
    assert base == got
    assert eng.cache.allocator.n_free == eng.cache.n_pages


# ---------------------------------------------------------------------------
# Zero-materialization paged decode == gather oracle (the tentpole invariant)
# ---------------------------------------------------------------------------

# one arch per attention-state family the paged engine serves: GQA attention,
# MLA absorbed latents, SSD recurrent state, RG-LRU hybrid (rec+local attn),
# SSD + global NoPE attention hybrid
PAGED_FAMILIES = ["qwen2.5-3b", "deepseek-v3-671b", "mamba2-130m",
                  "recurrentgemma-9b", "granite-4.0-h-micro"]


def _family_model(arch):
    cfg = get_arch(arch).reduced()
    model = build_model(dataclasses.replace(cfg, decode_unroll_layers=False))
    params = model.init(jax.random.key(0))
    return cfg, model, params


@pytest.mark.parametrize("arch", PAGED_FAMILIES)
def test_decode_step_paged_bitexact_vs_gather(arch):
    """Model-level: decode_step_paged logits AND post-step pools equal the
    gather-decode-absorb pipeline bit-for-bit (attention, MLA, SSD, RG-LRU),
    inactive lanes included."""
    from repro.serve.paged_cache import absorb_decode

    cfg, model, params = _family_model(arch)
    ps, max_len = 8, 32
    paged = PagedKVCache(model, lanes=3, n_pages=8, page_size=ps,
                         max_len=max_len)
    prompts = [np.asarray([5, 9, 2, 7, 11], np.int32),
               np.asarray([3, 1, 4], np.int32)]
    for slot, prompt in enumerate(prompts):
        _, pc = model.prefill(params, jnp.asarray(prompt)[None], RULES)
        pages = paged.acquire(len(prompt) + 1)
        paged.write_prefill(pages, pc, lane=slot)
        paged.assign_lane(slot, pages)
    bt = jnp.asarray(paged.block_tables)
    toks = jnp.asarray([[5], [3], [0]], jnp.int32)
    positions = jnp.asarray([5, 3, 0], jnp.int32)
    active = jnp.asarray([True, True, False])     # lane 2 is idle

    views = gather_views(paged.pools, bt)
    lg, new_views = model.decode_step(params, views, toks, positions, RULES)
    pools_g = absorb_decode(paged.pools, new_views, bt, positions, active, ps)

    lp, pools_p = model.decode_step_paged(
        params, paged.pools, bt, toks, positions, active, RULES
    )
    # active lanes bit-exact; the idle lane's logits are don't-care garbage
    # both engines discard (the gather path attends the lane's own transient
    # k/v write, the paged path drops it before attention)
    act = np.asarray(active)
    assert np.array_equal(np.asarray(lg)[act], np.asarray(lp)[act])
    for a, b in zip(jax.tree.leaves(pools_g), jax.tree.leaves(pools_p)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("arch", PAGED_FAMILIES)
def test_paged_decode_path_tokens_match_gather(arch):
    """Engine-level acceptance bar: the zero-materialization decode path
    reproduces the gather oracle token-for-token on ragged continuous
    batching (queueing + refill included) for every served family."""
    cfg, model, params = _family_model(arch)
    reqs = lambda: _reqs(cfg, n=4, plen=5, max_new=4, ragged=True)  # noqa: E731
    want, _ = _serve(ServeEngine, model, params,
                     EngineConfig(batch_slots=2, max_len=64,
                                  decode_path="gather"), reqs())
    got, eng = _serve(ServeEngine, model, params,
                      EngineConfig(batch_slots=2, max_len=64,
                                   decode_path="paged"), reqs())
    assert want == got
    assert eng.cache.allocator.n_free == eng.cache.n_pages


@pytest.mark.parametrize("arch",
                         ["deepseek-v3-671b", "mamba2-130m",
                          "recurrentgemma-9b", "granite-4.0-h-micro"])
def test_chunked_prefill_matches_whole_prompt_every_family(arch):
    """The MLA absorbed-extend and SSD/RG-LRU stepped-state extend close the
    prefill_chunk gap: chunked == whole-prompt serving for every family
    (attention is covered by test_chunked_prefill_matches_whole_prompt)."""
    cfg = get_arch(arch).reduced()
    if cfg.is_moe:
        # ample capacity: dropped-token routing is seq-length dependent by
        # construction; identity holds when nothing drops
        cfg = dataclasses.replace(cfg, capacity_factor=64.0)
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    assert model.supports_chunked_prefill
    whole, _ = _serve(ServeEngine, model, params,
                      EngineConfig(batch_slots=2, max_len=64),
                      _reqs(cfg, n=3, plen=11))
    chunked, eng = _serve(ServeEngine, model, params,
                          EngineConfig(batch_slots=2, max_len=64,
                                       prefill_chunk=4, max_step_tokens=12),
                          _reqs(cfg, n=3, plen=11))
    assert whole == chunked
    assert eng.stats["prefill_tokens"] == 3 * 11


def test_paged_decode_pallas_impl_serves_identically(served):
    """attn_impl='pallas' routes the paged decode through the fused
    paged_decode_attention kernel (interpret off-TPU) — same greedy tokens
    as the XLA paged path."""
    cfg, model, params = served
    want, _ = _serve(ServeEngine, model, params,
                     EngineConfig(batch_slots=2, max_len=32),
                     _reqs(cfg, n=2, max_new=3))
    got, _ = _serve(ServeEngine, model, params,
                    EngineConfig(batch_slots=2, max_len=32,
                                 attn_impl="pallas"),
                    _reqs(cfg, n=2, max_new=3))
    assert want == got


# ---------------------------------------------------------------------------
# The token choice: greedy inside the decode program, temperature per row
# ---------------------------------------------------------------------------


def _spy_decode(eng, on_step):
    """Wrap the engine's jitted decode step: ``on_step(choice, rows)`` sees
    each step's greedy choice and its logits rows as the host would have
    copied them (the float32 cast of the program's bf16), before the engine
    picks any lane's token."""
    decode = eng._decode

    def spy(*args):
        choice, logits, pools = decode(*args)
        on_step(np.asarray(choice), np.asarray(logits[:, 0], np.float32))
        return choice, logits, pools

    eng._decode = spy


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "granite-4.0-h-micro"])
def test_greedy_tokens_are_the_argmax_of_the_programs_logits(arch):
    """Ragged greedy requests with queueing and refill: every token a lane
    is served from a decode step is ``np.argmax`` of the float32 cast of
    the logits row the program returned for it, which is also the program's
    own choice; no lane's row is read on the host."""
    from repro.serve import AdmissionConfig

    cfg = get_arch(arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    eng = ServeEngine(model, params, EngineConfig(
        batch_slots=3, max_len=64,
        admission=AdmissionConfig(prefill_chunk=8)), RULES)
    want = {}

    def on_step(choice, rows):
        for lane, st in eng.sched.running.items():
            tok = int(np.argmax(rows[lane]))
            assert int(choice[lane]) == tok
            want[(st.req.uid, len(st.req.out_tokens))] = tok

    _spy_decode(eng, on_step)
    reqs = _reqs(cfg, n=5, plen=4, max_new=6, ragged=True, seed=3)
    for r in reqs:
        eng.submit(r)
    eng.run()
    got = {(uid, i): reqs[uid].out_tokens[i] for uid, i in want}
    assert got == want
    counters = eng.metrics.snapshot()["counters"]
    assert len(want) == counters["decode_tokens"] == 5 * 5
    assert counters["decode.host_rows"] == 0


class _CraftedLogits:
    """A served model with its decode logits replaced by ``rows`` (B, V):
    ties, and maxima in the padding columns past ``vocab_size``."""

    def __init__(self, model, rows):
        self._model, self._rows = model, rows

    def __getattr__(self, name):
        return getattr(self._model, name)

    def _crafted(self, out):
        logits, state = out
        return jnp.asarray(self._rows, logits.dtype)[:, None], state

    def decode_step(self, *args, **kw):
        return self._crafted(self._model.decode_step(*args, **kw))

    def decode_step_paged(self, *args, **kw):
        return self._crafted(self._model.decode_step_paged(*args, **kw))


@pytest.mark.parametrize("decode_path", ["paged", "gather"])
def test_device_choice_breaks_ties_like_the_host(served, decode_path):
    """A vocabulary of 500 pads to 512 columns.  Exact ties go to the first
    index and the padding columns take part, as ``np.argmax`` over the host
    copy did: the program's choice equals that rule on every row."""
    from repro.serve import CacheConfig

    cfg, _, _ = served
    cfg = dataclasses.replace(cfg, vocab_size=500)
    assert cfg.padded_vocab == 512
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    rows = np.zeros((4, 512), np.float32)      # row 0: all tied -> 0
    rows[1, [3, 7, 300]] = 1.0                 # -> 3
    rows[2, 0], rows[2, [505, 506]] = 1.0, 2.0  # padding column -> 505
    rows[3, [100, 510]] = 3.0                  # real before padding -> 100
    eng = ServeEngine(model, params, EngineConfig(
        batch_slots=4, max_len=32,
        cache=CacheConfig(decode_path=decode_path)), RULES)
    eng.model = _CraftedLogits(eng.model, rows)
    bt, toks, positions, active = eng.decode_inputs()
    choice, logits, eng.cache.pools = eng._decode(
        eng.params, eng.cache.pools, jnp.asarray(bt), jnp.asarray(toks),
        jnp.asarray(positions), jnp.asarray(active))
    host = np.argmax(np.asarray(logits[:, 0], np.float32), axis=-1)
    assert choice.dtype == jnp.int32 and choice.shape == (4,)
    assert np.asarray(choice).tolist() == host.tolist() == [0, 3, 505, 100]


def test_temperature_lanes_keep_their_random_stream(served):
    """Greedy and temperature lanes side by side under fixed step keys: a
    temperature lane's token is the host rule's draw, ``categorical`` of
    its float32 logits row over the temperature with one key split per
    sorted temperature lane, and a greedy lane's is the argmax.
    ``decode.host_rows`` counts exactly the temperature lanes' steps."""
    from repro.serve import AdmissionConfig

    cfg, model, params = served
    eng = ServeEngine(model, params, EngineConfig(
        batch_slots=3, max_len=64,
        admission=AdmissionConfig(async_prefill=False)), RULES)
    reqs = _reqs(cfg, n=5, plen=5, max_new=7, ragged=True, seed=11)
    # the reduced model's top logit leads the next by 20-50: temperatures
    # of that order make the draws leave the argmax
    for r, temp in zip(reqs, (0.0, 10.0, 0.0, 40.0, 80.0)):
        r.temperature = temp
    want, greedy, step_key = {}, {}, [None]

    def on_step(choice, rows):
        key = step_key[0]
        for lane in sorted(eng.sched.running):
            st = eng.sched.running[lane]
            req = st.req
            if req.temperature > 0:
                key, sub = jax.random.split(key)
                tok = int(jax.random.categorical(
                    sub, jnp.asarray(rows[lane]) / req.temperature))
                greedy[(req.uid, len(req.out_tokens))] = int(
                    np.argmax(rows[lane]))
            else:
                tok = int(np.argmax(rows[lane]))
            want[(req.uid, len(req.out_tokens))] = tok

    _spy_decode(eng, on_step)
    for r in reqs:
        eng.submit(r)
    key = jax.random.key(2**31 + 17)
    while eng.load:
        key, step_key[0] = jax.random.split(key)
        eng.step(step_key[0])
    got = {(uid, i): reqs[uid].out_tokens[i] for uid, i in want}
    assert got == want
    assert len(greedy) == 3 * 6
    counters = eng.metrics.snapshot()["counters"]
    assert counters["decode.host_rows"] == len(greedy)
    assert counters["decode_tokens"] == len(want) == 5 * 6
    # some draws leave the argmax, so the test sees the temperature path
    assert any(got[k] != tok for k, tok in greedy.items())


def test_engine_rejects_unknown_decode_path(served):
    cfg, model, params = served
    with pytest.raises(ValueError):
        ServeEngine(model, params,
                    EngineConfig(batch_slots=1, max_len=32,
                                 decode_path="fused"), RULES)


# ---------------------------------------------------------------------------
# EOS handling (regression: early EOS must refill the slot)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine_cls", [ServeEngine, DenseSlotEngine])
def test_early_eos_finishes_at_prefill_and_frees_slot(served, engine_cls):
    cfg, model, params = served
    prompt = np.asarray([5, 9, 2, 7], np.int32)
    logits, _ = model.forward(params, jnp.asarray(prompt)[None], RULES)
    eos = int(jnp.argmax(logits[0, -1]))     # the prefill token IS the eos
    ecfg = EngineConfig(batch_slots=1, max_len=32, eos_id=eos)
    eng = engine_cls(model, params, ecfg, RULES)
    first = Request(uid=0, prompt=prompt, max_new_tokens=8)
    second = Request(uid=1, prompt=np.asarray([1, 2, 3], np.int32),
                     max_new_tokens=3)
    eng.submit(first)
    eng.submit(second)
    eng.run()
    assert first.done and first.out_tokens == [eos]   # stopped at prefill
    assert second.done and len(second.out_tokens) >= 1
    if engine_cls is ServeEngine:
        assert eng.cache.allocator.n_free == eng.cache.n_pages


def test_eos_mid_decode(served):
    cfg, model, params = served
    req = Request(uid=0, prompt=np.asarray([5, 9, 2, 7], np.int32),
                  max_new_tokens=16)
    eng = ServeEngine(model, params,
                      EngineConfig(batch_slots=1, max_len=64), RULES)
    eng.submit(req)
    eng.run()
    full = list(req.out_tokens)
    assert len(full) == 16
    eos = full[2]
    req2 = Request(uid=1, prompt=np.asarray([5, 9, 2, 7], np.int32),
                   max_new_tokens=16)
    eng2 = ServeEngine(model, params,
                       EngineConfig(batch_slots=1, max_len=64, eos_id=eos),
                       RULES)
    eng2.submit(req2)
    eng2.run()
    assert req2.out_tokens == full[: full.index(eos) + 1]


# ---------------------------------------------------------------------------
# Scheduler policy units (no jax)
# ---------------------------------------------------------------------------


class _StubCache:
    prefix = None

    def __init__(self, n_pages, page_size=4):
        self.allocator = PageAllocator(n_pages)
        self.page_size = page_size

    def acquire(self, n_tokens):
        return self.allocator.acquire(-(-n_tokens // self.page_size))

    def claim_match(self, tokens, chunk):
        return None

    def clear_lane(self, lane):
        pass


def _stub_req(uid, plen):
    return Request(uid=uid, prompt=np.zeros(plen, np.int32))


def test_scheduler_fcfs_vs_spf_ordering():
    for policy, want in (("fcfs", [0, 1]), ("spf", [2, 3])):
        s = Scheduler(SchedulerConfig(policy=policy))
        for uid, plen in ((0, 12), (1, 9), (2, 3), (3, 5)):
            s.add(_stub_req(uid, plen))
        admitted = s.admissions(_StubCache(n_pages=8), budget=1 << 30)
        assert [st.req.uid for st in admitted] == want, policy


def test_scheduler_admission_respects_pool_and_inflight():
    s = Scheduler(SchedulerConfig(max_inflight_prefills=1))
    for uid in range(3):
        s.add(_stub_req(uid, 8))
    cache = _StubCache(n_pages=100)
    assert len(s.admissions(cache, budget=1 << 30)) == 1   # in-flight bound
    assert [st.phase for st in s.admitting] == ["prefill"]
    s.admitting.clear()
    assert len(s.admissions(_StubCache(n_pages=1), budget=1 << 30)) == 0
    assert len(s.waiting) == 2                             # nothing consumed


def test_scheduler_chunking_and_victim():
    s = Scheduler(SchedulerConfig(prefill_chunk=5))
    s.add(_stub_req(0, 12))
    st = s.admissions(_StubCache(64), budget=1 << 30)[0]
    assert s.chunk_for(st) == 5
    st.prefilled = 10
    assert s.chunk_for(st) == 2
    # victim = most generated tokens, excluding the asking lane if possible
    a, b = _stub_req(1, 4), _stub_req(2, 4)
    a.out_tokens = [1, 2, 3]
    b.out_tokens = [1]
    from repro.serve import RequestState
    s.running = {
        0: RequestState(req=a, resume_tokens=np.zeros(4, np.int32), lane=0),
        1: RequestState(req=b, resume_tokens=np.zeros(4, np.int32), lane=1),
    }
    assert s.pick_victim().req.uid == 1
    assert s.pick_victim(exclude_lane=0).req.uid == 2


def test_scheduler_rejects_unknown_policy():
    with pytest.raises(ValueError):
        Scheduler(SchedulerConfig(policy="lifo"))


# ---------------------------------------------------------------------------
# Cube router
# ---------------------------------------------------------------------------


def test_router_hash_and_least_loaded(served):
    cfg, model, params = served
    ecfg = EngineConfig(batch_slots=1, max_len=32)
    rt = CubeRouter(model, params, ecfg, n_cubes=2, policy="hash")
    assert [rt.submit(r) for r in _reqs(cfg, n=4, max_new=2)] == [0, 1, 0, 1]
    done = rt.run()
    assert [r.uid for r in done] == [0, 1, 2, 3]
    assert all(len(r.out_tokens) == 2 for r in done)
    tel = rt.telemetry()
    assert tel["total_routed"] == 4
    assert tel["pod0"]["routed"] == 2 and tel["pod1"]["routed"] == 2

    rt2 = CubeRouter(model, params, ecfg, n_cubes=2, policy="least_loaded")
    cubes = [rt2.submit(r) for r in _reqs(cfg, n=4, max_new=2)]
    assert sorted(cubes) == [0, 0, 1, 1]     # queue-depth balanced
    with pytest.raises(ValueError):
        CubeRouter(model, params, ecfg, n_cubes=1, policy="round_robin")


def _swap_ecfg(**cache):
    """Two lanes over 16 pages of 4 tokens: three 31-token requests on a
    cube outgrow the pool, so each cube swap-preempts.  Inline admission
    keeps the queue movement deterministic."""
    kw = dict(page_size=4, n_pages=16, preempt_policy="swap",
              swap_token_cost=0.0)
    kw.update(cache)
    return EngineConfig(batch_slots=2, max_len=32, cache=CacheConfig(**kw),
                        admission=AdmissionConfig(async_prefill=False))


def _prefixed_reqs(cfg, n, prefix_len=8, tail=3, max_new=16, uid0=0,
                   seed=11):
    """``n`` requests whose prompts share one ``prefix_len``-token prefix
    (two full pages) and differ in their last ``tail`` tokens."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab_size, size=(prefix_len,))
    return [
        Request(uid=uid0 + i,
                prompt=np.concatenate([
                    prefix, rng.integers(0, cfg.vocab_size, size=(tail,)),
                ]).astype(np.int32),
                max_new_tokens=max_new)
        for i in range(n)
    ]


def _route(model, params, ecfg, reqs, policy):
    """Serve ``reqs`` through a 2-cube router: (tokens by uid, router,
    the cube each request was sent to)."""
    rt = CubeRouter(model, params, ecfg, n_cubes=2, policy=policy)
    cubes = [rt.submit(r) for r in reqs]
    done = rt.run()
    return {r.uid: r.out_tokens for r in done}, rt, cubes


@pytest.mark.parametrize("arch", PAGED_FAMILIES)
def test_router_serves_like_one_engine(arch):
    """Two cubes under swap pressure emit, per uid, the tokens one engine
    emits for the same workload, and both pools drain back to full."""
    cfg, model, params = _family_model(arch)
    ecfg = _swap_ecfg()
    reqs = lambda: _reqs(cfg, n=6, plen=7, max_new=24, seed=7)  # noqa: E731
    want, _ = _serve(ServeEngine, model, params, ecfg, reqs())
    got, rt, cubes = _route(model, params, ecfg, reqs(), "least_loaded")
    assert got == want
    assert sorted(cubes) == [0, 0, 0, 1, 1, 1]
    tel = rt.telemetry()
    for i, eng in enumerate(rt.engines):
        assert tel[f"pod{i}"]["swap_preemptions"] > 0
        assert eng.cache.allocator.n_free == eng.cache.n_pages


@pytest.mark.parametrize("policy", ["hash", "least_loaded", "prefix_affinity"])
def test_router_policy_serves_like_one_engine(served, policy):
    cfg, model, params = served
    ecfg = _swap_ecfg(prefix_sharing=True)
    reqs = lambda: _prefixed_reqs(cfg, n=6)  # noqa: E731
    want, _ = _serve(ServeEngine, model, params, ecfg, reqs())
    got, rt, _ = _route(model, params, ecfg, reqs(), policy)
    assert got == want
    assert rt.telemetry()["total_routed"] == 6


def test_router_prefix_affinity_follows_resident_prefix(served):
    cfg, model, params = served
    rt = CubeRouter(model, params, _swap_ecfg(prefix_sharing=True),
                    n_cubes=2, policy="prefix_affinity")
    seed_req, follower = _prefixed_reqs(cfg, n=2)
    rt.engines[1].submit(seed_req)        # the prefix becomes resident on 1
    rt.run()
    for r in _reqs(cfg, n=2, plen=7, max_new=2, seed=3):
        rt.engines[1].submit(r)           # and cube 1 the busier one
    assert rt.engines[1].load > rt.engines[0].load
    assert rt.engines[1].prefix_match_tokens(follower.prompt) == 8
    assert rt.engines[0].prefix_match_tokens(follower.prompt) == 0
    assert rt.submit(follower) == 1
    done = rt.run()
    assert follower.uid in {r.uid for r in done}


def test_router_prefix_affinity_ties_go_least_loaded(served):
    cfg, model, params = served
    rt = CubeRouter(model, params, _swap_ecfg(prefix_sharing=True),
                    n_cubes=2, policy="prefix_affinity")
    fillers = iter(_reqs(cfg, n=4, plen=7, max_new=2, seed=3))
    fresh = _reqs(cfg, n=2, plen=7, max_new=2, seed=5)
    # no resident match anywhere: the emptier cube wins, either way round
    rt.engines[0].submit(next(fillers))
    assert rt.submit(fresh[0]) == 1
    rt.engines[1].submit(next(fillers))
    rt.engines[1].submit(next(fillers))
    assert rt.submit(fresh[1]) == 0
    rt.run()
    # the same prefix resident on both cubes: the match ties, load decides
    a, b, probe = _prefixed_reqs(cfg, n=3, uid0=10)
    rt.engines[0].submit(a)
    rt.engines[1].submit(b)
    rt.run()
    assert [e.prefix_match_tokens(probe.prompt) for e in rt.engines] == [8, 8]
    rt.engines[0].submit(next(fillers))
    assert rt.submit(probe) == 1


@pytest.mark.parametrize("case", ["wrong_count", "shared_device"])
def test_router_rejects_bad_devices(served, monkeypatch, case):
    cfg, model, params = served
    d = jax.devices()[0]
    devices = {"wrong_count": jax.devices()[:1], "shared_device": [d, d]}[case]

    def no_engine(*args, **kwargs):
        raise AssertionError("an engine was built before the device check")

    monkeypatch.setattr(router_mod, "ServeEngine", no_engine)
    with pytest.raises(ValueError):
        CubeRouter(model, params, EngineConfig(batch_slots=1, max_len=32),
                   n_cubes=2, devices=devices)


def test_router_step_reports_drained(served):
    cfg, model, params = served
    rt = CubeRouter(model, params, _swap_ecfg(), n_cubes=2)
    for r in _reqs(cfg, n=3, plen=7, max_new=4):
        rt.submit(r)
    steps = 0
    while any(e.load for e in rt.engines):
        assert rt.step() is True
        steps += 1
        assert steps < 200
    assert rt.step() is False
    assert sum(len(e.completed) for e in rt.engines) == 3


def test_router_keeps_each_request_on_its_cube(served):
    cfg, model, params = served
    got, rt, cubes = _route(model, params, _swap_ecfg(),
                            _reqs(cfg, n=6, plen=7, max_new=8), "hash")
    assert sorted(got) == list(range(6))
    for uid, cube in enumerate(cubes):
        assert uid in {r.uid for r in rt.engines[cube].completed}
        assert uid not in {r.uid for r in rt.engines[1 - cube].completed}


# ---------------------------------------------------------------------------
# Paged read kernel vs oracle vs model decode attention
# ---------------------------------------------------------------------------


def test_paged_kernels_match_ref_and_decode_attention():
    from repro.kernels import ops, ref
    from repro.models.attention import decode_attention

    rng = np.random.default_rng(0)
    n, ps, g, d, b, p, h = 12, 16, 2, 32, 3, 4, 4
    kpool = jnp.asarray(rng.normal(size=(n, ps, g, d)), jnp.float32)
    vpool = jnp.asarray(rng.normal(size=(n, ps, g, d)), jnp.float32)
    bt = jnp.asarray([[0, 3, -1, -1], [5, 2, 7, -1], [1, -1, -1, -1]],
                     jnp.int32)
    lengths = jnp.asarray([20, 45, 9], jnp.int32)

    got = ops.paged_gather(kpool, bt)
    want = ref.paged_gather(kpool, bt)
    assert np.array_equal(np.asarray(got), np.asarray(want))   # pure copy

    q = jnp.asarray(rng.normal(size=(b, h, d)), jnp.float32)
    got = ops.paged_attention(q, kpool, vpool, bt, lengths)
    want = ref.paged_decode_attention(
        q.reshape(b, g, h // g, d), kpool.transpose(2, 0, 1, 3),
        vpool.transpose(2, 0, 1, 3), bt, lengths,
    ).reshape(b, h, d)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)

    kd = ref.paged_gather(kpool, bt).reshape(b, p * ps, g, d)
    vd = ref.paged_gather(vpool, bt).reshape(b, p * ps, g, d)
    da = decode_attention(q[:, None].reshape(b, 1, h, d), kd, vd,
                          position=lengths - 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(da[:, 0]),
                               rtol=1e-5, atol=1e-5)


def test_pallas_gather_impl_serves_identically(served):
    cfg, model, params = served
    want, _ = _serve(ServeEngine, model, params,
                     EngineConfig(batch_slots=2, max_len=32),
                     _reqs(cfg, n=2, max_new=3))
    got, _ = _serve(ServeEngine, model, params,
                    EngineConfig(batch_slots=2, max_len=32,
                                 decode_path="gather", gather_impl="pallas"),
                    _reqs(cfg, n=2, max_new=3))
    assert want == got


# ---------------------------------------------------------------------------
# Sharding rules for page pools
# ---------------------------------------------------------------------------


def test_paged_cache_axes_resolve_on_host_mesh(served):
    from repro.dist.sharding import cube_rules, paged_cache_axes, tree_shardings

    cfg, model, params = served
    model2 = build_model(dataclasses.replace(cfg, decode_unroll_layers=False))
    specs = model2.cache_page_specs(lanes=2, n_pages=8, page_size=8)
    axes = paged_cache_axes(cfg, specs)
    for s, ax in zip(jax.tree.leaves(specs), jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple))):
        assert len(ax) == len(s.shape)
    mesh = jax.make_mesh((1,), ("pod",))
    rules = cube_rules(mesh)
    assert rules.rules["pages"] is None
    shardings = tree_shardings(mesh, specs, axes, rules)
    for sh in jax.tree.leaves(shardings):
        assert sh.mesh == mesh                 # resolved (replicated on 1 dev)


def test_model_cache_page_specs_shapes(served):
    cfg, model, params = served
    model2 = build_model(dataclasses.replace(cfg, decode_unroll_layers=False))
    specs = model2.cache_page_specs(lanes=3, n_pages=10, page_size=8)
    leaves = jax.tree.leaves(specs)
    # qwen reduced: 2 layers of GQA k/v — every leaf is a pool
    assert all(l.shape[1:3] == (10, 8) for l in leaves)
    base = model2.cache_specs(3, 8)
    assert jax.tree_util.tree_structure(specs) == jax.tree_util.tree_structure(base)


# ---------------------------------------------------------------------------
# Serving bench smoke (tier-1: the bench may not rot)
# ---------------------------------------------------------------------------


def test_serve_bench_smoke(tmp_path):
    import sys

    sys.path.insert(0, ".")
    try:
        from benchmarks import serve_bench
    finally:
        sys.path.pop(0)
    out = tmp_path / "serve_bench.json"
    results = serve_bench.main(["--smoke", "--out", str(out)])
    import json

    report = json.loads(out.read_text())
    assert {"dense", "paged", "decode_paths", "speedup",
            "workload"} <= report.keys()
    assert report["paged"]["tokens"] == report["dense"]["tokens"] > 0
    assert report["workload"]["smoke"] is True
    assert results["speedup"] == report["speedup"]
    # the smoke drives BOTH decode paths and asserts token identity inside
    # bench_pair — a silent numeric break cannot pass the CI gate
    assert report["paths_token_identical"] is True
    assert {"gather", "paged"} == set(report["decode_paths"])
    for path in ("gather", "paged"):
        p = report["decode_paths"][path]
        assert p["step_latency_ms"]["p50"] > 0
        assert p["gathered_view_bytes"] > 0
    if report["decode_paths"]["paged"]["decode_memory"]["available"]:
        # the paged step must not be bigger than the gather step: it never
        # materializes the dense view the gather path allocates
        assert (report["decode_paths"]["paged"]["decode_memory"]["peak_live_bytes"]
                <= report["decode_paths"]["gather"]["decode_memory"]["peak_live_bytes"])
    # the preemption-policy sweep rode along: swap/recompute identity held
    # under forced memory pressure and the crossover metric is present
    pre = report["preempt"]
    assert pre["preempt_tokens_identical"] is True
    assert pre["swap_vs_recompute_speedup"] > 0
    assert "crossover_prompt_len" in pre
    for row in pre["sweep"]:
        assert row["swap"]["preemptions"] > 0
        assert row["recompute"]["preemptions"] > 0
        assert row["swap"]["swap_preemptions"] > 0
        assert row["recompute"]["swap_preemptions"] == 0
    # the admission-pipeline storm: async/sync token identity held and the
    # gated ratio + per-mode decode-idle telemetry are present
    a = report["async"]
    assert a["tokens_identical"] is True
    assert a["async_vs_sync_tokens_per_s"] > 0
    for mode in ("on", "off"):
        assert 0.0 <= a["modes"][mode]["decode_idle_fraction"] <= 1.0
        assert a["modes"][mode]["step_latency_ms"]["p50"] > 0
    assert a["families"]["mamba2-130m"]["tokens_identical"] is True
    assert report["swap_batch"]["speedup"] > 0
    # prefix-reuse smoke: the zipfian replays actually hit the radix index
    # and reproduce the re-prefill tokens (both asserted inside bench_prefix
    # as well — a dead index or a CoW break cannot pass the smoke)
    assert report["prefix"]["tokens_identical"] is True
    assert report["prefix"]["prefix_hit_rate"] > 0.5
    assert report["prefix"]["prefix_vs_none_tokens_per_s"] > 0


# ---------------------------------------------------------------------------
# EngineConfig: nested groups + flat-kwarg back-compat
# ---------------------------------------------------------------------------


def test_engine_config_nested_groups_and_flat_compat():
    import dataclasses
    import warnings

    from repro.serve import AdmissionConfig, CacheConfig, ObsConfig
    from repro.serve import engine as engine_mod

    # nested construction is the real surface — no warnings
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        ecfg = EngineConfig(batch_slots=2, max_len=64,
                            cache=CacheConfig(page_size=8),
                            admission=AdmissionConfig(prefill_chunk=4),
                            obs=ObsConfig(trace=True))
    assert ecfg.cache.page_size == 8 and ecfg.admission.prefill_chunk == 4
    # flat reads/writes pass through to the owning group
    assert ecfg.page_size == 8 and ecfg.trace is True
    ecfg.page_size = 4
    assert ecfg.cache.page_size == 4

    # legacy flat kwargs still construct, warning once per knob
    engine_mod._warned_flat.clear()
    with pytest.warns(DeprecationWarning, match="page_size"):
        flat = EngineConfig(batch_slots=2, max_len=64, page_size=8,
                            prefill_chunk=4, trace=True)
    assert flat.cache.page_size == 8
    assert flat.admission.prefill_chunk == 4
    assert flat.obs.trace is True
    with warnings.catch_warnings():          # ...and only once
        warnings.simplefilter("error")
        EngineConfig(page_size=8)

    # unknown knobs still fail loudly
    with pytest.raises(TypeError, match="mistyped_knob"):
        EngineConfig(mistyped_knob=1)

    # dataclasses.replace composes with both spellings
    r = dataclasses.replace(flat, n_pages=12)
    assert r.cache.n_pages == 12 and r.cache.page_size == 8
    r2 = dataclasses.replace(flat, cache=CacheConfig(page_size=2))
    assert r2.cache.page_size == 2
