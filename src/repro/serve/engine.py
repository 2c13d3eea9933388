"""Paged continuous-batching serving engine (serve v2), two-loop form.

The v1 engine was a fixed-slot array over a dense ``batch_slots x max_len``
cache; v2 made the cache paged and the scheduler explicit.  This revision
splits the single host loop in two, the way the paper's NeuroCluster splits
DMA from compute (double-buffering keeps the NeuroStreams fed — the host
never serializes data movement with streaming):

* the **decode loop** (``step``/``run``, the caller's thread) owns the page
  pools and block tables exclusively: lane assignment, page growth,
  batched preemption (one device→host copy per leaf for the whole victim
  set), and the batched decode step;
* the **admission pipeline** (``serve.admission.AdmissionPipeline``) runs
  prefill chunks and host-tier swap-in staging — the serve loop's data
  movement — on a worker thread (``EngineConfig.async_prefill``, default
  on) or inline as a sync fallback, computing into *private* per-request
  buffers and handing finished requests to the decode loop through the
  scheduler's ready queue.

Shared bookkeeping (queues, free lists, stats) lives under one engine lock;
jax compute never runs inside it.  Both pipeline modes are bit-identical:
the pipeline never touches the pools, so moving it across threads moves
*when* work happens, never *what* it computes — asserted engine-wide by the
``--async-prefill both`` bench axis and the thread-stress tests.

The greedy/temperature sampling API (``Request``, ``submit``, ``step``,
``run``) is unchanged from v1; the dense engine survives as
``serve.dense_engine.DenseSlotEngine`` (the bit-exactness reference).
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import warnings
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import sanitizer
from repro.analysis.ownership import (
    admission_api,
    decode_loop_only,
    pool_mutator,
)
from repro.obs import clock as obs_clock
from repro.obs.metrics import MetricsRegistry
from repro.obs.trace import NULL_TRACER, ServeTracer

from .admission import AdmissionPipeline, prefill_logits_token
from .paged_cache import (
    PagedKVCache,
    absorb_decode,
    gather_views,
)
from .scheduler import Scheduler, SchedulerConfig


@dataclass
class Request:
    uid: int
    prompt: np.ndarray               # (S,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0
    out_tokens: list = field(default_factory=list)
    done: bool = False


@dataclass
class CacheConfig:
    """Paged-KV memory: device pool, host tier, preemption, read paths."""

    # paged-KV pool (memory width; defaults to the v1 dense budget)
    page_size: int = 16
    n_pages: int | None = None      # None → batch_slots * max_len / page_size
    host_pages: int | None = None   # host-tier size; None → 2x n_pages when
    #                                 preempt_policy='swap', else 0 (no tier)
    # preemption: 'swap' moves a victim's pages to a host-DRAM page pool and
    # restores them on resume (no prefill re-runs; falls back to recompute
    # when the host tier is exhausted or the cost model prefers it);
    # 'recompute' frees the pages and re-prefills prompt + generated tokens
    # (the v2 behavior, proven token-identical to 'swap')
    preempt_policy: str = "swap"
    swap_token_cost: float = 0.25   # cost model: moving one token of KV
    #                                 relative to recomputing it (0 ⇒ always
    #                                 swap when host pages allow)
    # decode path: 'paged' hands block tables straight to the model
    # (decode_step_paged — the dense (B, max_len) gathered view is never
    # built); 'gather' is the materialize-then-decode fallback oracle the
    # paged path is proven bit-exact against
    decode_path: str = "paged"
    # paged-path attention read: 'xla' (transient per-layer gather, bit-
    # exact vs the gather path) or 'pallas' (the fused paged_decode_attention
    # kernel — no gather at all; interpret mode off-TPU).  GQA layers only:
    # MLA layers (absorbed two-term scores) and sliding-window layers always
    # take the XLA form whatever this is set to
    attn_impl: str = "xla"
    # gather-path page read: 'xla' advanced-indexing gather, or 'pallas' for
    # the kernels/paged_attn gather kernel (interpret mode off-TPU)
    gather_impl: str = "xla"
    # prefix sharing: a radix index over page-sized prompt chunks lets
    # admissions reuse already-resident prefix pages (refcounted, copy-on-
    # write on the first divergent write; cold prefixes retire into the
    # host tier).  Token-identical by construction — shared pages hold
    # bit-equal content — but OFF by default so throughput baselines don't
    # silently include cache hits
    prefix_sharing: bool = False


@dataclass
class AdmissionConfig:
    """Scheduler + admission-pipeline policy knobs."""

    policy: str = "fcfs"            # fcfs | spf
    # per-step token budget (decode + prefill), 0 = unbounded.  Paces the
    # SYNC pipeline's inline prefill work; in async mode prefill runs on
    # the worker's own clock, so the budget bounds decode lanes only and
    # pipeline pacing comes from admission_inflight
    max_step_tokens: int = 0
    prefill_chunk: int = 0          # 0 = whole-prompt prefill
    # admission pipeline: True runs prefill chunks + swap-in staging on a
    # worker thread feeding the ready queue (decode lanes never stall on an
    # arrival or a restore); False runs the identical pipeline inline each
    # step — the debugging fallback and the bench baseline.  Bit-identical
    # tokens either way (the pipeline owns no shared device state)
    async_prefill: bool = True
    # backpressure: prefills/restores admitted (pages reserved, private
    # buffers held) but not yet decoding.  Bounds the pipeline's page +
    # memory footprint; raise it to keep a deep ready queue under storms
    admission_inflight: int = 2


@dataclass
class ObsConfig:
    """Observability (repro.obs) knobs."""

    # trace=True records engine-step / prefill / swap / phase events into a
    # preallocated ring buffer (see ServeEngine.save_trace →
    # Perfetto-loadable JSON); off, every record call is a single
    # disabled-flag check through the shared NULL_TRACER
    trace: bool = False
    trace_capacity: int = 1 << 15   # ring slots; wraparound drops oldest
    # trace_annotations=True opens every span of the trace's schema (step,
    # lane fill, page checks, the decode batch's inputs, dispatch, wait,
    # fetch and sampling, prefill chunks) also as a jax.profiler
    # TraceAnnotation named "repro." + the event name: the same names land
    # in a device profile, on the device trace's clock.  Independent of
    # `trace`: either sink works without the other
    trace_annotations: bool = False


def _flat_map() -> dict[str, str]:
    return {
        **{f.name: "cache" for f in dataclasses.fields(CacheConfig)},
        **{f.name: "admission" for f in dataclasses.fields(AdmissionConfig)},
        **{f.name: "obs" for f in dataclasses.fields(ObsConfig)},
    }


_FLAT_MAP = _flat_map()
_warned_flat: set[str] = set()


@dataclass(init=False)
class EngineConfig:
    """Engine configuration: three top-level knobs plus nested groups.

    The ~19 flat knobs the engine accreted across PRs now live in
    :class:`CacheConfig` / :class:`AdmissionConfig` / :class:`ObsConfig`.
    Flat kwargs (``EngineConfig(page_size=4)``) are still accepted — routed
    onto the right group with a once-per-knob ``DeprecationWarning`` — and
    every old flat name remains readable/writable as a property, so
    ``dataclasses.replace(ecfg, n_pages=8)`` keeps working.  See
    MIGRATION.md.
    """

    batch_slots: int = 4            # decode lanes (compute width, not memory)
    max_len: int = 256              # per-request context capacity
    eos_id: int | None = None
    cache: CacheConfig = field(default_factory=CacheConfig)
    admission: AdmissionConfig = field(default_factory=AdmissionConfig)
    obs: ObsConfig = field(default_factory=ObsConfig)

    def __init__(self, batch_slots: int = 4, max_len: int = 256,
                 eos_id: int | None = None, cache: CacheConfig | None = None,
                 admission: AdmissionConfig | None = None,
                 obs: ObsConfig | None = None, **flat):
        self.batch_slots = batch_slots
        self.max_len = max_len
        self.eos_id = eos_id
        self.cache = cache if cache is not None else CacheConfig()
        self.admission = (admission if admission is not None
                          else AdmissionConfig())
        self.obs = obs if obs is not None else ObsConfig()
        if not flat:
            return
        groups: dict[str, dict] = {"cache": {}, "admission": {}, "obs": {}}
        for k, v in flat.items():
            g = _FLAT_MAP.get(k)
            if g is None:
                raise TypeError(
                    f"EngineConfig got an unexpected keyword argument {k!r}"
                )
            if k not in _warned_flat:
                _warned_flat.add(k)
                warnings.warn(
                    f"EngineConfig({k}=...) is deprecated; use "
                    f"EngineConfig({g}={g.capitalize()}Config({k}=...))",
                    DeprecationWarning, stacklevel=2,
                )
            groups[g][k] = v
        if groups["cache"]:
            self.cache = dataclasses.replace(self.cache, **groups["cache"])
        if groups["admission"]:
            self.admission = dataclasses.replace(self.admission,
                                                 **groups["admission"])
        if groups["obs"]:
            self.obs = dataclasses.replace(self.obs, **groups["obs"])


def _flat_property(group: str, name: str):
    def get(self):
        return getattr(getattr(self, group), name)

    def set_(self, value):
        setattr(getattr(self, group), name, value)

    return property(get, set_)


for _name, _group in _FLAT_MAP.items():
    setattr(EngineConfig, _name, _flat_property(_group, _name))
del _name, _group


def stacked_decode_model(model):
    """Return ``model`` rebuilt on the stacked decode-cache layout if needed.

    The serving engines pack per-request caches into stacked
    ``(layers, B, ...)`` buffers — the page pools index layers as one leading
    dim and share one block table across layers.  A model built with
    ``decode_unroll_layers=True`` (the training/dry-run §Perf layout) instead
    emits per-layer cache *lists* whose leaves alias via donation, which
    cannot be packed per-slot; rebuild it stacked.
    """
    if getattr(model.cfg, "decode_unroll_layers", False):
        from repro.models.api import build_model

        model = build_model(
            dataclasses.replace(model.cfg, decode_unroll_layers=False)
        )
    return model


class ServeEngine:
    """Greedy/temperature sampling over the DecoderLM serving API, backed by
    a paged KV cache, a request scheduler, and an admission pipeline."""

    def __init__(self, model, params, ecfg: EngineConfig, rules=None,
                 device=None):
        """``device`` pins the engine to one device: the params are copied
        there and the page pools made there, so every jitted step runs
        there too (one replica per chip behind ``CubeRouter``).  None keeps
        JAX's default placement."""
        if ecfg.decode_path not in ("paged", "gather"):
            raise ValueError(f"unknown decode_path: {ecfg.decode_path!r}")
        if ecfg.preempt_policy not in ("swap", "recompute"):
            raise ValueError(
                f"unknown preempt_policy: {ecfg.preempt_policy!r}"
            )
        model = stacked_decode_model(model)
        if ecfg.decode_path == "paged" and not hasattr(model,
                                                      "decode_step_paged"):
            raise TypeError(
                f"{type(model).__name__} has no decode_step_paged; serve it "
                "with decode_path='gather'"
            )
        if device is not None:
            params = jax.device_put(params, device)
        self.model = model
        self.params = params
        self.ecfg = ecfg
        self.rules = rules
        self.cfg = model.cfg
        # ONE bookkeeping lock (queues, free lists, metrics) shared by the
        # decode loop and the admission pipeline; jax compute never runs
        # under it.  The condition variable signals hand-offs both ways
        # (ready-queue push, page free, submit) so neither loop spins.
        # Created FIRST: the metrics registry shares it (single-lock
        # telemetry snapshots) and the cache/host tier count through the
        # registry.
        self._lock = threading.RLock()
        self._cv = threading.Condition(self._lock)
        self.metrics = MetricsRegistry(lock=self._lock)
        self.tracer: ServeTracer = (
            ServeTracer(capacity=ecfg.trace_capacity if ecfg.trace else 1,
                        enabled=ecfg.trace,
                        annotate=ecfg.trace_annotations)
            if ecfg.trace or ecfg.trace_annotations else NULL_TRACER
        )
        ps = ecfg.page_size
        n_pages = (
            ecfg.n_pages
            if ecfg.n_pages is not None
            else ecfg.batch_slots * -(-ecfg.max_len // ps)
        )
        host_pages = ecfg.host_pages
        if host_pages is None:
            # host DRAM is the big tier: default to twice the device pool so
            # swap preemption rarely hits the exhaustion fallback
            host_pages = 2 * n_pages if ecfg.preempt_policy == "swap" else 0
        self.cache = PagedKVCache(
            model, lanes=ecfg.batch_slots, n_pages=n_pages, page_size=ps,
            max_len=ecfg.max_len, host_pages=host_pages,
            metrics=self.metrics, prefix_sharing=ecfg.prefix_sharing,
            device=device,
        )
        chunk = (ecfg.prefill_chunk
                 if getattr(model, "supports_chunked_prefill", False) else 0)
        self.sched = Scheduler(SchedulerConfig(
            policy=ecfg.policy, max_step_tokens=ecfg.max_step_tokens,
            prefill_chunk=chunk, preempt_policy=ecfg.preempt_policy,
            swap_token_cost=ecfg.swap_token_cost,
            max_inflight_prefills=ecfg.admission_inflight,
        ), tracer=self.tracer)
        self.completed: list[Request] = []
        # engine counters, pre-created so hot paths inc without a registry
        # lookup; lane_step/lane_slot: decode-lane utilization — active
        # lanes vs capacity, summed per step (1 - lane/slot is the idle
        # fraction the async pipeline exists to shrink)
        m = self.metrics
        self._c_steps = m.counter("steps")
        self._c_prefill = m.counter("prefill_tokens")
        self._c_decode = m.counter("decode_tokens")
        self._c_lane_step = m.counter("lane_step_sum")
        self._c_lane_slot = m.counter("lane_slot_sum")
        self._h_occ = m.histogram(
            "occupancy", tuple(i / 10 for i in range(1, 11)))
        self._g_occ = m.gauge("occupancy")
        self._h_step = m.histogram("step_latency_s")
        self._h_queue = m.histogram("queue_wait_s")
        # the decode step in three: step start to the program's enqueue,
        # the host blocked on the device, then the token fetch and each
        # lane's bookkeeping
        self._h_prep = m.histogram("decode.prep_s")
        self._h_wait = m.histogram("decode.wait_s")
        self._h_sample = m.histogram("decode.sample_s")
        # decode lanes whose token was drawn from their logits row (sampled
        # at a temperature) rather than taken from the program's greedy choice
        self._c_host_rows = m.counter("decode.host_rows")
        # the decode loop's time writing a lane's recurrent state at its
        # assignment (families with per-lane state only)
        self._h_state = m.histogram("lanes.state_write_s")
        self._has_state = self.cache.has_state_leaves()
        # the share of the Pallas paged kernel's KV blocks a step walks: the
        # blocks each active lane's length reaches over every lane's blocks
        # (observed only where the paged decode runs that kernel)
        self._h_kv_blocks = m.histogram(
            "decode.kv_block_share", tuple(i / 10 for i in range(1, 11)))
        self._kv_block_tokens = None
        if ecfg.decode_path == "paged" and ecfg.attn_impl == "pallas":
            from repro.kernels.ops import paged_block_pages

            self._kv_block_tokens = ps * paged_block_pages(
                ps, self.cache.pages_per_lane)
        sanitizer.register_engine(self)
        self.pipeline = AdmissionPipeline(self, ecfg.async_prefill)
        self._idle_since: float | None = None
        self._idle_pipe_mark = -1
        self._decode = jax.jit(self._decode_impl, donate_argnums=(1,))
        self._extend = jax.jit(self._extend_impl, donate_argnums=(1,))
        # whole-prompt prefill, jit-cached per prompt length (the dense v1
        # engine ran this eagerly — measured prefill-bound on mixed traffic)
        self._prefill = jax.jit(self._prefill_impl)

    def __del__(self):
        with contextlib.suppress(Exception):
            self.pipeline.shutdown()

    # -- jitted pieces --------------------------------------------------------

    def _decode_impl(self, params, pools, bt, tokens, positions, active):
        """One decode step over every lane: ``(tokens (B,), logits (B, 1,
        V), pools)``.  ``tokens`` is each lane's greedy choice, the first
        maximum over the whole padded vocabulary of the program's own
        logits, so only B int32 cross to the host for greedy lanes."""
        if self.ecfg.decode_path == "gather":
            # fallback oracle: materialize the dense per-lane views, decode,
            # scatter the written column back into the pools
            views = gather_views(pools, bt, self.cfg.hd,
                                 impl=self.ecfg.gather_impl)
            logits, new_views = self.model.decode_step(
                params, views, tokens, positions, self.rules
            )
            pools = absorb_decode(
                pools, new_views, bt, positions, active, self.cache.page_size
            )
        else:
            # zero-materialization path: the model reads/writes the page
            # pools through the block table (attn_decode_paged /
            # mla_decode_paged)
            logits, pools = self.model.decode_step_paged(
                params, pools, bt, tokens, positions, active, self.rules,
                attn_impl=self.ecfg.attn_impl,
            )
        choice = jnp.argmax(logits[:, 0], axis=-1).astype(jnp.int32)
        return choice, logits, pools

    def _prefill_impl(self, params, tokens):
        return self.model.prefill(params, tokens, self.rules)

    def _extend_impl(self, params, tree, tokens, start):
        # one chunked-prefill step over a request's PRIVATE cache tree —
        # the pipeline may run this on its thread while the decode loop
        # steps the pools, because they share no device buffers
        return self.model.extend_step(params, tree, tokens, start, self.rules)

    # -- request handling ------------------------------------------------------

    def submit(self, req: Request):
        need = self.cache.pages_for(len(req.prompt) + 1)
        if len(req.prompt) >= self.ecfg.max_len - 1:
            raise ValueError(
                f"prompt of {len(req.prompt)} tokens exceeds the "
                f"{self.ecfg.max_len}-token context limit"
            )
        if need > self.cache.n_pages:
            raise ValueError(
                f"prompt needs {need} pages, pool has {self.cache.n_pages}"
            )
        with self._lock:
            self.sched.add(req)
            self._cv.notify_all()
        self.pipeline.kick()

    # -- prefill (called by the admission pipeline, OUTSIDE the lock) ---------

    @admission_api
    def _fresh_prefill_tree(self):
        """Private single-request cache tree a chunked prefill computes
        into: seq leaves at full per-lane capacity (one jit signature per
        chunk length), state leaves per-lane.  Written into the reserved
        pages by the decode loop at lane assignment — the pipeline never
        touches the pools."""
        return jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            self.model.cache_specs(1, self.cache.capacity),
        )

    @admission_api
    def run_prefill(self, st, chunk: int) -> bool:
        """Advance ``st``'s prefill by one work unit (a chunk, or the whole
        prompt when chunking is off).  Pure compute on private state;
        returns True when the prefill is complete."""
        if self.sched.cfg.prefill_chunk <= 0:
            toks = jnp.asarray(st.resume_tokens, jnp.int32)[None]
            logits, st.prefill_cache = self._prefill(self.params, toks)
            st.prefilled = len(st.resume_tokens)
            st.last_logits = logits[0, -1]
            return True
        if st.prefill_cache is None:
            st.prefill_cache = self._fresh_prefill_tree()
            claim = st.prefix_claim
            if claim is not None and claim.seed_pages:
                # partial prefix hit: copy the shared pages' rows into the
                # private tree so the extend resumes mid-prompt (st.prefilled
                # was set to the matched token count at admission)
                st.prefill_cache = self.cache.seed_prefix(
                    st.prefill_cache, st.pages[: claim.seed_pages]
                )
        toks = st.resume_tokens[st.prefilled: st.prefilled + chunk]
        logits, st.prefill_cache = self._extend(
            self.params, st.prefill_cache,
            jnp.asarray(toks, jnp.int32)[None],
            jnp.asarray(st.prefilled, jnp.int32),
        )
        st.prefilled += chunk
        st.last_logits = logits[0, -1]
        return st.remaining_prefill == 0

    @admission_api
    def sample_prefill_token(self, st) -> int:
        """The prefill's one host-blocking sync — on the pipeline's thread
        in async mode, so it never stalls a decode step."""
        if st.is_resume:
            # recompute-resume: the continuation token was already sampled
            # before preemption — discard the re-derived logits
            return int(st.req.out_tokens[-1])
        return prefill_logits_token(st.last_logits)

    @admission_api
    def finish_prefill(self, st, tok: int) -> bool:
        """Queue bookkeeping after a finished prefill (under the lock):
        early EOS / single-token requests retire without ever taking a
        lane; everything else goes to ready.  Returns True if retired."""
        st.length = len(st.resume_tokens)
        req = st.req
        st.pending_token = tok
        if st.is_resume:
            self.sched.to_ready(st)
            return False
        req.out_tokens.append(tok)
        if (
            len(req.out_tokens) >= req.max_new_tokens
            or (self.ecfg.eos_id is not None and tok == self.ecfg.eos_id)
        ):
            self.sched.admitting.remove(st)
            self._retire(st)
            return True
        self.sched.to_ready(st)
        return False

    @admission_api
    def finish_match(self, st) -> bool:
        """Queue bookkeeping for a full prefix-cache hit (under the lock):
        the prompt's pages and its stored first greedy token are already in
        hand, so the request skips prefill and goes straight to ready — or
        retires immediately when the stored token already ends it.
        Returns True if retired."""
        st.length = len(st.resume_tokens)
        st.prefilled = len(st.resume_tokens)
        req = st.req
        if st.is_resume:
            # recompute-resume: the continuation token was sampled before
            # preemption — the terminal's stored token is irrelevant
            st.pending_token = int(req.out_tokens[-1])
            self.sched.to_ready(st)
            return False
        tok = int(st.prefix_claim.first_token)
        st.pending_token = tok
        req.out_tokens.append(tok)
        if (
            len(req.out_tokens) >= req.max_new_tokens
            or (self.ecfg.eos_id is not None and tok == self.ecfg.eos_id)
        ):
            self.sched.admitting.remove(st)
            self._retire(st)
            return True
        self.sched.to_ready(st)
        return False

    @admission_api
    def _retire(self, st):
        """Retirement bookkeeping shared by both threads: queues, free
        lists, held buffers — never lane or pool state (a decode-retired
        request goes through ``_retire_lane`` first, which releases those;
        a prefill-retired one never owned them)."""
        with self._lock:
            assert st.lane < 0, "retiring a laned request: use _retire_lane"
            st.req.done = True
            # release, not free: pages shared with the prefix index (or
            # another lane) survive — only sole-owned pages hit the free list
            self.cache.allocator.release(st.pages)
            sanitizer.note_release(st)
            st.pages = []
            if st.prefix_claim is not None:
                self.cache.abort_match(st.prefix_claim)
                st.prefix_claim = None
            st.prefix_staged = None
            if st.swap_handle is not None:
                self.cache.host_free(st.swap_handle)
                st.swap_handle = None
            # drop every held buffer: a retired request must pin no device
            # memory (prefill caches, staged restores, logits rows) — and
            # fold its per-uid preemption counter into the high-water mark
            # so long-lived engines don't grow a dict entry per request
            st.prefill_cache = st.state_cache = st.staged = None
            st.last_logits = None
            self.sched.retire_uid(st.req.uid)
            st.phase = "done"
            self.completed.append(st.req)
            self._cv.notify_all()        # freed pages: admissions may resume

    @decode_loop_only
    def _retire_lane(self, st):
        """Decode-loop half of retirement: release the lane and its block-
        table row (pool state only this thread may touch), then the shared
        bookkeeping."""
        with self._lock:
            self.cache.clear_lane(st.lane)
            self.sched.running.pop(st.lane, None)
            st.lane = -1
        self._retire(st)

    # -- lane assignment (decode loop only) ------------------------------------

    @decode_loop_only
    def _fill_lanes(self) -> bool:
        """Drain the ready queue into free decode lanes and fold the
        pipeline's private results into the pools (the decode loop is the
        only pools writer)."""
        s, c = self.sched, self.ecfg
        now = obs_clock.monotonic()
        with self._lock:
            free_lanes = [l for l in range(c.batch_slots)
                          if l not in s.running]
            take = []
            while s.ready and free_lanes:
                st = s.ready.pop(0)
                lane = free_lanes.pop(0)
                st.lane = lane
                st.phase = "running"
                s.running[lane] = st
                # submit (or preemption requeue) → lane assignment
                self._h_queue.observe(now - st.submit_ts)
                take.append(st)
            if take:
                self._cv.notify_all()    # ready drained: backpressure lifts
        inserts: list = []
        for st in take:
            # use-after-free/ABA check: every page id this request holds is
            # live and still of the generation granted at admission
            sanitizer.verify_grant(st, self.cache.allocator)
            self.cache.assign_lane(st.lane, st.pages)
            if st.prefix_staged is not None:
                # host-retired prefix pages staged by the pipeline: scatter
                # them back into their (freshly acquired) device pages
                staged, dev_pages = st.prefix_staged
                self.cache.commit_swap_in(staged, dev_pages)
                st.prefix_staged = None
            if st.staged is not None:                 # swap-in restore
                self.cache.commit_swap_in(st.staged, st.pages)
                st.staged = None
            elif st.prefill_cache is not None:        # held prefill cache
                claim = st.prefix_claim
                skip = claim.seed_pages if claim is not None else 0
                if self.cache.prefix is not None and not st.is_resume:
                    # snapshot recurrent state OUTSIDE the lock (device
                    # read) before the private tree is dropped, so the
                    # index can serve full-terminal hits for state families
                    inserts.append(
                        (st, self.cache.snapshot_state(st.prefill_cache))
                    )
                if self._has_state:
                    self._write_lane_state(st.lane, st.prefill_cache)
                self.cache.write_prefill(st.pages, st.prefill_cache,
                                         skip_pages=skip)
                st.prefill_cache = None
            if st.state_cache is not None:            # restored lane state
                self._write_lane_state(st.lane, st.state_cache)
                st.state_cache = None
        if self.cache.prefix is not None:
            post = [st for st in take if st.prefix_claim is not None]
            if post or inserts:
                with self._lock:
                    for st in post:
                        if st.prefix_claim.restore:
                            self.cache.prefix_finish_restore(st.prefix_claim)
                        st.prefix_claim = None
                    for st, state_np in inserts:
                        self.cache.prefix_insert(st.resume_tokens, st.pages,
                                                 state_np, st.pending_token)
        return bool(take)

    @decode_loop_only
    def _write_lane_state(self, lane: int, tree) -> None:
        """Write a request's recurrent state into ``lane``'s rows in place.
        The write is only enqueued here: the device runs it in order with
        the prefill chunks queued ahead of it, and the next decode step
        waits for it as for those."""
        tr = self.tracer
        t0 = obs_clock.monotonic()
        tr.begin(tr.EV_LANES_STATE, lane)
        self.cache.write_state(lane, tree)
        tr.end(tr.EV_LANES_STATE, lane)
        with self._lock:
            self._h_state.observe(obs_clock.monotonic() - t0)

    # -- decode ----------------------------------------------------------------

    @decode_loop_only
    def _ensure_pages(self):
        """Every running lane needs a page slot for its next write position.

        Plans the whole step's page demand at once: reserve what the free
        pool covers, pick victims for the shortfall (longest-running
        first), evict them as ONE batch (one device→host copy per leaf —
        see ``Scheduler.preempt_batch``), then grow the surviving lanes.
        Runs under the engine lock: the admission pipeline can neither
        steal the reserved pages nor race the victim bookkeeping."""
        s, cache = self.sched, self.cache
        alloc = cache.allocator
        ps = cache.page_size
        with self._lock:
            grow = {
                lane: max(0, st.length // ps + 1 - len(st.pages))
                for lane, st in s.running.items()
            }
            # copy-on-write: a lane whose next write position lands in a
            # page it shares (with the prefix index or another lane) must
            # fork that page before the decode step scatters into it
            forks: dict[int, int] = {}
            for lane, st in s.running.items():
                i = st.length // ps
                if i < len(st.pages) and alloc.refcount(st.pages[i]) > 1:
                    forks[lane] = i
            if not any(grow.values()) and not forks:
                return
            hold = alloc.acquire(
                min(sum(grow.values()), alloc.n_free)) or []
            victims: list = []

            def shortfall() -> int:
                want = sum(n for lane, n in grow.items()
                           if s.running[lane] not in victims)
                want += sum(1 for lane in forks
                            if s.running[lane] not in victims)
                # a victim's shared pages survive its eviction (the prefix
                # index or a co-tenant lane keeps them) — only sole-owned
                # pages come back to the free list
                freed = sum(1 for v in victims
                            for p in v.pages if alloc.refcount(p) == 1)
                return want - len(hold) - alloc.n_free - freed

            # before evicting a live lane, reclaim cold prefix-index pages:
            # the persistent prefix cache always yields to running requests
            if shortfall() > 0 and cache.prefix is not None:
                reclaimed = cache.prefix_retire(shortfall())
                if reclaimed:
                    self.tracer.instant(self.tracer.EV_PREFIX_RETIRE,
                                        reclaimed)
            while shortfall() > 0:
                cands = [st for st in s.running.values()
                         if st not in victims]
                # evicting the LAST running lane is only progress when some
                # admitted/ready request holds the missing pages and can
                # take the lane over (the pipeline reserves pages before
                # the request is preemptible — a state the old serial loop
                # could never see); with nothing else in flight the pool is
                # genuinely too small for this request
                if len(cands) <= 1 and not (s.ready or s.admitting):
                    alloc.release(hold)
                    raise RuntimeError(
                        "page pool exhausted with no preemptible request — "
                        "grow EngineConfig.n_pages"
                    )
                if not cands:
                    break
                victims.append(max(cands,
                                   key=lambda st: len(st.req.out_tokens)))
            if victims:
                s.preempt_batch(victims, cache)
                self._cv.notify_all()    # freed pages: admissions may resume
            for lane in sorted(s.running):
                st = s.running[lane]
                n = grow.get(lane, 0)
                while n > 0:
                    page = hold.pop() if hold else alloc.acquire(1)[0]
                    cache.extend_lane(lane, page, len(st.pages))
                    st.pages.append(page)
                    sanitizer.note_grant(st, [page], alloc)
                    n -= 1
            if hold:
                alloc.release(hold)
            # forks last, from the replenished pool: remap the lane to a
            # private copy, leaving the shared original with its co-owners
            copies: list[tuple[int, int]] = []
            for lane, i in forks.items():
                st = s.running.get(lane)
                if st is None:                  # lane was evicted above
                    continue
                old = st.pages[i]
                if alloc.refcount(old) <= 1:    # co-owner vanished meanwhile
                    continue
                new = alloc.fork_for_write(old)
                if new is None:
                    raise RuntimeError(
                        "page pool exhausted during copy-on-write fork — "
                        "grow EngineConfig.n_pages"
                    )
                st.pages[i] = new
                cache.assign_lane(lane, st.pages)
                sanitizer.note_grant(st, [new], alloc)
                copies.append((old, new))
                if cache.prefix is not None:
                    cache.prefix.note_fork()
                self.tracer.instant(self.tracer.EV_PREFIX_FORK,
                                    st.req.uid, old)
            if copies:
                # one batched device copy of the forked rows; jax under the
                # lock follows the preempt_batch/swap_out precedent (the
                # decode loop owns the pools — nothing can race the copy)
                cache.fork_pages(copies)

    @decode_loop_only
    def decode_inputs(self):
        """Host arrays of the next decode step: (block_tables, tokens (B,1),
        positions (B,), active (B,)) — what ``_decode_lanes`` feeds the
        jitted step, for callers that replay that step against the pools."""
        b = self.ecfg.batch_slots
        tokens = np.zeros((b, 1), np.int32)
        positions = np.zeros((b,), np.int32)
        active = np.zeros((b,), bool)
        for lane, st in self.sched.running.items():
            tokens[lane, 0] = st.pending_token
            positions[lane] = st.length
            active[lane] = True
        return self.cache.block_tables.copy(), tokens, positions, active

    @decode_loop_only
    @pool_mutator("pools")
    def _decode_lanes(self, key, t_step: float):
        """One batched decode step over the running lanes; ``t_step`` is
        when the engine step began (the ``decode.prep_s`` origin)."""
        s, tr = self.sched, self.tracer
        n_active = len(s.running)
        tr.begin(tr.EV_DECODE, n_active)
        tr.begin(tr.EV_DECODE_INPUTS)
        bt, tokens, positions, active = self.decode_inputs()
        inputs = (jnp.asarray(bt), jnp.asarray(tokens),
                  jnp.asarray(positions), jnp.asarray(active))
        tr.end(tr.EV_DECODE_INPUTS)
        tr.begin(tr.EV_DECODE_DISPATCH)
        choice, logits, self.cache.pools = self._decode(
            self.params, self.cache.pools, *inputs)
        tr.end(tr.EV_DECODE_DISPATCH)
        t_sent = obs_clock.monotonic()
        tr.begin(tr.EV_DECODE_WAIT)
        choice.block_until_ready()
        tr.end(tr.EV_DECODE_WAIT)
        t_ready = obs_clock.monotonic()
        tr.begin(tr.EV_DECODE_FETCH)
        choice = np.asarray(choice)
        tr.end(tr.EV_DECODE_FETCH)
        tr.begin(tr.EV_DECODE_SAMPLE)
        done = host_rows = 0
        for lane in sorted(list(s.running)):
            st = s.running[lane]
            req = st.req
            if req.temperature > 0 and key is not None:
                # this lane's row, read on the device: the whole logits
                # never cross to the host
                key, sub = jax.random.split(key)
                tok = int(jax.random.categorical(
                    sub, logits[lane, 0].astype(jnp.float32) / req.temperature
                ))
                host_rows += 1
            else:
                tok = int(choice[lane])
            req.out_tokens.append(tok)
            st.length += 1
            st.pending_token = tok
            done += 1
            if (
                len(req.out_tokens) >= req.max_new_tokens
                or (self.ecfg.eos_id is not None
                    and tok == self.ecfg.eos_id)
                # cap at max_len, not the page-rounded capacity, to match
                # the dense engine's truncation exactly
                or st.length >= self.ecfg.max_len - 1
            ):
                self._retire_lane(st)
        tr.end(tr.EV_DECODE_SAMPLE)
        tr.end(tr.EV_DECODE, n_active)
        t_done = obs_clock.monotonic()
        kv_share = self._kv_block_share(positions, active)
        with self._lock:
            self._c_decode.inc(done)
            self._c_host_rows.inc(host_rows)
            self._c_lane_step.inc(n_active)
            self._h_prep.observe(t_sent - t_step)
            self._h_wait.observe(t_ready - t_sent)
            self._h_sample.observe(t_done - t_ready)
            if kv_share is not None:
                self._h_kv_blocks.observe(kv_share)

    def _kv_block_share(self, positions, active) -> float | None:
        """Blocks the paged kernel walks for these decode inputs (each
        active lane's ``ceil((position + 1) / block_tokens)``) over all
        lanes' blocks; None where the step does not run the kernel."""
        blk = self._kv_block_tokens
        if blk is None:
            return None
        walked = int(np.sum(-(-(positions[active] + 1) // blk)))
        return walked * blk / (len(positions) * self.cache.capacity)

    # -- step loop -------------------------------------------------------------

    @decode_loop_only
    def step(self, key=None) -> bool:
        """One decode-loop round: (sync mode only: pump the admission
        pipeline) → drain ready into lanes → one batched decode step.
        Returns False when the engine is fully drained.  In async mode a
        round with nothing to decode *waits* briefly on the pipeline's
        hand-off instead of spinning."""
        self.tracer.ensure_thread_name("decode-loop")
        t0 = obs_clock.monotonic()
        self.tracer.begin(self.tracer.EV_STEP)
        try:
            return self._step_inner(key, t0)
        finally:
            self.tracer.end(self.tracer.EV_STEP)
            with self._lock:
                self._h_step.observe(obs_clock.monotonic() - t0)

    @decode_loop_only
    def _step_inner(self, key, t0: float) -> bool:
        if self.pipeline.error is not None:
            err, self.pipeline.error = self.pipeline.error, None
            raise RuntimeError("admission pipeline died") from err
        s, c = self.sched, self.ecfg
        with self._lock:
            idle = s.load == 0
        if idle:
            # park the worker until resubmit — OUTSIDE the lock: the join
            # waits for the worker, and the worker needs the lock to leave
            # its cv.wait
            self.pipeline.shutdown()
            return False
        budget = c.max_step_tokens or (1 << 30)
        budget = max(budget - len(s.running), 0)
        if c.async_prefill:
            self.pipeline.kick()
            progressed = False
        else:
            progressed = self.pipeline.pump(budget)
        tr = self.tracer
        tr.begin(tr.EV_LANES_FILL)
        progressed = self._fill_lanes() or progressed
        tr.end(tr.EV_LANES_FILL)
        if s.running:
            tr.begin(tr.EV_PAGES_ENSURE)
            self._ensure_pages()
            tr.end(tr.EV_PAGES_ENSURE)
            if s.running:        # _ensure_pages may have evicted every lane
                self._decode_lanes(key, t0)
            progressed = True
        with self._lock:
            self._c_steps.inc()
            self._c_lane_slot.inc(c.batch_slots)
            occ = self.cache.occupancy()
            self._h_occ.observe(occ)     # mean = sum/count (count == steps)
            self._g_occ.set(occ)         # last value + high-water max
            self.tracer.counter(self.tracer.EV_PAGES_FREE,
                                self.cache.allocator.n_free)
        if progressed:
            self._idle_since = None
            return True
        if not c.async_prefill:
            if s.load:
                raise RuntimeError(
                    "scheduler stalled: waiting requests cannot be admitted "
                    "(page pool too small for the oldest request?)"
                )
            return True
        # async: the pipeline holds all in-flight work — wait for a ready
        # hand-off (or a completion) instead of burning the step clock.
        # The deadlock watchdog resets whenever the PIPELINE progresses
        # (chunks/stages/admissions), not just the decode loop: one slow
        # work item (a long whole-prompt compile, say) is not a deadlock
        now = obs_clock.monotonic()
        # one coherent cut of the pipeline counters (registry lock == engine
        # lock) — the old form summed a stats dict the worker could be
        # mid-update on
        pipe_mark = self.metrics.total("pipeline.")
        if self._idle_since is None or pipe_mark != self._idle_pipe_mark:
            self._idle_since = now
            self._idle_pipe_mark = pipe_mark
        elif now - self._idle_since > 60.0:
            raise RuntimeError(
                "decode loop idle >60s with no admission-pipeline progress "
                "and undrained requests — pipeline deadlocked or stalled "
                f"(load={s.load}, admitting={len(s.admitting)})"
            )
        with self._lock:
            if s.load and not s.ready and not s.running:
                self._cv.wait(timeout=0.01)
        return True

    @decode_loop_only
    def run(self, key=None) -> list[Request]:
        done_mark = len(self.completed)
        while self.load:
            if key is not None:
                key, step_key = jax.random.split(key)
            else:
                step_key = None
            self.step(step_key)
        self.pipeline.shutdown()         # park the worker until resubmit
        return self.completed[done_mark:]

    # -- telemetry (the router's queue-depth signal) ---------------------------

    @property
    def load(self) -> int:
        with self._lock:
            return self.sched.load

    def prefix_match_tokens(self, prompt) -> int:
        """Resident-prefix coverage for a prompt, in tokens — the router's
        prefix-affinity signal.  0 when prefix sharing is off."""
        if self.cache.prefix is None:
            return 0
        with self._lock:
            return self.cache.prefix.preview(np.asarray(prompt, np.int32))

    @property
    def stats(self) -> dict:
        """Back-compat view of the original hand-rolled stats dict, built
        from one metrics snapshot.  A *copy* — mutating it never touches
        live metrics; benches reset via :meth:`reset_stats`."""
        snap = self.metrics.snapshot()
        c = snap["counters"]
        occ = snap["histograms"]["occupancy"]
        return {
            "steps": c["steps"],
            "prefill_tokens": c["prefill_tokens"],
            "decode_tokens": c["decode_tokens"],
            "occupancy_sum": occ["sum"],
            "occupancy_max": snap["gauges"]["occupancy"]["max"],
            "lane_step_sum": c["lane_step_sum"],
            "lane_slot_sum": c["lane_slot_sum"],
        }

    def reset_stats(self) -> None:
        """Zero every metric (engine + pipeline + host tier) in place."""
        self.metrics.reset()

    def save_trace(self, path: str) -> dict:
        """Export the engine's ring buffer as Perfetto-loadable JSON."""
        from repro.obs.export import write_chrome_trace

        return write_chrome_trace(path, {"engine": self.tracer})

    def telemetry(self) -> dict:
        # ONE engine-lock acquisition for the whole cut: the metrics
        # registry shares the engine lock, so counters (engine, pipeline,
        # host tier), histograms, and scheduler queue state are one
        # consistent point in time — and the snapshot is deep (plain
        # ints/floats/fresh lists), so callers can mutate it freely
        with self._lock:
            snap = self.metrics.snapshot()
            sched = {
                "queue_depth": self.sched.queue_depth(),
                "admitting": len(self.sched.admitting),
                "ready": len(self.sched.ready),
                "running": len(self.sched.running),
                "preemptions": self.sched.n_preemptions,
                "swap_preemptions": self.sched.n_swap_preemptions,
                "recompute_preemptions": self.sched.n_recompute_preemptions,
                "max_request_preemptions": max(
                    [self.sched.max_preemptions_per_request]
                    + list(self.sched.preemptions_by_uid.values())
                ),
                "max_request_prefix_hit_tokens": max(
                    [self.sched.max_prefix_hit_tokens]
                    + list(self.sched.prefix_hit_tokens_by_uid.values())
                ),
            }
            page_occ = self.cache.occupancy()
            host_occ = self.cache.host_occupancy()
            has_host = self.cache.host is not None
            has_prefix = self.cache.prefix is not None
        c = snap["counters"]
        st: dict = {
            "steps": c["steps"],
            "prefill_tokens": c["prefill_tokens"],
            "decode_tokens": c["decode_tokens"],
        }
        st.update(sched)
        occ = snap["histograms"]["occupancy"]
        st["occupancy_mean"] = occ["sum"] / occ["count"] if occ["count"] else 0.0
        st["occupancy_max"] = snap["gauges"]["occupancy"]["max"]
        lane_cap = c["lane_slot_sum"]
        lane_act = c["lane_step_sum"]
        st["lane_utilization"] = lane_act / lane_cap if lane_cap else 0.0
        st["decode_idle_fraction"] = 1.0 - st["lane_utilization"]
        st["async_prefill"] = self.ecfg.async_prefill
        st["pipeline"] = {
            k[len("pipeline."):]: v for k, v in c.items()
            if k.startswith("pipeline.")
        }
        st["page_occupancy"] = page_occ
        st["host_page_occupancy"] = host_occ
        if has_host:
            st["host_tier"] = {
                k[len("host."):]: v for k, v in c.items()
                if k.startswith("host.")
            }
        if has_prefix:
            pr = {
                k[len("prefix."):]: v for k, v in c.items()
                if k.startswith("prefix.")
            }
            lookup = pr.get("lookup_tokens", 0)
            pr["hit_rate"] = (
                pr.get("hit_tokens", 0) / lookup if lookup else 0.0
            )
            st["prefix"] = pr
        st["histograms"] = snap["histograms"]
        return st
