"""Admission pipeline: prefill chunks + host-tier swap-in staging.

The paper's NeuroCluster never stalls a NeuroStream on data movement — DMA
double-buffering overlaps the next tile's transfer with the current tile's
compute (PAPER.md §4).  The serving analogue: admissions (prefill compute)
and host-tier restores (swap-in DMA) are the serve loop's data movement,
and running them inline in ``ServeEngine.step`` stalls every decode lane on
each arrival.  This module runs them as a *pipeline* beside the decode
loop:

* **async mode** (``EngineConfig.async_prefill=True``): a single worker
  thread pulls work items — stage a restore, run one prefill chunk, admit
  the next waiting request — and hands finished requests to the decode loop
  through the scheduler's ready queue.  One chunk per work item keeps a
  long prompt from blocking a restore behind it.
* **sync mode** (``async_prefill=False``): ``pump`` runs the identical
  code inline once per engine step — the debugging fallback, and the
  baseline the bench's ``async_vs_sync_tokens_per_s`` ratio is measured
  against.  Both modes produce bit-identical tokens: the pipeline computes
  into *private* per-request buffers (``RequestState.prefill_cache`` /
  ``staged``) and only the decode loop ever writes the shared page pools,
  so the only cross-mode difference is *when* work runs, never *what* it
  computes.

Thread discipline (the whole design in four lines):

1. all queue/allocator/stats mutation happens under ``engine._lock``;
2. compute and DMA (jax calls) happen outside it, on private state;
3. the decode loop owns ``cache.pools`` and the block tables exclusively;
4. hand-offs signal ``engine._cv`` so neither loop ever spins.

Pages are *reserved* at admission (under the lock) so the pipeline and the
decode loop's preemption/growth path can never hand out the same page.
"""
from __future__ import annotations

import threading

import jax
import jax.numpy as jnp

from repro.analysis import sanitizer
from repro.analysis.ownership import admission_api


class AdmissionPipeline:
    """Prefill/restore pipeline feeding a ``ServeEngine``'s ready queue."""

    _STAT_KEYS = ("admitted", "chunks_run", "restores_staged",
                  "prefills_done", "matches")

    def __init__(self, engine, async_mode: bool):
        self.engine = engine
        self.async_mode = async_mode
        self._thread: threading.Thread | None = None
        self._stop = False
        self.error: BaseException | None = None
        # pipeline counters live in the ENGINE's metrics registry (prefix
        # "pipeline."), whose lock is the engine lock — so the decode
        # loop's progress check (`metrics.total("pipeline.")`) and
        # telemetry read them as one coherent cut, never a torn dict scan
        self._c = {k: engine.metrics.counter("pipeline." + k)
                   for k in self._STAT_KEYS}

    @property
    def stats(self) -> dict[str, int]:
        """Point-in-time copy of the pipeline counters (one lock cut)."""
        return self.engine.metrics.counters("pipeline.")

    # -- shared work items (compute/DMA outside the lock) -------------------

    @admission_api
    def _stage(self, st) -> None:
        """Host→device DMA for a swapped-out request, then hand to ready.
        Touches the host buffers and fresh device arrays only — never the
        pools."""
        eng = self.engine
        tr = eng.tracer
        tr.begin(tr.EV_STAGE_IN, st.req.uid, len(st.swap_handle.host_pages))
        staged, state = eng.cache.stage_in(st.swap_handle)
        tr.end(tr.EV_STAGE_IN, st.req.uid)
        with eng._lock:
            st.staged, st.state_cache = staged, state
            st.swapped = False
            # restore-resume: length/pending_token survived the swap —
            # straight to ready, no prefill re-run
            eng.sched.to_ready(st)
            self._c["restores_staged"].inc()
            eng._cv.notify_all()

    @admission_api
    def _chunk(self, st, chunk: int) -> None:
        """One prefill work unit (a chunk, or the whole prompt when
        chunking is off) into the request's private cache tree."""
        eng = self.engine
        tr = eng.tracer
        tr.begin(tr.EV_PREFILL_CHUNK, st.req.uid, chunk)
        done = eng.run_prefill(st, chunk)
        tok = None
        if done:
            tr.begin(tr.EV_PREFILL_SAMPLE, st.req.uid)
            tok = eng.sample_prefill_token(st)
            tr.end(tr.EV_PREFILL_SAMPLE, st.req.uid)
        tr.end(tr.EV_PREFILL_CHUNK, st.req.uid)
        with eng._lock:
            self._c["chunks_run"].inc()
            eng.metrics.counter("prefill_tokens").inc(chunk)
            if done:
                self._c["prefills_done"].inc()
                eng.finish_prefill(st, tok)
            eng._cv.notify_all()

    @admission_api
    def _match(self, st) -> None:
        """Full prefix-cache hit: stage any host-retired prefix pages
        (host→device DMA outside the lock), adopt the terminal's state
        snapshot, and hand the request straight to ready — no prefill
        compute at all."""
        eng = self.engine
        tr = eng.tracer
        claim = st.prefix_claim
        staged = None
        if claim.restore:
            tr.begin(tr.EV_STAGE_IN, st.req.uid, len(claim.restore))
            staged = eng.cache.host.get_pages(
                [hp for _h, hp, _d in claim.restore],
                eng.cache.host_shardings,
            )
            tr.end(tr.EV_STAGE_IN, st.req.uid)
        state = (jax.tree.map(jnp.asarray, claim.state)
                 if claim.state is not None else None)
        with eng._lock:
            if staged is not None:
                st.prefix_staged = (
                    staged, [d for _h, _hp, d in claim.restore])
            if state is not None:
                st.state_cache = state
            self._c["matches"].inc()
            eng.finish_match(st)
            eng._cv.notify_all()

    # -- sync mode ----------------------------------------------------------

    @admission_api
    def pump(self, budget: int) -> bool:
        """Run the pipeline inline for one engine step (sync mode): admit
        under the token budget, stage every pending restore, advance each
        in-flight prefill by one chunk."""
        eng, s = self.engine, self.engine.sched
        with eng._lock:
            progressed = bool(s.admissions(eng.cache, budget))
        for st in [x for x in s.admitting if x.phase == "restore"]:
            self._stage(st)
            progressed = True
        for st in [x for x in s.admitting if x.phase == "match"]:
            self._match(st)
            progressed = True
        for st in list(s.admitting):
            if st.phase != "prefill":
                continue
            chunk = s.chunk_for(st)
            if s.cfg.prefill_chunk > 0:
                chunk = min(chunk, budget)
            elif budget <= 0:
                chunk = 0                      # whole-prompt: chunk-granular
            if chunk <= 0:
                continue
            self._chunk(st, chunk)
            budget -= chunk
            progressed = True
        return progressed

    # -- async mode ---------------------------------------------------------

    @property
    def in_flight(self) -> int:
        return len(self.engine.sched.admitting)

    def kick(self) -> None:
        """Ensure the worker thread is running (started lazily on submit,
        parked again when the engine drains).  The check and the start are
        one step under the engine lock: a client thread's ``submit`` and
        the decode loop may kick at once, and only one worker may run."""
        if not self.async_mode:
            return
        with self.engine._lock:
            if self._thread is None or not self._thread.is_alive():
                self._stop = False
                self._thread = threading.Thread(
                    target=self._worker, daemon=True,
                    name="serve-admission-pipeline",
                )
                self._thread.start()

    def shutdown(self) -> None:
        """Stop and join the worker (idempotent; engine idle or teardown)."""
        with self.engine._lock:
            t = self._thread
            if t is None:
                return
            self._stop = True
            self.engine._cv.notify_all()
        if t.is_alive():
            t.join(timeout=10)
        with self.engine._lock:
            # a kick during the join may have started a successor: keep it
            if self._thread is t:
                self._thread = None

    @admission_api
    def _select(self):
        """Pick the next work item, under the engine lock.  Restores first
        (pure DMA, unblocks a decode lane soonest), then in-flight prefill
        chunks in admission order, then a fresh admission."""
        s = self.engine.sched
        for st in s.admitting:
            if st.phase == "restore":
                return ("restore", st, 0)
        for st in s.admitting:
            if st.phase == "match":
                return ("match", st, 0)
        for st in s.admitting:
            if st.phase == "prefill":
                return ("chunk", st, s.chunk_for(st))
        st = s.admit_next(self.engine.cache)
        if st is not None:
            self._c["admitted"].inc()
            if st.phase == "restore":
                return ("restore", st, 0)
            if st.phase == "match":
                return ("match", st, 0)
            return ("chunk", st, s.chunk_for(st))
        return None

    @admission_api
    def _worker(self) -> None:
        eng = self.engine
        eng.tracer.ensure_thread_name("admission-pipeline")
        # sanitizer mode: this thread may never mutate pools/block tables or
        # enter a @decode_loop_only method (no-op when disabled)
        if sanitizer.enabled():
            sanitizer.register_admission_thread(eng)
        try:
            while True:
                with eng._lock:
                    if self._stop:
                        return
                    work = self._select()
                    if work is None:
                        # nothing admissible: wait for a submit, a page
                        # free, or shutdown (cv releases the lock; every
                        # state change notifies, the timeout is a backstop)
                        eng._cv.wait(timeout=0.5)
                        if self._stop:
                            return
                        continue
                kind, st, chunk = work
                if kind == "restore":
                    self._stage(st)
                elif kind == "match":
                    self._match(st)
                else:
                    self._chunk(st, chunk)
        except BaseException as e:  # noqa: B036 - surface in the decode loop
            with eng._lock:
                self.error = e
                eng._cv.notify_all()
        finally:
            # thread idents are reused by the OS — a dead worker's ident
            # must not taint a future decode thread
            if sanitizer.enabled():
                sanitizer.unregister_admission_thread(eng)


def prefill_logits_token(last_logits) -> int:
    """Greedy prefill token (argmax of the final-position logits row) —
    the one host-blocking sync a prefill needs, kept out of the engine so
    both pipeline modes share it."""
    return int(jnp.argmax(last_logits))


__all__ = ["AdmissionPipeline", "prefill_logits_token"]
