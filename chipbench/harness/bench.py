"""One run of one cell: set-up, the measured window, the metrics, the check.

Set-up puts the weights on the device from the seed, builds the engine,
runs one short request per prompt length the mix can send (so every
program the window uses is compiled or read from the cache), then runs the
mix's own traffic until the generator says the engine is warm.  The window
then drives ``ServeEngine.submit`` / ``ServeEngine.step`` for the given
seconds and records, on the host's clock, when each request was due, when
it was submitted and when each of its tokens was first seen.  A mix with a
schedule is submitted from a thread of its own at the due times, beside
the decode loop; a backlog is topped up by the decode loop.  With tracing
on, a sub-window in the middle is traced by the profiler.  After the
window the engine is freed and the served tokens are checked against the
reference; with ``control`` the reference in lower precision takes the
program's place in that check.
"""
from __future__ import annotations

import contextlib
import gc
import shutil
import tempfile
import threading
import time
from dataclasses import dataclass, field
from types import SimpleNamespace

import numpy as np

from chipbench.harness import check, registry, weights
from chipbench.harness import trace as tracemod

TRACE_S = 4.0            # length of the traced sub-window


@dataclass
class Rec:
    req: object
    due: float               # scheduled arrival (host clock)
    submit: float            # handed to the engine
    prompt_len: int
    times: list = field(default_factory=list)     # when each token was seen
    done_at: float | None = None


class Driver:
    """Submits the generator's requests and harvests tokens after each
    engine step."""

    def __init__(self, eng, gen, annot):
        from repro.serve import Request

        self.Request = Request
        self.eng, self.gen, self.annot = eng, gen, annot
        self.origin = time.perf_counter()
        self.live: list[Rec] = []
        self.recs: list[Rec] = []
        self.new: list[Rec] = []          # submitted, not yet harvested
        self.lock = threading.Lock()
        self.uid = 0
        self.started = 0                  # requests with a first token
        self.completed = 0
        self.last_seen = self.origin
        self.on_token = None
        self.pages_used_max = 0
        self.stop = threading.Event()
        self.thread = None
        self.error = None
        if gen.scheduled:
            # ``AdmissionPipeline.kick`` starts its worker without a lock,
            # and a step on a drained engine parks it: so the worker is
            # started here, before the submitting thread, and ``step``
            # never steps a drained engine while that thread runs
            eng.pipeline.kick()
            self.thread = threading.Thread(target=self._submit_loop,
                                           name="chipbench-submit", daemon=True)
            self.thread.start()

    def _submit(self, now: float, load: int) -> None:
        with self.annot("chipbench.submit"):
            for spec in self.gen.due(now - self.origin, load):
                req = self.Request(uid=self.uid, prompt=spec.prompt,
                                   max_new_tokens=spec.max_new)
                self.uid += 1
                due = now if spec.due_s is None else self.origin + spec.due_s
                rec = Rec(req, due, time.perf_counter(), len(spec.prompt))
                self.eng.submit(req)
                with self.lock:
                    self.new.append(rec)
                    self.recs.append(rec)

    def _submit_loop(self) -> None:
        try:
            while not self.stop.is_set():
                wait = self.origin + self.gen.next_due_s() - time.perf_counter()
                if wait > 0 and self.stop.wait(wait):
                    return
                self._submit(time.perf_counter(), 0)
        except BaseException as e:           # raised again by close()
            self.error = e

    def close(self) -> None:
        self.stop.set()
        if self.thread is not None:
            self.thread.join()
        if self.error is not None:
            raise self.error

    def step(self) -> None:
        if self.thread is None:
            self._submit(time.perf_counter(), self.eng.load)
        elif self.eng.load == 0:      # only this thread retires requests
            time.sleep(0.001)
            self.last_seen = time.perf_counter()
            return
        with self.annot("chipbench.step"):
            self.eng.step()
        now = time.perf_counter()
        with self.annot("chipbench.harvest"):
            with self.lock:
                self.live += self.new
                self.new = []
            keep = []
            for rec in self.live:
                n = len(rec.req.out_tokens)
                if n and not rec.times:
                    self.started += 1
                while len(rec.times) < n:
                    if self.on_token is not None:
                        self.on_token(rec, len(rec.times))
                    rec.times.append(now)
                if rec.req.done:
                    rec.done_at = now
                    self.completed += 1
                else:
                    keep.append(rec)
            self.live = keep
            alloc = self.eng.cache.allocator
            self.pages_used_max = max(self.pages_used_max,
                                      alloc.n_pages - alloc.n_free)
        self.last_seen = now


def engine_config(cfg: dict, annotations: bool):
    from repro.serve import AdmissionConfig, CacheConfig, EngineConfig, ObsConfig

    e = cfg["engine"]
    return EngineConfig(
        batch_slots=e["batch_slots"], max_len=e["max_len"],
        eos_id=e.get("eos_id"), cache=CacheConfig(**e.get("cache", {})),
        admission=AdmissionConfig(**e.get("admission", {})),
        obs=ObsConfig(trace_annotations=annotations))


def _same_layout(jax, got, want) -> None:
    a = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), got)
    b = jax.tree.map(lambda x: (tuple(x.shape), str(x.dtype)), want)
    if a != b:
        raise ValueError(f"weights do not fit the program's tree:\n{a}\n{b}")


def warm_shapes(eng, lengths: list[int], vocab: int, seed: int) -> None:
    """One two-token request per prompt length: every chunk shape, page
    count and the decode program, before the window."""
    from repro.serve import Request

    rng = np.random.default_rng([int(seed), 1])
    for i, n in enumerate(lengths):
        eng.submit(Request(uid=-1 - i, max_new_tokens=2,
                           prompt=rng.integers(0, vocab, n, dtype=np.int32)))
    eng.run()


def run_cell(cfg: dict, mix: dict, seed: int, seconds: float, trace: bool,
             t0: float, *, device: dict, meter, end_to_end: list,
             per_layer: list, control: bool = False) -> dict:
    """One run; returns the result object that ``run.py`` prints."""
    import jax

    from repro.models import build_model
    from repro.serve import ServeEngine

    from chipbench.harness.device import memory_peak_bytes

    ref, prog, work = registry.family(cfg)
    arch = prog.arch(cfg)
    model = build_model(arch)
    bits = weights.seed_bits(seed)
    make = jax.jit(lambda b: prog.params(
        cfg, arch, weights.global_weights(ref, cfg, b),
        weights.stacked_weights(ref, cfg, b)))
    params = jax.block_until_ready(make(bits))
    _same_layout(jax, params, model.abstract())
    eng = ServeEngine(model, params, engine_config(cfg, trace))
    del params
    ecfg = eng.ecfg
    gen = registry.generator(mix["kind"]).make(
        mix, ecfg.batch_slots, ecfg.max_len, int(cfg["vocab_size"]), seed)
    warm_shapes(eng, gen.prompt_lengths(), int(cfg["vocab_size"]), seed)

    if trace:
        def annot(name):
            return jax.profiler.TraceAnnotation(name)
    else:
        def annot(name):
            return contextlib.nullcontext()

    drv = Driver(eng, gen, annot)
    while not gen.warm_done(time.perf_counter() - drv.origin, drv.started):
        drv.step()

    # -- the window ------------------------------------------------------
    eng.reset_stats()
    preempted0 = eng.sched.n_preemptions
    drv.pages_used_max = 0
    loaded0 = meter.programs_loaded()
    t_start = time.perf_counter()
    t_end = t_start + seconds
    tr = SimpleNamespace(decode_ctx=[], prefill0=0, prefill1=0, dir=None,
                         span=None, on=False, done=False)
    tr_from = t_start + max(0.0, (seconds - TRACE_S) / 2)
    tr_to = tr_from + min(TRACE_S, seconds)

    def traced_token(rec, k):
        if k >= 1:                       # token k >= 1 came from a decode step
            tr.decode_ctx.append(rec.prompt_len + k)

    def prefill_tokens():
        return eng.metrics.snapshot()["counters"]["prefill_tokens"]

    def trace_stop():
        tr.prefill1 = prefill_tokens()
        tr.span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        drv.on_token = None
        tr.on, tr.done = False, True

    while True:
        now = time.perf_counter()
        if now >= t_end:
            break
        if trace and not tr.on and not tr.done and now >= tr_from:
            tr.dir = tempfile.mkdtemp(prefix="chipbench-trace-")
            jax.profiler.start_trace(tr.dir)
            tr.prefill0 = prefill_tokens()
            tr.span = jax.profiler.TraceAnnotation(tracemod.WINDOW_SPAN)
            tr.span.__enter__()
            tr.on = True
            drv.on_token = traced_token
        elif tr.on and now >= tr_to:
            trace_stop()
        drv.step()
    if tr.on:
        trace_stop()
    drv.close()
    t_stop = drv.last_seen
    window_compiles = meter.programs_loaded() - loaded0
    snap = eng.metrics.snapshot()
    mem = memory_peak_bytes(jax)
    kv_pool = sum(x.nbytes for x in jax.tree.leaves(eng.cache.pools))
    kv = {"kv_pool_bytes": kv_pool,
          "kv_used_peak_bytes": kv_pool * drv.pages_used_max // eng.cache.n_pages,
          "preemptions": eng.sched.n_preemptions - preempted0}

    reduced = None
    if tr.dir is not None:
        reduced = tracemod.reduce(tracemod.load(tr.dir))
        shutil.rmtree(tr.dir, ignore_errors=True)

    rec = SimpleNamespace(
        cfg=cfg, work=work, peaks=device["peaks"], chips=device["count"],
        setup_s=t_start - t0, t_start=t_start, t_stop=t_stop,
        recs=drv.recs, counters=snap["counters"],
        histograms=snap["histograms"], trace=reduced, decode_ctx=tr.decode_ctx,
        prefill_tokens=tr.prefill1 - tr.prefill0)
    names = per_layer if trace else end_to_end
    metrics = {}
    for m in names:
        v = registry.metric_reader(m["name"])(rec)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # -- the check, after the program's state is gone ----------------------
    done = [r.req for r in drv.recs
            if r.done_at is not None and t_start <= r.done_at <= t_stop]
    active = [r for r in drv.recs if r.submit <= t_stop
              and (r.done_at is None or r.done_at >= t_start)]
    short = sum(1 for r in done if len(r.out_tokens) != r.max_new_tokens)
    c = cfg["check"]
    seqs = check.sample(done, seed, int(c["min_served_tokens"]),
                        int(c["max_requests"]))
    eng.pipeline.shutdown()
    del eng, drv
    gc.collect()
    t_check = time.perf_counter()
    got = check.gaps(ref, cfg, seed, seqs, control) if seqs else {}
    limit = c.get("max_logit_gap")
    gap = got.get("control_logit_gap" if control else "logit_gap")
    checks = {
        "logit_gap": {"value": gap, "limit": limit},
        "short_requests": {"value": short, "limit": 0},
    }
    correct = (gap is not None and limit is not None and np.isfinite(gap)
               and gap <= limit and short == 0)
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"], "memory_peak_bytes": mem}
    out = {"correct": bool(correct), "attempted": len(active),
           "failed": short, "metrics": metrics, "device": dev}
    if reduced is not None:
        dev["busy_s"] = reduced.busy_ns * 1e-9
        dev["window_s"] = reduced.window_ns * 1e-9
        out["breakdown"] = tracemod.breakdown(reduced)
    run = {"seed": seed, "window_s": t_stop - t_start,
           "setup_s": t_start - t0, "window_compiles": window_compiles,
           "completed_in_window": len(done), "requests_in_window": len(active),
           "in_flight_at_start": sum(1 for r in active if r.submit < t_start),
           "in_flight_at_end": sum(1 for r in active if r.done_at is None),
           "checked_requests": len(seqs),
           "check_s": time.perf_counter() - t_check,
           "checked_tokens": got.get("tokens", 0), **kv, **meter.snapshot()}
    if control:
        run["program_logit_gap"] = got.get("logit_gap")
    out["checks"] = checks
    return {"result": out, "run": run}
