"""Observability layer: ring-buffer tracer, metrics registry, Perfetto
export, and their integration into the two-loop serve engine.

The load-bearing invariants:

* a traced engine produces token-for-token the same output as an untraced
  one — recording an event can move nothing but time;
* every request lifecycle reconstructed from the exported trace obeys the
  scheduler's declared state machine (``repro.analysis.phases``), phase
  edge for phase edge, including under forced preemption;
* ``telemetry()`` is a deep point-in-time snapshot: mutating it never
  perturbs live stats, on the engine or through the router;
* the ring buffer degrades by forgetting the oldest events (counted as
  ``dropped``), never by blocking or growing;
* with the injectable clock swapped for a :class:`ManualClock`, trace
  timestamps and histogram buckets are exact assertions, not tolerances.
"""
import copy
import glob
import itertools
import json
import os
import threading
import tracemalloc

import numpy as np
import pytest

from repro.analysis import sanitizer
from repro.analysis.phases import PHASE_EDGES
from repro.obs import clock as obs_clock
from repro.obs.export import (chrome_trace, load_chrome_trace,
                              request_phases, validate_lifecycles,
                              write_chrome_trace)
from repro.obs.metrics import Histogram, MetricsRegistry
from repro.obs.trace import NULL_TRACER, PH_COUNTER, ServeTracer, Tracer


@pytest.fixture
def manual_clock():
    clk = obs_clock.ManualClock()
    obs_clock.set_source(clk)
    try:
        yield clk
    finally:
        obs_clock.reset_source()


# -- tracer unit tests -------------------------------------------------------


def test_tracer_deterministic_timestamps(manual_clock):
    tr = Tracer(capacity=16)
    ev = tr.register("work", ("n",))
    tr.begin(ev, 3)
    manual_clock.advance(0.5)
    tr.end(ev, 3)
    a, b = tr.events()
    assert (a["ts"], b["ts"]) == (0.0, 0.5)
    assert a["name"] == b["name"] == "work"
    assert a["args"] == {"n": 3}
    assert tr.total == 2 and tr.dropped == 0


def test_ring_wraparound_keeps_newest():
    tr = Tracer(capacity=8)
    ev = tr.register("tick", ("i",))
    for i in range(20):
        tr.instant(ev, i)
    assert tr.total == 20
    assert tr.dropped == 12
    events = tr.events()
    assert [e["args"]["i"] for e in events] == list(range(12, 20))
    assert [e["seq"] for e in events] == list(range(12, 20))


def test_disabled_tracer_records_nothing():
    tr = Tracer(capacity=8, enabled=False)
    ev = tr.register("tick", ())
    tr.begin(ev)
    tr.instant_named("nope")
    tr.ensure_thread_name("ghost")
    assert tr.total == 0 and tr.events() == [] and tr.thread_names() == {}
    tr.enable()
    tr.instant(ev)
    assert tr.total == 1
    # the shared disabled singleton must have stayed empty through every
    # serve-layer default call site
    assert NULL_TRACER.total == 0
    assert not NULL_TRACER.enabled


def test_span_sites_with_both_sinks_off_allocate_nothing():
    tr = NULL_TRACER
    ev = tr.EV_DECODE_WAIT
    tracemalloc.start()
    try:
        before = tracemalloc.get_traced_memory()[0]
        for _ in itertools.repeat(None, 10_000):
            tr.begin(ev)
            tr.end(ev)
        after = tracemalloc.get_traced_memory()[0]
    finally:
        tracemalloc.stop()
    assert after == before
    assert tr.total == 0 and not tr.enabled and not tr._annotate


def test_profiler_sink_closes_spans_an_exception_left_open():
    tr = ServeTracer(capacity=1, enabled=False, annotate=True)
    tr.begin(tr.EV_STEP)
    tr.begin(tr.EV_DECODE)
    tr.begin(tr.EV_DECODE_WAIT)          # its end and decode.batch's skipped
    tr.end(tr.EV_STEP)
    assert tr._open.stack == []
    tr.begin(tr.EV_LANES_FILL)
    tr.end(tr.EV_LANES_FILL)
    assert tr._open.stack == []
    assert tr.total == 0                 # the ring stayed off


def test_phase_vocabulary_matches_state_machine():
    # the tracer's pre-registered phase events and the analysis layer's
    # declared edge set must speak the same vocabulary
    machine_phases = {p for edge in PHASE_EDGES for p in edge}
    assert machine_phases == set(ServeTracer.PHASES)
    tr = ServeTracer(capacity=8)
    tr.phase(5, "prefill")
    tr.phase(5, "not-a-phase")          # unknown names are ignored, not stored
    (e,) = tr.events()
    assert e["name"] == "phase.prefill" and e["args"] == {"uid": 5}


def test_counter_events_carry_value():
    tr = ServeTracer(capacity=8)
    tr.counter(tr.EV_PAGES_FREE, 11)
    (e,) = tr.events()
    assert e["ph"] == PH_COUNTER and e["args"]["value"] == 11


# -- metrics unit tests ------------------------------------------------------


def test_histogram_bucket_edges():
    h = Histogram((1.0, 2.0, 4.0))
    for v in (0.5, 1.0, 1.5, 2.0, 4.0, 9.0):
        h.observe(v)
    # cumulative-le semantics: a value lands in the first bucket whose
    # edge >= it; above the last edge is the overflow bucket
    assert h.counts == [2, 2, 1, 1]
    assert h.count == 6 and h.sum == pytest.approx(18.0)
    with pytest.raises(ValueError):
        Histogram((2.0, 1.0))
    with pytest.raises(ValueError):
        Histogram(())


def test_registry_snapshot_is_deep_and_reset_zeroes():
    reg = MetricsRegistry()
    reg.inc("steps", 3)
    reg.gauge_set("occ", 0.5)
    reg.observe("lat", 0.3, edges=(0.1, 1.0))
    snap = reg.snapshot()
    snap["counters"]["steps"] = 999
    snap["gauges"]["occ"]["max"] = 999
    snap["histograms"]["lat"]["counts"][0] = 999
    snap["histograms"]["lat"]["edges"].append(123.0)
    fresh = reg.snapshot()
    assert fresh["counters"]["steps"] == 3
    assert fresh["gauges"]["occ"] == {"value": 0.5, "max": 0.5}
    assert fresh["histograms"]["lat"] == {
        "edges": [0.1, 1.0], "counts": [0, 1, 0], "count": 1, "sum": 0.3,
    }
    reg.reset()
    z = reg.snapshot()
    assert z["counters"]["steps"] == 0
    assert z["gauges"]["occ"] == {"value": 0.0, "max": 0.0}
    assert z["histograms"]["lat"]["count"] == 0
    # reset zeroes in place: handles acquired before the reset stay live
    assert reg.counter("steps").value == 0
    assert reg.total() == 0 and reg.counters() == {"steps": 0}


def test_registry_counter_churn_across_threads():
    reg = MetricsRegistry()

    def bump():
        for _ in range(2000):
            reg.inc("hits")

    threads = [threading.Thread(target=bump) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.counter("hits").value == 8000


# -- export unit tests -------------------------------------------------------


def test_chrome_trace_structure(manual_clock, tmp_path):
    tr = Tracer(capacity=32)
    tr.name_thread("decode-loop")
    ev = tr.register("engine.step", ("step",))
    tr.begin(ev, 0)
    manual_clock.advance(0.002)
    tr.end(ev, 0)
    tr.instant_named("sanitizer: boom")
    path = str(tmp_path / "t.json")
    write_chrome_trace(path, {"engine": tr})
    trace = load_chrome_trace(path)
    evs = trace["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert {"name": "process_name", "ph": "M", "pid": 0, "tid": 0,
            "args": {"name": "engine"}} in meta
    assert any(e["name"] == "thread_name"
               and e["args"]["name"] == "decode-loop" for e in meta)
    spans = [e for e in evs if e["ph"] in ("B", "E")]
    assert [e["ts"] for e in spans] == [0.0, 2000.0]     # microseconds
    inst = [e for e in evs if e["ph"] == "i"]
    assert inst and all(e["s"] == "t" for e in inst)
    assert any(e["name"] == "sanitizer: boom" for e in inst)
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"nope": []}))
    with pytest.raises(ValueError):
        load_chrome_trace(str(bad))


def test_validate_lifecycles_rejects_illegal_edges():
    def fake(phases_by_uid):
        return {"traceEvents": [
            {"name": "phase." + p, "ph": "i", "ts": 0.0, "pid": 0, "tid": 0,
             "args": {"uid": uid}, "s": "t"}
            for uid, phases in phases_by_uid.items() for p in phases
        ]}

    ok = fake({1: ["waiting", "prefill", "ready", "running", "done"]})
    assert validate_lifecycles(ok) == {
        1: ["waiting", "prefill", "ready", "running", "done"]}
    assert request_phases(fake({})) == {}
    with pytest.raises(ValueError, match="illegal phase edge"):
        validate_lifecycles(fake({1: ["waiting", "running", "done"]}))
    with pytest.raises(ValueError, match="not 'waiting'"):
        validate_lifecycles(fake({1: ["ready", "running", "done"]}))
    with pytest.raises(ValueError, match="not 'done'"):
        validate_lifecycles(fake({1: ["waiting", "prefill", "ready"]}))
    # an in-flight trace (snapshot mid-serve) can opt out of the done bar
    mid = fake({1: ["waiting", "prefill", "ready", "running"]})
    assert validate_lifecycles(mid, require_done=False)
    with pytest.raises(ValueError, match="no phase"):
        validate_lifecycles(fake({}))


# -- sanitizer integration ---------------------------------------------------


def test_sanitizer_phase_finding_lands_in_trace():
    from repro.serve import RequestState

    class _Req:
        uid = 7

    tr = ServeTracer(capacity=32)
    st = RequestState(req=_Req(), resume_tokens=np.arange(3), tracer=tr)
    sanitizer.enable()
    try:
        with pytest.raises(sanitizer.SanitizerError, match="uid=7"):
            st.phase = "running"         # waiting -> running: illegal
    finally:
        sanitizer.disable()
    names = [e["name"] for e in tr.events()]
    assert "phase.waiting" in names      # construction-time write recorded
    assert any(n.startswith("sanitizer: illegal phase edge") for n in names)
    assert st.phase == "waiting"         # the write did not land


# -- engine integration (reduced model) --------------------------------------


@pytest.fixture(scope="module")
def small_model():
    import jax

    from repro.configs import get_arch
    from repro.models import build_model

    cfg = get_arch("qwen2.5-3b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    return cfg, model, params


# forced-preemption cell (see test_tiered_cache): requests grow past their
# reservation, the pool dries mid-decode, swap/restore churns
PRESSURE = dict(batch_slots=3, max_len=32, page_size=4, n_pages=7,
                swap_token_cost=0.0)


def _reqs(cfg, n, plen=7, max_new=6, seed=3):
    from repro.serve import Request

    rng = np.random.default_rng(seed)
    return [
        Request(uid=i,
                prompt=rng.integers(0, cfg.vocab_size,
                                    size=(plen + i % 3,)).astype(np.int32),
                max_new_tokens=max_new)
        for i in range(n)
    ]


def _run(model, params, cfg, n=5, plen=7, max_new=6, **ecfg_kw):
    from repro.models.common import AxisRules, DEFAULT_RULES
    from repro.serve import EngineConfig, ServeEngine

    eng = ServeEngine(model, params, EngineConfig(**ecfg_kw),
                      AxisRules(DEFAULT_RULES))
    done = {}
    for r in _reqs(cfg, n, plen=plen, max_new=max_new):
        eng.submit(r)
        eng.step()                       # interleave arrivals with decode
    for r in eng.run():
        done[r.uid] = list(r.out_tokens)
    done.update({r.uid: list(r.out_tokens) for r in eng.completed})
    return eng, done


def test_traced_engine_lifecycles_and_token_identity(small_model, tmp_path):
    cfg, model, params = small_model
    # harder pressure than PRESSURE: pool admits all three lanes' long
    # prompts exactly, then dries as decode grows — preemption guaranteed
    cell = dict(batch_slots=3, max_len=32, page_size=4, n_pages=13,
                swap_token_cost=0.0, prefill_chunk=6, plen=14, max_new=8)
    traced, toks_t = _run(model, params, cfg, trace=True,
                          async_prefill=True, **cell)
    plain, toks_p = _run(model, params, cfg, trace=False,
                         async_prefill=True, **cell)
    # recording events must not change a single token
    assert toks_t == toks_p and len(toks_t) == 5
    assert plain.tracer is NULL_TRACER and plain.tracer.total == 0

    path = str(tmp_path / "serve_trace.json")
    traced.save_trace(path)
    trace = load_chrome_trace(path)
    hist = validate_lifecycles(trace, require_done=True)
    assert set(hist) == set(toks_t)      # every request reconstructable
    tel = traced.telemetry()
    assert tel["preemptions"] > 0        # the pressure cell actually fired
    # a preempted request visits waiting again mid-flight
    assert any(ph.count("waiting") > 1 for ph in hist.values())
    names = {e["name"] for e in trace["traceEvents"]}
    assert {"engine.step", "lanes.fill", "pages.ensure", "decode.batch",
            "decode.inputs", "decode.dispatch", "decode.wait",
            "decode.fetch", "decode.sample", "prefill.chunk",
            "prefill.sample", "admission.reserve", "pages.free"} <= names
    assert {"swap_out.batch", "swap_in.stage"} & names
    # both loops own a labelled thread track
    tracks = {e["args"]["name"] for e in trace["traceEvents"]
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"decode-loop", "admission-pipeline"} <= tracks


def test_engine_telemetry_snapshot_isolation(small_model):
    cfg, model, params = small_model
    eng, _ = _run(model, params, cfg, **PRESSURE)
    tel = eng.telemetry()
    ref = copy.deepcopy(tel)
    tel["steps"] = -1
    tel["pipeline"]["chunks_run"] = -1
    tel["host_tier"]["pages_out"] = -1
    tel["histograms"]["step_latency_s"]["counts"][0] = -1
    assert eng.telemetry() == ref        # live stats never saw the mutation


def test_stats_property_is_copy_and_reset_stats_zeroes(small_model):
    cfg, model, params = small_model
    eng, toks = _run(model, params, cfg, batch_slots=2, max_len=32)
    s = eng.stats
    assert s["steps"] > 0
    # each request's first token is sampled at prefill, the rest by decode
    assert s["decode_tokens"] == sum(len(t) for t in toks.values()) - len(toks)
    s["steps"] = -5
    assert eng.stats["steps"] > 0        # a copy, not the live dict
    eng.reset_stats()
    z = eng.stats
    assert z["steps"] == 0 and z["decode_tokens"] == 0
    assert eng.pipeline.stats["chunks_run"] == 0     # one registry resets all


def test_step_and_queue_histograms_populate(small_model):
    cfg, model, params = small_model
    eng, _ = _run(model, params, cfg, batch_slots=2, max_len=32)
    tel = eng.telemetry()
    h = tel["histograms"]
    assert h["step_latency_s"]["count"] == tel["steps"]
    assert h["step_latency_s"]["sum"] > 0
    assert h["queue_wait_s"]["count"] == 5           # one wait per admission
    assert len(h["step_latency_s"]["counts"]) == \
        len(h["step_latency_s"]["edges"]) + 1


def test_trace_annotations_smoke(small_model):
    cfg, model, params = small_model
    annot, toks_a = _run(model, params, cfg, n=3, batch_slots=2, max_len=32,
                         trace_annotations=True)
    plain, toks_p = _run(model, params, cfg, n=3, batch_slots=2, max_len=32)
    both, toks_b = _run(model, params, cfg, n=3, batch_slots=2, max_len=32,
                        trace=True, trace_annotations=True)
    assert toks_a == toks_p == toks_b    # profiler spans change nothing
    # the profiler sink runs without the ring, on a tracer of its own
    assert annot.tracer is not NULL_TRACER and annot.tracer._annotate
    assert not annot.tracer.enabled and annot.tracer.total == 0
    assert both.tracer.enabled and both.tracer.total > 0
    assert not hasattr(annot, "_annot")


def _profiled_names(trace_dir) -> set:
    import jax

    (path,) = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                        recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return {e.name for plane in pd.planes for line in plane.lines
            for e in line.events}


def test_annotations_reach_the_profiler_without_the_ring(small_model,
                                                         tmp_path):
    import jax

    cfg, model, params = small_model
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng, toks = _run(model, params, cfg, n=3, batch_slots=2, max_len=32,
                         prefill_chunk=4, trace_annotations=True)
    finally:
        jax.profiler.stop_trace()
    assert len(toks) == 3
    names = {n for n in _profiled_names(tmp_path) if n.startswith("repro.")}
    assert {"repro.engine.step", "repro.lanes.fill", "repro.pages.ensure",
            "repro.decode.batch", "repro.decode.inputs",
            "repro.decode.dispatch", "repro.decode.wait",
            "repro.decode.fetch", "repro.decode.sample",
            "repro.prefill.chunk", "repro.prefill.sample"} <= names
    assert "repro.decode_step" not in names
    assert eng.tracer.total == 0         # the ring was off throughout
    # the shared disabled tracer never saw either sink
    assert NULL_TRACER.total == 0
    assert not NULL_TRACER.enabled and not NULL_TRACER._annotate
    assert getattr(NULL_TRACER._open, "stack", None) is None


def test_decode_split_histograms_add_up_per_step(small_model):
    """prep + wait + sample covers one decoding step from its start to its
    last token, inside the step's latency; each is observed once per step
    that decodes.  A clock that ticks 2**-10 s per read keeps every sum
    exact."""
    from repro.models.common import AxisRules, DEFAULT_RULES
    from repro.serve import AdmissionConfig, EngineConfig, ServeEngine

    cfg, model, params = small_model
    ticks = itertools.count()
    obs_clock.set_source(lambda: next(ticks) * 2.0 ** -10)
    try:
        eng = ServeEngine(model, params, EngineConfig(
            batch_slots=2, max_len=32,
            admission=AdmissionConfig(prefill_chunk=4)),
            AxisRules(DEFAULT_RULES))
        names = ("decode.prep_s", "decode.wait_s", "decode.sample_s")

        def cut():
            snap = eng.metrics.snapshot()
            h = snap["histograms"]
            return (snap["counters"]["decode_tokens"],
                    [(h[n]["count"], h[n]["sum"]) for n in names],
                    h["step_latency_s"]["sum"])

        for r in _reqs(cfg, 4):
            eng.submit(r)
        decoding_steps = 0
        prev = cut()
        while eng.load:
            eng.step()
            now = cut()
            decoded = now[0] > prev[0]
            decoding_steps += decoded
            counts = [c1 - c0 for (c1, _), (c0, _) in zip(now[1], prev[1])]
            assert counts == [int(decoded)] * 3
            split = sum(s1 - s0 for (_, s1), (_, s0) in zip(now[1], prev[1]))
            assert 0 <= split <= now[2] - prev[2]
            if decoded:
                assert all(s1 > s0 for (_, s1), (_, s0) in zip(now[1], prev[1]))
            prev = now
        eng.pipeline.shutdown()
    finally:
        obs_clock.reset_source()
    tel = eng.telemetry()
    assert decoding_steps > 0
    for n in names:
        assert tel["histograms"][n]["count"] == decoding_steps


def test_kv_block_share_counts_the_blocks_the_kernel_walks(small_model):
    """``decode.kv_block_share`` is observed once per decoding step of the
    Pallas paged path: the blocks each active lane's length reaches, over
    every lane's blocks, for the lengths the step's decode inputs hold.  Pages
    of 4 tokens and a 512-token lane give 128 pages, walked as 4 blocks of
    32 pages (128 tokens)."""
    from repro.models.common import AxisRules, DEFAULT_RULES
    from repro.serve import (AdmissionConfig, CacheConfig, EngineConfig,
                             Request, ServeEngine)

    cfg, model, params = small_model
    eng = ServeEngine(model, params, EngineConfig(
        batch_slots=3, max_len=512,
        cache=CacheConfig(page_size=4, attn_impl="pallas"),
        admission=AdmissionConfig(async_prefill=False)),
        AxisRules(DEFAULT_RULES))
    seen = []
    real_inputs = eng.decode_inputs

    def recorded():
        out = real_inputs()
        seen.append((out[2].copy(), out[3].copy()))
        return out

    eng.decode_inputs = recorded
    rng = np.random.default_rng(3)
    for i, plen in enumerate((126, 127)):       # lengths 127/128 first
        eng.submit(Request(uid=i, max_new_tokens=3,
                           prompt=rng.integers(0, cfg.vocab_size, size=plen,
                                               dtype=np.int32)))
    shares, prev = [], (0, 0.0)
    while eng.load:
        eng.step()
        h = eng.metrics.snapshot()["histograms"]["decode.kv_block_share"]
        if h["count"] > prev[0]:
            assert h["count"] == prev[0] + 1
            shares.append(h["sum"] - prev[1])
        prev = (h["count"], h["sum"])
    eng.pipeline.shutdown()
    # the two steps by hand: lengths 127 and 128 walk one block each, then
    # 128 and 129 walk one and two, of 3 lanes x 4 blocks
    assert [sorted(int(p) + 1 for p in pos[act]) for pos, act in seen] == [
        [127, 128], [128, 129]]
    np.testing.assert_allclose(shares, [2 / 12, 3 / 12], rtol=0, atol=1e-12)
    tel = eng.telemetry()
    assert tel["histograms"]["decode.kv_block_share"]["count"] == len(seen)


def test_program_names_the_device_trace_matches(small_model):
    """The benchmark finds the decode program by ``_decode_impl`` and the
    whole-prompt prefill by ``_prefill_impl`` in the device trace."""
    import jax.numpy as jnp

    from repro.models.common import AxisRules, DEFAULT_RULES
    from repro.serve import EngineConfig, ServeEngine

    cfg, model, params = small_model
    eng = ServeEngine(model, params, EngineConfig(batch_slots=2, max_len=32),
                      AxisRules(DEFAULT_RULES))
    decode = eng._decode.lower(eng.params, eng.cache.pools,
                               *eng.decode_inputs()).as_text()
    assert decode.split("\n", 1)[0].startswith("module @jit__decode_impl")
    prefill = eng._prefill.lower(eng.params,
                                 jnp.zeros((1, 8), jnp.int32)).as_text()
    assert prefill.split("\n", 1)[0].startswith("module @jit__prefill_impl")


# -- router integration ------------------------------------------------------


def test_router_telemetry_isolation_under_churn(small_model, tmp_path):
    cfg, model, params = small_model
    from repro.serve import CubeRouter, EngineConfig

    router = CubeRouter(model, params,
                        EngineConfig(batch_slots=2, max_len=32, trace=True),
                        n_cubes=2, policy="least_loaded")
    stop = threading.Event()
    snaps = []

    def churn():
        while not stop.is_set():
            snaps.append(router.telemetry())

    t = threading.Thread(target=churn)
    t.start()
    try:
        for r in _reqs(cfg, 6):
            router.submit(r)
        after_submit = router.routed
        done = router.run()
    finally:
        stop.set()
        t.join()
    assert len(done) == 6
    # least-loaded balances an un-stepped submission burst evenly
    assert abs(after_submit[0] - after_submit[1]) <= 1
    assert sum(router.routed) == 6
    assert snaps                          # telemetry really ran concurrently

    tel = router.telemetry()
    ref = copy.deepcopy(tel)
    tel["pod0"]["routed"] = -1
    tel["pod0"]["pipeline"]["admitted"] = -1
    tel["total_routed"] = -1
    assert router.telemetry() == ref
    assert tel2_keys_ok(ref)

    # one Perfetto file, one process track per cube, dispatch on target
    trace = router.save_trace(str(tmp_path / "router_trace.json"))
    procs = {e["args"]["name"] for e in trace["traceEvents"]
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert procs == {"pod0", "pod1"}
    dispatches = [e for e in trace["traceEvents"]
                  if e["name"] == "router.dispatch"]
    assert {e["args"]["uid"] for e in dispatches} == set(range(6))


def tel2_keys_ok(tel):
    return {"pod0", "pod1", "total_routed"} <= tel.keys() and \
        tel["pod0"]["routed"] + tel["pod1"]["routed"] == tel["total_routed"]


def test_chrome_trace_merges_multiple_tracers():
    a, b = Tracer(capacity=8), Tracer(capacity=8)
    ea, eb = a.register("x", ()), b.register("y", ())
    a.instant(ea)
    b.instant(eb)
    trace = chrome_trace({"pod0": a, "pod1": b})
    pids = {e["pid"] for e in trace["traceEvents"] if e["ph"] != "M"}
    assert pids == {0, 1}
