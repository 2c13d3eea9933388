"""Serving throughput bench: dense slot engine vs paged engine, and the
paged engine's gather-vs-paged decode paths.

Mixed-length Poisson traffic (8-128 token prompts, geometric interarrivals
on the step clock) is driven through the engines at an EQUAL memory budget:
the dense engine spends ``slots x max_len`` of cache; the paged engine gets
exactly the same token budget as a page pool and spends it per actual
request length, which buys it more concurrent decode lanes.

The ``--decode-path`` axis compares the paged engine's two decode paths on
identical workloads:

* ``gather`` — materialize the dense (lanes, capacity, ...) view tree, run
  ``decode_step``, scatter the written column back (the fallback oracle);
* ``paged``  — hand block tables straight to ``decode_step_paged`` (the
  dense view is never built).

Per-path the JSON report carries per-step decode latency percentiles and
the compiled decode step's peak live bytes (``memory_analysis``), plus the
dense gathered-view bytes the paged path never materializes.  ``both`` runs
both and asserts token identity — a silent numeric break cannot pass the
CI bench gate.

The ``--preempt-policy`` axis measures the tiered-KV cache under memory
pressure (a page pool sized to force preemption): ``swap`` moves victim
pages to the host-DRAM tier and restores them on resume, ``recompute``
re-prefills.  ``both`` sweeps prompt length, asserts token identity between
the policies per length, and reports the recompute-vs-swap crossover (the
shortest prompt length at which moving pages beats recomputing them) plus
the aggregate ``swap_vs_recompute_speedup`` the CI bench gate checks.

The ``--async-prefill`` axis drives an admission *storm* (a new arrival
nearly every step) through the two-loop engine with the admission pipeline
on its worker thread (``on``) vs inline (``off``): prefill chunks and
swap-in DMA overlap decode in the first case and serialize with it in the
second.  ``both`` asserts token identity (the pipeline owns no shared
device state, so threading it must not change a single token — also
asserted per model family on the full run) and reports
``async_vs_sync_tokens_per_s`` plus each mode's decode-lane idle fraction.
The swap-out *batching* microbench rides along: one device→host copy per
cache leaf for a whole victim set vs the per-victim copies it replaced
(``swap_out_batch_speedup``, also CI-gated).

The ``--prefix-reuse`` axis measures prefix sharing on a duplicate-heavy
prompt mix (distinct prompts first, zipf-weighted replays after): ``on``
serves every replay from the radix-indexed resident KV pages (copy-on-write
forks the tail page at the first divergent write), ``off`` re-prefills it.
``both`` asserts token identity and reports the gated ``prefix_hit_rate``
(deterministically > 0.5 by construction) and the
``prefix_vs_none_tokens_per_s`` replay-phase throughput ratio — the
prefills not run (the seeding phase is identical work in both modes and is
excluded from the ratio).

The ``--obs`` axis measures the observability layer's cost: the same
Poisson workload through a traced engine (ring-buffer tracer + metrics on
every step, phase change, prefill chunk, and DMA) vs the NULL_TRACER
engine, token identity asserted — the gated ``obs_overhead_tokens_per_s``
ratio must sit within 5% of 1.0.  ``--trace out.json`` additionally drives
a preemption-pressure workload with tracing on and writes the
Perfetto/Chrome timeline, validating every request lifecycle against the
scheduler state machine before exiting.

Run:   PYTHONPATH=src python benchmarks/serve_bench.py [--out serve_bench.json]
Smoke: PYTHONPATH=src python benchmarks/serve_bench.py --smoke   (tier-1 CI)
"""
from __future__ import annotations

import argparse
import json
import time

import numpy as np


def make_workload(n, lengths, max_new, mean_interarrival, seed=0):
    rng = np.random.default_rng(seed)
    reqs = []
    arrivals = np.cumsum(rng.geometric(1.0 / mean_interarrival, size=n)) - 1
    for i in range(n):
        plen = int(rng.choice(lengths))
        reqs.append(dict(
            uid=i,
            prompt=rng.integers(0, 512, size=(plen,)).astype(np.int32),
            max_new_tokens=max_new,
            arrival=int(arrivals[i]),
        ))
    return reqs


def drive(engine, workload, shutdown=True):
    """Submit requests on the engine's step clock (arrival = step index);
    returns (tokens, wall_seconds, steps, per_step_seconds, uid→tokens)."""
    from repro.serve import Request

    pending = sorted(workload, key=lambda r: r["arrival"])
    live = []
    step = 0
    step_s = []
    t0 = time.perf_counter()
    while pending or getattr(engine, "load", 0) or any(
        r is not None for r in getattr(engine, "slot_req", [])
    ) or getattr(engine, "queue", []):
        while pending and pending[0]["arrival"] <= step:
            w = pending.pop(0)
            req = Request(uid=w["uid"], prompt=w["prompt"],
                          max_new_tokens=w["max_new_tokens"])
            live.append(req)
            engine.submit(req)
        ts = time.perf_counter()
        engine.step()
        step_s.append(time.perf_counter() - ts)
        step += 1
    dt = time.perf_counter() - t0
    if shutdown and hasattr(engine, "pipeline"):
        engine.pipeline.shutdown()      # park the admission worker
    tokens = sum(len(r.out_tokens) for r in live)
    assert all(r.done for r in live), "bench drained with unfinished requests"
    out = {r.uid: list(r.out_tokens) for r in live}
    return tokens, dt, step, step_s, out


def _latency_ms(step_s) -> dict:
    a = np.asarray(step_s) * 1e3
    return {
        "mean": float(a.mean()),
        "p50": float(np.percentile(a, 50)),
        "p95": float(np.percentile(a, 95)),
        "max": float(a.max()),
    }


def gathered_view_bytes(engine) -> int:
    """Bytes of the dense (lanes, capacity, ...) seq-cache view tree the
    gather path materializes every decode step — the allocation the paged
    path never makes."""
    import jax

    from repro.models.common import SEQ_CACHE_KEYS, cache_leaf_key

    specs = engine.model.cache_specs(engine.ecfg.batch_slots,
                                     engine.cache.capacity)
    total = []

    def leaf(path, s):
        if cache_leaf_key(path) in SEQ_CACHE_KEYS:
            total.append(int(np.prod(s.shape)) * s.dtype.itemsize)

    jax.tree_util.tree_map_with_path(leaf, specs)
    return sum(total)


def decode_memory(engine) -> dict:
    """Compiled decode-step memory footprint (``memory_analysis``): the
    peak live bytes include the transient dense views on the gather path
    and only the page pools on the paged path."""
    import jax.numpy as jnp

    b = engine.ecfg.batch_slots
    args = (
        engine.params, engine.cache.pools,
        jnp.asarray(engine.cache.block_tables),
        jnp.zeros((b, 1), jnp.int32), jnp.zeros((b,), jnp.int32),
        jnp.zeros((b,), bool),
    )
    try:
        ma = engine._decode.lower(*args).compile().memory_analysis()
        out = {
            "argument_bytes": int(ma.argument_size_in_bytes),
            "output_bytes": int(ma.output_size_in_bytes),
            "temp_bytes": int(ma.temp_size_in_bytes),
            "alias_bytes": int(ma.alias_size_in_bytes),
            "available": True,
        }
        out["peak_live_bytes"] = (
            out["argument_bytes"] + out["output_bytes"] + out["temp_bytes"]
            - out["alias_bytes"]
        )
        return out
    except Exception:       # backend without memory_analysis
        return {"argument_bytes": 0, "output_bytes": 0, "temp_bytes": 0,
                "alias_bytes": 0, "peak_live_bytes": 0, "available": False}


def bench_pair(smoke: bool = False, seed: int = 0,
               decode_path: str = "both", size: str | None = None) -> dict:
    import jax

    from repro.configs import get_arch
    from repro.models import build_model
    from repro.models.common import AxisRules, DEFAULT_RULES
    from repro.serve import (CacheConfig, DenseSlotEngine, EngineConfig,
                             Request, ServeEngine)

    rules = AxisRules(DEFAULT_RULES)
    cfg = get_arch("qwen2.5-3b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))

    size = size or ("smoke" if smoke else "full")
    if size == "smoke":
        lengths, max_new, n, max_len = (8, 16), 6, 4, 64
        dense_slots, paged_lanes, page_size = 2, 3, 16
    elif size == "gate":
        # the CI bench-gate workload: enough decode steps that dispatch /
        # scheduler noise averages out of the gated throughput ratios, but
        # still minutes-not-hours on a shared runner
        lengths, max_new, n, max_len = (8, 16, 32), 10, 10, 96
        dense_slots, paged_lanes, page_size = 2, 5, 16
    else:
        lengths, max_new, n, max_len = (8, 16, 32, 64, 128), 16, 24, 160
        dense_slots, paged_lanes, page_size = 4, 8, 16
    budget_tokens = dense_slots * max_len          # the shared memory budget
    n_pages = budget_tokens // page_size

    def warmup(eng):
        eng.submit(Request(uid=-1, prompt=np.arange(4, dtype=np.int32),
                           max_new_tokens=2))
        eng.run()

    results = {}
    dense = DenseSlotEngine(
        model, params,
        EngineConfig(batch_slots=dense_slots, max_len=max_len), rules,
    )
    warmup(dense)
    toks, dt, steps, step_s, _ = drive(dense, make_workload(
        n, lengths, max_new, mean_interarrival=2, seed=seed))
    results["dense"] = {
        "tokens": toks, "seconds": dt, "tok_s": toks / dt, "steps": steps,
        "step_latency_ms": _latency_ms(step_s),
        "slots": dense_slots, "cache_budget_tokens": budget_tokens,
    }

    paths = ("gather", "paged") if decode_path == "both" else (decode_path,)
    results["decode_paths"] = {}
    path_tokens = {}
    for path in paths:
        eng = ServeEngine(
            model, params,
            EngineConfig(batch_slots=paged_lanes, max_len=max_len,
                         cache=CacheConfig(page_size=page_size,
                                           n_pages=n_pages,
                                           decode_path=path)), rules,
        )
        warmup(eng)
        toks, dt, steps, step_s, by_uid = drive(eng, make_workload(
            n, lengths, max_new, mean_interarrival=2, seed=seed))
        tel = eng.telemetry()
        path_tokens[path] = by_uid
        results["decode_paths"][path] = {
            "tokens": toks, "seconds": dt, "tok_s": toks / dt, "steps": steps,
            "step_latency_ms": _latency_ms(step_s),
            "lanes": paged_lanes, "page_size": page_size, "n_pages": n_pages,
            "cache_budget_tokens": n_pages * page_size,
            "page_occupancy_mean": tel["occupancy_mean"],
            "page_occupancy_max": tel["occupancy_max"],
            "preemptions": tel["preemptions"],
            "gathered_view_bytes": gathered_view_bytes(eng),
            "decode_memory": decode_memory(eng),
        }
    if decode_path == "both":
        # the acceptance bar: the zero-materialization path must reproduce
        # the gather oracle token-for-token (greedy) — asserted here so the
        # CI smoke/bench gate cannot pass over a silent numeric break
        assert path_tokens["gather"] == path_tokens["paged"], (
            "gather/paged decode paths produced different tokens"
        )
        results["paths_token_identical"] = True
        g = results["decode_paths"]["gather"]
        p = results["decode_paths"]["paged"]
        results["paged_vs_gather_speedup"] = g["seconds"] / p["seconds"]

    # legacy top-level "paged" block (benchmarks/run.py + the bench gate key
    # on it): the zero-materialization path when it ran, else the one path
    results["paged"] = results["decode_paths"].get(
        "paged", next(iter(results["decode_paths"].values()))
    )
    results["speedup"] = results["paged"]["tok_s"] / results["dense"]["tok_s"]
    results["workload"] = {
        "requests": n, "prompt_lengths": list(lengths), "max_new": max_new,
        "smoke": size == "smoke", "size": size, "decode_path": decode_path,
    }
    return results


def bench_preempt(smoke: bool = False, seed: int = 0,
                  policies=("swap", "recompute"),
                  size: str | None = None) -> dict:
    """Swap-vs-recompute preemption under memory pressure, swept over prompt
    length (the crossover axis: recomputation cost grows with tokens, swap
    cost with pages).

    Per prompt length the page pool is sized to admit every request but run
    dry as decode grows (``lanes * reserve + 1`` pages), forcing the
    preempt-longest-running policy to fire; each policy then serves an
    identical workload.  ``swap`` engines run with ``swap_token_cost=0`` so
    the sweep measures the pure mechanism (the shipped cost model blends the
    two — its decisions are unit-tested, not benchmarked).  Token identity
    between the policies is asserted per length.
    """
    import jax

    from repro.configs import get_arch
    from repro.models import build_model
    from repro.models.common import AxisRules, DEFAULT_RULES
    from repro.serve import CacheConfig, EngineConfig, Request, ServeEngine

    rules = AxisRules(DEFAULT_RULES)
    cfg = get_arch("qwen2.5-3b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))

    size = size or ("smoke" if smoke else "full")
    if size == "smoke":
        plens, max_new, n, lanes, ps = (6, 14), 8, 3, 3, 4
    elif size == "gate":
        plens, max_new, n, lanes, ps = (8, 24, 48), 10, 4, 3, 4
    else:
        plens, max_new, n, lanes, ps = (8, 16, 32, 64, 96), 12, 6, 3, 8
    max_len = -(-(max(plens) + max_new + 2) // 16) * 16

    out = {"sweep": [], "workload": {
        "prompt_lengths": list(plens), "max_new": max_new, "requests": n,
        "lanes": lanes, "page_size": ps, "size": size,
    }}
    totals = {p: {"tokens": 0, "seconds": 0.0} for p in policies}
    identical = True
    for plen in plens:
        reserve = -(-(plen + 1) // ps)
        n_pages = lanes * reserve + 1          # admits all, dries mid-decode
        row = {"prompt_len": plen, "n_pages": n_pages}
        by_policy_tokens = {}
        for policy in policies:
            eng = ServeEngine(model, params, EngineConfig(
                batch_slots=lanes, max_len=max_len,
                cache=CacheConfig(page_size=ps, n_pages=n_pages,
                                  preempt_policy=policy,
                                  swap_token_cost=0.0),
            ), rules)
            eng.submit(Request(uid=-1, prompt=np.arange(4, dtype=np.int32),
                               max_new_tokens=2))
            eng.run()                           # warm the jit caches
            toks, dt, steps, step_s, by_uid = drive(eng, make_workload(
                n, (plen,), max_new, mean_interarrival=1, seed=seed))
            tel = eng.telemetry()
            by_policy_tokens[policy] = by_uid
            totals[policy]["tokens"] += toks
            totals[policy]["seconds"] += dt
            row[policy] = {
                "tokens": toks, "seconds": dt, "tok_s": toks / dt,
                "steps": steps, "step_latency_ms": _latency_ms(step_s),
                "preemptions": tel["preemptions"],
                "swap_preemptions": tel["swap_preemptions"],
                "recompute_preemptions": tel["recompute_preemptions"],
                "host_tier": tel.get("host_tier"),
            }
        if len(policies) == 2:
            a, b = policies
            if by_policy_tokens[a] != by_policy_tokens[b]:
                identical = False
            row["swap_vs_recompute"] = (row[b]["seconds"]
                                        / row[a]["seconds"])
        out["sweep"].append(row)
    out["totals"] = {p: dict(t, tok_s=t["tokens"] / t["seconds"])
                     for p, t in totals.items()}
    if len(policies) == 2:
        # the acceptance bar mirrors the decode-path one: the tiered cache
        # must reproduce recompute-preemption token-for-token under pressure
        assert identical, (
            "swap/recompute preemption produced different tokens"
        )
        out["preempt_tokens_identical"] = True
        out["swap_vs_recompute_speedup"] = (
            totals["recompute"]["seconds"] / totals["swap"]["seconds"]
        )
        cross = [r["prompt_len"] for r in out["sweep"]
                 if r.get("swap_vs_recompute", 0.0) >= 1.0]
        out["crossover_prompt_len"] = cross[0] if cross else None
    return out


ASYNC_FAMILIES = ["qwen2.5-3b", "deepseek-v3-671b", "mamba2-130m",
                  "recurrentgemma-9b"]


def bench_async(smoke: bool = False, seed: int = 0,
                modes=("on", "off"), size: str | None = None) -> dict:
    """Admission-pipeline overlap under an arrival storm: ``on`` runs
    prefill chunks + swap-in staging on the worker thread beside the decode
    loop, ``off`` runs the identical pipeline inline per step.

    The storm workload admits a new request nearly every step (Poisson with
    mean interarrival 1 on the step clock), so the sync engine serializes a
    prefill chunk in front of almost every decode step while the async
    engine overlaps them — the paper's DMA-double-buffering discipline
    transplanted to serving.  Both modes must produce bit-identical tokens
    (asserted; additionally per model family on the full run — the pipeline
    owns no shared device state, so *when* it runs can never change *what*
    it computes).

    Honest measurement note: the overlap win requires prefill compute to
    run somewhere decode isn't.  On a few-core CPU host the XLA CPU
    client's async-dispatch queue serializes all executions (measured: a
    two-thread decode+extend overlap runs 1.03x serial with it, 1.62x with
    ``JAX_CPU_ENABLE_ASYNC_DISPATCH=0``), so the gated ratio on such hosts
    sits near or below 1.0 and the gate guards it against *regression*;
    per-step decode latency (also reported) is what the pipeline improves
    everywhere.  On a real accelerator — decode on device, admissions on
    host — the ratio is the point of the architecture.
    """
    import jax

    from repro.configs import get_arch
    from repro.models import build_model
    from repro.models.common import AxisRules, DEFAULT_RULES
    from repro.serve import (AdmissionConfig, EngineConfig, Request,
                             ServeEngine)

    rules = AxisRules(DEFAULT_RULES)
    size = size or ("smoke" if smoke else "full")
    # admission-HEAVY on purpose: long prompts, short generations, an
    # arrival nearly every step — the regime where the sync engine stalls
    # decode behind a prefill chunk almost every round.  (Decode-dominated
    # traffic measures near 1.0 here instead: prefill compute then contends
    # with decode for the same few CPU cores — see the docstring note.)
    if size == "smoke":
        lengths, max_new, n, lanes, max_len, chunk = (8, 16), 6, 6, 3, 64, 8
        families = ["mamba2-130m"]      # storm covers qwen; add a recurrent
    elif size == "gate":
        lengths, max_new, n, lanes, max_len, chunk = (16, 32), 8, 32, 3, 96, 8
        families = []                   # the gate measures the ratio only
    else:
        lengths, max_new, n, lanes, max_len, chunk = ((16, 32, 48), 8, 40, 3,
                                                      160, 8)
        families = ASYNC_FAMILIES       # the acceptance bar: all 4 families

    cfg = get_arch("qwen2.5-3b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))

    def build(async_on: bool, a_cfg=cfg, a_model=None, a_params=None,
              a_lanes=None, a_max_len=None, a_chunk=None):
        eng = ServeEngine(
            a_model or model, a_params if a_params is not None else params,
            EngineConfig(batch_slots=a_lanes or lanes,
                         max_len=a_max_len or max_len,
                         admission=AdmissionConfig(
                             prefill_chunk=(chunk if a_chunk is None
                                            else a_chunk),
                             async_prefill=async_on)), rules,
        )
        # warm every prefill-chunk jit signature the storm will hit, so the
        # measured ratio is overlap, not one mode eating more compiles
        for i, plen in enumerate(lengths if a_chunk is None else (4,)):
            eng.submit(Request(uid=-1 - i,
                               prompt=np.arange(plen, dtype=np.int32),
                               max_new_tokens=2))
        eng.run()
        eng.reset_stats()            # drop warmup from the reported stats
        return eng

    out = {"workload": {
        "requests": n, "prompt_lengths": list(lengths), "max_new": max_new,
        "lanes": lanes, "prefill_chunk": chunk, "size": size,
        "mean_interarrival": 1,
    }, "modes": {}}
    by_mode_tokens = {}
    # interleave repeated drives of the two modes and median per mode: on a
    # shared/few-core host the absolute tok/s drifts ~2x over seconds
    # (thread-pool and frequency state), and a gated RATIO of two
    # single-shot runs inherits all of it — alternation decorrelates the
    # drift, the median discards the outliers
    reps = 2 if size == "smoke" else 3
    engines = {mode: build(mode == "on") for mode in modes}
    runs = {mode: [] for mode in modes}
    for rep in range(reps):
        for mode in modes:
            eng = engines[mode]
            eng.reset_stats()
            toks, dt, steps, step_s, by_uid = drive(eng, make_workload(
                n, lengths, max_new, mean_interarrival=1, seed=seed))
            tel = eng.telemetry()
            if rep == 0:
                by_mode_tokens[mode] = by_uid
            else:
                # reruns of the same workload must reproduce themselves
                assert by_uid == by_mode_tokens[mode], (
                    f"non-deterministic tokens across reruns ({mode})")
            runs[mode].append({
                "tokens": toks, "seconds": dt, "tok_s": toks / dt,
                "steps": steps, "step_latency_ms": _latency_ms(step_s),
                "decode_idle_fraction": tel["decode_idle_fraction"],
                "lane_utilization": tel["lane_utilization"],
                "prefill_tokens": tel["prefill_tokens"],
                "pipeline": tel["pipeline"],
            })
    for mode in modes:
        rows = sorted(runs[mode], key=lambda r: r["tok_s"])
        med = rows[len(rows) // 2]
        med["tok_s_runs"] = [r["tok_s"] for r in runs[mode]]
        out["modes"][mode] = med
    if len(modes) == 2:
        # the acceptance bar: threading the admission pipeline must not
        # change a single token — a silent divergence cannot pass CI
        assert by_mode_tokens["on"] == by_mode_tokens["off"], (
            "async/sync admission pipeline produced different tokens"
        )
        out["tokens_identical"] = True
        out["async_vs_sync_tokens_per_s"] = (
            out["modes"]["on"]["tok_s"] / out["modes"]["off"]["tok_s"]
        )

    fam_rows = {}
    for arch in families:
        fcfg = get_arch(arch).reduced()
        import dataclasses as _dc

        fmodel = build_model(_dc.replace(fcfg, decode_unroll_layers=False))
        fparams = fmodel.init(jax.random.key(0))
        fam_tokens = {}
        for mode in ("on", "off"):
            eng = build(mode == "on", a_model=fmodel, a_params=fparams,
                        a_lanes=2, a_max_len=64, a_chunk=4)
            _, _, _, _, by_uid = drive(eng, make_workload(
                4, (6, 11), max_new, mean_interarrival=1, seed=seed))
            fam_tokens[mode] = by_uid
        assert fam_tokens["on"] == fam_tokens["off"], (
            f"async/sync tokens diverged on {arch}"
        )
        fam_rows[arch] = {"tokens_identical": True}
    if fam_rows:
        out["families"] = fam_rows
    return out


def bench_swap_batch(seed: int = 0, n_victims: int = 6, pages_each: int = 4,
                     reps: int = 5) -> dict:
    """Swap-out batching microbench: evicting a victim set with one
    device→host copy per cache leaf (``HostPagePool.commit_many``) vs the
    per-victim ``swap_out`` round-trips it replaced.  Pure copy timing on
    real qwen-reduced pool layouts — the ratio the CI gate checks as
    ``swap_out_batch_speedup``."""
    import time as _time

    import jax

    from repro.configs import get_arch
    from repro.models import build_model
    from repro.serve import PagedKVCache

    cfg = get_arch("qwen2.5-3b").reduced()
    model = build_model(cfg)
    n_pages = n_victims * pages_each + 2
    cache = PagedKVCache(model, lanes=n_victims, n_pages=n_pages,
                         page_size=16, max_len=pages_each * 16,
                         host_pages=2 * n_pages)
    victims = []
    for lane in range(n_victims):
        pages = cache.allocator.acquire(pages_each)
        cache.assign_lane(lane, pages)
        victims.append((pages, lane, pages_each * 16 - 3))
    host = cache.host

    def run_per_victim():
        t0 = _time.perf_counter()
        handles = [host.swap_out(cache.pools, pages, lane, length)
                   for pages, lane, length in victims]
        dt = _time.perf_counter() - t0
        for h in handles:
            host.free(h)
        return dt

    def run_batched():
        t0 = _time.perf_counter()
        items = []
        for pages, lane, length in victims:
            handle, dirty = host.reserve(None, len(pages))
            items.append((handle, list(pages), dirty, lane, length))
        host.commit_many(cache.pools, items)
        dt = _time.perf_counter() - t0
        for handle, *_ in items:
            host.free(handle)
        return dt

    run_per_victim(), run_batched()            # warm dispatch paths
    per_victim = [run_per_victim() for _ in range(reps)]
    batched = [run_batched() for _ in range(reps)]
    pv, bt = float(np.median(per_victim)), float(np.median(batched))
    return {
        "n_victims": n_victims, "pages_each": pages_each, "reps": reps,
        "per_victim_s": pv, "batched_s": bt,
        "device_gets_per_victim_sweep": n_victims,     # one per victim before
        "speedup": pv / bt,
    }


def bench_obs_overhead(smoke: bool = False, seed: int = 0,
                       size: str | None = None) -> dict:
    """Tracing-overhead bench: the same Poisson workload driven through a
    traced engine (ring-buffer tracer + metrics on every step, phase change,
    prefill chunk, and DMA) vs the NULL_TRACER engine, interleaved reps with
    per-mode medians exactly like ``bench_async``.  The gated ratio
    ``obs_overhead_tokens_per_s`` (traced / untraced) is the observability
    layer's whole admission ticket: the hot path is a handful of scalar
    stores into preallocated numpy arrays, so the ratio must sit within 5%
    of 1.0 (``OBS_OVERHEAD_FLOOR`` in bench_gate).  Token identity between
    the modes is asserted — recording an event must never change a token.
    """
    import jax

    from repro.configs import get_arch
    from repro.models import build_model
    from repro.models.common import AxisRules, DEFAULT_RULES
    from repro.serve import (AdmissionConfig, EngineConfig, ObsConfig,
                             Request, ServeEngine)

    rules = AxisRules(DEFAULT_RULES)
    size = size or ("smoke" if smoke else "full")
    if size == "smoke":
        lengths, max_new, n, lanes, max_len = (8, 16), 6, 6, 3, 64
        reps = 2
    elif size == "gate":
        lengths, max_new, n, lanes, max_len = (16, 32), 8, 24, 3, 96
        reps = 3
    else:
        lengths, max_new, n, lanes, max_len = (16, 32, 48), 8, 32, 3, 160
        reps = 3

    cfg = get_arch("qwen2.5-3b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))

    def build(traced: bool):
        eng = ServeEngine(
            model, params,
            EngineConfig(batch_slots=lanes, max_len=max_len,
                         admission=AdmissionConfig(prefill_chunk=8,
                                                   async_prefill=True),
                         obs=ObsConfig(trace=traced)), rules,
        )
        for i, plen in enumerate(lengths):     # warm the jit signatures
            eng.submit(Request(uid=-1 - i,
                               prompt=np.arange(plen, dtype=np.int32),
                               max_new_tokens=2))
        eng.run()
        eng.reset_stats()
        return eng

    engines = {"traced": build(True), "untraced": build(False)}
    runs = {mode: [] for mode in engines}
    by_mode_tokens = {}
    for rep in range(reps):
        for mode, eng in engines.items():
            eng.reset_stats()
            toks, dt, steps, step_s, by_uid = drive(eng, make_workload(
                n, lengths, max_new, mean_interarrival=1, seed=seed))
            if rep == 0:
                by_mode_tokens[mode] = by_uid
            else:
                assert by_uid == by_mode_tokens[mode], (
                    f"non-deterministic tokens across reruns ({mode})")
            runs[mode].append({
                "tokens": toks, "seconds": dt, "tok_s": toks / dt,
                "steps": steps, "step_latency_ms": _latency_ms(step_s),
            })
    out = {"workload": {
        "requests": n, "prompt_lengths": list(lengths), "max_new": max_new,
        "lanes": lanes, "size": size, "reps": reps,
    }, "modes": {}}
    for mode, rows in runs.items():
        rows = sorted(rows, key=lambda r: r["tok_s"])
        med = rows[len(rows) // 2]
        med["tok_s_runs"] = [r["tok_s"] for r in runs[mode]]
        out["modes"][mode] = med
    # the acceptance bar: tracing must be invisible in the tokens
    assert by_mode_tokens["traced"] == by_mode_tokens["untraced"], (
        "traced/untraced engines produced different tokens"
    )
    out["tokens_identical"] = True
    out["traced_vs_untraced_tokens_per_s"] = (
        out["modes"]["traced"]["tok_s"] / out["modes"]["untraced"]["tok_s"]
    )
    tracer = engines["traced"].tracer
    out["trace_events"] = tracer.total
    out["trace_dropped"] = tracer.dropped
    return out


def bench_prefix(smoke: bool = False, seed: int = 0,
                 modes=("on", "off"), size: str | None = None) -> dict:
    """Prefix-reuse bench: a duplicate-heavy prompt mix through the paged
    engine with ``prefix_sharing`` on vs off.

    Phase 1 serves the ``distinct`` base prompts to completion, seeding the
    radix index (their pages survive request retirement because the index
    holds a refcount); phase 2 replays ``n - distinct`` requests drawn
    zipf-weighted from the same prompts — with sharing on, every replay is a
    full-terminal match that reuses the resident KV pages and skips its
    prefill entirely (copy-on-write forks the tail page before the lane's
    first decode write).  The two-phase shape makes the gated
    ``prefix_hit_rate`` deterministic (replayed tokens / looked-up tokens)
    instead of a race between duplicate arrivals and the first instance's
    index insert.

    Token identity between the modes is asserted — serving a prompt from
    cached pages must reproduce the re-prefill tokens bit-for-bit (greedy)
    — and the on-mode hit rate must clear 0.5, so a silently dead index
    cannot pass the smoke or the CI gate.  ``prefix_vs_none_tokens_per_s``
    (the gated throughput ratio) is the prefills not run."""
    import jax

    from repro.configs import get_arch
    from repro.models import build_model
    from repro.models.common import AxisRules, DEFAULT_RULES
    from repro.serve import CacheConfig, EngineConfig, Request, ServeEngine

    rules = AxisRules(DEFAULT_RULES)
    size = size or ("smoke" if smoke else "full")
    if size == "smoke":
        distinct, n, plen, max_new, lanes, max_len = 2, 6, 12, 5, 3, 64
    elif size == "gate":
        distinct, n, plen, max_new, lanes, max_len = 3, 12, 24, 8, 3, 96
    else:
        distinct, n, plen, max_new, lanes, max_len = 4, 24, 48, 10, 4, 160
    ps = 16
    # pool sized so the whole distinct-prompt working set stays resident
    # beside the decode lanes: this bench measures reuse, not eviction
    # (host-tier retire/restore has its own tests)
    pages_each = -(-(plen + max_new + 1) // ps)
    n_pages = (distinct + lanes) * pages_each + lanes

    cfg = get_arch("qwen2.5-3b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))

    rng = np.random.default_rng(seed)
    bases = [rng.integers(0, 512, size=(plen,)).astype(np.int32)
             for _ in range(distinct)]
    zipf = 1.0 / np.arange(1, distinct + 1)
    picks = rng.choice(distinct, size=n - distinct, p=zipf / zipf.sum())

    def phase(idxs, uid0, gap):
        return [dict(uid=uid0 + j, prompt=bases[k], max_new_tokens=max_new,
                     arrival=j * gap) for j, k in enumerate(idxs)]

    out = {"workload": {
        "requests": n, "distinct_prompts": distinct, "prompt_len": plen,
        "max_new": max_new, "lanes": lanes, "page_size": ps,
        "n_pages": n_pages, "size": size,
    }, "modes": {}}
    by_mode_tokens = {}
    for mode in modes:
        eng = ServeEngine(model, params, EngineConfig(
            batch_slots=lanes, max_len=max_len,
            cache=CacheConfig(page_size=ps, n_pages=n_pages,
                              prefix_sharing=mode == "on"),
        ), rules)
        eng.submit(Request(uid=-1, prompt=np.arange(4, dtype=np.int32),
                           max_new_tokens=2))
        eng.run()                       # warm the jit caches
        eng.reset_stats()
        t1, dt1, s1, ss1, by1 = drive(eng, phase(range(distinct), 0, 2),
                                      shutdown=False)
        t2, dt2, s2, ss2, by2 = drive(eng, phase(picks, distinct, 1))
        tel = eng.telemetry()
        by_mode_tokens[mode] = {**by1, **by2}
        toks, dt = t1 + t2, dt1 + dt2
        out["modes"][mode] = {
            "tokens": toks, "seconds": dt, "tok_s": toks / dt,
            "steps": s1 + s2, "step_latency_ms": _latency_ms(ss1 + ss2),
            "replay_seconds": dt2, "replay_tok_s": t2 / dt2,
            "prefill_tokens": tel["prefill_tokens"],
            "prefix": tel.get("prefix"),
        }
        if mode == "on":
            pr = tel["prefix"]
            # the acceptance bar: the replays actually hit the index
            assert pr["hit_rate"] > 0.5, (
                f"prefix index dead: hit rate {pr['hit_rate']:.2f} on a "
                "duplicate-heavy workload"
            )
            out["prefix_hit_rate"] = pr["hit_rate"]
            out["prefix_forks"] = pr["forks"]
    if len(modes) == 2:
        # the acceptance bar: serving from cached pages must reproduce the
        # re-prefill tokens bit-for-bit — greedy, so any divergence is a
        # numeric break, not sampling noise
        assert by_mode_tokens["on"] == by_mode_tokens["off"], (
            "prefix sharing on/off produced different tokens"
        )
        out["tokens_identical"] = True
        # the gated ratio is measured on the REPLAY phase only: phase 1
        # (seeding the index with the distinct prompts) is identical work
        # in both modes, so folding it in only dilutes the reuse signal
        # with decode time the mechanism never touches
        out["prefix_vs_none_tokens_per_s"] = (
            out["modes"]["on"]["replay_tok_s"]
            / out["modes"]["off"]["replay_tok_s"]
        )
    return out


def bench_trace(out_path: str, seed: int = 0, smoke: bool = False) -> dict:
    """Traced preemption-pressure drive: a page pool sized to run dry
    mid-decode (``lanes * reserve + 1``, the ``bench_preempt`` pattern) with
    the async admission pipeline on and the swap policy, so the exported
    Perfetto timeline shows every span class the tracer knows — engine
    steps, decode batches, prefill chunks on the admission track, swap-out
    DMA, swap-in staging, phase instants, and the free-page counter track.

    Writes the Chrome-trace JSON to ``out_path`` and validates it on the
    spot: every request's lifecycle is reconstructed from the phase instants
    and checked against the scheduler state machine (``PHASE_EDGES``), with
    every lifecycle starting at ``waiting`` and ending at ``done``.
    """
    import jax

    from repro.configs import get_arch
    from repro.models import build_model
    from repro.models.common import AxisRules, DEFAULT_RULES
    from repro.obs.export import (load_chrome_trace, request_phases,
                                  validate_lifecycles)
    from repro.serve import (AdmissionConfig, CacheConfig, EngineConfig,
                             ObsConfig, Request, ServeEngine)

    rules = AxisRules(DEFAULT_RULES)
    cfg = get_arch("qwen2.5-3b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))

    lanes, ps, plen, max_new = 3, 4, 14, 8
    n = 4 if smoke else 8
    reserve = -(-(plen + 1) // ps)
    n_pages = lanes * reserve + 1       # admits all, dries mid-decode
    max_len = -(-(plen + max_new + 2) // 16) * 16
    eng = ServeEngine(model, params, EngineConfig(
        batch_slots=lanes, max_len=max_len,
        cache=CacheConfig(page_size=ps, n_pages=n_pages,
                          preempt_policy="swap", swap_token_cost=0.0),
        admission=AdmissionConfig(prefill_chunk=6, async_prefill=True),
        obs=ObsConfig(trace=True),
    ), rules)
    eng.submit(Request(uid=-1, prompt=np.arange(4, dtype=np.int32),
                       max_new_tokens=2))
    eng.run()                           # warm the jit caches (uid -1 traced
                                        # too: its lifecycle must validate)
    toks, dt, steps, _, _ = drive(eng, make_workload(
        n, (plen,), max_new, mean_interarrival=1, seed=seed))
    tel = eng.telemetry()
    eng.save_trace(out_path)

    trace = load_chrome_trace(out_path)
    hist = validate_lifecycles(trace, require_done=True)
    lifecycles = request_phases(trace)
    return {
        "out": out_path, "tokens": toks, "steps": steps, "seconds": dt,
        "requests_traced": len(lifecycles),
        "preemptions": tel["preemptions"],
        "trace_events": len(trace["traceEvents"]),
        "phase_histogram": hist,
        "lifecycles_valid": True,
    }


def bench():
    """CSV rows for benchmarks/run.py (small non-smoke run)."""
    r = bench_pair(smoke=True)
    paged = r["decode_paths"]["paged"]
    return [
        ("serve.dense.tok_s", f"{r['dense']['tok_s']:.2f}", "tokens/s"),
        ("serve.paged.tok_s", f"{paged['tok_s']:.2f}", "tokens/s"),
        ("serve.paged.speedup", f"{r['speedup']:.3f}", "x vs dense"),
        ("serve.paged.step_p50_ms",
         f"{paged['step_latency_ms']['p50']:.2f}", "per-step decode"),
        ("serve.paged.peak_live_MB",
         f"{paged['decode_memory']['peak_live_bytes']/1e6:.2f}",
         "compiled decode step"),
        ("serve.paged.occupancy_max",
         f"{paged['page_occupancy_max']:.3f}", "pool fraction"),
    ]


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="few-step CI run (still writes the JSON report)")
    ap.add_argument("--decode-path", choices=["gather", "paged", "both"],
                    default="both",
                    help="which paged-engine decode path(s) to drive; "
                         "'both' also asserts token identity")
    ap.add_argument("--preempt-policy",
                    choices=["swap", "recompute", "both", "none"],
                    default="both",
                    help="preemption-policy sweep under memory pressure; "
                         "'both' asserts token identity and reports the "
                         "recompute-vs-swap crossover; 'none' skips it")
    ap.add_argument("--async-prefill", choices=["on", "off", "both", "none"],
                    default="both",
                    help="admission-pipeline storm: worker-thread vs inline "
                         "prefill/swap-in; 'both' asserts token identity "
                         "and reports async_vs_sync_tokens_per_s; 'none' "
                         "skips it")
    ap.add_argument("--prefix-reuse", choices=["on", "off", "both", "none"],
                    default="both",
                    help="prefix-sharing bench on a duplicate-heavy prompt "
                         "mix: radix-index reuse + copy-on-write vs "
                         "re-prefilling every repeat; 'both' asserts token "
                         "identity and reports the gated prefix_hit_rate "
                         "and prefix_vs_none_tokens_per_s; 'none' skips it")
    ap.add_argument("--obs", choices=["on", "none"], default="on",
                    help="tracing-overhead bench (traced vs untraced "
                         "engines, token identity asserted); reports the "
                         "gated obs_overhead_tokens_per_s ratio")
    ap.add_argument("--trace", metavar="OUT.json", default=None,
                    help="also drive a traced preemption-pressure workload "
                         "and write its Perfetto/Chrome trace here; the "
                         "trace is validated against the scheduler state "
                         "machine before the bench exits")
    ap.add_argument("--out", default="serve_bench.json")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    results = bench_pair(smoke=args.smoke, seed=args.seed,
                         decode_path=args.decode_path)
    if args.preempt_policy != "none":
        policies = (("swap", "recompute") if args.preempt_policy == "both"
                    else (args.preempt_policy,))
        results["preempt"] = bench_preempt(smoke=args.smoke, seed=args.seed,
                                           policies=policies)
    if args.async_prefill != "none":
        modes = (("on", "off") if args.async_prefill == "both"
                 else (args.async_prefill,))
        results["async"] = bench_async(smoke=args.smoke, seed=args.seed,
                                       modes=modes)
        results["swap_batch"] = bench_swap_batch(seed=args.seed)
    if args.prefix_reuse != "none":
        modes = (("on", "off") if args.prefix_reuse == "both"
                 else (args.prefix_reuse,))
        results["prefix"] = bench_prefix(smoke=args.smoke, seed=args.seed,
                                         modes=modes)
    if args.obs != "none":
        results["obs"] = bench_obs_overhead(smoke=args.smoke, seed=args.seed)
    if args.trace:
        results["trace"] = bench_trace(args.trace, seed=args.seed,
                                       smoke=args.smoke)
    with open(args.out, "w") as f:
        json.dump(results, f, indent=2, default=float)
    d = results["dense"]
    print(f"dense : {d['tok_s']:8.2f} tok/s  ({d['slots']} slots x "
          f"{d['cache_budget_tokens'] // d['slots']} ctx = "
          f"{d['cache_budget_tokens']} cache tokens)")
    for path, p in results["decode_paths"].items():
        mem = p["decode_memory"]
        print(f"{path:6s}: {p['tok_s']:8.2f} tok/s  ({p['lanes']} lanes, "
              f"{p['n_pages']} x {p['page_size']} pages, "
              f"step p50 {p['step_latency_ms']['p50']:.2f} ms, "
              f"peak live {mem['peak_live_bytes']/1e6:.2f} MB, "
              f"view bytes {p['gathered_view_bytes']/1e6:.2f} MB, "
              f"occupancy max {p['page_occupancy_max']:.2f}, "
              f"{p['preemptions']} preemptions)")
    if "paged_vs_gather_speedup" in results:
        print(f"paged vs gather: {results['paged_vs_gather_speedup']:.2f}x "
              "(tokens identical)")
    if "preempt" in results:
        pre = results["preempt"]
        for row in pre["sweep"]:
            parts = [f"plen {row['prompt_len']:3d} ({row['n_pages']} pages)"]
            for pol in ("swap", "recompute"):
                if pol in row:
                    parts.append(f"{pol} {row[pol]['tok_s']:7.2f} tok/s "
                                 f"({row[pol]['preemptions']} preempts)")
            if "swap_vs_recompute" in row:
                parts.append(f"ratio {row['swap_vs_recompute']:.2f}x")
            print("preempt: " + "  ".join(parts))
        if "swap_vs_recompute_speedup" in pre:
            cross = pre["crossover_prompt_len"]
            print(f"preempt: swap vs recompute {pre['swap_vs_recompute_speedup']:.2f}x "
                  f"overall, crossover at plen "
                  f"{cross if cross is not None else '>sweep'} "
                  "(tokens identical)")
    if "async" in results:
        a = results["async"]
        for mode, row in a["modes"].items():
            print(f"async={mode:3s}: {row['tok_s']:8.2f} tok/s  "
                  f"(decode idle {row['decode_idle_fraction']:.2f}, "
                  f"step p50 {row['step_latency_ms']['p50']:.2f} ms)")
        if "async_vs_sync_tokens_per_s" in a:
            fams = ", ".join(a.get("families", {})) or "storm arch"
            print(f"async vs sync: {a['async_vs_sync_tokens_per_s']:.2f}x "
                  f"(tokens identical on {fams})")
        sb = results["swap_batch"]
        print(f"swap-out batching: {sb['speedup']:.2f}x "
              f"({sb['n_victims']} victims x {sb['pages_each']} pages, "
              f"one device_get per leaf vs one per victim)")
    if "prefix" in results:
        px = results["prefix"]
        for mode, row in px["modes"].items():
            print(f"prefix={mode:3s}: {row['tok_s']:8.2f} tok/s  "
                  f"(replay {row['replay_tok_s']:.2f} tok/s, "
                  f"{row['prefill_tokens']} prefill tokens)")
        if "prefix_vs_none_tokens_per_s" in px:
            print(f"prefix vs none: "
                  f"{px['prefix_vs_none_tokens_per_s']:.2f}x  "
                  f"(hit rate {px['prefix_hit_rate']:.2f}, "
                  f"{px['prefix_forks']} CoW forks, tokens identical)")
    if "obs" in results:
        ob = results["obs"]
        print(f"obs overhead: {ob['traced_vs_untraced_tokens_per_s']:.3f}x "
              f"traced vs untraced tok/s ({ob['trace_events']} events, "
              f"{ob['trace_dropped']} dropped, tokens identical)")
    if "trace" in results:
        tr = results["trace"]
        print(f"trace: {tr['requests_traced']} lifecycles / "
              f"{tr['trace_events']} events validated against the phase "
              f"state machine ({tr['preemptions']} preemptions) "
              f"-> {tr['out']}")
    print(f"speedup: {results['speedup']:.2f}x  -> {args.out}")
    return results


if __name__ == "__main__":
    main()
