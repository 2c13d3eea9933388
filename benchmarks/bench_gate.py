"""CI perf-regression gate over the committed BENCH_*.json baselines.

``--update`` runs the smoke benches and (re)writes the baselines
(``BENCH_serve.json`` / ``BENCH_kernels.json`` at the repo root — the bench
trajectory lives in git); ``--check`` re-runs them and fails (exit 1) when a
gated metric regresses >20% vs its baseline (below ``TOLERANCE * base`` for
the higher-is-better serve speedups, above ``base / TOLERANCE`` for the
lower-is-better kernel timing ratios).

Gated metrics are *ratios measured on one machine* (paged-vs-dense serving
speedup, swap-vs-recompute preemption speedup, kernel-vs-oracle timing
ratios), so they transfer across runners far better than absolute wall
times; absolute ``*_us`` / latency numbers are recorded in the JSON for
trend reading but never gated.  Each of the ``--repeats`` runs executes in
a FRESH SUBPROCESS and the gate takes the per-key median: XLA-CPU compile
choices and thread-pool state vary 2x *between processes* while staying
stable within one, and ``--update`` and ``--check`` always live in
different processes — in-process repeats would never sample the variance
the gate is actually exposed to.

Run:  PYTHONPATH=src python benchmarks/bench_gate.py --check
      PYTHONPATH=src python benchmarks/bench_gate.py --update
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import platform
import subprocess
import sys

# fail on a >20% regression vs baseline (direction-aware, see check()).
# The gated
# metrics are same-machine ratios, which transfer across runners far better
# than absolute times but not perfectly — when the CI runner fleet or the
# pinned jax changes, refresh the baselines (--update, ideally from a CI
# run) rather than loosening the gate; BENCH_GATE_TOLERANCE exists for a
# deliberate temporary override, not as a knob to silence a regression.
TOLERANCE = float(os.environ.get("BENCH_GATE_TOLERANCE", "0.8"))
ROOT = pathlib.Path(__file__).resolve().parent.parent
SERVE_BASELINE = ROOT / "BENCH_serve.json"
KERNEL_BASELINE = ROOT / "BENCH_kernels.json"

# gated ratio metrics extracted from each bench's JSON.  Directions
# differ by label: the serve ratios are speedups (HIGHER is better); the
# kernel ratios are impl-vs-oracle and tuned-vs-default timing ratios
# (LOWER is better — < 1.0 means the production/tuned leg is faster).
GATED_SERVE = ("speedup", "paged_vs_gather_speedup",
               "swap_vs_recompute_speedup",
               # two-loop engine: worker-thread vs inline admission pipeline
               # under an arrival storm (near/below 1.0 on few-core CPU
               # hosts — the XLA CPU client serializes cross-thread
               # executions — so this gates the overlap plumbing against
               # regression, not an absolute win), and the batched swap-out
               # (one device_get per leaf per victim SET vs one per victim)
               "async_vs_sync_tokens_per_s", "swap_out_batch_speedup",
               # observability: traced vs untraced engines on one storm
               "obs_overhead_tokens_per_s",
               # prefix sharing on a duplicate-heavy mix: replayed-prompt
               # tokens served from the radix index (deterministic > 0.5 by
               # the bench's two-phase construction) and the throughput
               # ratio vs re-prefilling every repeat
               "prefix_hit_rate", "prefix_vs_none_tokens_per_s")
GATED_KERNELS = ("attn.flash_xla.oracle_ratio", "attn.paged_decode.oracle_ratio",
                 "ssd.chunked.oracle_ratio", "moe.dispatch.oracle_ratio",
                 # cutout-autotuner wins: tuned-vs-default timing of the
                 # table-active impl legs (repro.tune; docs/kernels.md)
                 "ssd.chunked.tuned_ratio", "attn.paged_decode.tuned_ratio")

# absolute floor for the tracing-overhead ratio (traced/untraced tok/s):
# unlike the other gated ratios this one has a physical target — 1.0, the
# tracer hot path being a handful of scalar stores into preallocated
# arrays — so beyond the relative baseline check it is gated absolutely at
# <=5% overhead, regardless of where the committed baseline drifts.
OBS_OVERHEAD_FLOOR = float(os.environ.get("BENCH_GATE_OBS_FLOOR", "0.95"))


def run_serve() -> dict:
    from benchmarks import serve_bench

    r = serve_bench.bench_pair(decode_path="both", size="gate")
    pre = serve_bench.bench_preempt(size="gate")
    a = serve_bench.bench_async(size="gate")
    sb = serve_bench.bench_swap_batch()
    ob = serve_bench.bench_obs_overhead(size="gate")
    px = serve_bench.bench_prefix(size="gate")
    paged = r["decode_paths"]["paged"]
    return {
        # prefix sharing: replay hit rate + reuse-vs-reprefill throughput
        "prefix_hit_rate": px["prefix_hit_rate"],
        "prefix_vs_none_tokens_per_s": px["prefix_vs_none_tokens_per_s"],
        "prefix_tokens_identical": px["tokens_identical"],
        "prefix_on_tok_s": px["modes"]["on"]["tok_s"],
        "prefix_off_tok_s": px["modes"]["off"]["tok_s"],
        "prefix_cow_forks": px["prefix_forks"],
        # observability: tracing must cost <=5% throughput (also gated
        # absolutely via OBS_OVERHEAD_FLOOR) and zero tokens
        "obs_overhead_tokens_per_s": ob["traced_vs_untraced_tokens_per_s"],
        "obs_tokens_identical": ob["tokens_identical"],
        "traced_tok_s": ob["modes"]["traced"]["tok_s"],
        "untraced_tok_s": ob["modes"]["untraced"]["tok_s"],
        "obs_trace_events": ob["trace_events"],
        "obs_trace_dropped": ob["trace_dropped"],
        # admission pipeline: storm throughput ratio + per-mode telemetry
        "async_vs_sync_tokens_per_s": a["async_vs_sync_tokens_per_s"],
        "async_tokens_identical": a["tokens_identical"],
        "async_tok_s": a["modes"]["on"]["tok_s"],
        "sync_tok_s": a["modes"]["off"]["tok_s"],
        "async_decode_idle_fraction": a["modes"]["on"]["decode_idle_fraction"],
        "sync_decode_idle_fraction": a["modes"]["off"]["decode_idle_fraction"],
        "async_step_p50_ms": a["modes"]["on"]["step_latency_ms"]["p50"],
        "sync_step_p50_ms": a["modes"]["off"]["step_latency_ms"]["p50"],
        # batched swap-out: one device_get per leaf for the victim set
        "swap_out_batch_speedup": sb["speedup"],
        "speedup": r["speedup"],
        "paged_vs_gather_speedup": r["paged_vs_gather_speedup"],
        "paths_token_identical": r["paths_token_identical"],
        "dense_tok_s": r["dense"]["tok_s"],
        "paged_tok_s": paged["tok_s"],
        "paged_step_p50_ms": paged["step_latency_ms"]["p50"],
        "paged_peak_live_bytes": paged["decode_memory"]["peak_live_bytes"],
        "gathered_view_bytes": paged["gathered_view_bytes"],
        # tiered-KV preemption: host-DRAM swap vs recompute under pressure
        # (an offload regression drags the aggregate ratio below the gate)
        "swap_vs_recompute_speedup": pre["swap_vs_recompute_speedup"],
        "preempt_tokens_identical": pre["preempt_tokens_identical"],
        # advisory; -1 = swap never crossed over within the sweep (must stay
        # numeric: _median_of medians this key across repeats)
        "preempt_crossover_prompt_len": (
            -1 if pre["crossover_prompt_len"] is None
            else pre["crossover_prompt_len"]),
        "swap_tok_s": pre["totals"]["swap"]["tok_s"],
        "recompute_tok_s": pre["totals"]["recompute"]["tok_s"],
    }


def run_kernels() -> dict:
    from benchmarks import kernel_bench

    return kernel_bench.bench_json()


def _one_run(which: str) -> dict:
    return run_serve() if which == "serve" else run_kernels()


def _median_of(which: str, repeats: int) -> dict:
    """Per-key median over ``repeats`` runs, EACH IN A FRESH SUBPROCESS — a
    single slow run on a noisy shared runner, or one process's unlucky XLA
    compile, must not swing a gated ratio."""
    import statistics

    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(ROOT / "src"), str(ROOT)]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    runs = []
    for _ in range(repeats):
        proc = subprocess.run(
            [sys.executable, str(pathlib.Path(__file__).resolve()),
             "--emit", which],
            capture_output=True, text=True, env=env,
        )
        if proc.returncode != 0:
            raise RuntimeError(
                f"bench subprocess ({which}) failed:\n{proc.stderr[-2000:]}"
            )
        runs.append(json.loads(proc.stdout.splitlines()[-1]))
    out = dict(runs[0])
    for k, v in out.items():
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            out[k] = statistics.median(r[k] for r in runs)
    return out


def check(current: dict, baseline: dict, gated, label: str,
          lower_is_better: bool = False) -> list[str]:
    """Regression check, direction-aware: higher-is-better metrics fail
    below ``TOLERANCE * base`` (the historical serve behavior); lower-is-
    better metrics (the kernel timing ratios) fail above
    ``base / TOLERANCE`` — the same >20% relative regression either way."""
    failures = []
    for key in gated:
        base = baseline.get(key)
        cur = current.get(key)
        if base is None or cur is None:
            failures.append(f"{label}: metric {key!r} missing "
                            f"(baseline={base}, current={cur})")
            continue
        if lower_is_better:
            limit = base / TOLERANCE
            bad = cur > limit
            bound_name = "ceiling"
        else:
            limit = TOLERANCE * base
            bad = cur < limit
            bound_name = "floor"
        status = "REGRESSED" if bad else "ok"
        print(f"  {label}.{key}: baseline={base:.3f} current={cur:.3f} "
              f"{bound_name}={limit:.3f} [{status}]")
        if bad:
            failures.append(
                f"{label}: {key} regressed >20%: {cur:.3f} "
                f"{'>' if lower_is_better else '<'} "
                f"{limit:.3f} (baseline {base:.3f})"
            )
    return failures


def trend(out_serve: str, out_kernels: str) -> int:
    """Nightly drift alarm over the gated ratios: unlike ``--check`` (which
    only fails on regression), drift is symmetric — a ratio that *improved*
    >20% means the committed baseline is stale, and a stale baseline hides
    the next regression inside its slack.  Reads the gate JSONs a prior
    ``--check`` wrote instead of re-running the benches.

    The serve ratios measure stable (±~10% between runs, medians over
    interleaved drives), so their drift check is symmetric.  The
    kernel timing ratios (impl/oracle, tuned/default — LOWER is better)
    swing 2-3x between processes on few-core hosts and their committed
    baselines deliberately sit at the pessimistic HIGH end of that
    distribution (see BENCH_kernels.json) — downward "drift" (faster than
    baseline) is structural there, so kernels alarm on upward collapse
    only."""
    failures = []
    reports: dict[str, dict | None] = {}
    for label, out_path in (("serve", out_serve), ("kernels", out_kernels)):
        p = pathlib.Path(out_path)
        if not p.exists():
            failures.append(f"{label}: gate report {out_path} missing "
                            "(did --check run?)")
            reports[label] = None
        else:
            reports[label] = json.loads(p.read_text())
    for label, base_path, gated, symmetric, lower_is_better in (
        ("serve", SERVE_BASELINE, GATED_SERVE, True, False),
        ("kernels", KERNEL_BASELINE, GATED_KERNELS, False, True),
    ):
        cur = reports[label]
        if cur is None:
            continue
        base = json.loads(base_path.read_text())
        for key in gated:
            b, c = base.get(key), cur.get(key)
            if b is None or c is None:
                failures.append(f"{label}: metric {key!r} missing "
                                f"(baseline={b}, current={c})")
                continue
            drift = c / b - 1.0
            band = 1.0 - TOLERANCE
            # one-sided checks alarm on the WORSE direction only: upward
            # for lower-is-better metrics, downward otherwise
            one_sided = drift if lower_is_better else -drift
            bad = (abs(drift) if symmetric else one_sided) > band
            status = "DRIFTED" if bad else "ok"
            print(f"  {label}.{key}: baseline={b:.3f} current={c:.3f} "
                  f"drift={drift:+.1%} [{status}]")
            if bad:
                failures.append(
                    f"{label}: {key} drifted {drift:+.1%} vs baseline "
                    f"({c:.3f} vs {b:.3f}) — refresh BENCH_*.json via "
                    "--update if this is a real, intended shift"
                )
    if failures:
        print("\nbench trend FAILED:")
        for f in failures:
            print(f"  - {f}")
        return 1
    print("\nbench trend ok")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    mode = ap.add_mutually_exclusive_group(required=True)
    mode.add_argument("--check", action="store_true",
                      help="fail when a gated ratio regresses >20%")
    mode.add_argument("--update", action="store_true",
                      help="(re)write the committed baselines")
    mode.add_argument("--emit", choices=["serve", "kernels"],
                      help="internal: run one bench in this process and "
                           "print its metrics JSON (the subprocess half of "
                           "--repeats)")
    mode.add_argument("--trend", action="store_true",
                      help="no bench runs: diff existing gate JSONs "
                           "(--out-serve/--out-kernels, written by a prior "
                           "--check) against the committed baselines and "
                           "fail on >20%% drift in EITHER direction — "
                           "catches regressions AND silent improvements a "
                           "stale baseline would otherwise hide until a "
                           "refresh")
    ap.add_argument("--repeats", type=int, default=3,
                    help="fresh-subprocess runs per bench; the gate takes "
                         "the per-key median")
    ap.add_argument("--only", action="append", metavar="LABEL.KEY",
                    default=None,
                    help="with --update: re-measure and merge only these "
                         "metrics (e.g. serve.obs_overhead_tokens_per_s) "
                         "into the committed baseline, leaving every other "
                         "value untouched — for introducing a new gated key "
                         "without re-baselining the rest on a possibly "
                         "different machine")
    ap.add_argument("--out-serve", default="serve_gate.json",
                    help="where --check writes the current serve metrics")
    ap.add_argument("--out-kernels", default="kernels_gate.json")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    if args.emit:
        print(json.dumps(_one_run(args.emit)))
        return 0
    if args.trend:
        return trend(args.out_serve, args.out_kernels)
    if args.only and not args.update:
        ap.error("--only requires --update")
    if args.only:
        need: dict[str, list[str]] = {}
        for spec in args.only:
            label, _, key = spec.partition(".")
            if label not in ("serve", "kernels") or not key:
                ap.error(f"--only expects LABEL.KEY with LABEL in "
                         f"serve/kernels, got {spec!r}")
            need.setdefault(label, []).append(key)
        paths = {"serve": SERVE_BASELINE, "kernels": KERNEL_BASELINE}
        for label, keys in need.items():
            cur = _median_of(label, args.repeats)
            base = json.loads(paths[label].read_text())
            for key in keys:
                if key not in cur:
                    raise SystemExit(
                        f"--only: {label} run produced no metric {key!r}")
                base[key] = cur[key]
                print(f"  {label}.{key} <- {cur[key]}")
            paths[label].write_text(json.dumps(base, indent=2) + "\n")
            print(f"baseline merged: {paths[label].name} "
                  f"({len(keys)} key{'s' if len(keys) != 1 else ''})")
        return 0
    serve = _median_of("serve", args.repeats)
    kernels = _median_of("kernels", args.repeats)
    import jax

    env = {"jax": jax.__version__, "python": platform.python_version(),
           "machine": platform.machine()}
    serve["env"], kernels["env"] = env, env

    if args.update:
        SERVE_BASELINE.write_text(json.dumps(serve, indent=2) + "\n")
        KERNEL_BASELINE.write_text(json.dumps(kernels, indent=2) + "\n")
        print(f"baselines written: {SERVE_BASELINE.name} {KERNEL_BASELINE.name}")
        return 0

    pathlib.Path(args.out_serve).write_text(json.dumps(serve, indent=2))
    pathlib.Path(args.out_kernels).write_text(json.dumps(kernels, indent=2))
    failures = []
    if not serve.get("paths_token_identical"):
        failures.append("serve: gather/paged token identity broken")
    if not serve.get("preempt_tokens_identical"):
        failures.append("serve: swap/recompute preemption token identity broken")
    if not serve.get("async_tokens_identical"):
        failures.append("serve: async/sync admission pipeline token identity broken")
    if not serve.get("obs_tokens_identical"):
        failures.append("serve: traced/untraced token identity broken")
    if not serve.get("prefix_tokens_identical"):
        failures.append("serve: prefix-sharing on/off token identity broken")
    obs_ratio = serve.get("obs_overhead_tokens_per_s")
    if obs_ratio is not None and obs_ratio < OBS_OVERHEAD_FLOOR:
        failures.append(
            f"serve: tracing overhead exceeds the absolute budget: "
            f"traced/untraced tok/s {obs_ratio:.3f} < {OBS_OVERHEAD_FLOOR}"
        )
    serve_base = json.loads(SERVE_BASELINE.read_text())
    failures += check(serve, serve_base, GATED_SERVE, "serve")
    failures += check(kernels, json.loads(KERNEL_BASELINE.read_text()),
                      GATED_KERNELS, "kernels", lower_is_better=True)
    if failures:
        print("\nbench gate FAILED:")
        for f in failures:
            print(f"  - {f}")
        return 1
    print("\nbench gate ok")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
