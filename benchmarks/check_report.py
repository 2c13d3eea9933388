"""Validate a ``serve_bench.py`` JSON report: one checker for every CI
lane (tier-1 smoke, nightly full bench) instead of
per-workflow inline ``python -c`` assert blobs that drift apart.

Asserts the structural invariants the benches promise:

* the base report always carries the dense/paged comparison;
* every requested section (``--expect``) is present, and its token-identity
  flag is True — a silent numeric break cannot pass CI;
* section-specific floors: the prefix bench's hit rate is deterministically
  > 0.5 by construction, and the trace's lifecycles validated against the
  scheduler state machine.

Run:  python benchmarks/check_report.py serve_bench.json \\
          --expect preempt async swap_batch prefix obs trace
Exit: 0 and a one-line summary on success; AssertionError otherwise.
"""
from __future__ import annotations

import argparse
import json

SECTIONS = ("preempt", "async", "swap_batch", "prefix", "obs", "trace")


def check_report(r: dict, expect: list[str]) -> list[str]:
    """Assert the report's invariants; returns the summary fragments."""
    assert {"dense", "decode_paths", "speedup"} <= r.keys(), sorted(r)
    missing = [s for s in expect if s not in r]
    assert not missing, f"expected section(s) missing from report: {missing}"
    summary = [f"speedup {r['speedup']:.2f}x"]
    if "paths_token_identical" in r:        # --decode-path both
        assert r["paths_token_identical"] is True
    if "preempt" in expect:
        pre = r["preempt"]
        assert pre["preempt_tokens_identical"] is True
        summary.append(f"swap/recompute {pre['swap_vs_recompute_speedup']:.2f}x")
    if "async" in expect:
        a = r["async"]
        assert a["tokens_identical"] is True
        summary.append(f"async/sync {a['async_vs_sync_tokens_per_s']:.2f}x")
    if "swap_batch" in expect:
        summary.append(f"swap-batch {r['swap_batch']['speedup']:.2f}x")
    if "prefix" in expect:
        px = r["prefix"]
        assert px["tokens_identical"] is True
        assert px["prefix_hit_rate"] > 0.5, px["prefix_hit_rate"]
        summary.append(f"prefix {px['prefix_vs_none_tokens_per_s']:.2f}x")
    if "obs" in expect:
        ob = r["obs"]
        assert ob["tokens_identical"] is True
        summary.append(f"obs {ob['traced_vs_untraced_tokens_per_s']:.3f}x")
    if "trace" in expect:
        assert r["trace"]["lifecycles_valid"] is True
        summary.append(f"{r['trace']['requests_traced']} lifecycles")
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("report", help="serve_bench JSON report path")
    ap.add_argument("--expect", nargs="*", choices=SECTIONS,
                    default=["preempt", "async", "swap_batch", "prefix",
                             "obs"],
                    help="bench sections that must be present and valid")
    args = ap.parse_args(argv)
    with open(args.report) as f:
        r = json.load(f)
    summary = check_report(r, list(args.expect))
    print(f"serve bench report ok: {', '.join(summary)}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
