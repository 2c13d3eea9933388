#!/usr/bin/env python3
"""Run one benchmark cell once, on the machine's own chips.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1> [--control]

The cell, its configuration and its traffic mix are found by name through
``BENCHMARK.json``.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics from a traced run.  The last line of
standard output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and last
``checks``: each number the correctness check compared, beside its limit).
The line before it gives the run's own facts: compile and cache counts,
programs loaded inside the window, requests completed.  Without a TPU whose
kind ``chipbench/peaks.json`` knows, or with fewer chips than the cell asks
for, it prints no result and exits 3.

``--control`` runs the check's control: the reference computed in fp8
takes the program's place in the comparison (its first choices are the
tokens compared), and the run has to come out not correct.  The
benchmark's own runs never pass it.
"""
import time

T0 = time.perf_counter()                 # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    from chipbench.harness import registry

    bench = registry.benchmark()
    cell = registry.cell(bench, args.workload)
    cfg = registry.config(bench, cell["config"])
    mix = registry.mix(cell["traffic"])

    import jax

    from chipbench.harness.device import NoChip, require

    try:
        device = require(jax, int(cell["chips"]))
    except NoChip as e:
        print(f"chipbench: {e}; nothing was run", file=sys.stderr)
        return 3
    from chipbench.harness.bench import run_cell
    from chipbench.harness.compile_meter import CompileMeter, enable_cache

    enable_cache(jax)
    meter = CompileMeter(jax)
    out = run_cell(
        cfg, mix, args.seed, args.seconds, bool(args.trace), T0,
        device=device, meter=meter, control=args.control,
        end_to_end=registry.cell_metrics(bench, cell["name"], "end_to_end"),
        per_layer=registry.cell_metrics(bench, cell["name"], "per_layer"))
    print(json.dumps({"run": out["run"]}), flush=True)
    for name, c in out["result"]["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out["result"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
