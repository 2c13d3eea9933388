"""Serving launcher: ``python -m repro.launch.serve --arch <id> [...]``.

Serves the arch's reduced config by default; ``--full`` serves its published
widths (one v5e chip holds qwen2.5-3b: ``--full --attn-impl pallas``).
``--cubes N`` routes requests across N cube-replica engines
(``serve.router.CubeRouter``), one per device when the host has N devices;
the scheduler/paged-cache knobs mirror ``serve.engine.EngineConfig``.
"""
import argparse
import contextlib
import json
import time

import jax
import numpy as np

from repro.configs import get_arch
from repro.dist.sharding import arch_rules
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.serve import (
    AdmissionConfig,
    CacheConfig,
    CubeRouter,
    EngineConfig,
    ObsConfig,
    Request,
    ServeEngine,
)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--full", action="store_true",
                    help="serve the arch at its published widths instead of "
                         "the reduced CPU config")
    ap.add_argument("--attn-impl", choices=["xla", "pallas"], default="xla",
                    help="paged decode attention: XLA transient gather, or "
                         "the fused Pallas kernel (interpret mode off-TPU)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--pages", type=int, default=0,
                    help="page pool size (0 = dense-equivalent budget)")
    ap.add_argument("--policy", choices=["fcfs", "spf"], default="fcfs")
    ap.add_argument("--prefill-chunk", type=int, default=0)
    ap.add_argument("--max-step-tokens", type=int, default=0)
    ap.add_argument("--async-prefill", choices=["on", "off"], default="on",
                    help="run prefill chunks + swap-in staging on the "
                         "admission pipeline thread (on, default) or "
                         "inline per step (off — the debugging fallback; "
                         "bit-identical tokens either way)")
    ap.add_argument("--admission-inflight", type=int, default=2,
                    help="backpressure: admissions in flight (pages "
                         "reserved, not yet decoding) before the pipeline "
                         "stops admitting")
    ap.add_argument("--preempt-policy", choices=["swap", "recompute"],
                    default="swap",
                    help="eviction: swap pages to the host-DRAM tier and "
                         "restore on resume, or free + recompute")
    ap.add_argument("--host-pages", type=int, default=0,
                    help="host-tier page pool size (0 = 2x device pool "
                         "under --preempt-policy swap)")
    ap.add_argument("--swap-cost", type=float, default=0.25,
                    help="cost model: moving one token of KV relative to "
                         "recomputing it (0 = always swap)")
    ap.add_argument("--prefix-sharing", action="store_true",
                    help="radix-index resident prompt prefixes so repeat "
                         "prompts reuse their KV pages (copy-on-write on "
                         "divergence; token-identical either way)")
    ap.add_argument("--cubes", type=int, default=1,
                    help="route over N cube-replica engines")
    ap.add_argument("--route",
                    choices=["hash", "least_loaded", "prefix_affinity"],
                    default="least_loaded")
    ap.add_argument("--trace", metavar="OUT.json", default=None,
                    help="record request lifecycles + engine events into "
                         "the ring-buffer tracer and write a Perfetto/"
                         "Chrome trace here after the run (open with "
                         "ui.perfetto.dev)")
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = get_arch(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    ecfg = EngineConfig(
        batch_slots=args.slots, max_len=args.max_len,
        cache=CacheConfig(
            page_size=args.page_size, n_pages=args.pages or None,
            preempt_policy=args.preempt_policy,
            host_pages=args.host_pages or None,
            swap_token_cost=args.swap_cost,
            prefix_sharing=args.prefix_sharing,
            attn_impl=args.attn_impl,
        ),
        admission=AdmissionConfig(
            policy=args.policy, prefill_chunk=args.prefill_chunk,
            max_step_tokens=args.max_step_tokens,
            async_prefill=args.async_prefill == "on",
            admission_inflight=args.admission_inflight,
        ),
        obs=ObsConfig(trace=args.trace is not None),
    )
    mesh_ctx = contextlib.nullcontext()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    if args.cubes > 1:
        devs = jax.devices()
        eng = CubeRouter(
            model, params, ecfg, n_cubes=args.cubes, policy=args.route,
            devices=devs[:args.cubes] if len(devs) >= args.cubes else None,
        )
    else:
        mesh = make_host_mesh()
        mesh_ctx = jax.set_mesh(mesh)
        rules = arch_rules(cfg, mesh, step="decode",
                           global_batch=args.slots)
        eng = ServeEngine(model, params, ecfg, rules)
    with mesh_ctx:
        rng = np.random.default_rng(0)
        for i in range(args.requests):
            eng.submit(Request(
                uid=i,
                prompt=rng.integers(0, cfg.vocab_size, size=(8,)).astype(np.int32),
                max_new_tokens=args.max_new,
                temperature=args.temperature,
            ))
        t0 = time.time()
        done = eng.run(key=jax.random.key(1))
        dt = time.time() - t0
    toks = sum(len(r.out_tokens) for r in done)
    print(f"{cfg.name}: {len(done)} requests, {toks} tokens, "
          f"{toks/dt:.1f} tok/s")
    print(json.dumps(eng.telemetry(), indent=2, default=float))
    if args.trace:
        eng.save_trace(args.trace)
        print(f"trace -> {args.trace}")


if __name__ == "__main__":
    main()
