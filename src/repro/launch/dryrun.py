import os
# These lines MUST precede every other import: jax locks the device count on
# first initialization.  A caller that already forced a device count (the
# multi-pod subprocess tests, REPRO_DRYRUN_DEVICES) wins; the CLI default is
# the 512-chip production footprint.
_flags = os.environ.get("XLA_FLAGS", "")
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count="
        + os.environ["REPRO_DRYRUN_DEVICES"]
    ).strip()
elif "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=512"
    ).strip()

"""Multi-pod dry-run (deliverable e).

For every (architecture × shape × mesh) cell: build abstract params + input
ShapeDtypeStructs, attach NamedShardings from the per-arch rules, then
``jax.jit(step).lower(...).compile()`` — proving the distribution config is
coherent (sharding propagation succeeds, collectives legal, memory fits) —
and extract ``memory_analysis`` / ``cost_analysis`` / the three roofline
terms for EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch gemma-7b --shape train_4k [--multi-pod]
  python -m repro.launch.dryrun --all [--multi-pod] [--out experiments/dryrun]
"""
import argparse
import dataclasses
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import ALL_SHAPES, applicable, get_arch
from repro.configs.archs import ASSIGNED
from repro.configs.shapes import ShapeSuite
from repro.core.roofline import V5E, analyze_compiled
from repro.dist.sharding import (
    arch_rules,
    batch_shardings,
    cache_axes,
    param_shardings,
    replicated,
    tree_shardings,
)
from repro.launch.mesh import describe, make_production_mesh
from repro.models.api import build_model, input_specs
from repro.models.common import abstract_params
from repro.optim.optimizer import adamw
from repro.train.train_step import make_train_step


def model_flops_estimate(cfg, shape: ShapeSuite) -> float:
    """MODEL_FLOPS per §Roofline: 6·N·D train, 2·N·D forward."""
    n = cfg.n_active_params() if cfg.is_moe else cfg.n_params()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    return 2.0 * n * shape.global_batch          # decode: one token per seq


def _memory_fields(compiled) -> dict:
    """``memory_analysis`` fields with a zero fallback: the CPU backend (used
    by the scaled-down subprocess dry-run) may not implement it."""
    try:
        ma = compiled.memory_analysis()
        fields = {
            "argument": ma.argument_size_in_bytes,
            "output": ma.output_size_in_bytes,
            "temp": ma.temp_size_in_bytes,
            "alias": ma.alias_size_in_bytes,
            "available": True,
        }
    except Exception:
        fields = {"argument": 0, "output": 0, "temp": 0, "alias": 0,
                  "available": False}
    fields["per_device"] = (
        fields["argument"] + fields["output"] + fields["temp"] - fields["alias"]
    )
    return fields


def lower_cell(
    arch: str,
    shape_name: str,
    multi_pod: bool = False,
    mesh=None,
    rule_overrides: dict | None = None,
    cfg_overrides: dict | None = None,
    label_suffix: str = "",
):
    """Lower + compile one cell; returns (report_dict, compiled)."""
    cfg = get_arch(arch)
    if cfg_overrides:
        cfg = dataclasses.replace(cfg, **cfg_overrides)
    shape = ALL_SHAPES[shape_name]
    if not applicable(cfg.family, shape):
        return {"label": f"{arch}/{shape_name}", "skipped":
                "long_500k requires sub-quadratic sequence mixing "
                "(full-attention arch) — see DESIGN.md §Arch-applicability"}, None
    mesh = mesh if mesh is not None else make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    model = build_model(cfg)
    step_kind = shape.kind
    rules = arch_rules(cfg, mesh, step=step_kind,
                       global_batch=shape.global_batch, overrides=rule_overrides)
    specs = model.param_specs()
    aparams = abstract_params(specs)
    pshard = param_shardings(mesh, specs, rules)
    inputs = input_specs(cfg, shape)
    label = f"{arch}/{shape_name}/{describe(mesh)}{label_suffix}"

    t0 = time.time()
    with jax.set_mesh(mesh):
        if step_kind == "train":
            opt = adamw(state_dtype=jnp.dtype(cfg.opt_state_dtype))
            ostate = jax.eval_shape(opt.init, aparams)
            oshard = {"m": pshard, "v": pshard, "count": replicated(mesh)}
            step = make_train_step(
                model, opt, rules, n_microbatches=cfg.train_microbatches,
                grad_shardings=pshard,
                accum_dtype=jnp.dtype(cfg.grad_accum_dtype),
            )
            in_shard = batch_shardings(mesh, inputs, rules)
            jitted = jax.jit(
                step,
                in_shardings=(pshard, oshard, in_shard),
                out_shardings=(pshard, oshard, None),
                donate_argnums=(0, 1),
            )
            lowered = jitted.lower(aparams, ostate, inputs)
        elif step_kind == "prefill":
            def prefill_step(params, batch):
                extra = batch.get("patches", batch.get("frames"))
                return model.prefill(
                    params, batch["tokens"], rules, extra_embeds=extra
                )

            in_shard = batch_shardings(mesh, inputs, rules)
            # explicit cache out-shardings (inference otherwise replicates)
            out_sds = jax.eval_shape(prefill_step, aparams, inputs)
            cache_out = tree_shardings(
                mesh, out_sds[1], cache_axes(cfg, out_sds[1]), rules
            )
            lowered = jax.jit(
                prefill_step,
                in_shardings=(pshard, in_shard),
                out_shardings=(None, cache_out),
            ).lower(aparams, inputs)
        else:  # decode
            cspec = model.cache_specs(shape.global_batch, shape.seq_len)
            cshard = tree_shardings(mesh, cspec, cache_axes(cfg, cspec), rules)

            def serve_step(params, cache, tokens, position):
                return model.decode_step(params, cache, tokens, position, rules)

            tok_shard = batch_shardings(
                mesh, {"tokens": inputs["tokens"]}, rules
            )["tokens"]
            lowered = jax.jit(
                serve_step,
                in_shardings=(pshard, cshard, tok_shard, replicated(mesh)),
                out_shardings=(None, cshard),
                donate_argnums=(1,),
            ).lower(aparams, cspec, inputs["tokens"], inputs["position"])
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0

    mem = _memory_fields(compiled)
    rep = analyze_compiled(
        compiled, label, n_dev, model_flops=model_flops_estimate(cfg, shape)
    )
    out = {
        "label": label,
        "arch": arch,
        "shape": shape_name,
        "mesh": describe(mesh),
        "devices": n_dev,
        "step": step_kind,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_GB": mem["argument"] / 1e9,
            "output_GB": mem["output"] / 1e9,
            "temp_GB": mem["temp"] / 1e9,
            "alias_GB": mem["alias"] / 1e9,
            "per_device_GB": mem["per_device"] / 1e9,
            # None (not True) when the backend can't do memory analysis —
            # a fit verdict with no data would be worse than no verdict
            "fits_v5e_16GB": (
                mem["per_device"] < V5E.hbm_bytes if mem["available"] else None
            ),
            "available": mem["available"],
        },
        "roofline": rep.row(),
        "collective_bytes": rep.collectives,
        "collective_count": rep.collective_count,
        "xla_cost_analysis": rep.xla_cost_analysis,
        "loop_trips": rep.loop_trips[:16],
        "model_flops": rep.model_flops_global,
    }
    return out, compiled


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args(argv)

    cells = []
    archs = ASSIGNED if (args.all or not args.arch) else [args.arch]
    shapes = list(ALL_SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for a, s, mp in cells:
        tag = f"{a}__{s}__{'multi' if mp else 'single'}"
        path = os.path.join(args.out, tag + ".json")
        try:
            rep, compiled = lower_cell(a, s, multi_pod=mp)
            del compiled
            with open(path, "w") as f:
                json.dump(rep, f, indent=1)
            if rep.get("skipped"):
                print(f"[SKIP] {tag}: {rep['skipped']}", flush=True)
            else:
                r = rep["roofline"]
                m = rep["memory"]
                mem_str = (
                    f"{m['per_device_GB']:.2f}GB" if m["available"] else "n/a"
                )
                print(
                    f"[OK]   {tag}: mem/dev={mem_str} "
                    f"bound={r['bound']} t=({r['t_compute_s']},{r['t_memory_s']},"
                    f"{r['t_collective_s']}) compile={rep['compile_s']}s",
                    flush=True,
                )
        except Exception as e:  # noqa: BLE001
            failures += 1
            print(f"[FAIL] {tag}: {type(e).__name__}: {e}", flush=True)
            traceback.print_exc()
            with open(path, "w") as f:
                json.dump({"label": tag, "error": str(e)}, f)
    print(f"done: {len(cells) - failures}/{len(cells)} cells OK")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
