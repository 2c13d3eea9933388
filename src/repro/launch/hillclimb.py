import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        + os.environ["REPRO_DRYRUN_DEVICES"]
    )

"""§Perf hillclimb driver: lower one cell under named variants and print the
three roofline terms + memory side by side (hypothesis → change → measure).

Usage:
  python -m repro.launch.hillclimb --cell qwen3-32b/decode_32k/single \
      --variants baseline,cache_carry
"""
import argparse
import sys

from repro.launch.dryrun import lower_cell
from repro.launch.searchloop import search

# Named variants: (rule_overrides, cfg_overrides)
VARIANTS = {
    "baseline": ({}, {}),
    # decode: alias cache in the scan carry instead of xs→ys double buffer
    "cache_carry": ({}, {"decode_cache_in_carry": True}),
    # decode: unroll layers, per-layer cache leaves alias via jit donation
    "decode_unroll": ({}, {"decode_unroll_layers": True}),
    # decode: replicate cache over model (ablation: what seq-sharding buys)
    "cache_replicated": ({"cache_seq": None}, {}),
    # attention chunk sweep (memory term knob)
    "chunk512": ({}, {"attn_chunk": 512}),
    "chunk2048": ({}, {"attn_chunk": 2048}),
    # remat policy ablation (compute vs memory trade)
    "remat_dots": ({}, {"remat": "dots"}),
    "remat_none": ({}, {"remat": "none"}),
    # microbatch sweep
    "mb2x": ({}, {}),          # filled dynamically
    # train: keep FSDP gathers intra-pod only (embed over data, not pod+data)
    "fsdp_intra_pod": ({"embed": "data"}, {}),
    # MoE: larger capacity factor (quality/perf trade visibility)
    "cap2x": ({}, {"capacity_factor": 2.5}),
    # sequence-sharded activations (SP) for train
    "seq_parallel": ({"seq": "model", "cache_seq": "model"}, {}),
    # SSD ablations
    "ssd_chunk128": ({}, {"__ssd_chunk": 128}),
    "ssd_chunk128_mb4": ({}, {"__ssd_chunk": 128, "train_microbatches": 4}),
    "ssd_chunk256": ({}, {"__ssd_chunk": 256}),
    "ssd_chunk512": ({}, {"__ssd_chunk": 512}),
    "ssd_chunk256_mb2": ({}, {"__ssd_chunk": 256, "train_microbatches": 2}),
    "mb8": ({}, {"train_microbatches": 8}),
    "bf16_accum": ({}, {"grad_accum_dtype": "bfloat16"}),
    "mb8_bf16_accum": ({}, {"train_microbatches": 8,
                            "grad_accum_dtype": "bfloat16"}),
}


def _resolve_overrides(arch: str, v: str):
    """Expand a named variant into (rule_overrides, cfg_overrides)."""
    ro, co = VARIANTS[v]
    co = dict(co)
    if v == "mb2x":
        from repro.configs import get_arch

        co["train_microbatches"] = get_arch(arch).train_microbatches * 2
    if any(k.startswith("__ssd") for k in co):
        import dataclasses
        from repro.configs import get_arch

        base = get_arch(arch).ssm
        co["ssm"] = dataclasses.replace(base, chunk=co.pop("__ssd_chunk"))
    return ro, co


def run(cell: str, variants: list[str], out_dir: str | None = None):
    arch, shape, meshname = cell.split("/")
    multi = meshname.startswith("multi")

    def measure(v: str, _payload) -> dict:
        ro, co = _resolve_overrides(arch, v)
        rep, compiled = lower_cell(
            arch, shape, multi_pod=multi,
            rule_overrides=ro or None, cfg_overrides=co or None,
            label_suffix=f"+{v}",
        )
        del compiled
        r = rep["roofline"]
        return {
            "mem_GB": round(rep["memory"]["per_device_GB"], 2),
            "t_compute": float(r["t_compute_s"]),
            "t_memory": float(r["t_memory_s"]),
            "t_collective": float(r["t_collective_s"]),
            "bound": r["bound"],
            "useful": float(r["useful_flop_ratio"]),
            "compile_s": rep["compile_s"],
            "collectives": rep["collective_bytes"],
        }

    def render(row: dict) -> str:
        return (f"mem={row['mem_GB']:7.2f}GB "
                f"t=({row['t_compute']:.3e},{row['t_memory']:.3e},"
                f"{row['t_collective']:.3e}) bound={row['bound']} "
                f"useful={row['useful']:.3f}")

    tag = cell.replace("/", "__")
    return search(
        [(v, None) for v in variants], measure, render=render,
        log=lambda s: print(s, flush=True),
        out_path=(os.path.join(out_dir, f"hillclimb_{tag}.json")
                  if out_dir else None),
    )


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True,
                    help="arch/shape/single|multi")
    ap.add_argument("--variants", default="baseline,cache_carry")
    ap.add_argument("--out", default="experiments/hillclimb")
    args = ap.parse_args(argv)
    run(args.cell, args.variants.split(","), args.out)


if __name__ == "__main__":
    sys.exit(main())
