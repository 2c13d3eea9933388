"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

On this CPU container it trains the arch's REDUCED config end-to-end with the
full substrate (sharded mesh over available devices, microbatching,
checkpoint/restore, fault tolerance).  On a real TPU slice the same driver
takes ``--full`` and the production mesh; the dry-run proves that path
compiles.
"""
import argparse
import os

import jax

from repro.configs import get_arch
from repro.data.pipeline import SyntheticLMData
from repro.dist.collectives import overlap_flags
from repro.dist.sharding import arch_rules
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import (
    describe,
    make_host_mesh,
    make_production_mesh,
)
from repro.models import build_model
from repro.train.trainer import Trainer, TrainerConfig


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--ckpt", default="/tmp/repro_lm_ckpt")
    ap.add_argument("--full", action="store_true",
                    help="full-size config on the production mesh (TPU)")
    ap.add_argument("--overlap", default="aggressive")
    args = ap.parse_args(argv)

    enable_compile_cache()
    if args.overlap == "aggressive" and jax.default_backend() == "tpu":
        flags = " ".join(f"--{k}={v}" for k, v in overlap_flags().items())
        os.environ["LIBTPU_INIT_ARGS"] = flags

    cfg = get_arch(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    mesh = make_production_mesh() if args.full else make_host_mesh()
    rules = arch_rules(cfg, mesh, step="train", global_batch=args.batch)
    model = build_model(cfg)
    data = SyntheticLMData(cfg, batch=args.batch, seq=args.seq)

    tcfg = TrainerConfig(
        total_steps=args.steps, ckpt_dir=args.ckpt, ckpt_every=25,
        optimizer=args.optimizer, lr=args.lr,
        n_microbatches=cfg.train_microbatches if args.full else 1,
    )
    print(f"training {cfg.name} on mesh [{describe(mesh)}] "
          f"for {args.steps} steps")
    with jax.set_mesh(mesh):
        tr = Trainer(model, data, tcfg, rules)
        state, restarts = tr.run_with_restarts(jax.random.key(0))
    first = sum(state.losses[:10]) / max(len(state.losses[:10]), 1)
    last = sum(state.losses[-10:]) / max(len(state.losses[-10:]), 1)
    print(f"done: step={state.step} loss {first:.3f} -> {last:.3f} "
          f"(restarts={restarts})")


if __name__ == "__main__":
    main()
