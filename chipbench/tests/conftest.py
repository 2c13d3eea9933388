"""Small configurations and a CPU run of the harness for the tests.

The tests run on the CPU with ``python -m pytest chipbench/tests``: the
Pallas kernels run in interpret mode, and ``tiny_run`` skips the harness's
look for a chip (it hands ``run_cell`` a described CPU device) and drives
the rest of a run as ``run.py`` does.
"""
from __future__ import annotations

import copy
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from chipbench.harness import registry  # noqa: E402

CPU_DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1,
              "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}}

# the widths of ``ArchConfig.reduced()``: d_model 128, 4 heads of 32, 2 kv
# heads, d_ff 256, vocab 512, 2 layers
SMALL_DENSE = dict(hidden_size=128, intermediate_size=256, num_hidden_layers=2,
                   num_attention_heads=4, num_key_value_heads=2, vocab_size=512)
SMALL_ENGINE = {"batch_slots": 4, "max_len": 128, "eos_id": None,
                "admission": {"prefill_chunk": 32}}


def small_config(name: str, **over) -> dict:
    cfg = copy.deepcopy(registry.load_json(
        registry.BENCH / "configs" / f"{name}.json"))
    cfg.update(SMALL_DENSE)
    eng = copy.deepcopy(SMALL_ENGINE)
    eng["cache"] = dict(cfg["engine"]["cache"])
    eng["cache"].pop("n_pages", None)        # the whole pool at these sizes
    cfg["engine"] = eng
    cfg["check"] = {"max_logit_gap": 0.1, "min_served_tokens": 24,
                    "max_requests": 3}
    cfg.update(over)
    return cfg


def small_mix(name: str) -> dict:
    mix = copy.deepcopy(registry.mix(name))
    mix["prompt"] = {"median": 24, "sigma": 0.5, "min": 16, "max": 48, "bucket": 16}
    mix["output"] = {"median": 8, "sigma": 0.5, "min": 4, "max": 16}
    mix["pool"] = 64
    if mix["kind"] == "backlog":
        mix["queued_beyond_lanes"] = 2
    else:
        mix["rate_per_s"] = 20.0
        mix["warm_s"] = 0.5
        mix["in_progress"] = 2
    return mix


def tiny_run(cfg: dict, mix: dict, seed: int = 3, seconds: float = 2.0,
             control: bool = False, trace: bool = False) -> dict:
    import jax

    from chipbench.harness.bench import run_cell
    from chipbench.harness.compile_meter import CompileMeter

    bench = registry.benchmark()
    cell = next(w for w in bench["workloads"] if w["traffic"] == (
        "batch" if mix["kind"] == "backlog" else "chat"))
    return run_cell(
        cfg, mix, seed, seconds, trace, 0.0, device=CPU_DEVICE,
        meter=CompileMeter(jax), control=control,
        end_to_end=registry.cell_metrics(bench, cell["name"], "end_to_end"),
        per_layer=registry.cell_metrics(bench, cell["name"], "per_layer"))


@pytest.fixture
def dense_cfg():
    return small_config("qwen25_3b")
