"""The hybrid family's pieces: its work counts against hand counts, its two
readers on synthetic records, and a traced CPU run of a small granite
configuration through the whole harness."""
import copy
from types import SimpleNamespace

import pytest

from chipbench.harness import registry
from chipbench.tests.conftest import CPU_DEVICE, small_mix
from chipbench.work import ssd_hybrid as work

CELL = "granite4_h_micro.batch"


def small_granite() -> dict:
    """Reduced widths (hidden 128, 4 heads of 32 over 2, MLP 256, vocab 512,
    8 SSM heads of 32, state 16), two periods of the published pattern."""
    cfg = copy.deepcopy(registry.load_json(
        registry.BENCH / "configs" / "granite4_h_micro.json"))
    cfg.update(hidden_size=128, intermediate_size=256,
               shared_intermediate_size=256, num_attention_heads=4,
               num_key_value_heads=2, vocab_size=512, mamba_n_heads=8,
               mamba_d_head=32, mamba_d_state=16, mamba_chunk_size=32,
               num_hidden_layers=20, layer_types=cfg["layer_types"][:10] * 2)
    cfg["engine"] = {"batch_slots": 4, "max_len": 128, "eos_id": None,
                     "admission": {"prefill_chunk": 32},
                     "cache": {"page_size": 16, "decode_path": "paged",
                               "attn_impl": "pallas"}}
    cfg["check"] = {"max_logit_gap": 0.1, "min_served_tokens": 24,
                    "max_requests": 3}
    return cfg


def test_work_counts_one_period():
    cfg = small_granite()
    d, f, v, di, nh, n = 128, 256, 512, 256, 8, 16
    mamba = d * (2 * di + 2 * n + nh) + di * d + 3 * d * f
    attn = d * 128 + 2 * d * 64 + 128 * d + 3 * d * f
    assert work.matmul_params(cfg) == 2 * (9 * mamba + attn) + d * v
    state = 18 * (nh * 32 * n * 4 + 3 * (di + 2 * n) * 2)
    assert work.state_bytes(cfg) == state
    # a token at context 10: K/V rows of 2 heads x 32 in bf16 over 10
    # positions read and one written in 2 attention layers, state both ways
    assert work.decode_token_bytes(cfg, 10) == 2 * 2 * 2 * 32 * 2 * 11 + 2 * state
    flops, nbytes = work.decode_steps(cfg, 3, [10, 20])
    assert nbytes == (3 * 2 * work.matmul_params(cfg)
                      + work.decode_token_bytes(cfg, 10)
                      + work.decode_token_bytes(cfg, 20))
    assert flops == (work.decode_token_flops(cfg, 10)
                     + work.decode_token_flops(cfg, 20))


def test_state_write_reader():
    read = registry.metric_reader("state_write_ms")
    rec = SimpleNamespace(histograms={"lanes.state_write_s":
                                      {"count": 4, "sum": 0.002}})
    assert read(rec) == pytest.approx(0.5)
    assert read(SimpleNamespace(histograms={})) is None      # the parent


def test_decode_hbm_roofline_reader():
    read = registry.metric_reader("decode_hbm_roofline")
    cfg = small_granite()
    trace = SimpleNamespace(n_planes=1, module=lambda name: (
        (2, 4e6) if name == "_decode_impl" else (0, 0.0)))
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    rec = SimpleNamespace(trace=trace, decode_ctx=[10, 20], cfg=cfg,
                          work=work, peaks=peaks)
    flops, nbytes = work.decode_steps(cfg, 2, [10, 20])
    least = max(flops / 1e12, nbytes / 1e11)
    assert read(rec) == pytest.approx(100.0 * least / 4e-3)
    # untraced, nothing decoded, or a family whose work lacks the counts
    assert read(SimpleNamespace(**{**vars(rec), "trace": None})) is None
    assert read(SimpleNamespace(**{**vars(rec), "decode_ctx": []})) is None
    assert read(SimpleNamespace(**{**vars(rec), "work": object()})) is None


def test_hybrid_paged_attn_roofline_reader():
    """The kernel's least time counts the attention layers only (2 of the
    20 here), not every layer as the dense reader does."""
    read = registry.metric_reader("paged_attn_roofline.hybrid")
    cfg = small_granite()
    trace = SimpleNamespace(n_planes=1, op_ns=lambda name: (
        5e5 if name == "paged_attention" else 0.0))
    peaks = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
    rec = SimpleNamespace(trace=trace, decode_ctx=[10, 20], cfg=cfg,
                          work=work, peaks=peaks)
    assert work.attn_layers(cfg) == 2
    # 2 heads x 32 of K and V in bf16 over 30 positions, 4 heads x 32 of
    # query and output for 2 tokens
    flops, nbytes = work.paged_attn_call(cfg, [10, 20])
    assert flops == 4.0 * 4 * 32 * 30
    assert nbytes == 2 * 2 * 32 * 2 * 30 + 2 * 4 * 32 * 2 * 2
    least = 2 * max(flops / 1e12, nbytes / 1e11)
    assert read(rec) == pytest.approx(100.0 * least / 5e-4)
    # untraced, nothing decoded, the kernel absent, or a family whose work
    # does not count attention layers apart
    assert read(SimpleNamespace(**{**vars(rec), "trace": None})) is None
    assert read(SimpleNamespace(**{**vars(rec), "decode_ctx": []})) is None
    assert read(SimpleNamespace(**{**vars(rec), "trace": SimpleNamespace(
        n_planes=1, op_ns=lambda name: 0.0)})) is None
    assert read(SimpleNamespace(**{**vars(rec), "work": object()})) is None


def test_traced_run_matches_reference_and_reads_its_metrics():
    import jax

    from chipbench.harness.bench import run_cell
    from chipbench.harness.compile_meter import CompileMeter

    bench = registry.benchmark()
    out = run_cell(
        small_granite(), small_mix("batch"), 2**33 + 5, 1.5, True, 0.0,
        device=CPU_DEVICE, meter=CompileMeter(jax),
        end_to_end=registry.cell_metrics(bench, CELL, "end_to_end"),
        per_layer=registry.cell_metrics(bench, CELL, "per_layer"))
    res, run = out["result"], out["run"]
    assert res["correct"], res["checks"]
    assert run["window_compiles"] == 0 and run["preemptions"] == 0
    metrics = res["metrics"]
    # a CPU trace has no device plane, so the device readers stay silent;
    # the program's counters read as on the chip
    assert "decode_hbm_roofline" not in metrics
    assert "paged_attn_roofline.hybrid" not in metrics
    assert metrics["lane_occupancy.batch"]["value"] > 0
    assert metrics["state_write_ms"]["value"] > 0


def test_control_fails_where_program_passes():
    """The fp8 reference in the program's place comes out not correct where
    the program passes, at these widths as on the chip."""
    import jax

    from chipbench.harness.bench import run_cell
    from chipbench.harness.compile_meter import CompileMeter

    bench = registry.benchmark()
    out = run_cell(
        small_granite(), small_mix("batch"), 2**33 + 6, 1.0, False, 0.0,
        device=CPU_DEVICE, meter=CompileMeter(jax), control=True,
        end_to_end=registry.cell_metrics(bench, CELL, "end_to_end"),
        per_layer=registry.cell_metrics(bench, CELL, "per_layer"))
    res, run = out["result"], out["run"]
    limit = res["checks"]["logit_gap"]["limit"]
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > limit
    assert run["program_logit_gap"] <= limit
