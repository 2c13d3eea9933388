"""Distribution tests: sharding-rule resolution, roofline HLO analyzer, and
a scaled-down multi-pod dry-run executed in a SUBPROCESS with fake devices
(so the main pytest process keeps its single CPU device)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.core.roofline import analyze_hlo_text, parse_hlo

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# HLO analyzer unit tests (text-level, no devices needed)
# ---------------------------------------------------------------------------

SAMPLE_HLO = """
HloModule test

%cond (p: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

%body (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %x = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %d = f32[8,8]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  %ar = f32[8,8]{1,0} all-reduce(%d), replica_groups={{0,1,2,3}}, to_apply=%cond
  %one = s32[] constant(1)
  %ni = s32[] add(%i, %one)
  ROOT %t = (s32[], f32[8,8]) tuple(%ni, %ar)
}

ENTRY %main (a: f32[8,8]) -> (s32[], f32[8,8]) {
  %a = f32[8,8]{1,0} parameter(0)
  %z = s32[] constant(0)
  %tup = (s32[], f32[8,8]) tuple(%z, %a)
  ROOT %w = (s32[], f32[8,8]) while(%tup), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"5"}}
}
"""


def test_hlo_parser_finds_computations():
    comps, entry = parse_hlo(SAMPLE_HLO)
    assert entry == "main"
    assert set(comps) == {"cond", "body", "main"}
    ops = [i.opcode for i in comps["body"].instructions]
    assert "dot" in ops and "all-reduce" in ops


def test_hlo_analyzer_multiplies_loop_trips():
    cost = analyze_hlo_text(SAMPLE_HLO, n_devices=4)
    # dot: 2*8*8*8 = 1024 flops per iteration x 5 trips
    assert cost.dot_flops == pytest.approx(1024 * 5)
    assert cost.loop_trip_counts == [5]
    # all-reduce: 2*(n-1)/n * 256B x 5 trips
    assert cost.wire_bytes == pytest.approx(2 * 3 / 4 * 256 * 5)
    assert cost.collective_count["all-reduce"] == 5


def test_hlo_analyzer_operand_resolution():
    """Operand types resolved by name when not printed inline."""
    comps, _ = parse_hlo(SAMPLE_HLO)
    dot = [i for i in comps["body"].instructions if i.opcode == "dot"][0]
    assert dot.operand_types == ["f32[8,8]{1,0}", "f32[8,8]{1,0}"]


def test_hlo_comment_stripping():
    txt = SAMPLE_HLO.replace(
        "(s32[], f32[8,8]) tuple", "(s32[], /*index=1*/f32[8,8]) tuple"
    )
    comps, _ = parse_hlo(txt)
    assert "body" in comps


# ---------------------------------------------------------------------------
# Sharding rule resolution (uses a CPU mesh of size 1 — shapes still checked)
# ---------------------------------------------------------------------------


def test_arch_rules_divisibility_fallbacks():
    import jax
    from repro.configs import get_arch
    from repro.dist.sharding import arch_rules, param_shardings
    from repro.models import build_model

    # single-device mesh: everything must fall back to replication cleanly
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    for arch in ("qwen1.5-4b", "whisper-large-v3", "mamba2-130m"):
        cfg = get_arch(arch)
        rules = arch_rules(cfg, mesh, step="train", global_batch=8)
        model = build_model(cfg)
        sh = param_shardings(mesh, model.param_specs(), rules)
        assert len(jax.tree.leaves(sh)) > 0


# ---------------------------------------------------------------------------
# Scaled-down dry-run in a subprocess (8 fake devices, 2x2x2 mesh)
# ---------------------------------------------------------------------------

SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    import jax
    from repro.launch import dryrun
    mesh = jax.make_mesh((2, 2, 2), ("pod", "data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 3)
    out = {}
    for arch, shape in [("qwen2.5-3b", "train_4k"), ("mamba2-130m", "long_500k"),
                        ("qwen3-moe-235b-a22b", "decode_32k")]:
        rep, compiled = dryrun.lower_cell(arch, shape, mesh=mesh)
        del compiled
        out[f"{arch}/{shape}"] = {
            "bound": rep["roofline"]["bound"],
            "devices": rep["devices"],
            "flops": rep["roofline"]["flops/dev"],
        }
    print("RESULT:" + json.dumps(out))
""")


@pytest.mark.slow
def test_dryrun_subprocess_multipod():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    # the forced host device count applies to the CPU backend only; keep the
    # child on it so the dry-run never loads an accelerator runtime
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-c", SUBPROC], env=env, capture_output=True,
        text=True, timeout=900,
    )
    assert r.returncode == 0, r.stderr[-3000:]
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("RESULT:")][0]
    out = json.loads(line[len("RESULT:"):])
    assert len(out) == 3
    for v in out.values():
        assert v["devices"] == 8
        assert float(v["flops"]) > 0
