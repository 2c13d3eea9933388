"""Unit tests for ``repro.dist`` beyond what test_substrates exercises:
compression dtype-stability, sharding resolution on ragged pytrees and
non-divisible dims, cache-axis inference, straggler/fault edge cases."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.dist.collectives import (
    compress_tree,
    decompress_tree,
    overlap_flags,
    wire_bytes,
)
from repro.dist.fault import FaultInjector, StragglerDetector
from repro.dist.sharding import (
    arch_rules,
    batch_shardings,
    cache_axes,
    param_shardings,
    replicated,
    resolve_spec,
    tree_shardings,
)
from repro.models import build_model
from repro.models.common import AxisRules, DEFAULT_RULES, PSpec
from repro.obs import clock as obs_clock
from repro.obs.clock import ManualClock

RULES = AxisRules(DEFAULT_RULES)


# ---------------------------------------------------------------------------
# Compression: dtype stability + structure roundtrip
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["none", "bf16", "int8"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_compress_roundtrip_preserves_dtype_and_structure(mode, dtype):
    rng = np.random.default_rng(3)
    tree = {
        "a": jnp.asarray(rng.normal(size=(8, 4)), dtype),
        "nested": {"b": jnp.asarray(rng.normal(size=(3,)), dtype)},
        "stack": [jnp.asarray(rng.normal(size=(2, 2)), dtype),
                  jnp.asarray(rng.normal(size=(5,)), dtype)],
    }
    c, scales = compress_tree(tree, mode)
    back = decompress_tree(c, scales, mode)
    assert jax.tree.structure(back) == jax.tree.structure(tree)
    for orig, rec in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert rec.dtype == orig.dtype, mode
        assert rec.shape == orig.shape


def test_compress_int8_leaves_are_int8_and_zero_tree_safe():
    tree = {"w": jnp.zeros((4, 4), jnp.float32)}
    c, scales = compress_tree(tree, "int8")
    assert c["w"].dtype == jnp.int8
    back = decompress_tree(c, scales, "int8")
    np.testing.assert_array_equal(np.asarray(back["w"]), 0.0)
    assert np.all(np.isfinite(np.asarray(back["w"])))


def test_compress_unknown_mode_raises():
    with pytest.raises(ValueError):
        compress_tree({"w": jnp.ones(2)}, "fp4")


def test_wire_bytes_orders():
    tree = {"w": jnp.ones((16, 16), jnp.float32)}
    assert wire_bytes(tree, "none") == 16 * 16 * 4
    assert wire_bytes(tree, "bf16") == 16 * 16 * 2
    assert wire_bytes(tree, "int8") == 16 * 16 + 4    # + per-tensor scale


def test_overlap_flags_shape():
    flags = overlap_flags()
    assert flags and all(
        k.startswith("xla") and isinstance(v, str) for k, v in flags.items()
    )


# ---------------------------------------------------------------------------
# resolve_spec: divisibility fallbacks (fake multi-axis sizes — the real
# multi-device path runs in the subprocess dry-run)
# ---------------------------------------------------------------------------

SIZES = {"pod": 2, "data": 4, "model": 8}


def test_resolve_spec_drops_non_divisible_axis():
    spec = resolve_spec((6, 64), ("vocab", "ffn"), RULES, SIZES)
    assert spec == jax.sharding.PartitionSpec(None, "model")  # 6 % 8 != 0


def test_resolve_spec_partial_batch_prefix():
    # batch → (pod, data): batch=2 divides pod(2) but not pod*data(8)
    spec = resolve_spec((2, 16), ("batch", None), RULES, SIZES)
    assert spec == jax.sharding.PartitionSpec("pod", None)
    # batch=1: nothing divides → fully replicated
    spec = resolve_spec((1, 16), ("batch", None), RULES, SIZES)
    assert spec == jax.sharding.PartitionSpec(None, None)


def test_resolve_spec_full_batch_tuple():
    spec = resolve_spec((16, 4), ("batch", None), RULES, SIZES)
    assert spec == jax.sharding.PartitionSpec(("pod", "data"), None)


def test_resolve_spec_dedups_mesh_axis_across_dims():
    # both dims map to "model"; the second use must be dropped
    rules = AxisRules({"ffn": "model", "vocab": "model"})
    spec = resolve_spec((64, 64), ("ffn", "vocab"), rules, SIZES)
    assert spec == jax.sharding.PartitionSpec("model", None)


def test_resolve_spec_rank_mismatch_pads_with_none():
    spec = resolve_spec((8, 8, 8), ("batch",), RULES, SIZES)
    assert len(spec) == 3


# ---------------------------------------------------------------------------
# arch_rules + shardings on the 1-device CPU mesh
# ---------------------------------------------------------------------------


def test_arch_rules_all_archs_all_steps_resolve():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    from repro.configs.archs import ASSIGNED

    for arch in ASSIGNED:
        cfg = get_arch(arch)
        for step in ("train", "prefill", "decode"):
            rules = arch_rules(cfg, mesh, step=step, global_batch=4)
            # 1-device mesh: every mapping degrades to replication
            assert all(v is None for v in rules.rules.values()), (arch, step)


def test_param_shardings_ragged_pytree():
    mesh = jax.make_mesh((1,), ("data",))
    specs = {
        "w": PSpec((8, 16), ("embed", "ffn")),
        "layers": [
            {"a": PSpec((4,), ("embed",))},
            {"a": PSpec((4, 4, 4), ("layers", "embed", "ffn"))},
        ],
    }
    sh = param_shardings(mesh, specs, RULES)
    leaves = jax.tree.leaves(sh)
    assert len(leaves) == 3
    assert all(isinstance(s, jax.sharding.NamedSharding) for s in leaves)


def test_replicated_and_batch_shardings_scalars():
    mesh = jax.make_mesh((1,), ("data",))
    assert replicated(mesh).spec == jax.sharding.PartitionSpec()
    sh = batch_shardings(
        mesh,
        {"tokens": jax.ShapeDtypeStruct((4, 8), jnp.int32),
         "position": jax.ShapeDtypeStruct((), jnp.int32)},
        RULES,
    )
    assert sh["position"].spec == jax.sharding.PartitionSpec()


def test_tree_shardings_on_ragged_cache_tree():
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    cfg = get_arch("qwen2.5-3b").reduced()
    model = build_model(cfg)
    cspec = model.cache_specs(4, 32)
    axes = cache_axes(cfg, cspec)
    assert jax.tree.structure(axes, is_leaf=lambda x: isinstance(x, tuple)) \
        .num_leaves == jax.tree.structure(cspec).num_leaves
    sh = tree_shardings(mesh, cspec, axes, arch_rules(cfg, mesh, step="decode"))
    assert len(jax.tree.leaves(sh)) == len(jax.tree.leaves(cspec))


def test_cache_axes_positions():
    cfg = get_arch("qwen2.5-3b").reduced()
    model = build_model(cfg)
    cspec = model.cache_specs(2, 16)
    axes = cache_axes(cfg, cspec)
    flat = jax.tree.leaves(axes, is_leaf=lambda x: isinstance(x, tuple))
    # every attn cache leaf is (batch, cache_seq, kv_heads, None)
    assert all(a == ("batch", "cache_seq", "kv_heads", None) for a in flat)
    # stacked (layer-leading) layout gets a 'layers' prefix
    stacked = {"k": jax.ShapeDtypeStruct((3, 2, 16, 2, 32), jnp.float32)}
    (a,) = jax.tree.leaves(
        cache_axes(cfg, stacked), is_leaf=lambda x: isinstance(x, tuple)
    )
    assert a == ("layers", "batch", "cache_seq", "kv_heads", None)


# ---------------------------------------------------------------------------
# Fault tolerance edge cases
# ---------------------------------------------------------------------------


def test_fault_injector_fires_once_per_step():
    f = FaultInjector(fail_at={2, 5})
    f.maybe_fail(0)
    with pytest.raises(RuntimeError):
        f.maybe_fail(2)
    f.maybe_fail(2)          # replay after restore: no re-fire
    with pytest.raises(RuntimeError):
        f.maybe_fail(5)
    assert f.fired == [2, 5]


def test_straggler_exact_factor_boundary_not_flagged():
    det = StragglerDetector(n_hosts=2, factor=2.0)
    for step in range(3):
        det.report(0, step, now=float(step))          # 1.0 s/step
        det.report(1, step, now=float(step) * 2.0)    # 2.0 s/step == factor×med
    assert det.stragglers() == []                     # strictly greater only


def test_straggler_single_host_and_insufficient_reports():
    det = StragglerDetector(n_hosts=1)
    det.report(0, 0, now=0.0)
    assert det.stragglers() == []
    det.report(0, 1, now=100.0)
    assert det.stragglers() == []                     # no peer to compare


def test_dead_host_relative_to_freshest_report():
    det = StragglerDetector(n_hosts=3, timeout=5.0)
    det.report(0, 0, now=0.0)
    det.report(1, 0, now=0.0)
    det.report(2, 0, now=0.0)
    for step in range(1, 4):
        det.report(0, step, now=step * 10.0)
        det.report(1, step, now=step * 10.0)
    assert det.dead() == [2]                          # silent for 30 s
    assert det.dead(now=4.0) == []                    # injected clock wins
    assert det.stragglers() == []                     # slow ≠ dead


def test_straggler_detector_uses_injectable_clock():
    clk = ManualClock()
    obs_clock.set_source(clk)
    try:
        det = StragglerDetector(n_hosts=3, factor=2.0, timeout=10.0)
        # hosts 0/1 report every second; host 2 manages two reports 5s
        # apart — a 5x step time, flagged against the 1.0s median
        for i in range(6):
            det.report(0, i)
            det.report(1, i)
            if i in (0, 5):
                det.report(2, i)
            clk.advance(1.0)
        assert det.stragglers() == [2]
        clk.advance(20.0)                 # host 2 goes silent past timeout
        det.report(0, 6)
        det.report(1, 6)
        assert det.dead(now=obs_clock.monotonic()) == [2]
        det.forget(2)
        assert det.dead(now=obs_clock.monotonic()) == []
        assert det.stragglers() == []     # history gone with the cube
    finally:
        obs_clock.reset_source()


def test_straggler_detector_explicit_clock_override():
    t = {"now": 100.0}
    det = StragglerDetector(n_hosts=2, timeout=5.0, clock=lambda: t["now"])
    det.report(0, 1)
    t["now"] = 112.0
    det.report(1, 1)
    assert det.dead(now=t["now"]) == [0]
