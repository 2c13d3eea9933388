"""Logical→mesh sharding-rule resolution with divisibility fallbacks.

Every parameter/activation/cache tensor in the model stack carries *logical*
axis names (``PSpec.axes``, ``constrain`` calls, ``cache_axes``).  This module
maps those names onto the axes of a concrete device mesh and materializes
``NamedSharding`` pytrees for ``jax.jit`` in/out shardings.

Resolution is defensive at two levels:

* **rule level** (``arch_rules``): mesh axes that do not exist on the given
  mesh are dropped, and logical axes whose *global* dimension (known from the
  ArchConfig — heads, ffn, experts, vocab, batch, …) is not divisible by the
  mesh-axis product lose that mapping.
* **leaf level** (``resolve_spec``): every tensor dim re-checks divisibility
  against its own size and drops mesh axes already used by an earlier dim of
  the same tensor (a mesh axis may appear at most once per PartitionSpec).
  This is what lets e.g. Mamba's fused ``in_proj`` (odd last dim) replicate
  while ``out_proj`` shards, and makes everything degrade to replication on a
  1-device CPU mesh.
"""
from __future__ import annotations

from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec

from repro.models.common import (
    AxisRules,
    DEFAULT_RULES,
    KV_POOL_KEYS,
    PSpec,
    cache_leaf_key,
)


def _axis_sizes(mesh) -> dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _as_parts(value) -> tuple:
    if value is None:
        return ()
    if isinstance(value, (tuple, list)):
        return tuple(value)
    return (value,)


def _entry(keep: list):
    if not keep:
        return None
    return keep[0] if len(keep) == 1 else tuple(keep)


def _greedy_divisible(
    parts: tuple, dim: int, axis_sizes: dict[str, int], used: set
) -> list:
    """Mesh axes from ``parts`` whose cumulative product divides ``dim``,
    skipping missing/trivial axes and ones already ``used`` (the shared core
    of rule-level and leaf-level fallback — keep the two in lockstep)."""
    keep: list = []
    prod = 1
    for ax in parts:
        sz = axis_sizes.get(ax, 0)
        if sz <= 1 or ax in used or ax in keep:
            continue
        if dim % (prod * sz) != 0:
            continue
        keep.append(ax)
        prod *= sz
    return keep


def replicated(mesh) -> NamedSharding:
    """Fully-replicated sharding (scalars, counters, rng keys)."""
    return NamedSharding(mesh, PartitionSpec())


def resolve_spec(
    shape: tuple,
    axes: tuple,
    rules: AxisRules,
    axis_sizes: dict[str, int],
) -> PartitionSpec:
    """PartitionSpec for one tensor: per-dim greedy divisibility fallback.

    For each dim, walk the mesh axes the rule names and keep the prefix whose
    cumulative product divides the dim size; skip axes missing from the mesh,
    already used by an earlier dim, or trivial (size 1 — sharding over a
    1-slot axis IS replication, so we emit the cleaner ``None``).
    """
    axes = (tuple(axes) + (None,) * len(shape))[: len(shape)]
    used: set = set()
    entries = []
    for dim, name in zip(shape, axes):
        parts = _as_parts(rules.rules.get(name)) if name else ()
        keep = _greedy_divisible(parts, dim, axis_sizes, used)
        used.update(keep)
        entries.append(_entry(keep))
    return PartitionSpec(*entries)


# ---------------------------------------------------------------------------
# Rule table resolution
# ---------------------------------------------------------------------------


def arch_rules(
    cfg,
    mesh,
    step: str = "train",
    global_batch: int | None = None,
    overrides: dict | None = None,
) -> AxisRules:
    """Resolve the default logical→mesh table for one (arch, mesh, step) cell.

    Starts from ``DEFAULT_RULES``, drops mesh axes the mesh does not have,
    applies rule-level divisibility checks for the dims known globally from
    the config, and finally applies explicit ``overrides`` (the hillclimb
    knob).  On a 1-device mesh every mapping degrades to replication.
    """
    sizes = _axis_sizes(mesh)
    rules: dict[str, Any] = dict(DEFAULT_RULES)

    # logical dims whose global size the config pins down exactly
    dims: dict[str, int] = {
        "heads": cfg.n_heads * cfg.hd,
        "vocab": cfg.padded_vocab,
    }
    if cfg.d_ff:
        dims["ffn"] = cfg.d_ff
    if cfg.is_moe:
        dims["experts"] = cfg.n_experts
    if cfg.ssm is not None:
        dims["ssm_heads"] = cfg.ssm.n_heads(cfg.d_model)
        dims["lru"] = cfg.ssm.d_inner(cfg.d_model)
    if cfg.rglru is not None:
        dims["lru"] = cfg.rglru.lru_width
    if global_batch:
        dims["batch"] = global_batch

    for name, dim in dims.items():
        rules[name] = _entry(
            _greedy_divisible(_as_parts(rules.get(name)), dim, sizes, set())
        )

    # remaining rules: keep only axes this mesh actually has
    for name, value in rules.items():
        parts = tuple(
            ax for ax in _as_parts(value) if sizes.get(ax, 0) > 1
        )
        rules[name] = _entry(list(parts))

    if step == "train":
        # the train step builds no decode cache; neutralize the mapping so a
        # train table reused elsewhere can't shard a cache it never planned
        rules["cache_seq"] = None

    if overrides:
        rules.update(overrides)
    return AxisRules(rules)


# ---------------------------------------------------------------------------
# Pytree → NamedSharding trees
# ---------------------------------------------------------------------------


def param_shardings(mesh, specs, rules: AxisRules):
    """NamedSharding tree for a PSpec tree (per-leaf divisibility fallback)."""
    sizes = _axis_sizes(mesh)
    return jax.tree.map(
        lambda s: NamedSharding(mesh, resolve_spec(s.shape, s.axes, rules, sizes)),
        specs,
        is_leaf=lambda x: isinstance(x, PSpec),
    )


def batch_shardings(mesh, batch, rules: AxisRules):
    """Shard dim 0 of every batch leaf along the 'batch' rule; scalars and
    non-divisible batch dims replicate."""
    sizes = _axis_sizes(mesh)

    def leaf(x):
        shape = tuple(x.shape)
        axes = ("batch",) + (None,) * (len(shape) - 1) if shape else ()
        return NamedSharding(mesh, resolve_spec(shape, axes, rules, sizes))

    return jax.tree.map(leaf, batch)


def tree_shardings(mesh, tree, axes_tree, rules: AxisRules):
    """NamedSharding tree for an arbitrary ShapeDtypeStruct/array tree given a
    parallel tree of logical-axis tuples (e.g. from ``cache_axes``)."""
    sizes = _axis_sizes(mesh)
    return jax.tree.map(
        lambda x, ax: NamedSharding(
            mesh, resolve_spec(tuple(x.shape), tuple(ax), rules, sizes)
        ),
        tree,
        axes_tree,
    )


# ---------------------------------------------------------------------------
# Decode-cache logical axes
# ---------------------------------------------------------------------------

# per-leaf logical axes, keyed by the cache dict key each sub-layer emits
# (attention k/v, enc-dec cross k/v, MLA latent/k_rope, SSD state/conv,
# RG-LRU h/conv).  A leading 'layers' axis is inferred from rank when the
# cache is in stacked (lax.scan) rather than per-layer (unrolled) layout.
_CACHE_LEAF_AXES: dict[str, tuple] = {
    "k": ("batch", "cache_seq", "kv_heads", None),
    "v": ("batch", "cache_seq", "kv_heads", None),
    "ck": ("batch", "cache_seq", "kv_heads", None),
    "cv": ("batch", "cache_seq", "kv_heads", None),
    "latent": ("batch", "cache_seq", None),
    "k_rope": ("batch", "cache_seq", None),
    "state": ("batch", "ssm_heads", None, None),
    "conv": ("batch", None, "lru"),
    "h": ("batch", "lru"),
}


# paged-pool logical axes, derived from the same table: a seq-carrying leaf
# (batch, cache_seq, *tail) pools into (layers, pages, page_size, *tail) —
# the page axis takes the sharding role, within-page seq stays local.  A
# k/v page is stored head-major (``KV_POOL_KEYS``): (layers, pages,
# kv_heads, page_size, head_dim).
_PAGED_CACHE_LEAF_AXES: dict[str, tuple] = {
    name: (("layers", "pages", "kv_heads", None, None) if name in KV_POOL_KEYS
           else ("layers", "pages", None) + axes[axes.index("cache_seq") + 1:])
    for name, axes in _CACHE_LEAF_AXES.items()
    if "cache_seq" in axes
}


def paged_cache_axes(cfg, tree):
    """Logical-axis tree for a paged serving cache (``serve.paged_cache``
    layout): seq leaves are page pools (layers, n_pages, *page);
    recurrent-state leaves keep the per-lane (layers, lanes, *tail) layout
    with lanes as the batch axis."""

    def leaf_axes(path, x):
        ndim = len(x.shape)
        base = _PAGED_CACHE_LEAF_AXES.get(cache_leaf_key(path))
        if base is None:
            base = ("layers", "batch")
        return (tuple(base) + (None,) * ndim)[:ndim]

    return jax.tree_util.tree_map_with_path(leaf_axes, tree)


def host_cache_axes(tree):
    """All-``None`` logical axes for the host-DRAM swap tier
    (``serve.host_tier.HostPagePool`` buffers): the host only coordinates —
    its page copies are plain unsharded numpy, and a restored page is
    replicated wherever ``device_put`` stages it back."""
    return jax.tree.map(lambda x: (None,) * len(x.shape), tree)


def host_tier_shardings(mesh, tree):
    """Replicated ``NamedSharding`` tree for staging host-tier pages back
    onto a mesh (``PagedKVCache(host_shardings=...)``).  Host-tier leaves
    are never sharded: the swap link is host↔cube DMA, and the cube-serving
    rules keep page pools whole per cube anyway (see ``cube_rules``)."""
    return jax.tree.map(lambda _: replicated(mesh), tree)


def cube_rules(mesh) -> AxisRules:
    """The cube-serving rule table (the serve router's entry point): batch
    over (cube, data); weights, caches, and page pools replicated per cube —
    each SMC holds its own coefficients and KV pages (§VI-C)."""
    from repro.core.smc import cube_rules as _smc_cube_rules

    rules = dict(_smc_cube_rules(mesh).rules)
    rules["pages"] = None
    return AxisRules(rules)


def cache_axes(cfg, tree):
    """Tree of logical-axis tuples parallel to a decode-cache tree.

    Works on both cache layouts ``cache_specs`` can emit: per-layer lists
    (``decode_unroll_layers``) with batch-leading leaves, and stacked scans
    with a leading layers dim.  Unknown leaves fall back to batch-dim-0 only
    (safe: everything else replicates).
    """

    def leaf_axes(path, x):
        ndim = len(x.shape)
        base = _CACHE_LEAF_AXES.get(cache_leaf_key(path))
        if base is None:
            base = ("batch",) + (None,) * max(ndim - 1, 0)
        if ndim == len(base) + 1:
            base = ("layers",) + tuple(base)
        return (tuple(base) + (None,) * ndim)[:ndim]

    return jax.tree_util.tree_map_with_path(leaf_axes, tree)
