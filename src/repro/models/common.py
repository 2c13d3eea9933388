"""Shared model building blocks: param specs, norms, RoPE, activations,
logical-axis sharding constraints.

Single source of truth: every module declares its parameters as a pytree of
``PSpec`` (shape + logical axes + init).  From that one declaration we derive
(i) random initialization for smoke tests, (ii) ``jax.eval_shape`` trees for
the dry-run, and (iii) ``NamedSharding`` trees through a logical→mesh axis
rule table (``dist.sharding``).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

# ---------------------------------------------------------------------------
# Param specs
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class PSpec:
    """Declarative parameter: shape, per-dim logical axes, dtype, init."""

    shape: tuple[int, ...]
    axes: tuple[str | None, ...]
    dtype: Any = jnp.float32
    init: str = "normal"        # normal | zeros | ones | lecun
    scale: float | None = None  # stddev override

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _init_leaf(spec: PSpec, key: jax.Array) -> jax.Array:
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, spec.dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, spec.dtype)
    fan_in = spec.shape[-2] if len(spec.shape) >= 2 else spec.shape[-1]
    std = spec.scale if spec.scale is not None else float(fan_in) ** -0.5
    return (jax.random.normal(key, spec.shape, jnp.float32) * std).astype(spec.dtype)


def init_params(specs, key: jax.Array):
    """Initialize a PSpec pytree deterministically (key folded by path)."""
    leaves, treedef = jax.tree.flatten(
        specs, is_leaf=lambda x: isinstance(x, PSpec)
    )
    out = []
    for i, leaf in enumerate(leaves):
        out.append(_init_leaf(leaf, jax.random.fold_in(key, i)))
    return jax.tree.unflatten(treedef, out)


def abstract_params(specs):
    """ShapeDtypeStruct tree for lowering without allocation."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
        specs,
        is_leaf=lambda x: isinstance(x, PSpec),
    )


def param_count(specs) -> int:
    return sum(
        int(np.prod(s.shape))
        for s in jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, PSpec))
    )


# ---------------------------------------------------------------------------
# Logical-axis sharding constraints on activations
# ---------------------------------------------------------------------------


class AxisRules:
    """Maps logical axis names to mesh axes. The hillclimb knob."""

    def __init__(self, rules: dict[str, Any]):
        self.rules = dict(rules)

    def spec(self, *axes: str | None) -> jax.sharding.PartitionSpec:
        return jax.sharding.PartitionSpec(
            *[self.rules.get(a) if a else None for a in axes]
        )

    def replace(self, **kw) -> "AxisRules":
        r = dict(self.rules)
        r.update(kw)
        return AxisRules(r)


# default logical→mesh mapping (production mesh axes: pod, data, model)
DEFAULT_RULES = {
    "batch": ("pod", "data"),
    "seq": None,
    "embed": None,
    "heads": "model",          # q heads (only used when divisible)
    "kv_heads": None,          # replicated by default (small)
    "ffn": "model",
    "experts": "model",
    "vocab": "model",
    "cache_seq": "model",      # decode KV cache sequence sharding
    "lru": "model",
    "ssm_heads": "model",
    "layers": None,
    "pages": None,             # paged-KV pool page axis (per-cube pools)
}

# decode-cache leaf keys whose dim after batch is the cache sequence — these
# are the leaves the paged serving cache splits into fixed-size pages
# (attention k/v, enc-dec cross k/v, MLA latent + shared rotary key).
# Recurrent state leaves (ssm "state"/"conv", rglru "h"/"conv") have no seq
# dim and stay densely per-lane.  Keep in lockstep with
# ``dist.sharding._CACHE_LEAF_AXES``.
SEQ_CACHE_KEYS = ("k", "v", "ck", "cv", "latent", "k_rope")

# seq leaves whose pool pages are stored head-major, (Hkv, PS, Dp): one
# head's page is one contiguous (PS, Dp) block, the unit the paged decode
# kernel copies from the pool where it lies, and Dp is the head dim rounded
# up to the TPU's 128 lanes (Mosaic copies no narrower page).  The other
# seq leaves (MLA latent/k_rope) store pages token-major, (PS, *t), like
# the dense cache.
KV_POOL_KEYS = ("k", "v")


def kv_lanes(head_dim: int) -> int:
    """Stored width of a k/v pool page row: the head dim padded to 128."""
    return head_dim + (-head_dim) % 128


def kv_page_store(x: jax.Array) -> jax.Array:
    """Token-major k/v pages (..., PS, Hkv, D) → the pool's stored order
    (..., Hkv, PS, Dp)."""
    x = jnp.swapaxes(x, -3, -2)
    d = x.shape[-1]
    if kv_lanes(d) == d:      # no pad op: eager callers compile one per shape
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, kv_lanes(d) - d)])


def kv_page_load(x: jax.Array, head_dim: int) -> jax.Array:
    """Stored k/v pages (..., Hkv, PS, Dp) → token-major (..., PS, Hkv,
    head_dim)."""
    return jnp.swapaxes(x[..., :head_dim], -3, -2)


def cache_leaf_key(path) -> str | None:
    """Innermost string dict key of a tree_map_with_path leaf path — the
    cache-leaf name the tables above key on."""
    for entry in reversed(path):
        key = getattr(entry, "key", None)
        if isinstance(key, str):
            return key
    return None


def constrain(x: jax.Array, rules: AxisRules, *axes: str | None) -> jax.Array:
    """with_sharding_constraint by logical axes — no-op outside a mesh."""
    try:
        mesh = jax.sharding.get_abstract_mesh()
        if mesh is None or mesh.empty:
            return x
        names = set(mesh.axis_names)
    except Exception:
        return x
    spec = []
    used: set = set()
    for a in axes:
        r = rules.rules.get(a) if a else None
        if r is None:
            spec.append(None)
            continue
        parts = r if isinstance(r, tuple) else (r,)
        parts = tuple(p for p in parts if p in names and p not in used)
        used.update(parts)
        spec.append(parts if len(parts) > 1 else (parts[0] if parts else None))
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*spec)
    )


# ---------------------------------------------------------------------------
# Numerics
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6, plus_one: bool = False) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    w = w.astype(jnp.float32)
    return (x * ((1.0 + w) if plus_one else w)).astype(dt)


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array, eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


def rope(x: jax.Array, positions: jax.Array, theta: float = 10000.0) -> jax.Array:
    """Rotary embedding.  x: (..., S, H, D) or (..., S, D); positions: (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs      # (..., S, half)
    if x.ndim == ang.ndim + 1:                                   # heads dim present
        ang = ang[..., None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def activation(name: str):
    return {
        "silu": jax.nn.silu,
        "gelu": lambda x: jax.nn.gelu(x, approximate=True),
        "gelu_exact": lambda x: jax.nn.gelu(x, approximate=False),
        "relu": jax.nn.relu,
    }[name]


def sinusoidal_positions(n: int, d: int) -> jax.Array:
    """Whisper-style sinusoidal embeddings (n, d)."""
    half = d // 2
    log_timescale = np.log(10000.0) / max(half - 1, 1)
    inv = np.exp(-log_timescale * np.arange(half))
    scaled = np.arange(n)[:, None] * inv[None, :]
    return jnp.asarray(
        np.concatenate([np.sin(scaled), np.cos(scaled)], axis=1), jnp.float32
    )


def stack_specs(spec_fn, n: int):
    """Stack a per-layer PSpec tree along a new leading 'layers' axis."""
    one = spec_fn()
    return jax.tree.map(
        lambda s: PSpec(
            (n,) + s.shape, ("layers",) + s.axes, s.dtype, s.init, s.scale
        ),
        one,
        is_leaf=lambda x: isinstance(x, PSpec),
    )
