"""Weights from the seed, the same for the program and the reference.

Every leaf of a family's published layout (``reference/<family>.py``) is
drawn from a key folded from the seed, the leaf's index and the layer, so
one layer can be drawn again alone: the program gets all layers stacked in
one jitted call on the device, in the types they are served in, and the
reference draws them layer by layer after the program's state is gone.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np


@dataclass(frozen=True)
class Leaf:
    shape: tuple[int, ...]
    dtype: str                                  # the type it is served in
    init: Callable                              # (key, shape) -> float32


def normal(std: float, mean: float = 0.0) -> Callable:
    return lambda k, s: mean + std * jax.random.normal(k, s, jnp.float32)


def ones_plus(std: float) -> Callable:
    return normal(std, 1.0)


def seed_bits(seed: int) -> jax.Array:
    """Key data for any whole-number seed (wider than 32 bits too)."""
    s = np.random.SeedSequence(int(seed)).generate_state(2, dtype=np.uint32)
    return jnp.asarray(s, jnp.uint32)


_GLOBAL = 1 << 20        # leaf-index offset of the leaves outside the layers


def _draw(bits, index: int, layer, leaf: Leaf):
    key = jax.random.wrap_key_data(bits, impl="threefry2x32")
    key = jax.random.fold_in(jax.random.fold_in(key, index), layer)
    return leaf.init(key, leaf.shape).astype(leaf.dtype)


def layer_weights(ref, cfg: dict, bits, layer):
    return {name: _draw(bits, i, layer, leaf)
            for i, (name, leaf) in enumerate(ref.layer_leaves(cfg).items())}


def global_weights(ref, cfg: dict, bits):
    return {name: _draw(bits, _GLOBAL + i, 0, leaf)
            for i, (name, leaf) in enumerate(ref.global_leaves(cfg).items())}


def stacked_weights(ref, cfg: dict, bits):
    layers = jnp.arange(ref.n_layers(cfg))
    return jax.vmap(lambda l: layer_weights(ref, cfg, bits, l))(layers)
