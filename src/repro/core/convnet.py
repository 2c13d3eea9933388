"""JAX execution of the paper's tiled ConvNets (layer-by-layer, §IV).

``ConvNetExecutor`` runs a ``zoo`` layer list exactly the way the paper's
NeuroCluster does: layer-by-layer, each layer as a 4D-tiled streaming
computation.  Three interchangeable conv implementations:

  * ``impl="xla"``     — ``lax.conv_general_dilated`` (fast path on CPU/TPU,
                         used for training examples and smoke tests)
  * ``impl="pallas"``  — the ``kernels/stream_mac_conv`` Pallas kernel
                         (TPU target; ``interpret=True`` on CPU)
  * ``impl="tiled"``   — explicit 4D-tile schedule in pure JAX
                         (``lax.fori_loop`` over T_Ci partial accumulation —
                         a readable executable model of §IV-A)

All paths are verified against each other in tests.
"""
from __future__ import annotations

import math
from collections.abc import Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .tiling import ConvLayerSpec, Tile4D

Params = dict[str, dict[str, jax.Array]]


def init_params(
    layers: Sequence[ConvLayerSpec], key: jax.Array, dtype=jnp.float32
) -> Params:
    params: Params = {}
    for l in layers:
        if l.kind == "pool":
            continue
        key, wk = jax.random.split(key)
        fan_in = l.kx * l.ky * l.ci
        w = (jax.random.normal(wk, (l.kx, l.ky, l.ci, l.co), jnp.float32)
             * np.sqrt(2.0 / fan_in)).astype(dtype)
        b = jnp.zeros((l.co,), dtype)
        params[l.name] = {"w": w, "b": b}
    return params


def _conv_xla(x: jax.Array, w: jax.Array, l: ConvLayerSpec) -> jax.Array:
    return jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=(l.sy, l.sx),
        padding=((l.py, l.py), (l.px, l.px)),
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
    )


def _conv_tiled(x: jax.Array, w: jax.Array, l: ConvLayerSpec, tile: Tile4D) -> jax.Array:
    """Executable model of the 4D-tile schedule: T_Ci-partial accumulation
    (paper Fig 3d: D += A*K_AD for each input tile A)."""
    xp = jnp.pad(x, ((0, 0), (l.py, l.py), (l.px, l.px), (0, 0)))
    n_ci = math.ceil(l.ci / tile.tci)
    out_shape = (x.shape[0], l.yo, l.xo, l.co)

    def body(i, acc):
        lo = i * tile.tci
        xs = jax.lax.dynamic_slice_in_dim(xp, lo, tile.tci, axis=3)
        ws = jax.lax.dynamic_slice_in_dim(w, lo, tile.tci, axis=2)
        part = jax.lax.conv_general_dilated(
            xs, ws, (l.sy, l.sx), "VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        return acc + part

    if l.ci % tile.tci == 0 and n_ci > 1:
        acc = jnp.zeros(out_shape, x.dtype)
        return jax.lax.fori_loop(0, n_ci, body, acc)
    return jax.lax.conv_general_dilated(
        xp, w, (l.sy, l.sx), "VALID", dimension_numbers=("NHWC", "HWIO", "NHWC")
    )


def _maxpool(x: jax.Array, l: ConvLayerSpec) -> jax.Array:
    return jax.lax.reduce_window(
        x,
        -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min,
        jax.lax.max,
        (1, l.ky, l.kx, 1),
        (1, l.sy, l.sx, 1),
        ((0, 0), (l.py, l.py), (l.px, l.px), (0, 0)),
    )


class ConvNetExecutor:
    """Layer-by-layer tiled ConvNet forward/loss (the paper's §IV pipeline)."""

    def __init__(
        self,
        layers: Sequence[ConvLayerSpec],
        impl: str = "xla",
        tiles: dict[str, Tile4D] | None = None,
    ):
        self.layers = list(layers)
        self.impl = impl
        self.tiles = tiles or {}

    def init(self, key: jax.Array, dtype=jnp.float32) -> Params:
        return init_params(self.layers, key, dtype)

    def apply(self, params: Params, x: jax.Array) -> jax.Array:
        """x: NHWC input volume."""
        from repro.kernels import ops as kops

        for l in self.layers:
            if l.kind == "pool":
                if l.kx >= x.shape[1] and l.sx == 1:   # global avg pool
                    x = jnp.mean(x, axis=(1, 2), keepdims=True)
                else:
                    x = _maxpool(x, l)
                continue
            w, b = params[l.name]["w"], params[l.name]["b"]
            if l.kind == "fc" and x.ndim == 4 and l.kx == x.shape[1]:
                x = x.reshape(x.shape[0], 1, 1, -1)
                w = w.reshape(1, 1, -1, l.co)
                x = jnp.einsum("nhwc,hwco->nhwo", x, w.reshape(1, 1, -1, l.co)) + b
            else:
                if self.impl == "pallas":
                    x = kops.stream_mac_conv(
                        x, w, stride=(l.sy, l.sx), padding=(l.py, l.px)
                    ) + b
                elif self.impl == "tiled" and l.name in self.tiles:
                    x = _conv_tiled(x, w, l, self.tiles[l.name]) + b
                else:
                    x = _conv_xla(x, w, l) + b
            if l.act:
                x = jax.nn.relu(x)
        return x.reshape(x.shape[0], -1)

    def loss_fn(self, params: Params, x: jax.Array, labels: jax.Array) -> jax.Array:
        logits = self.apply(params, x)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, labels[:, None], axis=1))

    def flops_per_example(self) -> int:
        return sum(l.flops for l in self.layers if l.kind != "pool")


def make_small_convnet(
    num_classes: int = 10, width: int = 16, input_px: int = 32
) -> list[ConvLayerSpec]:
    """A reduced ConvNet of the paper's family for CPU training examples."""
    c = width
    L = [
        ConvLayerSpec("conv1", input_px, input_px, 3, c, 3, 3, 1, 1, 1, 1),
        ConvLayerSpec("conv2", input_px, input_px, c, c, 3, 3, 1, 1, 1, 1),
        ConvLayerSpec("pool1", input_px, input_px, c, c, 2, 2, 2, 2, 0, 0, "pool", False),
        ConvLayerSpec("conv3", input_px // 2, input_px // 2, c, 2 * c, 3, 3, 1, 1, 1, 1),
        ConvLayerSpec("pool2", input_px // 2, input_px // 2, 2 * c, 2 * c, 2, 2, 2, 2, 0, 0, "pool", False),
        ConvLayerSpec("conv4", input_px // 4, input_px // 4, 2 * c, 2 * c, 3, 3, 1, 1, 1, 1),
        ConvLayerSpec(
            "pool3", input_px // 4, input_px // 4, 2 * c, 2 * c,
            input_px // 4, input_px // 4, 1, 1, 0, 0, "pool", False,
        ),
        ConvLayerSpec("fc", 1, 1, 2 * c, num_classes, 1, 1, 1, 1, 0, 0, "fc", False),
    ]
    return L
