"""Canonical cutouts: the concrete kernel invocations the committed
``TUNED_kernels.json`` is tuned on.

These builders are the single source of truth for the gated bench shapes —
``benchmarks/kernel_bench.py`` builds its inputs through them, so the
shape-class key the bench resolves at trace time cannot drift from the key
``python -m repro.tune --update`` tuned (a drift would silently fall back
to defaults and flatten the ``*.tuned_ratio`` gates to 1.0).

Each spec has a ``build`` (the gated bench shape) and optionally a
``smoke`` (a tiny shape class CI tunes fresh in seconds —
``python -m repro.tune --smoke``).  Importing this module imports the
kernel modules, which populates ``repro.tune.REGISTRY`` as a side effect
of their ``@tunable`` decorators.
"""
from __future__ import annotations

from collections.abc import Callable
from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np

from repro.configs.base import SSMConfig
from repro.models.common import kv_lanes


class SsdBenchCfg:
    """Static cfg carrier for the SSD cutout (mirrors kernel_bench's)."""

    ssm = SSMConfig(d_state=64, d_conv=4, expand=2, head_dim=64, chunk=64)


class SsdSmokeCfg:
    ssm = SSMConfig(d_state=16, d_conv=4, expand=2, head_dim=16, chunk=16)


@dataclass(frozen=True)
class CutoutSpec:
    kernel: str
    build: Callable[[np.random.Generator], tuple]
    smoke: Callable[[np.random.Generator], tuple] | None = None


def _flash_build(rng: np.random.Generator) -> tuple:
    # prefill-shaped self-attention, the kernel_bench gated shape
    b, s, h, d = 1, 512, 8, 64
    q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
    return (q, q, q)


def _paged_build(rng: np.random.Generator) -> tuple:
    # one layer of stacked k/v pools as the serving cache stores them:
    # (1, n_pages, Hkv, PS, Dp), head dim 64 padded to 128 lanes
    n_pages, ps, hkv, lanes, p, h, d = 128, 16, 2, 8, 16, 8, 64
    shape = (1, n_pages, hkv, ps, kv_lanes(d))
    pad = np.arange(kv_lanes(d)) < d
    kpool = jnp.asarray(rng.normal(size=shape) * pad, jnp.float32)
    vpool = jnp.asarray(rng.normal(size=shape) * pad, jnp.float32)
    bt = jnp.asarray(
        rng.permutation(n_pages)[: lanes * p].reshape(lanes, p), jnp.int32
    )
    pos = jnp.asarray(rng.integers(1, p * ps - 1, size=(lanes,)), jnp.int32)
    qd = jnp.asarray(rng.normal(size=(lanes, 1, h, d)), jnp.float32)
    return (qd, kpool, vpool, bt, pos, 0)


def _ssd_args(rng: np.random.Generator, cfg, hs: int, ps_: int, ns: int,
              ss: int) -> tuple:
    xh = jnp.asarray(rng.normal(size=(1, ss, hs, ps_)), jnp.float32)
    bb = jnp.asarray(rng.normal(size=(1, ss, ns)), jnp.float32)
    cc = jnp.asarray(rng.normal(size=(1, ss, ns)), jnp.float32)
    dt = jnp.asarray(rng.normal(size=(1, ss, hs)), jnp.float32)
    a_log = jnp.asarray(rng.normal(size=(hs,)), jnp.float32)
    d_skip = jnp.asarray(rng.normal(size=(hs,)), jnp.float32)
    dt_bias = jnp.asarray(rng.normal(size=(hs,)), jnp.float32)
    return (cfg, xh, bb, cc, dt, a_log, d_skip, dt_bias)


def _ssd_build(rng: np.random.Generator) -> tuple:
    return _ssd_args(rng, SsdBenchCfg, hs=8, ps_=64, ns=64, ss=256)


def _ssd_smoke(rng: np.random.Generator) -> tuple:
    return _ssd_args(rng, SsdSmokeCfg, hs=2, ps_=16, ns=16, ss=64)


def _moe_build(rng: np.random.Generator) -> tuple:
    # the dispatched capacity slabs of the kernel_bench MoE shape
    # (g, t, e, c, d, f) = (1, 512, 8, 128, 128, 256); w_up=None mirrors
    # the bench's gate-only expert FFN
    g, e, c, d, f = 1, 8, 128, 128, 256
    xe = jnp.asarray(rng.normal(size=(g, e, c, d)), jnp.float32)
    wg = jnp.asarray(rng.normal(size=(e, d, f)) * d ** -0.5, jnp.float32)
    wd = jnp.asarray(rng.normal(size=(e, f, d)) * f ** -0.5, jnp.float32)
    return (xe, wg, None, wd)


def _flash_pallas_build(rng: np.random.Generator) -> tuple:
    # (B, H, S, D) layout of the Pallas kernel wrapper (TPU-only space)
    b, h, s, d = 1, 8, 512, 64
    q = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
    return (q, q, q)


CUTOUTS: dict[str, CutoutSpec] = {
    "attn.flash_xla": CutoutSpec("attn.flash_xla", _flash_build),
    "attn.paged_decode": CutoutSpec("attn.paged_decode", _paged_build),
    "ssd.chunked": CutoutSpec("ssd.chunked", _ssd_build, smoke=_ssd_smoke),
    "moe.dispatch": CutoutSpec("moe.dispatch", _moe_build),
    "attn.flash_pallas": CutoutSpec("attn.flash_pallas", _flash_pallas_build),
}


def build(name: str, seed: int = 0, smoke: bool = False) -> tuple:
    """Concrete args for a canonical cutout (fresh rng per call — builders
    must stay deterministic in ``seed`` for cross-process key stability)."""
    spec = CUTOUTS[name]
    fn = spec.smoke if smoke else spec.build
    if fn is None:
        raise KeyError(f"{name} has no smoke cutout")
    return fn(np.random.default_rng(seed))


# populate REGISTRY: the @tunable decorators run at import of the kernel
# modules (kept at the bottom — the builders above must not depend on them)
from repro.kernels import ops as _ops            # noqa: E402,F401
from repro.models import attention as _attn      # noqa: E402,F401
from repro.models import moe as _moe             # noqa: E402,F401
from repro.models import ssm as _ssm             # noqa: E402,F401
