"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (importing this module never touches
jax device state).  Single-pod: (data=16, model=16) = 256 chips; multi-pod:
(pod=2, data=16, model=16) = 512 chips.  The ``pod`` axis carries only
batch-parallel traffic (the paper's SMC-network axis — each pod ≙ one SMC
working on independent inputs, coefficients replicated per pod, links
duty-cycled).
"""
from __future__ import annotations

import jax


def _auto_mesh(shape, axes):
    # Auto axes: the models' logical-axis constraints are sharding hints the
    # compiler may propagate, not the asserts they become on Explicit axes
    # (``jax.make_mesh``'s default)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1):
    """Mesh over whatever devices exist (CPU tests: usually 1)."""
    n = len(jax.devices())
    model = min(model, n)
    return _auto_mesh((n // model, model), ("data", "model"))


def describe(mesh) -> str:
    return "x".join(
        f"{a}={s}" for a, s in zip(mesh.axis_names, mesh.devices.shape)
    )
