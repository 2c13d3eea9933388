"""The chip: refuse anything but a known accelerator, and read its peaks."""
from __future__ import annotations

from chipbench.harness.registry import BENCH, load_json


class NoChip(RuntimeError):
    """No accelerator, too few of them, or one the peaks table lacks."""


def peaks(kind: str) -> dict:
    table = load_json(BENCH / "peaks.json")
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in chipbench/peaks.json "
                     f"(known: {sorted(table)})")
    return table[kind]


def require(jax, chips: int) -> dict:
    """Device facts of this run; raises NoChip on a CPU, on fewer devices
    than the cell asks for, or on a kind without peaks."""
    devs = jax.devices()
    d0 = devs[0]
    if d0.platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {d0.platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX has {len(devs)}")
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devs), "peaks": peaks(d0.device_kind)}


def memory_peak_bytes(jax) -> int | None:
    """Peak bytes in use on the fullest device, where the backend says."""
    best = None
    for d in jax.devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            best = max(best or 0, int(stats["peak_bytes_in_use"]))
    return best
