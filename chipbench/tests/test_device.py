"""The harness refuses a CPU, too few chips, and a device kind it has no
peaks for."""
from types import SimpleNamespace

import pytest

from chipbench.harness.device import NoChip, peaks, require


def _jax(platform, kind, n):
    dev = SimpleNamespace(platform=platform, device_kind=kind)
    return SimpleNamespace(devices=lambda: [dev] * n)


def test_known_kind():
    p = peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    got = require(_jax("tpu", "TPU v5 lite", 4), 4)
    assert got["count"] == 4 and got["kind"] == "TPU v5 lite"


def test_unknown_kind_raises():
    with pytest.raises(NoChip, match="not in chipbench/peaks.json"):
        require(_jax("tpu", "TPU v9 imaginary", 1), 1)


def test_cpu_and_too_few_chips_raise():
    with pytest.raises(NoChip, match="needs a TPU"):
        require(_jax("cpu", "cpu", 1), 1)
    with pytest.raises(NoChip, match="asks for 4 chips"):
        require(_jax("tpu", "TPU v5 lite", 1), 4)
