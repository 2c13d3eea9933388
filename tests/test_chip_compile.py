"""Compile-only checks of the main-path Pallas kernels for a TPU v5e chip.

The TPU compiler is installed even where no chip is attached: it compiles
for a *described* ``v5e:2x2`` topology, refusing what the chip's compiler
would refuse (unaligned blocks, unsupported primitives, VMEM overflow).
Nothing runs.  Widths are the served ones — qwen2.5-3b and granite-4.0-h-micro
attention, a granite SSD prefill chunk, and VGG16 convolutions at 224 px.  The topology is described only inside the fixture
below, never at import: one process at a time may load the TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

BF16, F32 = jnp.bfloat16, jnp.float32

# qwen2.5-3b decode: 16 query heads over 2 KV heads of 128, 16-token pages,
# 8 lanes of 1024 tokens
H, HKV, D, PS, LANES, MAX_LEN = 16, 2, 128, 16, 8, 1024
N_PAGES = LANES * MAX_LEN // PS


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def test_paged_attention_compiles(one_chip):
    hlo = _compile(
        lambda q, k, v, bt, ln: ops.paged_attention(q, k, v, bt, ln,
                                                    interpret=False),
        one_chip,
        ((LANES, H, D), BF16), ((N_PAGES, PS, HKV, D), BF16),
        ((N_PAGES, PS, HKV, D), BF16), ((LANES, MAX_LEN // PS), jnp.int32),
        ((LANES,), jnp.int32),
    )
    assert "tpu_custom_call" in hlo
    # the benchmark's kernel reading finds the kernel's events by this name
    assert re.search(r"%paged_attention(\.\d+)? = \S+ custom-call\(.*"
                     r'custom_call_target="tpu_custom_call"', hlo)


# granite-4.0-h-micro: 32 query heads over 8 KV heads of 64 (4 queries a KV
# head; the wrapper pads them to 8 rows and 128 lanes), NoPE scale 1/64
G_H, G_HKV, G_D = 32, 8, 64


def test_paged_attention_compiles_head_dim_64(one_chip):
    hlo = _compile(
        lambda q, k, v, bt, ln: ops.paged_attention(q, k, v, bt, ln,
                                                    scale=1 / 64,
                                                    interpret=False),
        one_chip,
        ((LANES, G_H, G_D), BF16), ((N_PAGES, PS, G_HKV, G_D), BF16),
        ((N_PAGES, PS, G_HKV, G_D), BF16),
        ((LANES, MAX_LEN // PS), jnp.int32), ((LANES,), jnp.int32),
    )
    assert re.search(r"%paged_attention(\.\d+)? = \S+ custom-call\(.*"
                     r'custom_call_target="tpu_custom_call"', hlo)


def test_ssd_prefill_chunk_compiles_granite_widths(one_chip):
    """One 256-token SSD chunk of a granite mamba layer: 64 heads of 64,
    state 128, the (Q, Q, H) decay formed exactly and every contraction at
    HIGHEST precision."""
    from repro.configs.base import SSMConfig
    from repro.models.ssm import ssd_chunked

    class Cfg:
        ssm = SSMConfig(d_state=128, d_conv=4, expand=2, head_dim=64,
                        chunk=256)

    q, h, p, n = 256, 64, 64, 128
    hlo = _compile(
        lambda x, b, c, dt, a, d, bias, h0: ssd_chunked(
            Cfg, x, b, c, dt, a, d, bias, h0),
        one_chip,
        ((1, q, h, p), BF16), ((1, q, n), BF16), ((1, q, n), BF16),
        ((1, q, h), BF16), ((h,), F32), ((h,), F32), ((h,), F32),
        ((1, h, p, n), F32),
    )
    assert "dot" in hlo or "convolution" in hlo


def test_flash_attention_compiles(one_chip):
    hlo = _compile(
        lambda q, k, v: ops.flash_attention(q, k, v, interpret=False),
        one_chip,
        ((1, H, 2048, D), BF16), ((1, HKV, 2048, D), BF16),
        ((1, HKV, 2048, D), BF16),
    )
    assert "tpu_custom_call" in hlo


def test_paged_gather_compiles(one_chip):
    hlo = _compile(
        lambda pool, bt: ops.paged_gather(pool, bt, interpret=False),
        one_chip,
        ((N_PAGES, PS, HKV, D), BF16), ((LANES, MAX_LEN // PS), jnp.int32),
    )
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("hw,ci,co", [
    (224, 3, 64),        # VGG16 conv0: Ci=3 pads to the 128-lane tile
    (28, 512, 512),      # VGG16 conv11: four T_Ci passes
])
def test_stream_mac_conv_compiles_vgg16_layers(one_chip, hw, ci, co):
    hlo = _compile(
        lambda x, w: ops.stream_mac_conv(x, w, padding=(1, 1),
                                         interpret=False),
        one_chip, ((8, hw, hw, ci), BF16), ((3, 3, ci, co), BF16),
    )
    assert "tpu_custom_call" in hlo


def test_stream_mac_conv_strided_compiles_for_f32(one_chip):
    # ResNet's 7x7/2 stem: a strided tap is a strided VMEM load (32-bit only)
    hlo = _compile(
        lambda x, w: ops.stream_mac_conv(x, w, stride=(2, 2), padding=(3, 3),
                                         interpret=False),
        one_chip, ((8, 224, 224, 3), F32), ((7, 7, 3, 64), F32),
    )
    assert "tpu_custom_call" in hlo


def test_stream_mac_conv_strided_bf16_refuses():
    x = jnp.zeros((1, 16, 16, 8), BF16)
    w = jnp.zeros((3, 3, 8, 8), BF16)
    with pytest.raises(NotImplementedError, match="32-bit"):
        ops.stream_mac_conv(x, w, stride=(2, 2), padding=(1, 1),
                            interpret=False)
