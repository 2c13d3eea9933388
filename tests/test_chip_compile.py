"""Compile-only checks of the main-path Pallas kernels for a TPU v5e chip.

The TPU compiler is installed even where no chip is attached: it compiles
for a *described* ``v5e:2x2`` topology, refusing what the chip's compiler
would refuse (unaligned blocks, unsupported primitives, VMEM overflow).
Nothing runs.  Widths are the served ones — qwen2.5-3b and granite-4.0-h-micro
attention, both models' decode programs (two scanned layers), a granite SSD
prefill chunk, and VGG16 convolutions at 224 px.  The topology is described
only inside the fixture below, never at import: one process at a time may
load the TPU library.
"""
import math
import os
import re
from collections import Counter

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
        lambda q, k, v, bt, ln, layer: ops.paged_attention(
            q, k, v, bt, ln, layer, interpret=False),
        one_chip,
        ((LANES, H, D), BF16), ((2, N_PAGES, HKV, PS, D), BF16),
        ((2, N_PAGES, HKV, PS, D), BF16), ((LANES, MAX_LEN // PS), jnp.int32),
        ((LANES,), jnp.int32), ((), jnp.int32),
    )
    assert "tpu_custom_call" in hlo
    # the benchmark's kernel reading finds the kernel's events by this name
    assert re.search(r"%paged_attention(\.\d+)? = \S+ custom-call\(.*"
                     r'custom_call_target="tpu_custom_call"', hlo)


# granite-4.0-h-micro: 32 query heads over 8 KV heads of 64 (4 queries a KV
# head; the wrapper pads them to 8 rows), NoPE scale 1/64.  Its pool pages
# are stored 128 lanes wide: Mosaic refuses to copy a 64-wide page
G_H, G_HKV, G_D = 32, 8, 64


def test_paged_attention_compiles_head_dim_64(one_chip):
    hlo = _compile(
        lambda q, k, v, bt, ln, layer: ops.paged_attention(
            q, k, v, bt, ln, layer, scale=1 / 64, interpret=False),
        one_chip,
        ((LANES, G_H, G_D), BF16), ((2, N_PAGES, G_HKV, PS, 128), BF16),
        ((2, N_PAGES, G_HKV, PS, 128), BF16),
        ((LANES, MAX_LEN // PS), jnp.int32), ((LANES,), jnp.int32),
        ((), jnp.int32),
    )
    assert re.search(r"%paged_attention(\.\d+)? = \S+ custom-call\(.*"
                     r'custom_call_target="tpu_custom_call"', hlo)


def _computations(hlo: str) -> dict[str, list[str]]:
    comps, cur = {}, None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%(\S+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif cur is not None and line.startswith("  "):
            cur.append(line)
    return comps


def _pool_shaped(hlo: str, n_pages: int, hkv: int, ps: int,
                 min_elems: int) -> list[str]:
    """Instructions outside fusions whose result holds an array shaped like
    (a layer of) a KV pool in any order — a page, a head and a page-size
    dim, at least ``min_elems`` elements — other than the parameters and
    loop plumbing that alias the pool buffers."""
    comps = _computations(hlo)
    fused = set(re.findall(r"calls=%([\w.\-]+)", hlo))
    plumbing = {"parameter", "get-tuple-element", "tuple", "bitcast",
                "while", "opt-barrier"}
    found = []
    for name, lines in comps.items():
        if name in fused:
            continue
        for line in lines:
            m = re.match(r"\s*(?:ROOT )?%(\S+) = (.+?) ([\w\-]+)\(", line)
            if not m or m.group(3) in plumbing:
                continue
            for dims in re.findall(r"\w+\[([\d,]+)\]", m.group(2)):
                shape = [int(x) for x in dims.split(",")]
                if (not Counter((n_pages, hkv, ps)) - Counter(shape)
                        and math.prod(shape) >= min_elems):
                    found.append(line.strip())
                    break
    return found


@pytest.mark.parametrize("arch,layers", [
    pytest.param("qwen2.5-3b", 2, id="qwen"),
    pytest.param("granite-4.0-h-micro", 20, id="granite"),   # 2 periods of 10
])
def test_decode_step_paged_keeps_pools_in_place(one_chip, monkeypatch, arch,
                                                layers):
    """The engine's decode program (Pallas paged kernel, greedy choice,
    pools donated) at published widths, 2 scanned layers (granite: two
    periods), 64 lanes, 4096 pages: the kernel reads the stacked pools where
    they lie.  No op but the in-place token scatter makes an array of a
    layer's pool size (no per-layer slice, transpose, pad, write-back or
    exit copy), the donated pools alias the output, and the program's
    temporaries are smaller than one layer's pool."""
    import dataclasses

    from repro.configs import get_arch
    from repro.models import build_model

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    cfg = dataclasses.replace(get_arch(arch), n_layers=layers,
                              decode_unroll_layers=False)
    model = build_model(cfg)
    lanes, n_pages, ps, per_lane = 64, 4096, 16, 128

    def placed(tree):
        return jax.tree.map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=one_chip), tree)

    pools = placed(model.cache_page_specs(lanes, n_pages, ps))
    i32 = jnp.int32
    args = (placed(model.abstract()), pools) + tuple(
        jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in (
            ((lanes, per_lane), i32), ((lanes, 1), i32), ((lanes,), i32),
            ((lanes,), jnp.bool_)))

    def step(params, pools, bt, toks, pos, act):
        logits, pools = model.decode_step_paged(
            params, pools, bt, toks, pos, act, attn_impl="pallas")
        return jnp.argmax(logits[:, 0], -1).astype(i32), logits, pools

    compiled = jax.jit(step, donate_argnums=(1,)).lower(*args).compile()
    hlo = compiled.as_text()
    assert re.search(r"%paged_attention(\.\d+)? = \S+ custom-call\(", hlo)
    layer_pool = n_pages * cfg.n_kv_heads * ps * cfg.hd
    copies = [line[:240] for line in _pool_shaped(
        hlo, n_pages, cfg.n_kv_heads, ps, layer_pool)
        if not re.search(r'op_name="[^"]*/scatter"', line)]
    assert not copies, "\n".join(copies)
    mem = compiled.memory_analysis()
    pool_bytes = sum(math.prod(s.shape) * s.dtype.itemsize
                     for s in jax.tree.leaves(pools))
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < layer_pool * 2      # bf16 bytes


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
