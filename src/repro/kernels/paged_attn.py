"""Paged-KV cache read kernels (Pallas, TPU target).

The serving engine's KV cache is a pool of fixed-size pages addressed by a
per-lane block table (``serve/paged_cache``) — the software analogue of the
paper's vault-interleaved SMC memory: each request's state lives scattered
across near-memory pages and the compute streams it through on-chip memory.
Two kernels cover the read path:

* ``paged_gather`` — block-table gather of page pools into the contiguous
  ``(B, S, ...)`` decode view (pure DMA; pages are whole blocks so each grid
  step is one page copy, unallocated pages read as zeros).
* ``paged_decode_attention`` — the fused read: one decode query per lane
  attends directly over the pages its block table names with a streaming
  online-softmax accumulator (the ``flash_attention`` dataflow), never
  materializing the dense view.

Both have pure-jnp oracles in ``ref.py``; ``ops.py`` holds the padded,
interpret-off-TPU public wrappers.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_LANE = 128


# ---------------------------------------------------------------------------
# Block-table gather (pages → contiguous decode view)
# ---------------------------------------------------------------------------


def _gather_kernel(bt_ref, pool_ref, out_ref):
    b, p = pl.program_id(0), pl.program_id(1)
    page = bt_ref[b, p]
    out_ref[...] = jnp.where(page >= 0, pool_ref[...],
                             jnp.zeros_like(out_ref))


def paged_gather(
    pool: jax.Array,          # (n_pages, page_bytes) — one row per page
    block_table: jax.Array,   # (B, pages_per_lane) int32, -1 = unallocated
    interpret: bool = False,
) -> jax.Array:
    """(B, pages_per_lane, page_bytes) gather; -1 entries read as zeros.

    Each page row travels as a (1, f) block whose two minor dims are the
    array's own (a unit dim ahead of the row on both sides), so the block
    obeys the TPU tiling rule without padding pages to 8 rows."""
    n_pages, f = pool.shape
    b, p = block_table.shape
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, p),
        in_specs=[
            pl.BlockSpec((1, 1, f),
                         lambda b_, p_, bt: (jnp.maximum(bt[b_, p_], 0), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, f), lambda b_, p_, bt: (b_, p_, 0, 0)),
    )
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, p, 1, f), pool.dtype),
        interpret=interpret,
    )(block_table, pool.reshape(n_pages, 1, f))
    return out.reshape(b, p, f)


# ---------------------------------------------------------------------------
# Fused paged decode attention (read + online softmax, no dense view)
# ---------------------------------------------------------------------------


def _paged_attn_kernel(
    bt_ref,           # (B, P) int32 scalar-prefetch block table
    len_ref,          # (B,) int32 valid tokens per lane (current token incl.)
    q_ref,            # (1, 1, rep, D)
    k_ref,            # (1, 1, PS, D) — the page this grid step streams
    v_ref,            # (1, 1, PS, D)
    o_ref,            # (1, 1, rep, D)
    acc_ref,          # (rep, D) f32
    m_ref,            # (rep, _LANE) f32 lane-replicated running max
    l_ref,            # (rep, _LANE) f32 lane-replicated running sum
    *,
    n_pages: int,
    page_size: int,
    scale: float,
):
    b, pi = pl.program_id(0), pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale          # (rep, D)
    k = k_ref[0, 0].astype(jnp.float32)                  # (PS, D)
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                                    # (rep, PS)

    rep, ps = s.shape
    kpos = pi * page_size + jax.lax.broadcasted_iota(jnp.int32, (rep, ps), 1)
    mask = (kpos < len_ref[b]) & (bt_ref[b, pi] >= 0)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)                          # all-masked pages
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = jnp.broadcast_to(l_ref[:, :1] * alpha
                                  + jnp.sum(p, axis=1, keepdims=True),
                                  l_ref.shape)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v_ref[0, 0].astype(jnp.float32),
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
    )
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(pi == n_pages - 1)
    def _flush():
        l = l_ref[:, :1]
        o_ref[...] = (acc_ref[...] / jnp.maximum(l, 1e-30))[None, None].astype(
            o_ref.dtype
        )


def paged_decode_attention(
    q: jax.Array,             # (B, Hkv, rep, D) one decode token per lane
    k_pool: jax.Array,        # (Hkv, n_pages, PS, D)
    v_pool: jax.Array,        # (Hkv, n_pages, PS, D)
    block_table: jax.Array,   # (B, pages_per_lane) int32, -1 = unallocated
    lengths: jax.Array,       # (B,) int32 — tokens valid in the pages
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    b, hkv, rep, d = q.shape
    _, n_pool, ps, _ = k_pool.shape
    _, p = block_table.shape
    scale = float(scale) if scale is not None else float(d) ** -0.5
    kern = functools.partial(
        _paged_attn_kernel, n_pages=p, page_size=ps, scale=scale,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv, p),
        in_specs=[
            pl.BlockSpec((1, 1, rep, d),
                         lambda b_, g, pi, bt, ln: (b_, g, 0, 0)),
            pl.BlockSpec((1, 1, ps, d),
                         lambda b_, g, pi, bt, ln:
                         (g, jnp.maximum(bt[b_, pi], 0), 0, 0)),
            pl.BlockSpec((1, 1, ps, d),
                         lambda b_, g, pi, bt, ln:
                         (g, jnp.maximum(bt[b_, pi], 0), 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, rep, d),
                               lambda b_, g, pi, bt, ln: (b_, g, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((rep, d), jnp.float32),
            pltpu.VMEM((rep, _LANE), jnp.float32),
            pltpu.VMEM((rep, _LANE), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(block_table, lengths, q, k_pool, v_pool)
