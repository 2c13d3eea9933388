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
  materializing the dense view.  It reads only the pages the lane's length
  reaches, a block of pages per loop step, DMA'd from HBM while the
  previous block is attended.

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
    layer_ref,        # (1,) int32 the layer of the stacked pools to read
    q_ref,            # (1, 1, rep, D)
    k_hbm,            # (L, n_pool, Hkv, PS, D) — stays in HBM, pages DMA'd
    v_hbm,            # (L, n_pool, Hkv, PS, D)
    o_ref,            # (1, 1, rep, D)
    k_buf,            # (2, ppb, PS, D) double-buffered block of K pages
    v_buf,            # (2, ppb, PS, D)
    sems,             # DMA semaphores (2, 2): [K/V, buffer slot]
    *,
    n_pages: int,
    pages_per_block: int,
    scale: float,
):
    b, g = pl.program_id(0), pl.program_id(1)
    layer = layer_ref[0]
    _, _, ps, d = k_buf.shape
    bk = pages_per_block * ps
    length = len_ref[b]
    # only the blocks the lane's length reaches: a lane of length 0 walks
    # none, and no block lies past the last one the length needs
    n_blocks = jnp.minimum((length + bk - 1) // bk, n_pages // pages_per_block)

    def copies(blk, slot):
        out = []
        for j in range(pages_per_block):
            page = jnp.maximum(bt_ref[b, blk * pages_per_block + j], 0)
            out.append(pltpu.make_async_copy(
                k_hbm.at[layer, page, g], k_buf.at[slot, j], sems.at[0, slot]))
            out.append(pltpu.make_async_copy(
                v_hbm.at[layer, page, g], v_buf.at[slot, j], sems.at[1, slot]))
        return out

    @pl.when(n_blocks > 0)
    def _prefetch_first():
        for c in copies(0, 0):
            c.start()

    q = q_ref[0, 0]                                      # (rep, D)
    rep = q.shape[0]

    def body(i, carry):
        m_prev, l_prev, acc = carry
        slot = i % 2

        @pl.when(i + 1 < n_blocks)
        def _prefetch_next():
            for c in copies(i + 1, 1 - slot):
                c.start()

        for c in copies(i, slot):
            c.wait()
        k = k_buf[slot].reshape(bk, d)
        v = v_buf[slot].reshape(bk, d).astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                        # (rep, bk)

        kpos = i * bk + jax.lax.broadcasted_iota(jnp.int32, (rep, bk), 1)
        mask = kpos < length
        for j in range(pages_per_block):
            in_page = (kpos // ps) == i * pages_per_block + j
            mask &= ~in_page | (bt_ref[b, i * pages_per_block + j] >= 0)
        s = jnp.where(mask, s, NEG_INF)
        # rows past the length may hold pages the table never filled (a
        # freed page, anything): zero them so 0 x NaN cannot reach acc
        vpos = i * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, d), 0)
        v = jnp.where(vpos < length, v, 0.0)

        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(mask, p, 0.0)                      # all-masked pages
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc

    m0 = jnp.full((rep, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((rep, 1), jnp.float32)
    acc0 = jnp.zeros((rep, d), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, n_blocks, body, (m0, l0, acc0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30))[None, None].astype(o_ref.dtype)


def paged_decode_attention(
    q: jax.Array,             # (B, Hkv, rep, D) one decode token per lane
    k_pool: jax.Array,        # (L, n_pages, Hkv, PS, D) every layer's pages
    v_pool: jax.Array,        # (L, n_pages, Hkv, PS, D)
    block_table: jax.Array,   # (B, pages_per_lane) int32, -1 = unallocated
    lengths: jax.Array,       # (B,) int32 — tokens valid in the pages
    layer: jax.Array,         # (1,) int32 — the layer of the pools to read
    pages_per_block: int,     # pages one loop step streams; divides P
    scale: float | None = None,
    interpret: bool = False,
) -> jax.Array:
    """One grid step per (lane, KV head); inside it a loop over the blocks
    of ``pages_per_block`` pages the lane's length reaches, each block's
    pages copied from HBM into one half of a VMEM double buffer while the
    other half is attended.  The pools are read where they lie: a page of
    one head, ``k_pool[layer, page, head]``, is one contiguous (PS, D) copy,
    so no layer slice or relayout of the pool is ever made."""
    b, hkv, rep, d = q.shape
    ps = k_pool.shape[3]
    _, p = block_table.shape
    if p % pages_per_block:
        raise ValueError(
            f"pages_per_block {pages_per_block} does not divide {p} pages")
    scale = float(scale) if scale is not None else float(d) ** -0.5
    kern = functools.partial(
        _paged_attn_kernel, n_pages=p, pages_per_block=pages_per_block,
        scale=scale,
    )
    q_spec = pl.BlockSpec((1, 1, rep, d),
                          lambda b_, g, bt, ln, lr: (b_, g, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, hkv),
        in_specs=[
            q_spec,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=q_spec,
        scratch_shapes=[
            pltpu.VMEM((2, pages_per_block, ps, d), k_pool.dtype),
            pltpu.VMEM((2, pages_per_block, ps, d), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
    )(block_table, lengths, layer, q, k_pool, v_pool)
