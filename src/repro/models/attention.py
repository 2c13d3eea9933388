"""Attention math: XLA streaming (chunked online-softmax) implementation for
lowering/dry-run + dispatch to the Pallas flash kernel on TPU.

Layout convention: q (B, Sq, H, D), k/v (B, Sk, Hkv, D).  GQA is computed
grouped — kv heads are never materialized ``repeat``-ed.  The chunked scan is
the same streaming-accumulator dataflow as ``kernels/flash_attention`` (and as
the paper's STREAM_MAC partial sums), expressed in ``lax.scan`` so XLA:CPU/TPU
can compile it without a Pallas backend.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.tune.registry import dtype_code, tunable

from .common import kv_page_load

NEG_INF = -1e30


def _mask(qpos, kpos, causal: bool, window: int | None, kv_len):
    m = kpos[None, :] < kv_len
    if causal:
        m &= qpos[:, None] >= kpos[None, :]
    if window is not None:
        m &= (qpos[:, None] - kpos[None, :]) < window
    return m            # (Sq, Sk_chunk)


def _flash_shape_class(q, k, *_a) -> str:
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    return (f"b{b}.sq{sq}.sk{sk}.h{h}.hkv{hkv}.d{d}"
            f".{dtype_code(q.dtype)}")


def _flash_cost(params, q, k, *_a):
    """(flops, HBM bytes) of the chunked streaming form as a function of
    the chunk size: the score/PV contractions are chunk-invariant, but k/v
    stream through once per q block — shrinking the chunk multiplies the
    k/v read traffic by ceil(Sq/chunk)."""
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    nq = -(-sq // min(params["chunk"], sq))
    itemsize = jnp.dtype(q.dtype).itemsize
    flops = 4.0 * b * h * sq * sk * d
    bytes_ = float(itemsize) * (2 * b * sq * h * d
                                + nq * 2 * b * sk * hkv * d)
    return flops, bytes_


@tunable(
    "attn.flash_xla",
    space={"chunk": (64, 128, 256, 512, 1024)},
    defaults={"chunk": 1024},
    shape_class=_flash_shape_class,
    cost_model=_flash_cost,
)
def flash_attention_xla(
    q: jax.Array,                  # (B, Sq, H, D)
    k: jax.Array,                  # (B, Sk, Hkv, D)
    v: jax.Array,                  # (B, Sk, Hkv, D)
    *,
    causal: bool = True,
    window: int | None = None,
    scale: float | None = None,
    q_positions: jax.Array | None = None,   # (Sq,) absolute positions
    kv_len: jax.Array | int | None = None,
    chunk: int | None = None,
) -> jax.Array:
    """Nested-chunk streaming attention (the Pallas kernel's dataflow in
    pure lax): outer map over q blocks, inner scan over kv blocks with an
    online-softmax accumulator.  The per-q-block function is checkpointed so
    training memory is O(block²) transient, not O(seq²) resident.

    ``chunk=None`` resolves through the tuned table (``@tunable``, falls
    back to 1024); model paths pass ``cfg.attn_chunk`` explicitly and are
    untouched by tuning.
    """
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    rep = h // hkv
    scale = scale if scale is not None else float(d) ** -0.5
    kv_len = kv_len if kv_len is not None else sk
    qpos = (
        q_positions if q_positions is not None else jnp.arange(sq, dtype=jnp.int32)
    )
    kchunk = min(chunk, sk)
    nk = (sk + kchunk - 1) // kchunk
    kpad = nk * kchunk - sk
    if kpad:
        k = jnp.pad(k, ((0, 0), (0, kpad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, kpad), (0, 0), (0, 0)))
    kc = k.reshape(b, nk, kchunk, hkv, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, nk, kchunk, hkv, d).transpose(1, 0, 2, 3, 4)
    kpos_all = jnp.arange(nk * kchunk, dtype=jnp.int32).reshape(nk, kchunk)

    qchunk = min(chunk, sq)
    nq = (sq + qchunk - 1) // qchunk
    qpad = nq * qchunk - sq
    qf = (q.reshape(b, sq, hkv, rep, d) * scale).astype(jnp.float32)
    if qpad:
        qf = jnp.pad(qf, ((0, 0), (0, qpad), (0, 0), (0, 0), (0, 0)))
        qpos = jnp.pad(qpos, (0, qpad))
    qcs = qf.reshape(b, nq, qchunk, hkv, rep, d).transpose(1, 0, 2, 3, 4, 5)
    qpos_cs = qpos.reshape(nq, qchunk)

    @jax.checkpoint
    def per_q(args):
        qc, qp = args                              # (B,qc,Hkv,rep,D), (qc,)

        def step(carry, xs):
            m_run, l_run, acc = carry
            kb, vb, kpos = xs                      # (B,c,Hkv,D), (c,)
            s = jnp.einsum(
                "bqgrd,bkgd->bgrqk", qc.astype(kb.dtype), kb,
                preferred_element_type=jnp.float32,
            )                                      # (B,Hkv,rep,qc,c)
            msk = _mask(qp, kpos, causal, window, kv_len)
            s = jnp.where(msk[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            p = jnp.where(msk[None, None, None], p, 0.0)
            alpha = jnp.exp(m_run - m_new)
            l_new = l_run * alpha + jnp.sum(p, axis=-1)
            pv = jnp.einsum(
                "bgrqk,bkgd->bgrqd", p.astype(vb.dtype), vb,
                preferred_element_type=jnp.float32,
            )
            acc = acc * alpha[..., None] + pv
            return (m_new, l_new, acc), None

        init = (
            jnp.full((b, hkv, rep, qchunk), NEG_INF, jnp.float32),
            jnp.zeros((b, hkv, rep, qchunk), jnp.float32),
            jnp.zeros((b, hkv, rep, qchunk, d), jnp.float32),
        )
        (m_run, l_run, acc), _ = jax.lax.scan(step, init, (kc, vc, kpos_all))
        out = acc / jnp.maximum(l_run[..., None], 1e-30)
        return out.transpose(0, 3, 1, 2, 4)       # (B,qc,Hkv,rep,D)

    if nq == 1:
        out = per_q((qcs[0], qpos_cs[0]))
    else:
        outs = jax.lax.map(per_q, (qcs, qpos_cs))  # (nq,B,qc,Hkv,rep,D)
        out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(
            b, nq * qchunk, hkv, rep, d
        )
    out = out.reshape(b, -1, h, d)[:, :sq]
    return out.astype(q.dtype)


def decode_attention(
    q: jax.Array,                  # (B, 1, H, D)
    k: jax.Array,                  # (B, Smax, Hkv, D) — cache
    v: jax.Array,
    *,
    position: jax.Array,           # scalar or (B,): index of the new token
    window: int | None = None,
    scale: float | None = None,
) -> jax.Array:
    """Single-token attention over a (possibly padded) KV cache.

    ``position`` may be a scalar (all rows at the same depth — the dense
    slot engine) or a (B,) vector (per-slot depths — the paged engine's
    ragged continuous batching).
    """
    b, _, h, d = q.shape
    _, smax, hkv, _ = k.shape
    rep = h // hkv
    scale = scale if scale is not None else float(d) ** -0.5
    position = jnp.asarray(position, jnp.int32)
    # no materialized f32 cast of the cache: bf16 reads, f32 MXU accumulate
    qf = (q.reshape(b, hkv, rep, d) * scale).astype(k.dtype)
    s = jnp.einsum("bgrd,bkgd->bgrk", qf, k,
                   preferred_element_type=jnp.float32)
    kpos = jnp.arange(smax, dtype=jnp.int32)
    if position.ndim:                            # per-slot (B,) positions
        msk = kpos[None, :] <= position[:, None]       # (B, Smax)
        if window is not None:
            msk &= (position[:, None] - kpos[None, :]) < window
        s = jnp.where(msk[:, None, None, :], s, NEG_INF)
    else:
        msk = kpos <= position                   # (Smax,)
        if window is not None:
            msk &= (position - kpos) < window
        s = jnp.where(msk[None, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrk,bkgd->bgrd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, d).astype(q.dtype)


def paged_lane_view(pool: jax.Array, layer, block_table: jax.Array,
                    head_dim: int | None = None) -> jax.Array:
    """Per-lane contiguous view of one layer of a stacked page pool:
    (L, n_pages, *page) + table (B, P) → (B, P*PS, *t); -1 entries read as
    zeros.  ``head_dim`` marks a k/v pool, whose pages are stored
    (Hkv, PS, Dp) and are read back token-major at ``head_dim``; without it
    a page is (PS, *t).

    Bit-identical to ``serve.paged_cache.gather_views`` on that layer — the
    decode-view oracle the paged attention paths are proven against.  The
    layer and its pages are taken in one gather, so no copy of the layer's
    pool is made, and the view is transient inside the layer (XLA fuses it
    into the attention contraction); nothing (B, max_len) survives it.
    """
    n_pages = pool.shape[1]
    b, p = block_table.shape
    idx = jnp.clip(block_table, 0, n_pages - 1)
    if head_dim is None:
        view = pool[layer, idx]                                 # (B, P, *pg)
    else:      # gather only the head dim's lanes of each (Hkv, PS, Dp) page
        view = pool[layer, idx, ..., :head_dim]
    mask = (block_table >= 0).reshape((b, p) + (1,) * (pool.ndim - 2))
    view = jnp.where(mask, view, jnp.zeros((), pool.dtype))
    if head_dim is not None:
        view = kv_page_load(view, head_dim)
    return view.reshape((b, -1) + view.shape[3:])


def _paged_shape_class(q, k_pool, v_pool, block_table, *_a) -> str:
    b, _, h, d = q.shape
    n_pages, hkv, ps = k_pool.shape[1:4]
    p = block_table.shape[1]
    return (f"b{b}.h{h}.hkv{hkv}.d{d}.ps{ps}.p{p}.np{n_pages}"
            f".{dtype_code(k_pool.dtype)}")


def _paged_validate(params, q, *_a) -> bool:
    lb = params["lane_block"]
    return lb == 0 or (0 < lb <= q.shape[0] and q.shape[0] % lb == 0)


@tunable(
    "attn.paged_decode",
    space={"lane_block": (0, 1, 2, 4, 8)},
    defaults={"lane_block": 0},
    shape_class=_paged_shape_class,
    validate=_paged_validate,
    # no cost model: total HBM traffic is lane_block-invariant (the knob
    # trades transient gathered-view footprint against dispatch count), so
    # the roofline cannot separate configs — the whole 5-point space is
    # measured
)
def paged_decode_attention_xla(
    q: jax.Array,             # (B, 1, H, D) one decode token per lane
    k_pool: jax.Array,        # (L, n_pages, Hkv, PS, Dp) stacked k/v pools
    v_pool: jax.Array,
    block_table: jax.Array,   # (B, P) int32, -1 = unallocated
    positions: jax.Array,     # (B,) index of each lane's new token
    layer,                    # int32 scalar: the layer of the pools to read
    *,
    window: int | None = None,
    scale: float | None = None,
    lane_block: int | None = None,
) -> jax.Array:
    """XLA paged decode attention: a transient per-layer page gather feeding
    the exact ``decode_attention`` math of the gather path (bit-exact by
    construction); the fused Pallas kernel (``kernels/paged_attn``) is the
    no-gather TPU form of the same contraction.

    ``lane_block`` > 0 gathers and attends ``lane_block`` lanes at a time
    (``lax.map`` over lane groups) — bit-exact per lane since lanes are
    independent, but the transient gathered view shrinks from
    (B, P·PS, ...) to (lane_block, P·PS, ...).  0 = one pass over all
    lanes (the pre-tuner behavior); ``None`` resolves through the tuned
    table and falls back to 0.
    """
    b, d = q.shape[0], q.shape[-1]
    if lane_block and 0 < lane_block < b:
        nb = b // lane_block
        qb = q.reshape((nb, lane_block) + q.shape[1:])
        tb = block_table.reshape(nb, lane_block, -1)
        pb = positions.reshape(nb, lane_block)

        def one(group):
            qq, tt, pp = group
            kc = paged_lane_view(k_pool, layer, tt, d)
            vc = paged_lane_view(v_pool, layer, tt, d)
            return decode_attention(qq, kc, vc, position=pp, window=window,
                                    scale=scale)

        out = jax.lax.map(one, (qb, tb, pb))
        return out.reshape((b,) + out.shape[2:])
    kc = paged_lane_view(k_pool, layer, block_table, d)
    vc = paged_lane_view(v_pool, layer, block_table, d)
    return decode_attention(q, kc, vc, position=positions, window=window,
                            scale=scale)


def attend(
    q, k, v, *,
    causal=True, window=None, scale=None, q_positions=None, kv_len=None,
    impl: str = "xla", chunk: int | None = None,
) -> jax.Array:
    """Dispatch: 'xla' (chunked scan — default, compiles everywhere),
    'pallas' (the kernels/flash_attention TPU kernel; interpret off-TPU),
    'naive' (materialized logits — small shapes only)."""
    if impl == "pallas":
        from repro.kernels import ops as kops

        off = 0
        if q_positions is not None:
            off = int(q_positions[0]) if not isinstance(q_positions, jax.core.Tracer) else 0
        return kops.flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
            causal=causal, window=window, scale=scale, q_offset=off,
        ).transpose(0, 2, 1, 3)
    if impl == "naive":
        from repro.kernels import ref

        return ref.flash_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
            causal=causal, window=window, scale=scale,
            q_offset=0 if q_positions is None else q_positions[0],
        ).transpose(0, 2, 1, 3)
    return flash_attention_xla(
        q, k, v, causal=causal, window=window, scale=scale,
        q_positions=q_positions, kv_len=kv_len, chunk=chunk,
    )
