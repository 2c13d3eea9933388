"""DeepSeek-V3 Multi-head Latent Attention (MLA).

Q and KV are compressed to low-rank latents; the decode cache stores only the
KV latent (+ the shared rotary key) — the paper's "partial computation"
mechanism applied to attention state.  Decode uses the published
weight-absorption form: queries are absorbed into latent space so the cache
is never decompressed (scores = q_abs · latent + q_rope · k_rope; output =
(attn · latent) · W_v^b).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .attention import flash_attention_xla, paged_lane_view
from .common import AxisRules, PSpec, constrain, rms_norm, rope


def mla_specs(cfg) -> dict:
    m = cfg.mla
    d, h = cfg.d_model, cfg.n_heads
    dt = cfg.jdtype
    qk = m.qk_nope_head_dim
    qr = m.qk_rope_head_dim
    vd = m.v_head_dim
    return {
        "wq_a": PSpec((d, m.q_lora_rank), ("embed", None), dt),
        "q_norm": PSpec((m.q_lora_rank,), (None,), jnp.float32, "ones"),
        # latent dims carry the FSDP axis ("latent" → data axes in train):
        # unsharded dims would replicate these grads and all-reduce them
        # across the fleet once PER MICROBATCH (measured 2.7e12 B/dev on
        # deepseek train — see EXPERIMENTS.md §Perf)
        "wq_b": PSpec((m.q_lora_rank, h * (qk + qr)), ("latent", "heads"), dt),
        "wkv_a": PSpec((d, m.kv_lora_rank + qr), ("embed", None), dt),
        "kv_norm": PSpec((m.kv_lora_rank,), (None,), jnp.float32, "ones"),
        "wk_b": PSpec((m.kv_lora_rank, h * qk), ("latent", "heads"), dt),
        "wv_b": PSpec((m.kv_lora_rank, h * vd), ("latent", "heads"), dt),
        "wo": PSpec((h * vd, d), ("heads", "embed"), dt),
    }


def _project_q_at(cfg, p, x, rope_pos):
    """rope_pos: broadcastable (..., S) absolute positions (e.g. (1,S) full
    sequence, (B,1) per-slot decode)."""
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.n_heads
    qk, qr = m.qk_nope_head_dim, m.qk_rope_head_dim
    qa = rms_norm(x @ p["wq_a"], p["q_norm"], cfg.norm_eps)
    q = (qa @ p["wq_b"]).reshape(b, s, h, qk + qr)
    q_nope, q_rope = q[..., :qk], q[..., qk:]
    q_rope = rope(q_rope, rope_pos, cfg.rope_theta)
    return q_nope, q_rope


def _latent_kv_at(cfg, p, x, rope_pos):
    m = cfg.mla
    kv = x @ p["wkv_a"]                                   # (B,S,rank+qr)
    latent = rms_norm(kv[..., : m.kv_lora_rank], p["kv_norm"], cfg.norm_eps)
    k_rope = rope(kv[..., m.kv_lora_rank:], rope_pos, cfg.rope_theta)
    return latent, k_rope                                 # (B,S,rank),(B,S,qr)


def _project_q(cfg, p, x, positions):
    return _project_q_at(cfg, p, x, positions[None])


def _latent_kv(cfg, p, x, positions):
    return _latent_kv_at(cfg, p, x, positions[None])


def mla_attention(
    cfg, p, x, rules: AxisRules, positions, impl="xla",
) -> tuple[jax.Array, dict]:
    """Training / prefill path: decompress K,V per block (weights stream,
    latents resident — streaming form).  Returns (out, cache)."""
    m = cfg.mla
    b, s, d = x.shape
    h = cfg.n_heads
    qk, qr, vd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim

    q_nope, q_rope = _project_q(cfg, p, x, positions)
    latent, k_rope = _latent_kv(cfg, p, x, positions)

    k_nope = (latent @ p["wk_b"]).reshape(b, s, h, qk)
    v = (latent @ p["wv_b"]).reshape(b, s, h, vd)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope[:, :, None], (b, s, h, qr))], axis=-1)
    q = constrain(q, rules, "batch", "seq", "act_heads", None)
    k = constrain(k, rules, "batch", "seq", "act_heads", None)
    scale = float(qk + qr) ** -0.5
    # pad V head dim up to qk+qr so one attention primitive serves both
    vpad = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, qk + qr - vd)))
    out = flash_attention_xla(
        q, k, vpad, causal=True, scale=scale, q_positions=positions,
        chunk=cfg.attn_chunk,
    )[..., :vd]
    out = constrain(out, rules, "batch", "seq", "act_heads", None)
    y = out.reshape(b, s, h * vd) @ p["wo"]
    cache = {"latent": latent, "k_rope": k_rope}
    return y, cache


def _absorbed_attend(cfg, p, q_nope, q_rope, latent, k_rope, mask):
    """The weight-absorbed attention contraction shared by every MLA decode
    / extend path:

    scores_h(t) = q_abs_h · latent_t + q_rope_h · k_rope_t
    out_h       = (Σ_t a_t latent_t) · W_vb_h

    q_nope/q_rope: (B,S,H,·); latent/k_rope: (B,T,·); mask broadcastable to
    the (B,H,S,T) score tensor.  bf16 cache reads + f32 MXU accumulation —
    no materialized f32 copy of the latent cache."""
    m = cfg.mla
    h = cfg.n_heads
    qk, qr, vd = m.qk_nope_head_dim, m.qk_rope_head_dim, m.v_head_dim
    rank = m.kv_lora_rank
    wk_b = p["wk_b"].reshape(rank, h, qk)
    q_abs = jnp.einsum("bshk,rhk->bshr", q_nope, wk_b,
                       preferred_element_type=jnp.float32)   # (B,S,H,rank)
    scale = float(qk + qr) ** -0.5
    s_lat = jnp.einsum("bshr,btr->bhst", q_abs.astype(latent.dtype), latent,
                       preferred_element_type=jnp.float32)
    s_rope = jnp.einsum("bshq,btq->bhst", q_rope.astype(k_rope.dtype), k_rope,
                        preferred_element_type=jnp.float32)
    s = (s_lat + s_rope) * scale                          # (B,H,S,T)
    s = jnp.where(mask, s, -1e30)
    a = jax.nn.softmax(s, axis=-1)
    # contract into (B,H,S,rank) and transpose after: XLA:CPU has no bf16
    # x bf16 -> f32 dot for the direct "bhst,btr->bshr" layout; the MXU
    # sees the same bf16 operands and f32 accumulation either way
    ctx = jnp.einsum("bhst,btr->bhsr", a.astype(latent.dtype), latent,
                     preferred_element_type=jnp.float32).transpose(0, 2, 1, 3)
    wv_b = p["wv_b"].reshape(rank, h, vd)
    return jnp.einsum("bshr,rhv->bshv", ctx.astype(wv_b.dtype), wv_b,
                      preferred_element_type=jnp.float32)


def mla_decode(
    cfg, p, x, cache: dict, position, rules: AxisRules,
) -> tuple[jax.Array, dict]:
    """Absorbed decode: cache holds (latent, k_rope) only.

    scores_h(t) = q_abs_h · latent_t + q_rope_h · k_rope_t
    out_h       = (Σ_t a_t latent_t) · W_vb_h
    """
    m = cfg.mla
    b, s1, d = x.shape                      # s1 == 1
    h = cfg.n_heads
    vd = m.v_head_dim
    position = jnp.asarray(position, jnp.int32)
    per_slot = position.ndim == 1           # (B,) paged-serving depths
    if per_slot:
        q_nope, q_rope = _project_q_at(cfg, p, x, position[:, None])
        new_latent, new_krope = _latent_kv_at(cfg, p, x, position[:, None])
        rows = jnp.arange(b)
        latent = cache["latent"].at[rows, position].set(
            new_latent[:, 0].astype(cache["latent"].dtype)
        )
        k_rope = cache["k_rope"].at[rows, position].set(
            new_krope[:, 0].astype(cache["k_rope"].dtype)
        )
    else:
        positions = jnp.full((1,), position, jnp.int32)
        q_nope, q_rope = _project_q(cfg, p, x, positions)     # (B,1,H,qk/qr)
        new_latent, new_krope = _latent_kv(cfg, p, x, positions)
        latent = jax.lax.dynamic_update_slice_in_dim(
            cache["latent"], new_latent.astype(cache["latent"].dtype), position,
            axis=1
        )
        k_rope = jax.lax.dynamic_update_slice_in_dim(
            cache["k_rope"], new_krope.astype(cache["k_rope"].dtype), position,
            axis=1
        )
    latent = constrain(latent, rules, "batch", "cache_seq", None)

    kpos = jnp.arange(latent.shape[1], dtype=jnp.int32)
    if per_slot:
        mask = (kpos[None, :] <= position[:, None])[:, None, None, :]
    else:
        mask = (kpos <= position)[None, None, None]
    out = _absorbed_attend(cfg, p, q_nope, q_rope, latent, k_rope, mask)
    y = out.reshape(b, 1, h * vd).astype(x.dtype) @ p["wo"]
    return y, {"latent": latent, "k_rope": k_rope}


def mla_decode_paged(
    cfg, p, x, cache: dict, layer, block_table, positions, active,
    rules: AxisRules,
) -> tuple[jax.Array, dict]:
    """Absorbed decode directly over stacked latent/k_rope page pools.

    cache: {"latent": (L, n_pages, PS, rank), "k_rope": (L, n_pages, PS,
    qr)} — every layer's pool; this call reads and writes ``layer``.  The
    new token's latents scatter into the lane's current page in place
    (inactive / unallocated lanes drop via the above-pool sentinel, exactly
    ``paged_cache.absorb_decode``); the attention reads a transient
    per-layer lane view, so the engine never materializes the dense
    (B, max_len) cache tree.  Bit-exact vs the gather path.

    Always the XLA form: the absorbed score is a two-term contraction
    (q_abs·latent + q_rope·k_rope) the single-pool fused Pallas kernel does
    not cover — ``EngineConfig.attn_impl='pallas'`` applies to GQA layers
    only (a fused MLA paged kernel is a recorded follow-on)."""
    b, s1, d = x.shape                      # s1 == 1
    h = cfg.n_heads
    vd = cfg.mla.v_head_dim
    n_pages, ps = cache["latent"].shape[1], cache["latent"].shape[2]
    positions = jnp.asarray(positions, jnp.int32)
    q_nope, q_rope = _project_q_at(cfg, p, x, positions[:, None])
    new_latent, new_krope = _latent_kv_at(cfg, p, x, positions[:, None])
    page = jnp.take_along_axis(
        block_table, (positions // ps)[:, None], axis=1
    )[:, 0]
    page = jnp.where(active & (page >= 0), page, n_pages)   # drop sentinel
    off = positions % ps
    latent_pool = cache["latent"].at[layer, page, off].set(
        new_latent[:, 0].astype(cache["latent"].dtype), mode="drop"
    )
    krope_pool = cache["k_rope"].at[layer, page, off].set(
        new_krope[:, 0].astype(cache["k_rope"].dtype), mode="drop"
    )
    latent_pool = constrain(latent_pool, rules, "layers", "pages", None, None)
    latent = paged_lane_view(latent_pool, layer, block_table)  # (B, cap, rank)
    k_rope = paged_lane_view(krope_pool, layer, block_table)
    kpos = jnp.arange(latent.shape[1], dtype=jnp.int32)
    mask = (kpos[None, :] <= positions[:, None])[:, None, None, :]
    out = _absorbed_attend(cfg, p, q_nope, q_rope, latent, k_rope, mask)
    y = out.reshape(b, 1, h * vd).astype(x.dtype) @ p["wo"]
    return y, {"latent": latent_pool, "k_rope": krope_pool}


def mla_extend(
    cfg, p, x, cache: dict, position, rules: AxisRules,
) -> tuple[jax.Array, dict]:
    """Chunked-prefill extend in the absorbed form: write the chunk's
    latent/k_rope at [position, position+C) into the cache view and score
    every chunk query against all cached latents (the chunk's own causal
    prefix included via absolute positions) — the multi-token counterpart
    of ``mla_decode`` that closes the ``prefill_chunk`` gap for MLA."""
    b, c, d = x.shape
    h = cfg.n_heads
    vd = cfg.mla.v_head_dim
    positions = position + jnp.arange(c, dtype=jnp.int32)
    q_nope, q_rope = _project_q_at(cfg, p, x, positions[None])
    new_latent, new_krope = _latent_kv_at(cfg, p, x, positions[None])
    latent = jax.lax.dynamic_update_slice_in_dim(
        cache["latent"], new_latent.astype(cache["latent"].dtype), position,
        axis=1
    )
    k_rope = jax.lax.dynamic_update_slice_in_dim(
        cache["k_rope"], new_krope.astype(cache["k_rope"].dtype), position,
        axis=1
    )
    latent = constrain(latent, rules, "batch", "cache_seq", None)
    kpos = jnp.arange(latent.shape[1], dtype=jnp.int32)
    mask = (kpos[None, :] <= positions[:, None])[None, None]   # (1,1,C,cap)
    out = _absorbed_attend(cfg, p, q_nope, q_rope, latent, k_rope, mask)
    y = out.reshape(b, c, h * vd).astype(x.dtype) @ p["wo"]
    return y, {"latent": latent, "k_rope": k_rope}


def mla_cache_spec(cfg, batch: int, max_len: int):
    m = cfg.mla
    dt = cfg.jdtype
    return {
        "latent": jax.ShapeDtypeStruct((batch, max_len, m.kv_lora_rank), dt),
        "k_rope": jax.ShapeDtypeStruct((batch, max_len, m.qk_rope_head_dim), dt),
    }
