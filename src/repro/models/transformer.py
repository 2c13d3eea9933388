"""Decoder-only LM assembly for all assigned families.

A model is a sequence of *segments*; each segment is a repeating *pattern* of
sub-layer kinds scanned with stacked parameters (``lax.scan`` over the repeat
dimension keeps the HLO one-pattern-deep regardless of depth — compile time
and dry-run cost analysis both depend on this):

    dense   : [("dense",) × L]
    moe     : [("dense",) × first_dense] + [("moe",) × (L-first_dense)]
    ssm     : [("ssm",) × L]
    hybrid  : [cfg.layer_pattern × (L // period)] + remainder, e.g.
              ("rec","rec","attn") or ("ssm",)×5 + ("dense",) + ("ssm",)×4
    vlm     : dense backbone + embedding injection (api.py)

Each layer = pre-norm temporal mix (attention / MLA / SSD / RG-LRU)
+ residual [+ pre-norm MLP/MoE + residual]; each branch is scaled by
``cfg.residual_multiplier`` where that is not 1.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import mla as _mla
from . import moe as _moe
from . import rglru as _rglru
from . import ssm as _ssm
from .attention import attend, decode_attention, paged_decode_attention_xla
from .common import (
    AxisRules,
    DEFAULT_RULES,
    PSpec,
    SEQ_CACHE_KEYS,
    cache_leaf_key,
    abstract_params,
    activation,
    constrain,
    init_params,
    rms_norm,
    rope,
)

# ---------------------------------------------------------------------------
# Sub-layer: GQA attention
# ---------------------------------------------------------------------------


def attn_specs(cfg) -> dict:
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dt = cfg.jdtype
    s = {
        "wq": PSpec((d, h * hd), ("embed", "heads"), dt),
        "wk": PSpec((d, hkv * hd), ("embed", "kv_fused"), dt),
        "wv": PSpec((d, hkv * hd), ("embed", "kv_fused"), dt),
        "wo": PSpec((h * hd, d), ("heads", "embed"), dt),
    }
    if cfg.qkv_bias:
        s["bq"] = PSpec((h * hd,), ("heads",), dt, "zeros")
        s["bk"] = PSpec((hkv * hd,), ("kv_fused",), dt, "zeros")
        s["bv"] = PSpec((hkv * hd,), ("kv_fused",), dt, "zeros")
    if cfg.qk_norm:
        s["q_norm"] = PSpec((hd,), (None,), jnp.float32, "ones")
        s["k_norm"] = PSpec((hd,), (None,), jnp.float32, "ones")
    return s


def _qkv(cfg, p, x):
    b, s, d = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, hkv, hd)
    v = v.reshape(b, s, hkv, hd)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def attn_apply(cfg, p, x, rules, positions, window=None, impl="xla"):
    b, s, d = x.shape
    q, k, v = _qkv(cfg, p, x)
    if cfg.rope:
        q = rope(q, positions[None], cfg.rope_theta)
        k = rope(k, positions[None], cfg.rope_theta)
    q = constrain(q, rules, "batch", "seq", "act_heads", None)
    k = constrain(k, rules, "batch", "seq", "kv_heads", None)
    v = constrain(v, rules, "batch", "seq", "kv_heads", None)
    out = attend(
        q, k, v, causal=True, window=window, scale=cfg.attention_multiplier,
        q_positions=positions, impl=impl, chunk=cfg.attn_chunk,
    )
    out = constrain(out, rules, "batch", "seq", "act_heads", None)
    y = out.reshape(b, s, cfg.n_heads * cfg.hd) @ p["wo"]
    return y, {"k": k, "v": v}


def attn_decode(cfg, p, x, cache, position, rules, window=None):
    """One-token decode.  ``position`` is a scalar (all slots at the same
    depth) or a (B,) vector (per-slot depths, paged serving)."""
    b, _, d = x.shape
    position = jnp.asarray(position, jnp.int32)
    per_slot = position.ndim == 1
    rope_pos = position[:, None] if per_slot else jnp.full((1, 1), position,
                                                          jnp.int32)
    q, k, v = _qkv(cfg, p, x)
    if cfg.rope:
        q = rope(q, rope_pos, cfg.rope_theta)
        k = rope(k, rope_pos, cfg.rope_theta)
    if per_slot:
        rows = jnp.arange(b)
        kc = cache["k"].at[rows, position].set(k[:, 0].astype(cache["k"].dtype))
        vc = cache["v"].at[rows, position].set(v[:, 0].astype(cache["v"].dtype))
    else:
        kc = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), position, axis=1
        )
        vc = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), position, axis=1
        )
    kc = constrain(kc, rules, "batch", "cache_seq", "kv_heads", None)
    vc = constrain(vc, rules, "batch", "cache_seq", "kv_heads", None)
    out = decode_attention(q, kc, vc, position=position, window=window,
                           scale=cfg.attention_multiplier)
    y = out.reshape(b, 1, cfg.n_heads * cfg.hd) @ p["wo"]
    return y, {"k": kc, "v": vc}


def attn_decode_paged(cfg, p, x, cache, block_table, positions, active, rules,
                      window=None, impl="xla"):
    """One-token decode directly against the page pools.

    cache: {"k"/"v": (n_pages, PS, Hkv, hd)} — one layer's pool slice.  The
    new token's k/v scatter into the lane's current page (inactive /
    unallocated lanes drop via the above-pool sentinel, exactly
    ``paged_cache.absorb_decode``), then one decode query per lane attends
    over the pages its block table names: the fused Pallas kernel on
    ``impl='pallas'``, the bit-exact transient-gather XLA form otherwise.
    The engine-side dense (B, max_len, ...) cache tree is never built."""
    b, _, d = x.shape
    n_pages, ps = cache["k"].shape[0], cache["k"].shape[1]
    positions = jnp.asarray(positions, jnp.int32)
    q, k, v = _qkv(cfg, p, x)
    if cfg.rope:
        q = rope(q, positions[:, None], cfg.rope_theta)
        k = rope(k, positions[:, None], cfg.rope_theta)
    page = jnp.take_along_axis(
        block_table, (positions // ps)[:, None], axis=1
    )[:, 0]
    page = jnp.where(active & (page >= 0), page, n_pages)   # drop sentinel
    off = positions % ps
    kc = cache["k"].at[page, off].set(k[:, 0].astype(cache["k"].dtype),
                                      mode="drop")
    vc = cache["v"].at[page, off].set(v[:, 0].astype(cache["v"].dtype),
                                      mode="drop")
    kc = constrain(kc, rules, "pages", None, "kv_heads", None)
    vc = constrain(vc, rules, "pages", None, "kv_heads", None)
    if impl == "pallas" and window is None:
        # the fused kernel has no sliding-window mask; windowed layers
        # (hybrid local attention) take the XLA form below
        from repro.kernels import ops as kops

        lengths = jnp.where(active, positions + 1, 0)
        out = kops.paged_attention(
            q.reshape(b, cfg.n_heads, cfg.hd), kc, vc, block_table, lengths,
            scale=cfg.attention_multiplier,
        )[:, None]
    else:
        out = paged_decode_attention_xla(
            q, kc, vc, block_table, positions, window=window,
            scale=cfg.attention_multiplier,
        )
    y = out.reshape(b, 1, cfg.n_heads * cfg.hd) @ p["wo"]
    return y, {"k": kc, "v": vc}


def attn_extend(cfg, p, x, cache, position, rules, window=None, impl="xla"):
    """Chunked-prefill step: write a C-token chunk at [position, position+C)
    into the cache view and attend it against everything cached so far (the
    chunk's own causal prefix included via absolute q positions)."""
    b, c, d = x.shape
    positions = position + jnp.arange(c, dtype=jnp.int32)
    q, k, v = _qkv(cfg, p, x)
    if cfg.rope:
        q = rope(q, positions[None], cfg.rope_theta)
        k = rope(k, positions[None], cfg.rope_theta)
    kc = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k.astype(cache["k"].dtype), position, axis=1
    )
    vc = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v.astype(cache["v"].dtype), position, axis=1
    )
    out = attend(
        q, kc, vc, causal=True, window=window, scale=cfg.attention_multiplier,
        q_positions=positions, kv_len=position + c, impl=impl,
        chunk=cfg.attn_chunk,
    )
    y = out.reshape(b, c, cfg.n_heads * cfg.hd) @ p["wo"]
    return y, {"k": kc, "v": vc}


def attn_cache_spec(cfg, batch, max_len):
    hkv, hd = cfg.n_kv_heads, cfg.hd
    dt = cfg.jdtype
    return {
        "k": jax.ShapeDtypeStruct((batch, max_len, hkv, hd), dt),
        "v": jax.ShapeDtypeStruct((batch, max_len, hkv, hd), dt),
    }


# ---------------------------------------------------------------------------
# Sub-layer: gated MLP
# ---------------------------------------------------------------------------


def mlp_specs(cfg) -> dict:
    d, f = cfg.d_model, cfg.d_ff
    dt = cfg.jdtype
    return {
        "w_gate": PSpec((d, f), ("embed", "ffn"), dt),
        "w_up": PSpec((d, f), ("embed", "ffn"), dt),
        "w_down": PSpec((f, d), ("ffn", "embed"), dt),
    }


def mlp_apply(cfg, p, x, rules):
    act = activation(cfg.act)
    h = act(x @ p["w_gate"]) * (x @ p["w_up"])
    h = constrain(h, rules, "batch", "seq", "ffn")
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# One decoder layer of a given kind
# ---------------------------------------------------------------------------


def layer_specs(cfg, kind: str) -> dict:
    dt32 = jnp.float32
    ln_init = "zeros" if cfg.rms_plus_one else "ones"
    s: dict = {"ln1": PSpec((cfg.d_model,), ("embed",), dt32, ln_init)}
    if kind == "dense":
        if cfg.mla:
            s["attn"] = _mla.mla_specs(cfg)
        else:
            s["attn"] = attn_specs(cfg)
        s["ln2"] = PSpec((cfg.d_model,), ("embed",), dt32, ln_init)
        s["mlp"] = mlp_specs(cfg)
    elif kind == "moe":
        if cfg.mla:
            s["attn"] = _mla.mla_specs(cfg)
        else:
            s["attn"] = attn_specs(cfg)
        s["ln2"] = PSpec((cfg.d_model,), ("embed",), dt32, ln_init)
        s["moe"] = _moe.moe_specs(cfg)
    elif kind == "ssm":
        s["mix"] = _ssm.ssm_specs(cfg)
        if cfg.d_ff:              # hybrids carry an MLP after the mixer
            s["ln2"] = PSpec((cfg.d_model,), ("embed",), dt32, ln_init)
            s["mlp"] = mlp_specs(cfg)
    elif kind == "rec":
        s["mix"] = _rglru.rglru_specs(cfg)
        s["ln2"] = PSpec((cfg.d_model,), ("embed",), dt32, ln_init)
        s["mlp"] = mlp_specs(cfg)
    elif kind == "attn":          # hybrid local-attention layer
        s["attn"] = attn_specs(cfg)
        s["ln2"] = PSpec((cfg.d_model,), ("embed",), dt32, ln_init)
        s["mlp"] = mlp_specs(cfg)
    else:
        raise ValueError(kind)
    return s


def _norm(cfg, w, x):
    return rms_norm(x, w, cfg.norm_eps, plus_one=cfg.rms_plus_one)


def _residual(cfg, x, y):
    """x plus a branch's output, scaled by ``cfg.residual_multiplier``."""
    if cfg.residual_multiplier != 1.0:
        y = y * jnp.asarray(cfg.residual_multiplier, y.dtype)
    return x + y


def _ffn(cfg, kind, p, x, rules):
    """The layer's second half at inference: pre-norm MLP (or MoE, no
    dropped tokens) and its residual; an ``ssm`` layer without an MLP
    passes x through."""
    if "ln2" not in p:
        return x
    h = _norm(cfg, p["ln2"], x)
    if kind == "moe":
        y, _ = _moe.moe_ffn(cfg, p["moe"], h, rules, n_groups=1, drop=False)
    else:
        y = mlp_apply(cfg, p["mlp"], h, rules)
    return _residual(cfg, x, y)


def layer_apply(cfg, kind, p, x, rules, positions, impl="xla"):
    """Full-sequence forward.  Returns (x, cache, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    window = cfg.sliding_window
    h = _norm(cfg, p["ln1"], x)
    if kind in ("dense", "moe"):
        if cfg.mla:
            y, cache = _mla.mla_attention(cfg, p["attn"], h, rules, positions, impl)
        else:
            y, cache = attn_apply(cfg, p["attn"], h, rules, positions, window, impl)
        x = _residual(cfg, x, y)
        if kind == "moe":          # training routing: capacity, aux loss
            y, aux = _moe.moe_ffn(cfg, p["moe"], _norm(cfg, p["ln2"], x), rules)
            return _residual(cfg, x, y), cache, aux
    elif kind == "ssm":
        y, cache = _ssm.ssm_block(cfg, p["mix"], h, rules)
        x = _residual(cfg, x, y)
    elif kind == "rec":
        y, cache = _rglru.rglru_block(cfg, p["mix"], h, rules)
        x = _residual(cfg, x, y)
    elif kind == "attn":
        y, cache = attn_apply(
            cfg, p["attn"], h, rules, positions, cfg.rglru.attn_window, impl
        )
        x = _residual(cfg, x, y)
    else:
        raise ValueError(kind)
    return _ffn(cfg, kind, p, x, rules), cache, aux


def layer_decode(cfg, kind, p, x, cache, position, rules):
    window = cfg.sliding_window
    h = _norm(cfg, p["ln1"], x)
    if kind in ("dense", "moe"):
        if cfg.mla:
            y, cache = _mla.mla_decode(cfg, p["attn"], h, cache, position, rules)
        else:
            y, cache = attn_decode(cfg, p["attn"], h, cache, position, rules, window)
    elif kind == "ssm":
        y, cache = _ssm.ssm_decode(cfg, p["mix"], h, cache, rules)
    elif kind == "rec":
        y, cache = _rglru.rglru_decode(cfg, p["mix"], h, cache, rules)
    elif kind == "attn":
        y, cache = attn_decode(
            cfg, p["attn"], h, cache, position, rules, cfg.rglru.attn_window
        )
    else:
        raise ValueError(kind)
    x = _residual(cfg, x, y)
    return _ffn(cfg, kind, p, x, rules), cache


def layer_decode_paged(cfg, kind, p, x, cache, block_table, positions, active,
                       rules, impl="xla"):
    """One-token decode over one layer's *paged* cache slice: attention
    kinds read/write the page pools through the block table; recurrent
    kinds step their per-lane state as in ``layer_decode``, with inactive
    lanes keeping their previous state (``absorb_decode`` semantics)."""
    if kind in ("dense", "moe", "attn"):
        h = _norm(cfg, p["ln1"], x)
        if cfg.mla:
            y, cache = _mla.mla_decode_paged(
                cfg, p["attn"], h, cache, block_table, positions, active, rules
            )
        else:
            window = (cfg.rglru.attn_window if kind == "attn"
                      else cfg.sliding_window)
            y, cache = attn_decode_paged(
                cfg, p["attn"], h, cache, block_table, positions, active,
                rules, window, impl
            )
        x = _residual(cfg, x, y)
        x = _ffn(cfg, kind, p, x, rules)
    elif kind in ("ssm", "rec"):
        x, new_cache = layer_decode(cfg, kind, p, x, cache, positions, rules)

        def _keep(old, new):
            sel = active.reshape((active.shape[0],) + (1,) * (old.ndim - 1))
            return jnp.where(sel, new.astype(old.dtype), old)

        cache = jax.tree.map(_keep, cache, new_cache)
    else:
        raise ValueError(kind)
    return x, cache


def layer_extend(cfg, kind, p, x, cache, position, rules):
    """Multi-token extend (chunked prefill) — every layer kind: attention
    caches extend by a KV chunk, MLA by an absorbed latent chunk, and the
    recurrent kinds (ssm/rec) thread their stepped state through the chunk
    (so ``prefill_chunk`` applies to every family)."""
    h = _norm(cfg, p["ln1"], x)
    if kind in ("dense", "moe"):
        if cfg.mla:
            y, cache = _mla.mla_extend(cfg, p["attn"], h, cache, position,
                                       rules)
        else:
            y, cache = attn_extend(cfg, p["attn"], h, cache, position, rules,
                                   cfg.sliding_window)
    elif kind == "attn":
        y, cache = attn_extend(cfg, p["attn"], h, cache, position, rules,
                               cfg.rglru.attn_window)
    elif kind == "ssm":
        y, cache = _ssm.ssm_extend(cfg, p["mix"], h, cache, rules)
    elif kind == "rec":
        y, cache = _rglru.rglru_extend(cfg, p["mix"], h, cache, rules)
    else:
        raise ValueError(kind)
    x = _residual(cfg, x, y)
    return _ffn(cfg, kind, p, x, rules), cache


def layer_cache_spec(cfg, kind, batch, max_len):
    if kind in ("dense", "moe"):
        if cfg.mla:
            return _mla.mla_cache_spec(cfg, batch, max_len)
        return attn_cache_spec(cfg, batch, max_len)
    if kind == "ssm":
        return _ssm.ssm_cache_spec(cfg, batch)
    if kind == "rec":
        return _rglru.rglru_cache_spec(cfg, batch)
    if kind == "attn":
        # local attention: full-length cache masked by the window (a
        # window-sized ring buffer is a recorded §Perf optimization)
        return attn_cache_spec(cfg, batch, max_len)
    raise ValueError(kind)


# layer kinds whose cache is fixed-size per-lane state (no sequence dim)
_STATE_KINDS = ("ssm", "rec")


# ---------------------------------------------------------------------------
# Segments (pattern × repeats, scanned)
# ---------------------------------------------------------------------------


def segments_for(cfg) -> list[tuple[tuple[str, ...], int]]:
    if cfg.layer_pattern:
        pat = tuple(cfg.layer_pattern)
        n_full, rem = divmod(cfg.n_layers, len(pat))
        segs = [(pat, n_full)]
        if rem:
            segs.append((pat[:rem], 1))
        return segs
    if cfg.family in ("dense", "vlm"):
        return [(("dense",), cfg.n_layers)]
    if cfg.family == "moe":
        segs = []
        if cfg.first_dense_layers:
            segs.append((("dense",), cfg.first_dense_layers))
        segs.append((("moe",), cfg.n_layers - cfg.first_dense_layers))
        return segs
    if cfg.family == "ssm":
        return [(("ssm",), cfg.n_layers)]
    raise ValueError(cfg.family)


def _pattern_specs(cfg, pattern):
    return {f"s{i}_{k}": layer_specs(cfg, k) for i, k in enumerate(pattern)}


def _pattern_cache_spec(cfg, pattern, batch, max_len):
    out = {}
    for i, k in enumerate(pattern):
        cs = layer_cache_spec(cfg, k, batch, max_len)
        cs = {kk: vv for kk, vv in cs.items() if vv is not None}
        out[f"s{i}_{k}"] = cs
    return out


def _stack_tree(tree, n):
    return jax.tree.map(
        lambda s: PSpec((n,) + s.shape, ("layers",) + s.axes, s.dtype, s.init, s.scale),
        tree,
        is_leaf=lambda x: isinstance(x, PSpec),
    )


def _remat(cfg, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        )
    return jax.checkpoint(fn)     # full remat


# ---------------------------------------------------------------------------
# DecoderLM
# ---------------------------------------------------------------------------


class DecoderLM:
    """Decoder-only LM over heterogeneous scanned segments."""

    def __init__(self, cfg):
        self.cfg = cfg
        self.segments = segments_for(cfg)

    # -- parameters ---------------------------------------------------------

    def param_specs(self) -> dict:
        cfg = self.cfg
        dt = cfg.jdtype
        specs: dict = {
            "embed": PSpec((cfg.padded_vocab, cfg.d_model), ("vocab", "embed"), dt,
                           scale=1.0),
            "final_norm": PSpec((cfg.d_model,), ("embed",), jnp.float32,
                                "zeros" if cfg.rms_plus_one else "ones"),
        }
        if not cfg.tie_embeddings:
            specs["lm_head"] = PSpec(
                (cfg.d_model, cfg.padded_vocab), ("embed", "vocab"), dt
            )
        for si, (pattern, reps) in enumerate(self.segments):
            specs[f"seg{si}"] = _stack_tree(_pattern_specs(cfg, pattern), reps)
        return specs

    def init(self, key):
        return init_params(self.param_specs(), key)

    def abstract(self):
        return abstract_params(self.param_specs())

    # -- embedding / head ---------------------------------------------------

    def _embed(self, params, tokens, rules):
        cfg = self.cfg
        x = jnp.take(params["embed"], tokens, axis=0)
        mult = cfg.embedding_multiplier * (
            cfg.d_model ** 0.5 if cfg.embed_scale else 1.0)
        if mult != 1.0:
            x = x * jnp.asarray(mult, x.dtype)
        return constrain(x, rules, "batch", "seq", "act_embed")

    def _head(self, params, x, rules):
        cfg = self.cfg
        x = rms_norm(x, params["final_norm"], cfg.norm_eps, cfg.rms_plus_one)
        w = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = x @ w.astype(x.dtype)
        if cfg.logits_scaling != 1.0:
            logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
        return constrain(logits, rules, "batch", "seq", "vocab")

    # -- forward (train) ----------------------------------------------------

    def forward(self, params, tokens, rules=None, impl="xla", extra_embeds=None):
        """tokens (B, S) → logits (B, S, V).  extra_embeds: (B, P, D) prefix
        (VLM patch embeddings / audio frames are injected by subclasses)."""
        cfg = self.cfg
        rules = rules or AxisRules(DEFAULT_RULES)
        x = self._embed(params, tokens, rules)
        if extra_embeds is not None:
            x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
        s = x.shape[1]
        positions = jnp.arange(s, dtype=jnp.int32)
        aux_total = jnp.zeros((), jnp.float32)

        for si, (pattern, reps) in enumerate(self.segments):
            def body(carry, pslice, _pattern=pattern):
                h, aux = carry
                for i, kind in enumerate(_pattern):
                    h, _, a = layer_apply(
                        cfg, kind, pslice[f"s{i}_{kind}"], h, rules, positions, impl
                    )
                    aux = aux + a
                return (h, aux), None

            wrapped = _remat(cfg, body)
            if cfg.scan_layers and reps > 1:
                (x, aux_total), _ = jax.lax.scan(
                    wrapped, (x, aux_total), params[f"seg{si}"]
                )
            else:
                for r in range(reps):
                    pslice = jax.tree.map(lambda a, r=r: a[r], params[f"seg{si}"])
                    (x, aux_total), _ = wrapped((x, aux_total), pslice)
        logits = self._head(params, x, rules)
        return logits, aux_total

    def loss(self, params, batch, rules=None, impl="xla"):
        """Next-token CE + MoE aux.  batch: {"tokens", "targets", ...}."""
        cfg = self.cfg
        rules = rules or AxisRules(DEFAULT_RULES)
        logits, aux = self.forward(
            params, batch["tokens"], rules, impl,
            extra_embeds=batch.get("extra_embeds"),
        )
        targets = batch["targets"]
        if logits.shape[1] != targets.shape[1]:      # VLM prefix: score text only
            logits = logits[:, -targets.shape[1]:]
        if cfg.padded_vocab != cfg.vocab_size:
            col = jnp.arange(logits.shape[-1]) >= cfg.vocab_size
            logits = jnp.where(col[None, None], -1e30, logits.astype(jnp.float32))
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        mask = batch.get("loss_mask")
        if mask is not None:
            nll = nll * mask
            denom = jnp.maximum(jnp.sum(mask), 1.0)
        else:
            denom = nll.size
        return jnp.sum(nll) / denom + aux

    # -- prefill / decode ---------------------------------------------------

    def prefill(self, params, tokens, rules=None, impl="xla", extra_embeds=None,
                max_len=None):
        """Returns (logits, cache).  cache seq dims sized to the prompt; the
        serving engine pads to max_len before decode."""
        cfg = self.cfg
        rules = rules or AxisRules(DEFAULT_RULES)
        x = self._embed(params, tokens, rules)
        if extra_embeds is not None:
            x = jnp.concatenate([extra_embeds.astype(x.dtype), x], axis=1)
        s = x.shape[1]
        positions = jnp.arange(s, dtype=jnp.int32)
        caches = []
        for si, (pattern, reps) in enumerate(self.segments):
            def body(h, pslice, _pattern=pattern):
                cs = {}
                for i, kind in enumerate(_pattern):
                    h, c, _ = layer_apply(
                        cfg, kind, pslice[f"s{i}_{kind}"], h, rules, positions, impl
                    )
                    cs[f"s{i}_{kind}"] = c
                return h, cs

            if cfg.scan_layers and reps > 1:
                x, cache = jax.lax.scan(body, x, params[f"seg{si}"])
            else:
                slices = []
                for r in range(reps):
                    pslice = jax.tree.map(lambda a, r=r: a[r], params[f"seg{si}"])
                    x, c = body(x, pslice)
                    slices.append(c)
                cache = jax.tree.map(lambda *xs: jnp.stack(xs), *slices)
            if cfg.decode_unroll_layers:
                # match decode_step's per-layer cache layout
                cache = [
                    jax.tree.map(lambda a, _r=r: a[_r], cache)
                    for r in range(reps)
                ]
            caches.append(cache)
        logits = self._head(params, x[:, -1:], rules)
        return logits, caches

    def decode_step(self, params, cache, tokens, position, rules=None):
        """tokens (B,1), position scalar int32 → (logits (B,1,V), cache)."""
        cfg = self.cfg
        rules = rules or AxisRules(DEFAULT_RULES)
        x = self._embed(params, tokens, rules)
        new_caches = []
        for si, (pattern, reps) in enumerate(self.segments):
            def body(h, xs, _pattern=pattern):
                pslice, cs = xs
                new_cs = {}
                for i, kind in enumerate(_pattern):
                    key = f"s{i}_{kind}"
                    h, c = layer_decode(
                        cfg, kind, pslice[key], h, cs[key], position, rules
                    )
                    new_cs[key] = c
                return h, new_cs

            if cfg.scan_layers and reps > 1 and cfg.decode_cache_in_carry:
                # §Perf optimization: the cache rides the scan CARRY (while
                # loop state is aliased in place by XLA buffer assignment)
                # instead of xs→ys, which double-buffers the whole cache.
                def carry_body(carry, xs, _body=body):
                    h, cfull = carry
                    pslice, idx = xs
                    cs = jax.tree.map(
                        lambda a: jax.lax.dynamic_index_in_dim(
                            a, idx, 0, keepdims=False), cfull)
                    h, new_cs = _body(h, (pslice, cs))
                    cfull = jax.tree.map(
                        lambda a, u: jax.lax.dynamic_update_index_in_dim(
                            a, u.astype(a.dtype), idx, 0), cfull, new_cs)
                    return (h, cfull), None

                (x, new_cache), _ = jax.lax.scan(
                    carry_body, (x, cache[si]),
                    (params[f"seg{si}"], jnp.arange(reps)),
                )
            elif cfg.scan_layers and reps > 1 and not cfg.decode_unroll_layers:
                x, new_cache = jax.lax.scan(body, x, (params[f"seg{si}"], cache[si]))
            elif cfg.decode_unroll_layers:
                # §Perf: unrolled decode — each layer's cache is a separate
                # donated buffer; the slot update aliases in place (no loop
                # carry copies, no full-cache stacking)
                new_cache = []
                for r in range(reps):
                    pslice = jax.tree.map(lambda a, _r=r: a[_r], params[f"seg{si}"])
                    x, c = body(x, (pslice, cache[si][r]))
                    new_cache.append(c)
            else:
                slices = []
                for r in range(reps):
                    pslice = jax.tree.map(lambda a, r=r: a[r], params[f"seg{si}"])
                    cslice = jax.tree.map(lambda a, r=r: a[r], cache[si])
                    x, c = body(x, (pslice, cslice))
                    slices.append(c)
                new_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *slices)
            new_caches.append(new_cache)
        logits = self._head(params, x, rules)
        return logits, new_caches

    def decode_step_paged(self, params, pools, block_tables, tokens,
                          positions, active, rules=None, attn_impl="xla"):
        """Zero-materialization decode: tokens (B,1), block_tables (B,P),
        positions (B,), active (B,) → (logits (B,1,V), pools).

        The paged counterpart of ``decode_step``: seq-cache leaves are page
        *pools* (reps, n_pages, PS, *t) read/written through the block table
        inside each layer (``attn_decode_paged`` / ``mla_decode_paged``), so
        the engine never gathers a dense (B, max_len, ...) cache tree.
        Recurrent-state leaves keep the per-lane layout and step in place
        in the scan's carry (inactive lanes keep their state).  Stacked
        decode layout only."""
        cfg = self.cfg
        if cfg.decode_unroll_layers:
            raise NotImplementedError("paged decode needs the stacked layout")
        rules = rules or AxisRules(DEFAULT_RULES)
        x = self._embed(params, tokens, rules)
        positions = jnp.asarray(positions, jnp.int32)
        block_tables = jnp.asarray(block_tables, jnp.int32)
        new_caches = []
        for si, (pattern, reps) in enumerate(self.segments):
            def body(h, xs, _pattern=pattern):
                pslice, cs = xs
                new_cs = {}
                for i, kind in enumerate(_pattern):
                    key = f"s{i}_{kind}"
                    h, c = layer_decode_paged(
                        cfg, kind, pslice[key], h, cs[key], block_tables,
                        positions, active, rules, attn_impl
                    )
                    new_cs[key] = c
                return h, new_cs

            carried = [f"s{i}_{k}" for i, k in enumerate(pattern)
                       if k in _STATE_KINDS]
            if cfg.scan_layers and reps > 1:
                # per-lane recurrent state rides the scan carry, so each
                # layer's row is updated in place (a while loop's carry
                # aliases its buffer); as xs -> ys the whole state would be
                # written to a second buffer.  Page pools stay xs -> ys.
                def carry_body(carry, xs, _body=body, _keys=carried):
                    h, st = carry
                    pslice, cs, r = xs
                    cs = dict(cs)
                    for k in _keys:
                        cs[k] = jax.tree.map(
                            lambda a: jax.lax.dynamic_index_in_dim(
                                a, r, 0, keepdims=False), st[k])
                    h, new_cs = _body(h, (pslice, cs))
                    st = {k: jax.tree.map(
                        lambda a, u: jax.lax.dynamic_update_index_in_dim(
                            a, u.astype(a.dtype), r, 0), st[k], new_cs.pop(k))
                        for k in _keys}
                    return (h, st), new_cs

                seg_pools = pools[si]
                (x, st), new_cache = jax.lax.scan(
                    carry_body, (x, {k: seg_pools[k] for k in carried}),
                    (params[f"seg{si}"],
                     {k: v for k, v in seg_pools.items() if k not in carried},
                     jnp.arange(reps)),
                )
                new_cache = {**new_cache, **st}
            else:
                slices = []
                for r in range(reps):
                    pslice = jax.tree.map(lambda a, r=r: a[r], params[f"seg{si}"])
                    cslice = jax.tree.map(lambda a, r=r: a[r], pools[si])
                    x, c = body(x, (pslice, cslice))
                    slices.append(c)
                new_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *slices)
            new_caches.append(new_cache)
        logits = self._head(params, x, rules)
        return logits, new_caches

    @property
    def supports_chunked_prefill(self) -> bool:
        """Every DecoderLM layer kind can extend by a multi-token chunk:
        attention/MLA caches extend by a KV (latent) chunk and the recurrent
        kinds thread their stepped state through ``ssm_extend`` /
        ``rglru_extend`` — ``prefill_chunk`` applies to every family."""
        return True

    def extend_step(self, params, cache, tokens, position, rules=None):
        """tokens (B, C), position scalar int32 → (logits (B, C, V), cache).
        Writes the chunk's KV at [position, position+C) and attends against
        the full cache view — the chunked-prefill counterpart of
        ``decode_step`` (stacked decode cache layout only)."""
        cfg = self.cfg
        if cfg.decode_unroll_layers:
            raise NotImplementedError("extend_step needs the stacked layout")
        rules = rules or AxisRules(DEFAULT_RULES)
        x = self._embed(params, tokens, rules)
        new_caches = []
        for si, (pattern, reps) in enumerate(self.segments):
            def body(h, xs, _pattern=pattern):
                pslice, cs = xs
                new_cs = {}
                for i, kind in enumerate(_pattern):
                    key = f"s{i}_{kind}"
                    h, c = layer_extend(
                        cfg, kind, pslice[key], h, cs[key], position, rules
                    )
                    new_cs[key] = c
                return h, new_cs

            if cfg.scan_layers and reps > 1:
                x, new_cache = jax.lax.scan(body, x, (params[f"seg{si}"], cache[si]))
            else:
                slices = []
                for r in range(reps):
                    pslice = jax.tree.map(lambda a, r=r: a[r], params[f"seg{si}"])
                    cslice = jax.tree.map(lambda a, r=r: a[r], cache[si])
                    x, c = body(x, (pslice, cslice))
                    slices.append(c)
                new_cache = jax.tree.map(lambda *xs: jnp.stack(xs), *slices)
            new_caches.append(new_cache)
        logits = self._head(params, x, rules)
        return logits, new_caches

    # -- cache / inputs -----------------------------------------------------

    def cache_specs(self, batch: int, max_len: int):
        cfg = self.cfg
        out = []
        for pattern, reps in self.segments:
            tree = _pattern_cache_spec(cfg, pattern, batch, max_len)
            if cfg.decode_unroll_layers:
                out.append([tree for _ in range(reps)])   # per-layer leaves
            else:
                out.append(
                    jax.tree.map(
                        lambda s, reps=reps: jax.ShapeDtypeStruct(
                            (reps,) + s.shape, s.dtype),
                        tree,
                    )
                )
        return out

    def cache_page_specs(self, lanes: int, n_pages: int, page_size: int):
        """Pool specs for the paged serving cache.

        Same pytree structure as ``cache_specs(lanes, page_size)``, but every
        sequence-carrying leaf (``SEQ_CACHE_KEYS``) swaps its lane dim for a
        page-pool dim: (reps, n_pages, page_size, *tail).  Leaves without a
        seq dim (recurrent state) keep the per-lane layout — they are the
        "one page per request" state the scheduler never splits.
        """
        specs = self.cache_specs(lanes, page_size)

        def leaf(path, s):
            name = cache_leaf_key(path)
            if name not in SEQ_CACHE_KEYS:
                return s
            bdim = seq_leaf_batch_dim(name, len(s.shape))
            shape = s.shape[:bdim] + (n_pages,) + s.shape[bdim + 1:]
            return jax.ShapeDtypeStruct(shape, s.dtype)

        return jax.tree_util.tree_map_with_path(leaf, specs)


# batch-led rank of each seq-carrying cache leaf (k/v: (B,S,H,D); MLA
# latent/k_rope: (B,S,R)); a higher observed rank means a leading layers dim
_SEQ_LEAF_BASE_RANK = {"k": 4, "v": 4, "ck": 4, "cv": 4, "latent": 3,
                       "k_rope": 3}


def seq_leaf_batch_dim(name: str, ndim: int) -> int:
    """Index of the lane/batch dim of a seq cache leaf (0 per-layer layout,
    1 stacked layout); the seq dim is always the next one."""
    return 1 if ndim == _SEQ_LEAF_BASE_RANK[name] + 1 else 0


def cache_window(cfg) -> int:
    return cfg.rglru.attn_window if cfg.rglru else (cfg.sliding_window or 0)
