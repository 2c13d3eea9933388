"""Plain float32 reference of a hybrid Mamba-2 / GQA decoder (IBM Granite
4.0-H).

Follows transformers' ``modeling_granitemoehybrid.py`` for a model without
experts (``num_local_experts`` 0):

* the embedding is multiplied by ``embedding_multiplier``;
* every layer is ``x += r * mixer(rms(x))`` then ``x += r * mlp(rms(x))``,
  with ``r = residual_multiplier`` and the shared SwiGLU MLP (``input_linear``
  split into gate and up halves, ``output_linear``);
* a ``mamba`` layer's mixer is the Mamba-2 mixer: ``in_proj`` to (z, xBC,
  dt), a causal depthwise conv of width ``mamba_d_conv`` with bias and SiLU
  over xBC, split into x, B, C (``mamba_n_groups`` 1), ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``, then token by token
  ``h <- exp(dt*A) h + dt x B^T`` and ``y = h C + D x``, the gated RMSNorm
  ``rms(y * silu(z)) * w`` over the whole inner width, and ``out_proj``;
* an ``attention`` layer is causal grouped-query softmax attention with no
  positional embedding (``position_embedding_type`` nope), scores scaled by
  ``attention_multiplier`` in place of 1/sqrt(head_dim), no biases;
* a final RMSNorm and the head tied to the embedding, logits divided by
  ``logits_scaling``.

Written in plain ``jax.numpy`` on one sequence at a time; it imports nothing
of the system under test.  One reference "layer" is one period of
``layer_types`` (ten layers, one of them attention), so ``n_layers`` counts
periods and each period's weights are drawn together: the mamba layers'
leaves stacked along a leading axis (``m_*``), the attention layers' along
another (``a_*``).

Departures from the published model: the weights are random, drawn from the
seed by the distributions in ``layer_leaves`` / ``global_leaves`` (below),
and every matmul runs in float32 at ``highest`` precision, set by the
caller.  ``cast`` is applied to both operands of every projection (the
mixers' and MLPs' linear maps and the head): the identity for the
reference, a lower precision for the control; the recurrence, conv and
softmax stay float32.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.harness.weights import Leaf, normal, ones_plus


def _period(types: list) -> int:
    for p in range(1, len(types) + 1):
        if len(types) % p == 0 and all(
                t == types[i % p] for i, t in enumerate(types)):
            return p
    return len(types)


def dims(cfg: dict) -> dict:
    types = list(cfg["layer_types"])
    if len(types) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types must name every layer")
    if cfg["mamba_n_groups"] != 1 or cfg["num_local_experts"]:
        raise ValueError("one B/C group and no experts only")
    if (not cfg["mamba_conv_bias"] or cfg["mamba_proj_bias"]
            or cfg["attention_bias"] or not cfg["tie_word_embeddings"]
            or cfg["position_embedding_type"] != "nope"):
        raise ValueError("conv bias, no projection biases, tied head and "
                         "NoPE only")
    pattern = tuple(types[:_period(types)])
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nh, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    if nh * p != cfg["mamba_expand"] * d:
        raise ValueError("mamba_n_heads x mamba_d_head != mamba_expand x hidden")
    n = cfg["mamba_d_state"]
    return dict(
        d=d, h=h, hkv=cfg["num_key_value_heads"], hd=d // h,
        f=cfg["shared_intermediate_size"], vocab=cfg["vocab_size"],
        eps=float(cfg["rms_norm_eps"]), layers=cfg["num_hidden_layers"],
        pattern=pattern, periods=len(types) // len(pattern),
        n_mamba=pattern.count("mamba"), n_attn=pattern.count("attention"),
        nh=nh, p=p, n=n, di=nh * p, k=cfg["mamba_d_conv"],
        conv=nh * p + 2 * n,
        emb_mult=float(cfg["embedding_multiplier"]),
        res_mult=float(cfg["residual_multiplier"]),
        attn_mult=float(cfg["attention_multiplier"]),
        logits_div=float(cfg["logits_scaling"]))


def _a_log(k, s):
    """A = -exp(A_log) with A uniform in [1, 16] (mamba_ssm's A_init_range)."""
    return jnp.log(jax.random.uniform(k, s, jnp.float32, 1.0, 16.0))


def _dt_bias(k, s):
    """softplus^-1 of dt drawn log-uniform in [0.001, 0.1] (mamba_ssm's
    dt_min / dt_max)."""
    dt = jnp.exp(jax.random.uniform(k, s, jnp.float32, math.log(1e-3),
                                    math.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def _ones(k, s):
    return jnp.ones(s, jnp.float32)


def global_leaves(cfg: dict) -> dict[str, Leaf]:
    m = dims(cfg)
    # the tied head divides by logits_scaling: rows of this spread give
    # logits of unit spread, as the dense reference's do
    return {
        "embed": Leaf((m["vocab"], m["d"]), "bfloat16",
                      normal(m["logits_div"] * m["d"] ** -0.5)),
        "norm": Leaf((m["d"],), "float32", ones_plus(0.1)),
    }


# Spread, times 1/sqrt(fan_in), of the projections that write the residual
# stream (out_proj, o_proj, the MLP's down projection).  At 1 the scaled
# embedding (x embedding_multiplier) stays the largest part of the stream
# and the tied head just repeats the input token at every position; at 64
# the stream outgrows it ~50-fold by the last layer, as trained hidden
# states outgrow their embeddings, and the head's choice depends on every
# layer.
RESIDUAL_GAIN = 64.0


def layer_leaves(cfg: dict) -> dict[str, Leaf]:
    """One period's weights: ``m_*`` stacked over its mamba layers, ``a_*``
    over its attention layers."""
    m = dims(cfg)
    d, f, di, nh, k = m["d"], m["f"], m["di"], m["nh"], m["k"]
    nm, na = m["n_mamba"], m["n_attn"]
    qd, kd = m["h"] * m["hd"], m["hkv"] * m["hd"]
    # q and k rows spread so that the scores, scaled by attention_multiplier,
    # have unit spread
    qk = (m["attn_mult"] * m["hd"] ** 0.5) ** -0.5 * d ** -0.5
    bf, f32 = "bfloat16", "float32"

    def mlp(pre: str, c: int) -> dict[str, Leaf]:
        return {
            pre + "ln2": Leaf((c, d), f32, ones_plus(0.1)),
            pre + "w_gate": Leaf((c, d, f), bf, normal(d ** -0.5)),
            pre + "w_up": Leaf((c, d, f), bf, normal(d ** -0.5)),
            pre + "w_down": Leaf((c, f, d), bf,
                                 normal(RESIDUAL_GAIN * f ** -0.5)),
        }

    return {
        "m_ln1": Leaf((nm, d), f32, ones_plus(0.1)),
        "m_in_proj": Leaf((nm, d, 2 * di + 2 * m["n"] + nh), bf,
                          normal(d ** -0.5)),
        "m_conv_w": Leaf((nm, k, m["conv"]), bf, normal(k ** -0.5)),
        "m_conv_b": Leaf((nm, m["conv"]), bf, normal(0.1)),
        "m_a_log": Leaf((nm, nh), f32, _a_log),
        "m_dt_bias": Leaf((nm, nh), f32, _dt_bias),
        "m_d": Leaf((nm, nh), f32, _ones),
        "m_norm": Leaf((nm, di), f32, ones_plus(0.1)),
        "m_out_proj": Leaf((nm, di, d), bf,
                           normal(RESIDUAL_GAIN * di ** -0.5)),
        **mlp("m_", nm),
        "a_ln1": Leaf((na, d), f32, ones_plus(0.1)),
        "a_wq": Leaf((na, d, qd), bf, normal(qk)),
        "a_wk": Leaf((na, d, kd), bf, normal(qk)),
        "a_wv": Leaf((na, d, kd), bf, normal(d ** -0.5)),
        "a_wo": Leaf((na, qd, d), bf, normal(RESIDUAL_GAIN * qd ** -0.5)),
        **mlp("a_", na),
    }


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def ssd_recurrence(x, dt, a, bm, cm, d_skip, h0=None):
    """The Mamba-2 recurrence, one token at a time.  x: (S, H, P); dt: (S, H)
    after softplus; a: (H,) negative; bm, cm: (S, N); d_skip: (H,); h0:
    (H, P, N) or None.  Returns (y (S, H, P), final state (H, P, N))."""
    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = (jnp.exp(dt_t * a)[:, None, None] * h
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        return h, jnp.einsum("hpn,n->hp", h, c_t)

    if h0 is None:
        h0 = jnp.zeros(x.shape[1:] + bm.shape[-1:], jnp.float32)
    h, y = jax.lax.scan(step, h0, (x, dt, bm, cm))
    return y + d_skip[None, :, None] * x, h


def mamba_mixer(cfg: dict, w: dict, i: int, x, cast):
    """The Mamba-2 mixer of the period's i-th mamba layer over x: (S, d)."""
    m = dims(cfg)
    s = x.shape[0]
    di, n, nh = m["di"], m["n"], m["nh"]
    zxbcdt = cast(x) @ cast(w["m_in_proj"][i])
    z, xbc, dt = zxbcdt[:, :di], zxbcdt[:, di: di + m["conv"]], zxbcdt[:, -nh:]
    xp = jnp.pad(xbc, ((m["k"] - 1, 0), (0, 0)))
    conv = sum(xp[j: j + s] * w["m_conv_w"][i][j] for j in range(m["k"]))
    xbc = jax.nn.silu(conv + w["m_conv_b"][i])
    xs, bm, cm = xbc[:, :di], xbc[:, di: di + n], xbc[:, di + n:]
    dt = jax.nn.softplus(dt + w["m_dt_bias"][i])
    y, _ = ssd_recurrence(xs.reshape(s, nh, m["p"]), dt,
                          -jnp.exp(w["m_a_log"][i]), bm, cm, w["m_d"][i])
    y = _rms(y.reshape(s, di) * jax.nn.silu(z), w["m_norm"][i], m["eps"])
    return cast(y) @ cast(w["m_out_proj"][i])


def attention(cfg: dict, w: dict, i: int, x, cast):
    """Causal GQA without positional embedding over x: (S, d)."""
    m = dims(cfg)
    s = x.shape[0]
    h, hkv, hd = m["h"], m["hkv"], m["hd"]
    q = (cast(x) @ cast(w["a_wq"][i])).reshape(s, h, hd)
    k = (cast(x) @ cast(w["a_wk"][i])).reshape(s, hkv, hd)
    v = (cast(x) @ cast(w["a_wv"][i])).reshape(s, hkv, hd)
    k = jnp.repeat(k, h // hkv, axis=1)
    v = jnp.repeat(v, h // hkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * m["attn_mult"]
    pos = jnp.arange(s)
    scores = jnp.where((pos[:, None] >= pos[None, :])[None], scores, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    return cast(o.reshape(s, h * hd)) @ cast(w["a_wo"][i])


def embed(cfg: dict, g: dict, tokens):
    return g["embed"][tokens] * dims(cfg)["emb_mult"]


def layer(cfg: dict, w: dict, x, cast):
    """One period of decoder layers over one sequence x: (S, d) float32."""
    m = dims(cfg)
    r, eps = m["res_mult"], m["eps"]
    idx = {"mamba": 0, "attention": 0}
    for kind in m["pattern"]:
        i = idx[kind]
        idx[kind] += 1
        pre = "m_" if kind == "mamba" else "a_"
        mixer = mamba_mixer if kind == "mamba" else attention
        x = x + r * mixer(cfg, w, i, _rms(x, w[pre + "ln1"][i], eps), cast)
        a = cast(_rms(x, w[pre + "ln2"][i], eps))
        hid = jax.nn.silu(a @ cast(w[pre + "w_gate"][i])) * (
            a @ cast(w[pre + "w_up"][i]))
        x = x + r * (cast(hid) @ cast(w[pre + "w_down"][i]))
    return x


def logits(cfg: dict, g: dict, x, cast):
    """Rows x: (R, d) of the last layer's output -> (R, vocab) logits."""
    m = dims(cfg)
    a = _rms(x, g["norm"], m["eps"])
    return (cast(a) @ cast(g["embed"]).T) / m["logits_div"]


def n_layers(cfg: dict) -> int:
    return dims(cfg)["periods"]


def vocab(cfg: dict) -> int:
    return dims(cfg)["vocab"]
