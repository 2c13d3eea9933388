"""Plain float32 reference of a dense GQA decoder with QKV bias (Qwen2).

Follows transformers' ``modeling_qwen2.py``: pre-norm RMSNorm, q/k/v
projections with bias, rotary embedding on the two halves of each head
(``rotate_half``), causal grouped-query softmax attention, o_proj without
bias, a SiLU-gated MLP, a final RMSNorm and an output head tied to the
embedding.  Written in plain ``jax.numpy`` on one sequence at a time; it
imports nothing of the system under test.

Departures from the published model: the weights are random (drawn from
the seed by the distributions in ``layer_leaves`` / ``global_leaves``), and
every matmul runs in float32 at ``highest`` precision, set by the caller.

``cast`` is applied to both operands of every projection: the identity for
the reference, a lower precision for the control.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.harness.weights import Leaf, normal, ones_plus


def dims(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return dict(d=d, h=h, hkv=cfg["num_key_value_heads"], hd=d // h,
                f=cfg["intermediate_size"], layers=cfg["num_hidden_layers"],
                vocab=cfg["vocab_size"], theta=float(cfg["rope_theta"]),
                eps=float(cfg["rms_norm_eps"]))


def global_leaves(cfg: dict) -> dict[str, Leaf]:
    m = dims(cfg)
    return {
        "embed": Leaf((m["vocab"], m["d"]), "bfloat16", normal(m["d"] ** -0.5)),
        "norm": Leaf((m["d"],), "float32", ones_plus(0.1)),
    }


def layer_leaves(cfg: dict) -> dict[str, Leaf]:
    m = dims(cfg)
    d, qd, kd, f = m["d"], m["h"] * m["hd"], m["hkv"] * m["hd"], m["f"]
    bf = "bfloat16"
    return {
        "ln1": Leaf((d,), "float32", ones_plus(0.1)),
        "wq": Leaf((d, qd), bf, normal(d ** -0.5)),
        "bq": Leaf((qd,), bf, normal(0.2)),
        "wk": Leaf((d, kd), bf, normal(d ** -0.5)),
        "bk": Leaf((kd,), bf, normal(0.2)),
        "wv": Leaf((d, kd), bf, normal(d ** -0.5)),
        "bv": Leaf((kd,), bf, normal(0.2)),
        "wo": Leaf((qd, d), bf, normal(qd ** -0.5)),
        "ln2": Leaf((d,), "float32", ones_plus(0.1)),
        "w_gate": Leaf((d, f), bf, normal(d ** -0.5)),
        "w_up": Leaf((d, f), bf, normal(d ** -0.5)),
        "w_down": Leaf((f, d), bf, normal(f ** -0.5)),
    }


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """x: (S, H, D); rotate the first and second halves of each head."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv            # (S, half)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def embed(cfg: dict, g: dict, tokens):
    return g["embed"][tokens]


def layer(cfg: dict, w: dict, x, cast):
    """One decoder layer over one sequence x: (S, d) float32."""
    m = dims(cfg)
    s = x.shape[0]
    h, hkv, hd = m["h"], m["hkv"], m["hd"]

    def mm(a, b):
        return cast(a) @ cast(b)

    a = _rms(x, w["ln1"], m["eps"])
    q = (mm(a, w["wq"]) + w["bq"]).reshape(s, h, hd)
    k = (mm(a, w["wk"]) + w["bk"]).reshape(s, hkv, hd)
    v = (mm(a, w["wv"]) + w["bv"]).reshape(s, hkv, hd)
    pos = jnp.arange(s)
    q, k = _rope(q, pos, m["theta"]), _rope(k, pos, m["theta"])
    k = jnp.repeat(k, h // hkv, axis=1)
    v = jnp.repeat(v, h // hkv, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
    causal = pos[:, None] >= pos[None, :]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, axis=-1), v)
    x = x + mm(o.reshape(s, h * hd), w["wo"])
    a = _rms(x, w["ln2"], m["eps"])
    return x + mm(jax.nn.silu(mm(a, w["w_gate"])) * mm(a, w["w_up"]),
                  w["w_down"])


def logits(cfg: dict, g: dict, x, cast):
    """Rows x: (R, d) of the last layer's output -> (R, vocab) logits."""
    a = _rms(x, g["norm"], dims(cfg)["eps"])
    return cast(a) @ cast(g["embed"]).T


def n_layers(cfg: dict) -> int:
    return dims(cfg)["layers"]


def vocab(cfg: dict) -> int:
    return dims(cfg)["vocab"]
