"""The system under test for the dense family: its ``ArchConfig`` built from
the configuration file, and the published-layout weights placed in its
parameter tree."""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from chipbench.reference.dense import dims


def arch(cfg: dict):
    from repro.configs import get_arch

    m = dims(cfg)
    return dataclasses.replace(
        get_arch(cfg["program_arch"]), name=cfg["name"],
        n_layers=m["layers"], d_model=m["d"], n_heads=m["h"],
        n_kv_heads=m["hkv"], head_dim=m["hd"], d_ff=m["f"],
        vocab_size=m["vocab"], rope_theta=m["theta"], norm_eps=m["eps"],
        qkv_bias=True, qk_norm=False, act="silu",
        tie_embeddings=bool(cfg["tie_word_embeddings"]), dtype=cfg["dtype"])


def params(cfg: dict, a, g: dict, s: dict) -> dict:
    """Program parameter tree from global leaves ``g`` and layer leaves
    ``s`` stacked along a leading layers axis (traceable)."""
    emb = g["embed"]
    emb = jnp.pad(emb, ((0, a.padded_vocab - emb.shape[0]), (0, 0)))
    layer = {
        "ln1": s["ln1"], "ln2": s["ln2"],
        "attn": {k: s[k] for k in ("wq", "bq", "wk", "bk", "wv", "bv", "wo")},
        "mlp": {k: s[k] for k in ("w_gate", "w_up", "w_down")},
    }
    return {"embed": emb, "final_norm": g["norm"], "seg0": {"s0_dense": layer}}
