"""The system under test for the Mamba-2 / attention hybrid family: its
``ArchConfig`` built from the configuration file, and the published-layout
weights placed in its parameter tree."""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp

from chipbench.reference.ssd_hybrid import dims

KIND = {"mamba": "ssm", "attention": "dense"}     # the program's layer kinds


def arch(cfg: dict):
    from repro.configs import get_arch
    from repro.configs.base import SSMConfig
    from repro.models.ssm import ssm_cache_spec

    m = dims(cfg)
    a = dataclasses.replace(
        get_arch(cfg["program_arch"]), name=cfg["name"],
        n_layers=m["layers"], d_model=m["d"], n_heads=m["h"],
        n_kv_heads=m["hkv"], head_dim=m["hd"], d_ff=m["f"],
        vocab_size=m["vocab"], norm_eps=m["eps"], act="silu",
        tie_embeddings=bool(cfg["tie_word_embeddings"]),
        rope=False,                  # NoPE (the reference accepts no other)
        attention_multiplier=m["attn_mult"],
        embedding_multiplier=m["emb_mult"],
        residual_multiplier=m["res_mult"], logits_scaling=m["logits_div"],
        layer_pattern=tuple(KIND[t] for t in m["pattern"]),
        ssm=SSMConfig(d_state=m["n"], d_conv=m["k"],
                      expand=cfg["mamba_expand"], head_dim=m["p"],
                      chunk=cfg["mamba_chunk_size"]),
        dtype=cfg["dtype"])
    # the check compares logits, which a bf16 lane state hardly moves (its
    # gap reads like the float32 state's), so the stated dtype is held here
    state = ssm_cache_spec(a, 1)["state"].dtype
    if state != jnp.dtype(cfg["ssm_state_dtype"]):
        raise ValueError(f"the program keeps its SSM state in {state}, the "
                         f"configuration states {cfg['ssm_state_dtype']}")
    return a


def params(cfg: dict, a, g: dict, s: dict) -> dict:
    """Program parameter tree from global leaves ``g`` and period leaves
    ``s`` stacked along a leading periods axis (traceable)."""
    m = dims(cfg)
    emb = g["embed"]
    emb = jnp.pad(emb, ((0, a.padded_vocab - emb.shape[0]), (0, 0)))
    seg = {}
    idx = {"mamba": 0, "attention": 0}
    for i, kind in enumerate(m["pattern"]):
        j = idx[kind]
        idx[kind] += 1
        pre = "m_" if kind == "mamba" else "a_"

        def at(name, _pre=pre, _j=j):
            return s[_pre + name][:, _j]

        layer = {"ln1": at("ln1"), "ln2": at("ln2"),
                 "mlp": {k: at(k) for k in ("w_gate", "w_up", "w_down")}}
        if kind == "mamba":
            layer["mix"] = {
                "in_proj": at("in_proj"), "conv_w": at("conv_w"),
                "conv_b": at("conv_b"), "a_log": at("a_log"),
                "d_skip": at("d"), "dt_bias": at("dt_bias"),
                "norm": at("norm"), "out_proj": at("out_proj")}
        else:
            layer["attn"] = {k: at(k) for k in ("wq", "wk", "wv", "wo")}
        seg[f"s{i}_{KIND[kind]}"] = layer
    return {"embed": emb, "final_norm": g["norm"], "seg0": seg}
