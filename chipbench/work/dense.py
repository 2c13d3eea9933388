"""Operations and bytes of the dense GQA decoder, from shapes and lengths.

The counts are what the mathematics needs, however the program computes
it: recomputation, padding and layout copies are not counted.
"""
from __future__ import annotations

from chipbench.reference.dense import dims

BF16 = 2


def matmul_params(cfg: dict) -> int:
    """Weights that multiply each token: every layer's projections and the
    output head (the embedding lookup is a gather, not a matmul)."""
    m = dims(cfg)
    d, qd, kd, f = m["d"], m["h"] * m["hd"], m["hkv"] * m["hd"], m["f"]
    per_layer = d * qd + 2 * d * kd + qd * d + 3 * d * f
    return m["layers"] * per_layer + d * m["vocab"]


def decode_token_flops(cfg: dict, context: int) -> float:
    """One decoded token whose attention reads ``context`` positions: 2
    FLOPs per weight, plus q.k and p.v over the context in every layer."""
    m = dims(cfg)
    attn = 4.0 * m["h"] * m["hd"] * context * m["layers"]
    return 2.0 * matmul_params(cfg) + attn


def prefill_token_flops(cfg: dict) -> float:
    """A prompt token's projections and MLP, without the output head and
    without attention (a lower bound: the chunk positions are not known)."""
    m = dims(cfg)
    return 2.0 * (matmul_params(cfg) - m["d"] * m["vocab"])


def paged_attn_call(cfg: dict, lengths) -> tuple[float, float]:
    """One layer's decode attention over the lanes' KV: FLOPs and HBM bytes
    for ``lengths`` (valid positions per active lane).  Bytes: each lane's
    K and V rows (bf16) plus its query and output rows."""
    m = dims(cfg)
    h, hkv, hd = m["h"], m["hkv"], m["hd"]
    total = float(sum(lengths))
    flops = 4.0 * h * hd * total
    kv = 2.0 * hkv * hd * BF16 * total
    qo = 2.0 * h * hd * BF16 * len(lengths)
    return flops, kv + qo
