"""Operations and bytes of the Mamba-2 / attention hybrid, from shapes and
lengths.

The counts are what the mathematics needs, however the program computes
it: recomputation, padding and layout copies are not counted.  A decode
step reads every weight once; each decoded token reads its K/V rows over
its context in the attention layers and writes its own, and reads and
writes its lane's float32 SSM state and its conv state once in every mamba
layer.
"""
from __future__ import annotations

from chipbench.reference.ssd_hybrid import dims

BF16, F32 = 2, 4


def _per_kind(cfg: dict) -> tuple[int, int]:
    """(mamba layers, attention layers) of the whole model."""
    m = dims(cfg)
    return m["n_mamba"] * m["periods"], m["n_attn"] * m["periods"]


def matmul_params(cfg: dict) -> int:
    """Weights that multiply each token: every layer's projections and MLP
    and the output head (the embedding lookup is a gather, not a matmul)."""
    m = dims(cfg)
    d, f, di, nh = m["d"], m["f"], m["di"], m["nh"]
    qd, kd = m["h"] * m["hd"], m["hkv"] * m["hd"]
    mlp = 3 * d * f
    mamba = d * (2 * di + 2 * m["n"] + nh) + di * d + mlp
    attn = d * qd + 2 * d * kd + qd * d + mlp
    n_m, n_a = _per_kind(cfg)
    return n_m * mamba + n_a * attn + d * m["vocab"]


def ssm_token_flops(cfg: dict) -> float:
    """One decoded token's state update in one mamba layer: ``exp(dt A) h +
    dt x B^T`` (3 FLOPs per state element) and ``y = h C`` (2), plus the
    conv's multiply-adds."""
    m = dims(cfg)
    return 5.0 * m["nh"] * m["p"] * m["n"] + 2.0 * m["k"] * m["conv"]


def decode_token_flops(cfg: dict, context: int) -> float:
    """One decoded token whose attention reads ``context`` positions: 2
    FLOPs per weight, q.k and p.v over the context in every attention
    layer, and the state update in every mamba layer."""
    m = dims(cfg)
    n_m, n_a = _per_kind(cfg)
    attn = 4.0 * m["h"] * m["hd"] * context * n_a
    return 2.0 * matmul_params(cfg) + attn + n_m * ssm_token_flops(cfg)


def prefill_token_flops(cfg: dict) -> float:
    """A prompt token's projections and MLP, without the output head, the
    attention and the SSD scan (a lower bound: the chunk positions are not
    known)."""
    m = dims(cfg)
    return 2.0 * (matmul_params(cfg) - m["d"] * m["vocab"])


def attn_layers(cfg: dict) -> int:
    """The attention layers of the whole model, the only layers that run
    the paged decode-attention kernel."""
    return _per_kind(cfg)[1]


def paged_attn_call(cfg: dict, lengths) -> tuple[float, float]:
    """One attention layer's decode attention over the lanes' KV: FLOPs and
    HBM bytes for ``lengths`` (valid positions per decoded token).  Bytes:
    each token's K and V rows (bf16) over its context, plus its query and
    output rows; the kernel's padding of 64-wide heads is not counted."""
    m = dims(cfg)
    h, hkv, hd = m["h"], m["hkv"], m["hd"]
    total = float(sum(lengths))
    flops = 4.0 * h * hd * total
    kv = 2.0 * hkv * hd * BF16 * total
    qo = 2.0 * h * hd * BF16 * len(lengths)
    return flops, kv + qo


def state_bytes(cfg: dict) -> int:
    """One lane's recurrent state over the whole model: the float32 SSM
    state and the conv ring in every mamba layer."""
    m = dims(cfg)
    n_m, _ = _per_kind(cfg)
    ssm = m["nh"] * m["p"] * m["n"] * F32
    conv = (m["k"] - 1) * m["conv"] * BF16
    return n_m * (ssm + conv)


def decode_token_bytes(cfg: dict, context: int) -> float:
    """HBM bytes one decoded token moves besides the weights: its K/V rows
    over ``context`` read and its own written in every attention layer, and
    its lane state read and written once."""
    m = dims(cfg)
    _, n_a = _per_kind(cfg)
    kv_row = 2 * m["hkv"] * m["hd"] * BF16
    return float(n_a * kv_row * (context + 1) + 2 * state_bytes(cfg))


def decode_steps(cfg: dict, n_steps: int, contexts) -> tuple[float, float]:
    """FLOPs and HBM bytes of ``n_steps`` decode steps that decoded tokens
    with ``contexts``: the weights once a step, and each token's own
    traffic."""
    flops = sum(decode_token_flops(cfg, c) for c in contexts)
    nbytes = (n_steps * matmul_params(cfg) * BF16
              + sum(decode_token_bytes(cfg, c) for c in contexts))
    return flops, nbytes
