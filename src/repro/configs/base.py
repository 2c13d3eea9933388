"""Architecture configuration schema + registry.

One ``ArchConfig`` describes any member of the assigned pool (dense / MoE /
SSM / hybrid / VLM / audio).  ``reduced()`` derives the CPU smoke-test config
of the same family.  The four assigned input-shape suites live in
``configs.shapes``.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
import jax.numpy as jnp


@dataclass(frozen=True)
class MLAConfig:
    """DeepSeek multi-head latent attention dims."""

    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128


@dataclass(frozen=True)
class SSMConfig:
    """Mamba-2 SSD dims."""

    d_state: int = 128
    d_conv: int = 4
    expand: int = 2
    head_dim: int = 64
    chunk: int = 256

    def d_inner(self, d_model: int) -> int:
        return self.expand * d_model

    def n_heads(self, d_model: int) -> int:
        return self.d_inner(d_model) // self.head_dim


@dataclass(frozen=True)
class RGLRUConfig:
    """RecurrentGemma RG-LRU recurrent block dims."""

    lru_width: int = 4096
    d_conv: int = 4
    attn_window: int = 2048


@dataclass(frozen=True)
class EncoderConfig:
    """Whisper-style encoder (conv frontend stubbed — precomputed frames)."""

    n_layers: int = 32
    n_ctx: int = 1500          # 30 s of audio at 50 Hz after conv stride 2


@dataclass(frozen=True)
class VisionStubConfig:
    """LLaVA-NeXT anyres frontend stub: precomputed patch embeddings."""

    n_image_tokens: int = 2880     # anyres: base 576 + 4 tiles x 576
    image_every: int = 1           # images per sequence


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                    # dense | moe | ssm | hybrid | vlm | audio
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int | None = None
    # attention flavor
    qkv_bias: bool = False
    qk_norm: bool = False
    rope_theta: float = 10000.0
    sliding_window: int | None = None
    rope: bool = True                 # off: whisper's learned positions, NoPE
    attention_multiplier: float | None = None   # softmax scale; None = hd**-0.5
    max_position: int = 1 << 20
    # embedding / head
    tie_embeddings: bool = False
    # gemma: x *= sqrt(d_model); a flag, not a multiplier, because the
    # factor follows d_model, which reduced() changes
    embed_scale: bool = False
    rms_plus_one: bool = False        # gemma: (1 + w) RMSNorm weight
    act: str = "silu"
    norm_eps: float = 1e-6
    # scalar multipliers (Granite): embeddings x embedding_multiplier, every
    # residual branch x residual_multiplier, logits / logits_scaling; 1.0
    # leaves the graph untouched
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # hybrids: the layer kinds of one period, repeated (scanned) over depth
    # with a remainder segment; () = the family's uniform stack
    layer_pattern: tuple[str, ...] = ()
    # MoE
    n_experts: int = 0
    experts_per_token: int = 0
    n_shared_experts: int = 0
    moe_d_ff: int | None = None
    first_dense_layers: int = 0
    capacity_factor: float = 1.25
    router_aux_weight: float = 0.001
    # family extensions
    mla: MLAConfig | None = None
    ssm: SSMConfig | None = None
    rglru: RGLRUConfig | None = None
    encoder: EncoderConfig | None = None
    vision: VisionStubConfig | None = None
    # numerics / execution
    dtype: str = "bfloat16"
    attn_chunk: int = 1024
    remat: str = "full"               # full | dots | none
    scan_layers: bool = True
    train_microbatches: int = 1
    opt_state_dtype: str = "float32"  # "bfloat16" = compressed moments
    grad_accum_dtype: str = "float32" # "bfloat16" halves grad-reduce wire
    decode_cache_in_carry: bool = False  # §Perf: alias cache in scan carry
    decode_unroll_layers: bool = True    # §Perf: unroll decode, per-layer
                                         # cache leaves alias via donation
    # provenance
    source: str = ""

    # ------------------------------------------------------------------

    @property
    def hd(self) -> int:
        return self.head_dim if self.head_dim else self.d_model // self.n_heads

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def padded_vocab(self) -> int:
        """Vocab padded to a multiple of 128 so the vocab axis shards on any
        mesh (standard TPU practice); loss masks the padding columns."""
        return ((self.vocab_size + 127) // 128) * 128

    @property
    def jdtype(self):
        return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[self.dtype]

    def n_params(self) -> int:
        """Approximate total parameter count (embeddings included)."""
        from repro.models.api import build_model

        from repro.models.common import param_count

        return param_count(build_model(self).param_specs())

    def n_active_params(self) -> int:
        """Active parameters per token (MoE: routed top-k + shared only)."""
        if not self.is_moe:
            return self.n_params()
        total = self.n_params()
        ff = self.moe_d_ff or self.d_ff
        n_moe_layers = self.n_layers - self.first_dense_layers
        per_expert = 3 * self.d_model * ff
        inactive = n_moe_layers * per_expert * (
            self.n_experts - self.experts_per_token
        )
        return total - inactive

    def reduced(self) -> "ArchConfig":
        """Tiny same-family config for CPU smoke tests."""
        kw: dict = dict(
            name=self.name + "-smoke",
            n_layers=min(self.n_layers, len(self.layer_pattern) or 2),
            d_model=128,
            n_heads=4,
            n_kv_heads=min(self.n_kv_heads, 2) if self.n_kv_heads < self.n_heads else 4,
            d_ff=256,
            vocab_size=512,
            head_dim=32,
            max_position=4096,
            attn_chunk=64,
            remat="none",
        )
        if self.is_moe:
            kw.update(n_experts=4, experts_per_token=2, moe_d_ff=64,
                      first_dense_layers=min(self.first_dense_layers, 1))
        if self.mla:
            kw.update(mla=MLAConfig(q_lora_rank=64, kv_lora_rank=32,
                                    qk_nope_head_dim=32, qk_rope_head_dim=16,
                                    v_head_dim=32))
        if self.ssm:
            kw.update(ssm=SSMConfig(d_state=16, d_conv=4, expand=2,
                                    head_dim=32, chunk=32))
        if self.rglru:
            kw.update(rglru=RGLRUConfig(lru_width=128, d_conv=4,
                                        attn_window=64))
        if self.encoder:
            kw.update(encoder=EncoderConfig(n_layers=2, n_ctx=64))
        if self.vision:
            kw.update(vision=VisionStubConfig(n_image_tokens=16))
        return dataclasses.replace(self, **kw)


_REGISTRY: dict[str, ArchConfig] = {}


def register(cfg: ArchConfig) -> ArchConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_arch(name: str) -> ArchConfig:
    if not _REGISTRY:
        from repro import configs as _  # noqa: F401  (populates registry)
    from repro import configs as c

    c.load_all()
    if name not in _REGISTRY:
        raise KeyError(f"unknown arch {name!r}; have {sorted(_REGISTRY)}")
    return _REGISTRY[name]


def all_archs() -> dict[str, ArchConfig]:
    from repro import configs as c

    c.load_all()
    return dict(_REGISTRY)
