"""Model construction + per-shape input specs (the public model API).

``build_model(cfg)`` returns a model object with the uniform surface:
  param_specs / init / abstract / forward / loss / prefill / decode_step /
  decode_step_paged / extend_step / cache_specs / cache_page_specs.
``input_specs(cfg, shape)`` builds the ShapeDtypeStruct stand-ins for every
model input of a (arch × shape) dry-run cell — no device allocation;
``paged_input_specs`` does the same for the block-table-native decode path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.configs.shapes import ShapeSuite
from .common import PSpec
from .encdec import EncDecLM
from .transformer import DecoderLM

VISION_DIM = 1024    # CLIP-L hidden size (stub frontend output)


class VLM(DecoderLM):
    """LLaVA-NeXT: dense backbone + 2-layer GeLU multimodal projector.

    The anyres vision tower is a stub per the assignment — ``input_specs``
    provides precomputed patch embeddings (B, P, VISION_DIM); the projector
    and everything after it are real, trainable layers.
    """

    def param_specs(self):
        specs = super().param_specs()
        d = self.cfg.d_model
        dt = self.cfg.jdtype
        specs["mm_proj"] = {
            "w1": PSpec((VISION_DIM, d), (None, "embed"), dt),
            "b1": PSpec((d,), ("embed",), dt, "zeros"),
            "w2": PSpec((d, d), ("embed", "embed2"), dt),
            "b2": PSpec((d,), ("embed",), dt, "zeros"),
        }
        return specs

    def project_patches(self, params, patches):
        p = params["mm_proj"]
        h = jax.nn.gelu(patches.astype(self.cfg.jdtype) @ p["w1"] + p["b1"])
        return h @ p["w2"] + p["b2"]

    def forward(self, params, tokens, rules=None, impl="xla", extra_embeds=None):
        if extra_embeds is not None and extra_embeds.shape[-1] == VISION_DIM:
            extra_embeds = self.project_patches(params, extra_embeds)
        return super().forward(params, tokens, rules, impl, extra_embeds)

    def prefill(self, params, tokens, rules=None, impl="xla", extra_embeds=None,
                max_len=None):
        if extra_embeds is not None and extra_embeds.shape[-1] == VISION_DIM:
            extra_embeds = self.project_patches(params, extra_embeds)
        return super().prefill(params, tokens, rules, impl, extra_embeds, max_len)

    def loss(self, params, batch, rules=None, impl="xla"):
        batch = dict(batch)
        if "patches" in batch:
            batch["extra_embeds"] = batch.pop("patches")
        return super().loss(params, batch, rules, impl)


def build_model(cfg: ArchConfig):
    if cfg.family == "audio":
        return EncDecLM(cfg)
    if cfg.family == "vlm":
        return VLM(cfg)
    return DecoderLM(cfg)


def cache_page_specs(cfg_or_model, lanes: int, n_pages: int, page_size: int):
    """Per-layer page-pool shapes of the paged serving cache (the public
    entry the serve subsystem and sharding rules consume): every seq-dim
    cache leaf becomes (layers, n_pages, *page) — a k/v page (Hkv,
    page_size, Dp), any other (page_size, *tail); recurrent-state
    leaves keep their per-lane layout.  Accepts an ArchConfig or a built
    model."""
    model = (
        cfg_or_model
        if hasattr(cfg_or_model, "cache_page_specs")
        else build_model(cfg_or_model)
    )
    return model.cache_page_specs(lanes, n_pages, page_size)


def paged_input_specs(cfg_or_model, lanes: int, pages_per_lane: int) -> dict:
    """ShapeDtypeStruct stand-ins for the ``decode_step_paged`` host inputs
    (the block-table-native decode surface the paged engine drives): one
    token per lane, a per-lane block table, per-lane positions and the
    active mask.  Pair with ``cache_page_specs`` for the pool tree."""
    i32 = jnp.int32
    return {
        "tokens": jax.ShapeDtypeStruct((lanes, 1), i32),
        "block_tables": jax.ShapeDtypeStruct((lanes, pages_per_lane), i32),
        "positions": jax.ShapeDtypeStruct((lanes,), i32),
        "active": jax.ShapeDtypeStruct((lanes,), jnp.bool_),
    }


# ---------------------------------------------------------------------------
# Dry-run input specs (ShapeDtypeStruct stand-ins, shardable, no allocation)
# ---------------------------------------------------------------------------


def input_specs(cfg: ArchConfig, shape: ShapeSuite) -> dict:
    b, s = shape.global_batch, shape.seq_len
    i32 = jnp.int32

    def tok(*sh):
        return jax.ShapeDtypeStruct(sh, i32)

    if shape.kind == "train":
        batch: dict = {}
        if cfg.family == "vlm":
            p = cfg.vision.n_image_tokens
            batch["tokens"] = tok(b, s - p)
            batch["targets"] = tok(b, s - p)
            batch["patches"] = jax.ShapeDtypeStruct((b, p, VISION_DIM), jnp.bfloat16)
        elif cfg.family == "audio":
            batch["tokens"] = tok(b, s)
            batch["targets"] = tok(b, s)
            batch["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.encoder.n_ctx, cfg.d_model), jnp.bfloat16
            )
        else:
            batch["tokens"] = tok(b, s)
            batch["targets"] = tok(b, s)
        return batch

    if shape.kind == "prefill":
        batch = {}
        if cfg.family == "vlm":
            p = cfg.vision.n_image_tokens
            batch["tokens"] = tok(b, s - p)
            batch["patches"] = jax.ShapeDtypeStruct((b, p, VISION_DIM), jnp.bfloat16)
        elif cfg.family == "audio":
            batch["tokens"] = tok(b, s)
            batch["frames"] = jax.ShapeDtypeStruct(
                (b, cfg.encoder.n_ctx, cfg.d_model), jnp.bfloat16
            )
        else:
            batch["tokens"] = tok(b, s)
        return batch

    # decode: one new token against a seq_len-deep cache
    return {
        "tokens": tok(b, 1),
        "position": jax.ShapeDtypeStruct((), i32),
    }
