"""Uniform model API: one ``ModelBundle`` per architecture family.

The bundle is what every higher layer consumes — the FL fedstep (loss_fn),
the launcher (train/serve steps), the dry-run (input_specs) and the smoke
tests. Batch layouts per family:

* text (dense/moe/ssm/hybrid): ``{"tokens": (B, S) int32}``
* vlm:   ``{"tokens", "patch_embeds": (B, P, d), "positions": (3, B, S)}``
  — patch embeddings (stub vision frontend) overwrite the first P token
  slots; M-RoPE positions carry the three t/h/w streams.
* audio: ``{"tokens", "frames": (B, F, d)}`` — stub conv-frontend frames
  feed the encoder; the decoder computes the LM loss.

Serve batches are ``{"token": (B, 1) int32}`` against a model cache.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models import encdec, transformer
from repro.models.config import ModelConfig, ShapeConfig

Tree = Any


@dataclasses.dataclass(frozen=True)
class ModelBundle:
    cfg: ModelConfig
    init: Callable[[jax.Array], Tree]
    # (params, batch, rng) -> (loss, counters): a MoE model counts its
    # picks (``repro.models.moe.COUNTERS``), summed over layers; others none
    loss_fn: Callable[[Tree, Dict[str, jax.Array], jax.Array],
                      Tuple[jax.Array, Dict[str, jax.Array]]]
    init_cache: Callable[[int, int], Tree]
    serve_step: Callable[[Tree, Tree, Dict[str, jax.Array]], Tuple[jax.Array, Tree]]
    prefill: Callable[[Tree, Dict[str, jax.Array], Tree], Tuple[jax.Array, Tree]]
    input_specs: Callable[[ShapeConfig], Dict[str, jax.ShapeDtypeStruct]]


def _embed_with_patches(params, cfg, tokens, patch_embeds):
    """Vision tokens (stub patch embeddings) occupy the first P slots."""
    from repro.models.layers import embed_apply

    h = embed_apply(params["embed"], tokens)
    P = patch_embeds.shape[1]
    return h.at[:, :P].set(patch_embeds.astype(h.dtype))


def build_model(cfg: ModelConfig) -> ModelBundle:
    if cfg.encoder_layers:
        return _build_encdec(cfg)
    return _build_decoder_only(cfg)


# --------------------------------------------------------------------- #
# decoder-only families (dense / moe / ssm / hybrid / vlm)
# --------------------------------------------------------------------- #
def _build_decoder_only(cfg: ModelConfig) -> ModelBundle:
    is_vlm = cfg.family == "vlm"
    dtype = jnp.dtype(cfg.param_dtype)

    def init(rng):
        return transformer.init_params(rng, cfg)

    def loss_fn(params, batch, rng):
        del rng
        if is_vlm:
            embeds = _embed_with_patches(
                params, cfg, batch["tokens"], batch["patch_embeds"]
            )
            return transformer.lm_loss(
                params,
                cfg,
                batch["tokens"],
                embeds=embeds,
                positions=batch.get("positions"),
            )
        return transformer.lm_loss(params, cfg, batch["tokens"])

    def init_cache(batch_size, max_len):
        return transformer.init_cache(cfg, batch_size, max_len)

    def serve_step(params, cache, batch):
        return transformer.decode_step(params, cfg, batch["token"], cache)

    def prefill(params, batch, cache):
        embeds = None
        if is_vlm:
            embeds = _embed_with_patches(
                params, cfg, batch["tokens"], batch["patch_embeds"]
            )
        logits, new_cache, _ = transformer.forward(
            params,
            cfg,
            tokens=batch["tokens"],
            embeds=embeds,
            positions=batch.get("positions"),
            cache=cache,
            mode="full",
        )
        # serving prefill: only the last position's logits are needed to
        # sample the first generated token (full logits would be B*S*V).
        return logits[:, -1:], new_cache

    def input_specs(shape: ShapeConfig):
        B, S = shape.global_batch, shape.seq_len
        if shape.kind == "decode":
            return {"token": jax.ShapeDtypeStruct((B, 1), jnp.int32)}
        specs = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        if is_vlm:
            P = cfg.vision_patches or 256
            specs["patch_embeds"] = jax.ShapeDtypeStruct((B, P, cfg.d_model), dtype)
            specs["positions"] = jax.ShapeDtypeStruct((3, B, S), jnp.int32)
        return specs

    return ModelBundle(
        cfg=cfg,
        init=init,
        loss_fn=loss_fn,
        init_cache=init_cache,
        serve_step=serve_step,
        prefill=prefill,
        input_specs=input_specs,
    )


# --------------------------------------------------------------------- #
# encoder-decoder (audio)
# --------------------------------------------------------------------- #
def _build_encdec(cfg: ModelConfig) -> ModelBundle:
    dtype = jnp.dtype(cfg.param_dtype)

    def init(rng):
        return encdec.init_params(rng, cfg)

    def loss_fn(params, batch, rng):
        del rng
        return encdec.lm_loss(params, cfg, batch["tokens"], batch["frames"]), {}

    def init_cache(batch_size, max_len):
        return encdec.init_cache(cfg, batch_size, max_len)

    def serve_step(params, cache, batch):
        logits, new_cache, _ = encdec.decode_forward(
            params, cfg, batch["token"], memory=None, cache=cache, mode="decode"
        )
        return logits, new_cache

    def prefill(params, batch, cache):
        memory = encdec.encode(params, cfg, batch["frames"])
        logits, new_cache, _ = encdec.decode_forward(
            params, cfg, batch["tokens"], memory, cache=cache, mode="full"
        )
        return logits[:, -1:], new_cache

    def input_specs(shape: ShapeConfig):
        B, S = shape.global_batch, shape.seq_len
        F = cfg.frontend_len or 1024
        if shape.kind == "decode":
            return {"token": jax.ShapeDtypeStruct((B, 1), jnp.int32)}
        return {
            "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
            "frames": jax.ShapeDtypeStruct((B, F, cfg.d_model), dtype),
        }

    return ModelBundle(
        cfg=cfg,
        init=init,
        loss_fn=loss_fn,
        init_cache=init_cache,
        serve_step=serve_step,
        prefill=prefill,
        input_specs=input_specs,
    )
