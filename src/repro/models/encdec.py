"""Encoder-decoder backbone (seamless-m4t): bidirectional encoder over stub
frame embeddings + causal decoder with cross-attention.

Per the modality carve-out, the audio frontend (mel + conv feature extractor)
is a stub: the encoder consumes precomputed frame embeddings (B, F, d). The
decoder is a standard causal transformer with per-layer cross-attention; at
decode time the cross K/V are precomputed once from the encoder memory and
carried in the cache.
"""
from __future__ import annotations

from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as attn
from repro.models.blocks import _attn_core_decode, _attn_core_full, attn_cache_init
from repro.models.config import ModelConfig
from repro.models.layers import (
    embed_apply,
    embed_init,
    mlp_apply,
    mlp_init,
    rmsnorm_apply,
    rmsnorm_init,
    unembed_apply,
)
from repro.models.transformer import default_positions

Tree = Any


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.param_dtype)


def _enc_block_init(rng, cfg: ModelConfig, dtype) -> Tree:
    k1, k2 = jax.random.split(rng)
    return {
        "ln1": rmsnorm_init(cfg.d_model, dtype),
        "attn": attn.attn_init(k1, cfg, dtype),
        "ln2": rmsnorm_init(cfg.d_model, dtype),
        "mlp": mlp_init(k2, cfg.d_model, cfg.d_ff, dtype),
    }


def _dec_block_init(rng, cfg: ModelConfig, dtype) -> Tree:
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "ln1": rmsnorm_init(cfg.d_model, dtype),
        "self": attn.attn_init(k1, cfg, dtype),
        "ln_x": rmsnorm_init(cfg.d_model, dtype),
        "cross": attn.attn_init(k2, cfg, dtype),
        "ln2": rmsnorm_init(cfg.d_model, dtype),
        "mlp": mlp_init(k3, cfg.d_model, cfg.d_ff, dtype),
    }


def init_params(rng, cfg: ModelConfig) -> Tree:
    dtype = _dtype(cfg)
    k_emb, k_enc, k_dec, k_un = jax.random.split(rng, 4)
    enc_ks = jax.random.split(k_enc, cfg.encoder_layers)
    dec_ks = jax.random.split(k_dec, cfg.num_layers)

    def stack(trees):
        return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)

    params = {
        "embed": embed_init(k_emb, cfg.padded_vocab, cfg.d_model, dtype),
        "encoder": stack([_enc_block_init(k, cfg, dtype) for k in enc_ks]),
        "decoder": stack([_dec_block_init(k, cfg, dtype) for k in dec_ks]),
        "ln_enc": rmsnorm_init(cfg.d_model, dtype),
        "ln_f": rmsnorm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = embed_init(k_un, cfg.padded_vocab, cfg.d_model, dtype)
    return params


def encode(params: Tree, cfg: ModelConfig, frames: jax.Array) -> jax.Array:
    """frames: (B, F, d) stub frontend embeddings -> encoder memory."""
    B, F, _ = frames.shape
    positions = default_positions(cfg, B, F)

    def body(h, p):
        a = rmsnorm_apply(p["ln1"], h, cfg.norm_eps)
        q = attn.project_q(p["attn"], a, cfg)
        k, v = attn.project_kv(p["attn"], a, cfg)
        out = attn.chunked_attention(
            q, k, v, causal=False, q_chunk=cfg.q_chunk,
            use_scan=cfg.scan_attn_chunks,
        )
        h = h + attn.attn_output(p["attn"], out, cfg)
        m = rmsnorm_apply(p["ln2"], h, cfg.norm_eps)
        return h + mlp_apply(p["mlp"], m, cfg.activation), None

    if cfg.remat:
        body = jax.checkpoint(body)
    h, _ = jax.lax.scan(body, frames, params["encoder"])
    return rmsnorm_apply(params["ln_enc"], h, cfg.norm_eps)


def _cross_attend(p: Tree, h: jax.Array, mem_k, mem_v, cfg: ModelConfig) -> jax.Array:
    q = attn.project_q(p, h, cfg)  # no rope on cross-attention
    out = attn.chunked_attention(
        q, mem_k, mem_v, causal=False, q_chunk=cfg.q_chunk,
        use_scan=cfg.scan_attn_chunks,
    )
    return attn.attn_output(p, out, cfg)


def decode_hidden(
    params: Tree,
    cfg: ModelConfig,
    tokens: jax.Array,
    memory: jax.Array,
    cache: Optional[Tree] = None,
    mode: str = "full",
) -> Tuple[jax.Array, Optional[Tree], jax.Array]:
    """Causal decoder over ``tokens`` attending to encoder ``memory``."""
    h = embed_apply(params["embed"], tokens)
    B, S = h.shape[:2]
    offset = cache["len"] if (cache is not None and mode == "decode") else 0
    positions = default_positions(cfg, B, S, offset=offset)

    def body(carry, xs):
        h = carry
        if cache is not None:
            p, c = xs
        else:
            p, c = xs, None
        a = rmsnorm_apply(p["ln1"], h, cfg.norm_eps)
        if mode == "decode":
            s, sc = _attn_core_decode(p["self"], a, positions, c["self"], cfg)
        else:
            s, sc = _attn_core_full(
                p["self"], a, positions, c["self"] if c else None, cfg
            )
        h = h + s
        xh = rmsnorm_apply(p["ln_x"], h, cfg.norm_eps)
        if c is not None and mode == "decode":
            mem_k, mem_v = c["cross_k"], c["cross_v"]
        else:
            mem_k, mem_v = attn.project_kv(p["cross"], memory, cfg)
        h = h + _cross_attend(p["cross"], xh, mem_k, mem_v, cfg)
        m = rmsnorm_apply(p["ln2"], h, cfg.norm_eps)
        h = h + mlp_apply(p["mlp"], m, cfg.activation)
        if c is not None:
            new_c = dict(c)
            new_c["self"] = sc
            if mode != "decode":
                new_c["cross_k"], new_c["cross_v"] = mem_k, mem_v
            return h, new_c
        return h, 0

    if cfg.remat:
        body = jax.checkpoint(body)
    xs = (params["decoder"], cache["layers"]) if cache is not None else params["decoder"]
    h, scanned = jax.lax.scan(body, h, xs)
    new_cache = None
    if cache is not None:
        new_cache = {"layers": scanned, "len": cache["len"] + S}
    h = rmsnorm_apply(params["ln_f"], h, cfg.norm_eps)
    return h, new_cache, jnp.float32(0.0)


def decode_forward(
    params: Tree,
    cfg: ModelConfig,
    tokens: jax.Array,
    memory: jax.Array,
    cache: Optional[Tree] = None,
    mode: str = "full",
) -> Tuple[jax.Array, Optional[Tree], jax.Array]:
    h, new_cache, aux = decode_hidden(
        params, cfg, tokens, memory, cache=cache, mode=mode
    )
    unemb = params["embed"] if cfg.tie_embeddings else params["unembed"]
    logits = unembed_apply(unemb, h)[..., : cfg.vocab_size]
    return logits, new_cache, aux


def init_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=None) -> Tree:
    """Decoder cache: per-layer self-attn ring + precomputed cross K/V."""
    dtype = dtype or _dtype(cfg)
    L = cfg.num_layers
    F = cfg.frontend_len

    def one():
        return {
            "self": attn_cache_init(cfg, batch, max_len, dtype),
            "cross_k": jnp.zeros((batch, F, cfg.num_kv_heads, cfg.head_dim), dtype),
            "cross_v": jnp.zeros((batch, F, cfg.num_kv_heads, cfg.head_dim), dtype),
        }

    layers = jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x, (L,) + x.shape), one()
    )
    return {"layers": layers, "len": jnp.zeros((), jnp.int32)}


def lm_loss(
    params: Tree, cfg: ModelConfig, tokens: jax.Array, frames: jax.Array
) -> jax.Array:
    from repro.models.transformer import chunked_ce

    memory = encode(params, cfg, frames)
    h, _, _ = decode_hidden(params, cfg, tokens, memory)
    unemb = params["embed"] if cfg.tie_embeddings else params["unembed"]
    return chunked_ce(
        h[:, :-1], unemb, tokens[:, 1:], cfg.vocab_size,
        use_scan=cfg.scan_attn_chunks,
    )
