"""Core layers: norms, rotary embeddings (RoPE / M-RoPE), gated MLPs.

Parameters are plain dict pytrees; init fns take an rng and return the dict.
All matmuls keep an explicit f32 accumulation via ``preferred_element_type``
so bf16 params behave like TPU MXU matmuls.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

Tree = Dict[str, jax.Array]


def dot(x: jax.Array, w: jax.Array) -> jax.Array:
    return jax.lax.dot_general(
        x,
        w,
        (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)


# --------------------------------------------------------------------- #
# init
# --------------------------------------------------------------------- #
def dense_init(rng, d_in: int, d_out: int, dtype, bias: bool = False) -> Tree:
    w = jax.random.normal(rng, (d_in, d_out), jnp.float32) * (d_in**-0.5)
    p = {"w": w.astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense_apply(p: Tree, x: jax.Array) -> jax.Array:
    y = dot(x, p["w"])
    if "b" in p:
        y = y + p["b"].astype(y.dtype)
    return y


def embed_init(rng, vocab: int, d: int, dtype) -> Tree:
    return {"emb": jax.random.normal(rng, (vocab, d), jnp.float32).astype(dtype) * 0.02}


def embed_apply(p: Tree, tokens: jax.Array) -> jax.Array:
    # looked up from an f32 view: the values are the table's, and the
    # backward adds every occurrence of a token into its row in f32 before
    # rounding once (a bf16 scatter-add would round at every occurrence)
    emb = p["emb"]
    return jnp.take(emb.astype(jnp.float32), tokens, axis=0).astype(emb.dtype)


def unembed_apply(p: Tree, x: jax.Array) -> jax.Array:
    """Logits via the (tied or separate) unembedding matrix."""
    return jax.lax.dot_general(
        x,
        p["emb"],
        (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


# --------------------------------------------------------------------- #
# norms
# --------------------------------------------------------------------- #
def rmsnorm_init(d: int, dtype) -> Tree:
    return {"scale": jnp.ones((d,), dtype)}


def rmsnorm_apply(p: Tree, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------- #
# rotary embeddings
# --------------------------------------------------------------------- #
def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (
        theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    )


def apply_rope(
    x: jax.Array, positions: jax.Array, theta: float = 10_000.0
) -> jax.Array:
    """x: (..., S, H, Dh); positions: broadcastable to (..., S)."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta)  # (Dh/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, Dh/2)
    angles = angles[..., None, :]  # (..., S, 1, Dh/2) broadcast over heads
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(
    x: jax.Array,
    positions: jax.Array,
    sections: Tuple[int, int, int],
    theta: float = 10_000.0,
) -> jax.Array:
    """M-RoPE (Qwen2-VL): rotary split into temporal/height/width sections.

    x: (B, S, H, Dh); positions: (3, B, S) — one position stream per section.
    ``sections`` are sizes in *frequency* space (sum == Dh/2).
    """
    head_dim = x.shape[-1]
    assert sum(sections) == head_dim // 2, (sections, head_dim)
    freqs = rope_freqs(head_dim, theta)  # (Dh/2,)
    # pick which position stream drives each frequency band
    section_id = jnp.repeat(
        jnp.arange(3), jnp.array(sections), total_repeat_length=head_dim // 2
    )  # static
    pos = positions.astype(jnp.float32)  # (3, B, S)
    # angles: (B, S, Dh/2), choosing pos[section_id[i]] for band i
    pos_per_band = jnp.take(pos, section_id, axis=0)  # (Dh/2, B, S)
    angles = jnp.moveaxis(pos_per_band, 0, -1) * freqs  # (B, S, Dh/2)
    angles = angles[..., None, :]
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------- #
# gated MLPs
# --------------------------------------------------------------------- #
def mlp_init(rng, d: int, d_ff: int, dtype) -> Tree:
    k1, k2, k3 = jax.random.split(rng, 3)
    return {
        "gate": dense_init(k1, d, d_ff, dtype),
        "up": dense_init(k2, d, d_ff, dtype),
        "down": dense_init(k3, d_ff, d, dtype),
    }


def mlp_apply(p: Tree, x: jax.Array, activation: str = "swiglu") -> jax.Array:
    g = dense_apply(p["gate"], x)
    u = dense_apply(p["up"], x)
    if activation == "geglu":
        h = jax.nn.gelu(g.astype(jnp.float32)).astype(x.dtype) * u
    else:  # swiglu
        h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    return dense_apply(p["down"], h)
