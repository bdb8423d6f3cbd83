"""Plain reference of the Qwen3-MoE decoder (Qwen/Qwen3-235B-A22B,
arXiv:2505.09388) at one chip's expert share: forward pass, loss and
gradients in straightforward ``jax.numpy`` and float32, every matrix
product at ``jax.default_matmul_precision("highest")``. No kernels, scan,
remat or batching tricks; it shares no code with ``repro.models`` beyond
reading sizes from a ``ModelConfig``.

Per layer, with u = RMSNorm(x):

* q = RMSNorm_q(u W_q), k = RMSNorm_k(u W_k), each head normalised over its
  ``head_dim`` before RoPE (rotate-half, theta ``rope_theta``); v = u W_v.
* causal GQA attention with scale head_dim^-1/2; h = x + attn W_o.
* p = softmax(RMSNorm(h) W_r) over all ``num_experts``; the top
  ``experts_per_token`` weights renormalised to sum to one.
* y = h + sum over the chosen held experts of w_e W_down,e (silu(W_gate,e u')
  * W_up,e u'), u' = RMSNorm(h).

The loss is the mean next-token cross-entropy over the vocabulary slice
plus ``router_aux_weight`` times the load-balancing loss summed over layers.

Departures from the published model, each shared with the program:

* Only the held experts' weights exist (``experts_held`` of them, the
  ``expert_shard``-th consecutive share); picks of absent experts add
  nothing, as on one chip of an expert-parallel deployment without its
  exchange. The router still scores every expert.
* The vocabulary is the first ``vocab_size`` ids (a slice of the published
  151,936), for the embedding, the head and the softmax.
* The load-balancing loss is E sum_e f_e P_e per layer with f_e the share of
  the layer's picks, summed over layers; the published training code takes
  one loss over all layers' router outputs together, with f_e the share of
  tokens (k times larger).
* The depth is ``num_layers``, cut from 94.
"""
from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ModelConfig

Params = Dict[str, Any]
F32 = jnp.float32


def params_from_model(params: Any, cfg: ModelConfig) -> Params:
    """The program's parameter tree (scan-stacked or per-layer) in this
    module's plain layout, as float32: one dict per layer, and the
    embedding and head cut to the vocabulary slice."""
    f32 = lambda a: jnp.asarray(a, F32)  # noqa: E731
    if "groups" in params:
        (stack,) = params["groups"]
        blocks = [jax.tree_util.tree_map(lambda a: a[i], stack)
                  for i in range(cfg.num_layers)]
    else:
        blocks = list(params["layers"])
    V = cfg.vocab_size
    unembed = params["embed"] if cfg.tie_embeddings else params["unembed"]
    layers = []
    for b in blocks:
        a, m = b["attn"], b["moe"]
        layers.append({
            "ln1": f32(b["ln1"]["scale"]), "ln2": f32(b["ln2"]["scale"]),
            "wq": f32(a["wq"]["w"]), "wk": f32(a["wk"]["w"]),
            "wv": f32(a["wv"]["w"]), "wo": f32(a["wo"]["w"]),
            "q_norm": f32(a["q_norm"]["scale"]), "k_norm": f32(a["k_norm"]["scale"]),
            "router": f32(m["router"]["w"]),
            "gate": f32(m["gate"]), "up": f32(m["up"]), "down": f32(m["down"]),
        })
    return {"embed": f32(params["embed"]["emb"][:V]),
            "unembed": f32(unembed["emb"][:V]),
            "norm": f32(params["ln_f"]["scale"]), "layers": layers}


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def rope(x: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, Dh) at positions 0..S-1, halves rotated."""
    S, Dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, Dh, 2, dtype=np.float32) / Dh)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv[None, :]  # (S, Dh/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : Dh // 2], x[..., Dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(lp: Params, u: jax.Array, cfg: ModelConfig) -> jax.Array:
    B, S, _ = u.shape
    H, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    q = rms_norm((u @ lp["wq"]).reshape(B, S, H, Dh), lp["q_norm"], cfg.norm_eps)
    k = rms_norm((u @ lp["wk"]).reshape(B, S, Hkv, Dh), lp["k_norm"], cfg.norm_eps)
    v = (u @ lp["wv"]).reshape(B, S, Hkv, Dh)
    q, k = rope(q, cfg.rope_theta), rope(k, cfg.rope_theta)
    k = jnp.repeat(k, H // Hkv, axis=2)  # query head h reads kv head h // G
    v = jnp.repeat(v, H // Hkv, axis=2)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(Dh)
    causal = jnp.tril(jnp.ones((S, S), bool))
    probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(B, S, H * Dh)
    return out @ lp["wo"]


def route(lp: Params, u2: jax.Array, cfg: ModelConfig):
    """(N, d) normed tokens -> top-k experts (N, k), their renormalised
    weights (N, k), and the layer's load-balancing loss."""
    E, k = cfg.num_experts, cfg.experts_per_token
    probs = jax.nn.softmax(u2 @ lp["router"], axis=-1)
    top_w, top_e = jax.lax.top_k(probs, k)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    f = jnp.sum(jax.nn.one_hot(top_e, E, dtype=F32), axis=(0, 1)) / top_e.size
    return top_e, top_w, E * jnp.sum(f * jnp.mean(probs, axis=0))


def experts(lp: Params, u2: jax.Array, top_e, top_w, cfg: ModelConfig):
    """Sum over the held experts of each token's weight for the expert times
    the expert's gated MLP; tokens that did not pick it get weight 0."""
    H = cfg.held_experts
    out = jnp.zeros_like(u2)
    held_picks = jnp.int32(0)
    for j in range(H):
        e = cfg.expert_shard * H + j
        chosen = top_e == e
        w = jnp.sum(jnp.where(chosen, top_w, 0.0), axis=-1)  # (N,)
        ffn = (jax.nn.silu(u2 @ lp["gate"][j]) * (u2 @ lp["up"][j])) @ lp["down"][j]
        out = out + w[:, None] * ffn
        held_picks = held_picks + jnp.sum(chosen)
    return out, held_picks


def layer(lp: Params, x: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, Dict]:
    """One decoder layer: (B, S, d) -> (B, S, d), with its aux loss, its
    held picks and its routing (top experts per token)."""
    B, S, d = x.shape
    h = x + attention(lp, rms_norm(x, lp["ln1"], cfg.norm_eps), cfg)
    u2 = rms_norm(h, lp["ln2"], cfg.norm_eps).reshape(B * S, d)
    top_e, top_w, aux = route(lp, u2, cfg)
    out, held_picks = experts(lp, u2, top_e, top_w, cfg)
    return h + out.reshape(B, S, d), {"aux": aux, "held_picks": held_picks,
                                      "top_e": top_e}


def loss(params: Params, tokens: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, Dict]:
    """Mean next-token cross-entropy plus the weighted aux loss; the stats
    hold the summed aux loss, the held picks and each layer's routing."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens]
        aux, held_picks, routes = F32(0.0), jnp.int32(0), []
        for lp in params["layers"]:
            x, st = layer(lp, x, cfg)
            aux, held_picks = aux + st["aux"], held_picks + st["held_picks"]
            routes.append(st["top_e"])
        h = rms_norm(x, params["norm"], cfg.norm_eps)[:, :-1]
        logp = jax.nn.log_softmax(h @ params["unembed"].T, axis=-1)
        nll = -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)
        total = jnp.mean(nll) + cfg.router_aux_weight * aux
    return total, {"aux": aux, "moe_held_picks": held_picks, "routes": routes}


def loss_and_grads(params: Params, tokens: jax.Array, cfg: ModelConfig):
    """(loss, stats, gradients with the layout of ``params``)."""
    (value, stats), grads = jax.value_and_grad(loss, has_aux=True)(params, tokens, cfg)
    return value, stats, grads

