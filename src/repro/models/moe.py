"""Mixture-of-Experts layer: top-k routing over every expert, dropless
compute for the experts this chip holds.

The router scores all ``num_experts`` experts, keeps the top
``experts_per_token`` of each token and renormalises their weights to sum
to one. The layer holds the weights of ``held_experts`` of them (the
``expert_shard``-th consecutive share, ``ModelConfig.experts_held``), so
the expert weights lead with that count. Under expert parallelism every
chip holds one share and computes its experts' part of the result; on one
chip the layer runs without the exchange, and picks for absent experts add
nothing.

The picks that land on held experts are sorted by expert and run as one
grouped matrix product per weight (``jax.lax.ragged_dot``), which computes
only the rows its groups fill. The sorted buffer has a row for every pick
that can land here, N · min(k, held), so no pick is dropped at any
imbalance. Each call counts the picks it computed (``moe_held_picks``),
those it could not (``moe_dropped``, 0 by construction) and the largest
held expert's picks (``moe_load_max``).

Router aux loss is the standard load-balancing loss (Shazeer/Switch) over
every expert: ``E * sum_e f_e * P_e`` with f the share of the layer's picks
and P the mean router probability.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.spans import MOE_COMBINE, MOE_DISPATCH, MOE_EXPERTS, MOE_ROUTE, scope
from repro.models.config import ModelConfig
from repro.models.layers import dense_init, mlp_apply, mlp_init

Tree = Dict[str, jax.Array]
COUNTERS = ("moe_held_picks", "moe_dropped", "moe_load_max")


def moe_init(rng, cfg: ModelConfig, dtype) -> Tree:
    """Router over all experts; gate/up (H, d, ff) and down (H, ff, d) of the
    H held experts. Expert ``e``'s weights come from ``fold_in(rng, e)``, so
    a share's weights are the uncut layer's rows for those experts."""
    kr, ke, ks = jax.random.split(rng, 3)
    d, ff, H = cfg.d_model, cfg.moe_d_ff, cfg.held_experts
    experts = cfg.expert_shard * H + jnp.arange(H)

    def stacked(i, shape, scale):
        keys = jax.vmap(lambda e: jax.random.fold_in(jax.random.fold_in(ke, i), e))(experts)
        w = jax.vmap(lambda k: jax.random.normal(k, shape, jnp.float32))(keys)
        return w.astype(dtype) * scale

    p: Tree = {
        "router": dense_init(kr, d, cfg.num_experts, jnp.float32),  # router math stays f32
        "gate": stacked(0, (d, ff), d**-0.5),
        "up": stacked(1, (d, ff), d**-0.5),
        "down": stacked(2, (ff, d), ff**-0.5),
    }
    if cfg.shared_expert:
        p["shared"] = mlp_init(ks, d, cfg.d_ff, dtype)
    return p


def router_probs(p: Tree, x: jax.Array) -> jax.Array:
    """x: (..., d) -> (..., E) softmax router probabilities (f32)."""
    logits = x.astype(jnp.float32) @ p["router"]["w"]
    return jax.nn.softmax(logits, axis=-1)


def zero_stats() -> Dict[str, jax.Array]:
    """What one MoE layer reports, zeroed: the router aux loss and the
    counters, to be summed over layers and steps."""
    out = {"router_aux": jnp.float32(0.0)}
    out.update({c: jnp.int32(0) for c in COUNTERS})
    return out


def _experts(xs, gate, up, down, sizes):
    """The held experts' gated MLP over picks sorted by expert. Rows past
    the groups' total are not computed: on the TPU they hold whatever the
    buffer held, so every reader of them masks by selection, never by a
    zero weight."""
    dtype = xs.dtype
    g = jax.lax.ragged_dot(xs, gate, sizes)
    u = jax.lax.ragged_dot(xs, up, sizes)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(dtype) * u
    return jax.lax.ragged_dot(h, down, sizes)


def _gather_sum(r, slot, held, w):
    """(N, d): sum over the held picks j of ``w[n, j] * r[slot[n, j]]``,
    added in f32; an absent pick's row is never read into the sum."""
    N, k = slot.shape
    picked = r[slot.reshape(-1)].reshape(N, k, -1).astype(jnp.float32)
    return jnp.sum(jnp.where(held[..., None], picked * w[..., None], 0.0),
                   axis=1).astype(r.dtype)


# Dispatch and combine move tokens to sorted rows and back. As plain
# indexing, each one's backward would be a scatter; written as each other's
# transpose, both directions are gathers and a token's picks sum in f32.
@jax.custom_vjp
def _dispatch(x, pick, slot, held):
    """x: (N, d) -> (rows, d): row r holds the token of pick ``pick[r]``."""
    return x[pick // slot.shape[1]]


def _dispatch_fwd(x, pick, slot, held):
    return _dispatch(x, pick, slot, held), (slot, held)


def _dispatch_bwd(res, g):
    slot, held = res
    return _gather_sum(g, slot, held, jnp.ones(held.shape, jnp.float32)), None, None, None


_dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def _combine(out, w, held, pick, slot):
    """out: (rows, d), w: (N, k) -> (N, d): each token's held picks weighted."""
    return _gather_sum(out, slot, held, w)


def _combine_fwd(out, w, held, pick, slot):
    return _gather_sum(out, slot, held, w), (out, w, held, pick, slot)


def _combine_bwd(res, g):
    out, w, held, pick, slot = res
    N, k = slot.shape
    held_row = held.reshape(-1)[pick]  # rows of absent picks get nothing
    w_row = jnp.where(held_row, w.reshape(-1)[pick], 0.0)
    d_out = (g[pick // k].astype(jnp.float32) * w_row[:, None]).astype(out.dtype)
    picked = out[slot.reshape(-1)].reshape(N, k, -1).astype(jnp.float32)
    d_w = jnp.where(held, jnp.sum(picked * g.astype(jnp.float32)[:, None, :], axis=-1), 0.0)
    return d_out, d_w, None, None, None


_combine.defvjp(_combine_fwd, _combine_bwd)


def moe_apply(p: Tree, x: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, Dict]:
    """x: (B, S, d) -> (y: (B, S, d), stats: ``zero_stats()``'s keys)."""
    B, S, d = x.shape
    N, E, k, H = B * S, cfg.num_experts, cfg.experts_per_token, cfg.held_experts
    M, rows = N * k, N * min(k, H)
    xf = x.reshape(N, d)

    with scope(MOE_ROUTE):
        probs = router_probs(p, xf)  # (N, E) f32
        top_w, top_e = jax.lax.top_k(probs, k)  # (N, k)
        top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
        frac = jnp.zeros((E,), jnp.float32).at[top_e.reshape(-1)].add(1.0) / M
        aux = E * jnp.sum(frac * jnp.mean(probs, axis=0))

    with scope(MOE_DISPATCH):
        local = top_e - cfg.expert_shard * H  # (N, k) index among held
        held = (local >= 0) & (local < H)
        group = jnp.where(held, local, H).reshape(-1)  # absent picks sort last
        order = jnp.argsort(group, stable=True)
        sizes = jnp.zeros((H,), jnp.int32).at[group].add(1, mode="drop")
        pick = order[:rows]  # the pick of each sorted row
        # each pick's row; an absent pick past the buffer points at the last
        # row, and ``held`` masks it out
        slot = jnp.zeros((M,), jnp.int32).at[order].set(jnp.arange(M, dtype=jnp.int32))
        slot = jnp.minimum(slot, rows - 1).reshape(N, k)
        xs = _dispatch(xf, pick, slot, held)

    with scope(MOE_EXPERTS):
        out = _experts(xs, p["gate"], p["up"], p["down"], sizes)  # (rows, d)

    with scope(MOE_COMBINE):
        y = _combine(out, top_w, held, pick, slot)

    y = y.reshape(B, S, d)
    if cfg.shared_expert:
        y = y + mlp_apply(p["shared"], x, cfg.activation)
    held_picks = jnp.sum(sizes)
    stats = {
        "router_aux": aux,
        "moe_held_picks": held_picks,
        "moe_dropped": jnp.maximum(held_picks - rows, 0),
        "moe_load_max": jnp.max(sizes),
    }
    return y, stats
