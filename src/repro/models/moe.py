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
only the rows its groups fill. Dispatch gathers each sorted row's token,
and combine adds each row's weighted output back into its token in f32.

The buffer rule: the sorted buffer's row count is a rung of a ladder fixed
by the shapes (``buffer_rungs``): N · min(k, held), a row for every pick
that can land here, then halvings of it down to twice the even fill
N · k · held / E. Each call runs dispatch, experts and combine over the
smallest rung that holds its held picks, chosen on the device
(``jax.lax.switch``), so no pick is dropped at any imbalance and evenly
routed picks do not pay for the worst case. A layer that holds every
expert has the one rung. Each call counts the picks it computed
(``moe_held_picks``), those it could not (``moe_dropped``, 0 by
construction), the largest held expert's picks (``moe_load_max``) and the
rows of the rung it ran (``moe_rows``).

Router aux loss is the standard load-balancing loss (Shazeer/Switch) over
every expert: ``E * sum_e f_e * P_e`` with f the share of the layer's picks
and P the mean router probability.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.core.spans import MOE_COMBINE, MOE_DISPATCH, MOE_EXPERTS, MOE_ROUTE, scope
from repro.models.config import ModelConfig
from repro.models.layers import dense_init, mlp_apply, mlp_init

Tree = Dict[str, jax.Array]
COUNTERS = ("moe_held_picks", "moe_dropped", "moe_load_max", "moe_rows")
# the grouped product's row tile: every rung of the buffer is a multiple of it
ROW_TILE = 128


def buffer_rungs(N: int, k: int, E: int, H: int) -> Tuple[int, ...]:
    """The sorted buffer's row counts, largest first. The first is
    N · min(k, H), a row for every pick that can land on a held expert; the
    others halve it down to the last that still holds twice the even fill
    N · k · H / E, each rounded up to a whole ``ROW_TILE``. A layer that
    holds every expert, or whose even fill passes a quarter of the first,
    has that one alone."""
    rows = N * min(k, H)
    out = [rows]
    j = 1
    while rows * E >= 2 ** (j + 1) * N * k * H:  # rows / 2^j >= 2 · even fill
        rung = -(-rows // (2**j * ROW_TILE)) * ROW_TILE
        if rung < out[-1]:
            out.append(rung)
        j += 1
    return tuple(out)


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


def _rows(order, sizes, C, k):
    """The first ``C`` sorted rows: each row's pick, its token, and whether
    it holds a held pick (rows past the groups' total hold none)."""
    pick = order[:C]
    return pick, pick // k, jnp.arange(C) < jnp.sum(sizes)


def _segment_sum(rows, tok, live, N, chunk, w=None):
    """(N, d) float32: each live row (times its weight ``w``) added into its
    token, ``chunk`` rows at a time, so no more than ``chunk`` rows are held
    in float32 at once. A row that holds no pick is sent past the table, so
    its value is never read."""
    tok = jnp.where(live, tok, N)
    C, d = rows.shape

    def add(acc, r, t, w):
        r = r.astype(jnp.float32)
        if w is not None:
            r = r * w[:, None]
        return acc.at[t].add(r, mode="drop")

    acc = jnp.zeros((N, d), jnp.float32)
    if C == chunk:
        return add(acc, rows, tok, w)

    def body(i, acc):
        part = lambda a: jax.lax.dynamic_slice_in_dim(a, i * chunk, chunk)  # noqa: E731
        return add(acc, part(rows), part(tok), None if w is None else part(w))

    return jax.lax.fori_loop(0, C // chunk, body, acc)


def _forward(C, chunk, xf, w, gate, up, down, order, sizes):
    """y (N, d) over a buffer of ``C`` rows: each token's held picks'
    outputs weighted and summed in f32."""
    N, k = w.shape
    with scope(MOE_DISPATCH):
        pick, tok, live = _rows(order, sizes, C, k)
        xs = xf[tok]
    with scope(MOE_EXPERTS):
        out = _experts(xs, gate, up, down, sizes)
    with scope(MOE_COMBINE):
        y = _segment_sum(out, tok, live, N, chunk, w.reshape(-1)[pick])
    return y.astype(xf.dtype)


def _backward(C, chunk, xf, w, gate, up, down, order, sizes, g):
    """The transpose of ``_forward`` at ``g`` (N, d), recomputing the
    experts over the same ``C`` rows: the combine's segment sum turns into a
    gather of each row's token, the dispatch's gather into a segment sum."""
    N, k = w.shape
    with scope(MOE_DISPATCH):
        pick, tok, live = _rows(order, sizes, C, k)
        xs = xf[tok]
    with scope(MOE_EXPERTS):
        out, experts_vjp = jax.vjp(
            lambda *a: _experts(*a, sizes), xs, gate, up, down)
    with scope(MOE_COMBINE):
        w_row = w.reshape(-1)[pick]
        g_row = g[tok]
        d_w_row = jnp.sum(out.astype(jnp.float32) * g_row.astype(jnp.float32), axis=-1)
        d_w = jnp.zeros((N * k,), jnp.float32).at[pick].set(
            jnp.where(live, d_w_row, 0.0), unique_indices=True)
        d_out = jnp.where(live[:, None], g_row.astype(jnp.float32) * w_row[:, None],
                          0.0).astype(out.dtype)
    with scope(MOE_EXPERTS):
        d_xs, d_gate, d_up, d_down = experts_vjp(d_out)
    with scope(MOE_DISPATCH):
        d_x = _segment_sum(d_xs, tok, live, N, chunk).astype(xf.dtype)
    return d_x, d_w.reshape(N, k), d_gate, d_up, d_down


def _rung(ladder, total):
    """The index in ``ladder`` (largest first) of the smallest rung that
    holds ``total`` rows."""
    return jnp.sum(jnp.asarray(ladder) >= total) - 1


def _on_rung(ladder, rung, fn, *args):
    """``fn(C, chunk, *args)`` with C the rung of index ``rung``."""
    chunk = math.gcd(*ladder)  # the smallest rung, where it divides the others
    if len(ladder) == 1:
        return fn(ladder[0], chunk, *args)
    return jax.lax.switch(rung, [functools.partial(fn, C, chunk) for C in ladder], *args)


# The rung is switched on inside the forward and again inside the backward,
# so nothing of a rung's size crosses from one to the other: the backward
# recomputes the experts over its rows (the step rematerialises the layer
# anyway), and the residuals are the inputs.
@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _held_experts(ladder, rung, xf, w, gate, up, down, order, sizes):
    """xf: (N, d), w: (N, k) -> (N, d): each token's held picks weighted."""
    return _on_rung(ladder, rung, _forward, xf, w, gate, up, down, order, sizes)


def _held_experts_fwd(ladder, rung, *args):
    return _held_experts(ladder, rung, *args), (rung,) + args


def _held_experts_bwd(ladder, res, g):
    rung, *args = res
    return (None,) + _on_rung(ladder, rung, _backward, *args, g) + (None, None)


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def moe_apply(p: Tree, x: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, Dict]:
    """x: (B, S, d) -> (y: (B, S, d), stats: ``zero_stats()``'s keys)."""
    B, S, d = x.shape
    N, E, k, H = B * S, cfg.num_experts, cfg.experts_per_token, cfg.held_experts
    M = N * k
    ladder = buffer_rungs(N, k, E, H)
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
        held_picks = jnp.sum(sizes)
        rung = _rung(ladder, held_picks)
        rows = jnp.asarray(ladder)[rung]

    y = _held_experts(ladder, rung, xf, top_w, p["gate"], p["up"], p["down"], order, sizes)
    y = y.reshape(B, S, d)
    if cfg.shared_expert:
        y = y + mlp_apply(p["shared"], x, cfg.activation)
    stats = {
        "router_aux": aux,
        "moe_held_picks": held_picks,
        "moe_dropped": jnp.maximum(held_picks - rows, 0),
        "moe_load_max": jnp.max(sizes),
        "moe_rows": rows,
    }
    return y, stats
