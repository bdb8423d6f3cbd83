"""On-mesh federated train step: TAG plan × model × strategy → pjit-able step.

This is where the paper's abstraction becomes a first-class TPU feature. The
TAG is lowered to an ``AggregationPlan`` (``repro.core.mesh_lowering``); the
step runs under ``shard_map`` that is *manual* over the client axes
(``pod``/``data`` — so each FL aggregation stage is an explicit psum with its
channel's wire policy) and *auto* over the ``model`` axis (XLA's SPMD
partitioner keeps handling tensor parallelism inside the per-client body).

Semantics per round (classic FedAvg-style local SGD):
  1. every client (= one ``data``-axis slice of the mesh) takes
     ``local_steps`` optimizer steps on its own batch shard;
  2. client delta = local_params - global_params (+ optional DP clip/noise);
  3. the plan reduces deltas stage by stage (e.g. intra-pod psum, then
     cross-pod psum in the channel's wire dtype);
  4. the per-stage server strategy (FedAvg/FedAdam/...) produces the new
     global params, identical on every device.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.mesh_lowering import AggregationPlan
from repro.fl.privacy import DPConfig, clip_and_noise
from repro.fl.strategies import ServerStrategy

Tree = Any
LossFn = Callable[[Tree, Dict[str, jax.Array], jax.Array], jax.Array]


@dataclasses.dataclass(frozen=True)
class FedStepConfig:
    local_steps: int = 1
    local_lr: float = 1e-2
    dp: Optional[DPConfig] = None
    # gradient instead of weight-delta exchange (local_steps == 1 fast path)
    exchange: str = "delta"  # "delta" | "grad"
    # on-mesh analogue of the runtime's deadline mode: each client makes the
    # per-round straggler deadline with probability ``participation``; missed
    # clients contribute nothing and the aggregate renormalizes over the
    # clients that did participate (partial participation, FedBuff-style).
    participation: float = 1.0


def local_round(
    loss_fn: LossFn, params: Tree, batch: Tree, rng: jax.Array,
    config: FedStepConfig,
) -> Tuple[Tree, jax.Array]:
    """One client's local round: ``config.local_steps`` SGD steps, one per
    equal microbatch split of its batch. Returns (local params, last loss)."""

    def one_step(carry, xs):
        p, _ = carry
        micro, step_rng = xs
        loss, grads = jax.value_and_grad(loss_fn)(p, micro, step_rng)
        new_p = jax.tree_util.tree_map(
            lambda w, g: w - config.local_lr * g.astype(w.dtype), p, grads
        )
        return (new_p, loss), None

    # split the client batch into local_steps microbatches along the
    # batch dim (dim 0; positions lead with the 3 M-RoPE streams)
    k = config.local_steps

    def split(path, x):
        if any(getattr(p, "key", None) == "positions" for p in path):
            b = x.shape[1]
            out = x.reshape((x.shape[0], k, b // k) + x.shape[2:])
            return jnp.moveaxis(out, 1, 0)
        b = x.shape[0]
        return x.reshape((k, b // k) + x.shape[1:])

    micro = jax.tree_util.tree_map_with_path(split, batch)
    rngs = jax.random.split(rng, config.local_steps)
    (final_params, last_loss), _ = jax.lax.scan(
        one_step, (params, jnp.float32(0.0)), (micro, rngs)
    )
    return final_params, last_loss


def make_fl_train_step(
    loss_fn: LossFn,
    strategy: ServerStrategy,
    plan: AggregationPlan,
    mesh: Mesh,
    config: FedStepConfig = FedStepConfig(),
    donate: bool = True,
) -> Callable[..., Tuple[Tree, Tree, Dict[str, jax.Array]]]:
    """Build ``step(params, server_state, batch, rng) ->
    (params, server_state, metrics)``.

    ``batch`` leaves must lead with the global batch dim; they are sharded
    over every client axis of the plan. ``params`` are replicated over client
    axes (their ``model``-axis sharding, if any, is preserved by the auto
    axes of shard_map).
    """
    client_axes: Tuple[str, ...] = plan.all_axes
    auto_axes = frozenset(a for a in mesh.axis_names if a not in client_axes)

    def step_body(params: Tree, server_state: Tree, batch: Tree, rng: jax.Array):
        # fold the client coordinates into the rng so clients differ
        idx = jnp.int32(0)
        for a in client_axes:
            idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
        rng = jax.random.fold_in(rng, idx)

        if config.exchange == "grad":
            loss, grads = jax.value_and_grad(loss_fn)(params, batch, rng)
            delta = jax.tree_util.tree_map(
                lambda g: (-config.local_lr * g).astype(jnp.float32), grads
            )
        else:
            local_params, loss = local_round(loss_fn, params, batch, rng, config)
            delta = jax.tree_util.tree_map(
                lambda lp, p: (lp - p).astype(jnp.float32), local_params, params
            )

        n_total = 1
        for a in client_axes:
            n_total *= mesh.shape[a]
        if config.dp is not None:
            # under partial participation the aggregate is renormalized to a
            # mean over ~participation*N clients, so per-client noise must be
            # calibrated to that count or the effective noise multiplier
            # drops below DPConfig's promise
            n_eff = max(1, int(round(config.participation * n_total)))
            delta = clip_and_noise(delta, config.dp, rng, n_eff)

        if config.participation < 1.0:
            # per-client Bernoulli "made the deadline" draw; excluded clients
            # contribute a zero delta and the mean renormalizes below
            made_it = jax.random.bernoulli(
                jax.random.fold_in(rng, 0x5EED), config.participation
            ).astype(jnp.float32)
            delta = jax.tree_util.tree_map(lambda d: d * made_it, delta)
            n_part = jax.lax.psum(made_it, client_axes)
        else:
            n_part = jnp.float32(n_total)

        # hierarchical, per-channel-policy aggregation (the TAG, executed)
        stage_states = server_state["stages"]

        new_stage_states = dict(stage_states)
        tree = delta
        for i, stage in enumerate(plan.stages):
            from repro.core.mesh_lowering import stage_reduce_mean

            tree = stage_reduce_mean(tree, stage)
            if i < len(plan.stages) - 1:
                continue  # intermediate levels relay; root applies strategy
        if config.participation < 1.0:
            # stage mean divided by all N clients; renormalize to the mean
            # over the clients that actually made the deadline
            renorm = n_total / jnp.maximum(n_part, 1.0)
            tree = jax.tree_util.tree_map(lambda d: d * renorm, tree)
        new_params, new_root_state = strategy.apply(
            params,
            jax.tree_util.tree_map(lambda d, p: d.astype(p.dtype), tree, params),
            stage_states["root"],
        )
        new_stage_states["root"] = new_root_state

        mean_loss = jax.lax.pmean(loss, client_axes)
        metrics = {
            "loss": mean_loss,
            "delta_norm": jnp.sqrt(
                sum(
                    jnp.sum(jnp.square(x))
                    for x in jax.tree_util.tree_leaves(tree)
                )
            ),
            "participants": n_part,
        }
        return new_params, {"stages": new_stage_states}, metrics

    # manual over client axes, auto over the rest (model/tensor axes)
    batch_spec = P(client_axes)
    # positions (M-RoPE) lead with the 3 t/h/w streams; batch is dim 1
    positions_spec = P(None, client_axes)

    def spec_tree(tree: Tree, spec: P) -> Tree:
        return jax.tree_util.tree_map(lambda _: spec, tree)

    def batch_spec_tree(tree: Tree) -> Tree:
        return jax.tree_util.tree_map_with_path(
            lambda path, _: positions_spec
            if any(getattr(k, "key", None) == "positions" for k in path)
            else batch_spec,
            tree,
        )

    def step(params: Tree, server_state: Tree, batch: Tree, rng: jax.Array):
        shardmapped = jax.shard_map(
            step_body,
            mesh=mesh,
            in_specs=(
                spec_tree(params, P()),
                spec_tree(server_state, P()),
                batch_spec_tree(batch),
                P(),
            ),
            out_specs=(
                spec_tree(params, P()),
                spec_tree(server_state, P()),
                {"loss": P(), "delta_norm": P(), "participants": P()},
            ),
            axis_names=set(client_axes),
            check_vma=False,
        )
        return shardmapped(params, server_state, batch, rng)

    return step


def init_server_state(strategy: ServerStrategy, plan: AggregationPlan, params: Tree) -> Tree:
    """Server-side state for the plan's root strategy."""
    return {"stages": {"root": strategy.init(params)}}
