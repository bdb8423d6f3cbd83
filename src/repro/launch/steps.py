"""Step builders: (arch config × mesh × TAG) → jit-compiled train/serve steps.

``build_train_step`` is where the paper's abstraction becomes a first-class
feature: the FL topology (a TAG) is lowered to an ``AggregationPlan`` over
the mesh's client axes and executed inside the train step (hierarchical
psum with per-channel wire policy). Architectures whose FL clients live on
the pod axis (``fl_axes=("pod",)``, FSDP-sharded giants) degrade to a plain
data-parallel step on the single-pod mesh (no pod axis ⇒ one client).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core.mesh_lowering import lower_tag_to_mesh
from repro.core.tag import TAG
from repro.core.topologies import classical_fl, hierarchical_fl
from repro.fl.fedstep import (
    FedStepConfig,
    init_server_state,
    local_round,
    make_fl_train_step,
)
from repro.fl.strategies import get_strategy
from repro.launch import sharding as shd
from repro.models.api import ModelBundle, build_model
from repro.models.config import ModelConfig
from repro.models.shard_ctx import shard_profile

Tree = Any


def _with_profile(fn, cfg: ModelConfig, mesh: Mesh,
                  manual_axes: Tuple[str, ...] = ()):
    """Activate the activation-layout sharding profile while ``fn`` traces.

    The profile's batch axes are the *auto* axes only — constraints inside a
    partial-manual shard_map must not reference manual (client) axes.
    """
    auto_batch = tuple(
        a for a in shd.batch_axes(cfg, mesh) if a not in manual_axes
    )
    if cfg.param_sharding == "fsdp":
        # compute layout: batch over every available axis (trimmed from the
        # right at trace time if indivisible); stash layout: sequence-
        # sharded over model so remat residuals stay O(tokens/devices)
        act = (auto_batch or None, None)
        stash = (
            tuple(a for a in auto_batch if a != "model") or None,
            ("model",) if "model" in auto_batch else None,
        )
    else:
        act = (auto_batch or None, None)
        stash = act

    axis_sizes = {a: mesh.shape[a] for a in mesh.axis_names}

    def wrapped(*a, **k):
        with shard_profile(act=act, stash=stash, axis_sizes=axis_sizes):
            return fn(*a, **k)

    return wrapped


@dataclasses.dataclass(frozen=True)
class TrainSetup:
    step: Callable[..., Tuple[Tree, Tree, Dict[str, jax.Array]]]
    init_state: Callable[[Tree], Tree]  # params -> server/opt state
    client_axes: Tuple[str, ...]
    tag: Optional[TAG]
    in_shardings: Tuple  # (params, state, batch, rng)
    out_shardings: Tuple


def fl_tag_for_mesh(cfg: ModelConfig, client_axes: Tuple[str, ...],
                    cross_pod_wire: str = "f32") -> TAG:
    """The TAG driving on-mesh aggregation.

    Two client axes → hierarchical FL (intra-pod edge aggregation over
    ``data``, cross-pod global aggregation over ``pod`` with its own wire
    policy — the per-channel backend of §6.2). One axis → classical FL.
    """
    if len(client_axes) >= 2:
        return hierarchical_fl(
            groups=("g0",), agg_wire_dtype=cross_pod_wire,
        )
    return classical_fl()


def build_train_step(
    cfg: ModelConfig,
    mesh: Mesh,
    fed: FedStepConfig = FedStepConfig(),
    cross_pod_wire: str = "f32",
    strategy_name: Optional[str] = None,
) -> Tuple[ModelBundle, TrainSetup]:
    bundle = build_model(cfg)
    client_axes = tuple(a for a in cfg.fl_axes if a in mesh.axis_names)
    if cfg.param_sharding == "fsdp" and len(mesh.devices.shape) > 2:
        # XLA SPMD partitioner CHECK-fails (spmd_partitioner_util.cc:504)
        # when a manual (shard_map) pod axis combines with the fsdp
        # sharding constraints. Until Shardy lands, the giants train pure
        # data-parallel across pods (batch sharded over pod — the pod axis
        # is still exercised); see DESIGN.md §Arch-applicability.
        client_axes = ()
    strategy = get_strategy(strategy_name or cfg.server_strategy)

    def loss_fn(params, batch, rng):
        return bundle.loss_fn(params, batch, rng)

    params_shapes = jax.eval_shape(bundle.init, jax.random.key(0))
    p_shard = shd.param_shardings(params_shapes, cfg, mesh)
    rng_shard = NamedSharding(mesh, P())
    rep = NamedSharding(mesh, P())

    if client_axes:
        # ---- the paper's technique: TAG-driven hierarchical aggregation --
        tag = fl_tag_for_mesh(cfg, client_axes, cross_pod_wire)
        # order axes fast->slow: data (intra-pod ICI) first, pod (DCN) last
        ordered = tuple(
            a for a in ("data", "pod") if a in client_axes
        ) or client_axes
        plan = lower_tag_to_mesh(tag, ordered)
        step = make_fl_train_step(loss_fn, strategy, plan, mesh, fed)
        step = _with_profile(step, cfg, mesh, manual_axes=client_axes)

        def init_state(params):
            return init_server_state(strategy, plan, params)

        state_shapes = jax.eval_shape(init_state, params_shapes)
        s_shard = jax.tree_util.tree_map_with_path(
            lambda path, leaf: NamedSharding(
                mesh, shd.param_pspec(path, leaf, cfg, mesh)
            ),
            state_shapes,
        )
        in_sh = (p_shard, s_shard, None, rng_shard)  # batch filled by caller
        out_sh = (p_shard, s_shard, rep)  # every metric, replicated
        return bundle, TrainSetup(step, init_state, client_axes, tag, in_sh, out_sh)

    # ---- degenerate single client: plain data-parallel local SGD --------
    # (the FL local round over the whole batch, so activation memory is
    # bounded the same way)
    def step(params, state, batch, rng):
        new_params, loss, counters = local_round(loss_fn, params, batch, rng, fed)
        dnorm = jnp.sqrt(
            sum(jnp.sum(jnp.square((a - b).astype(jnp.float32)))
                for a, b in zip(jax.tree_util.tree_leaves(new_params),
                                jax.tree_util.tree_leaves(params)))
        )
        return new_params, state, {"loss": loss, "delta_norm": dnorm, **counters}

    def init_state(params):
        return ()

    step = _with_profile(step, cfg, mesh)
    in_sh = (p_shard, (), None, rng_shard)
    out_sh = (p_shard, (), rep)
    return bundle, TrainSetup(step, init_state, (), None, in_sh, out_sh)


# --------------------------------------------------------------------- #
# serving
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class ServeSetup:
    serve_step: Callable
    prefill: Callable
    param_shardings: Tree
    cache_shardings: Tree


def build_serve_step(cfg: ModelConfig, mesh: Mesh, max_len: int,
                     batch: int) -> Tuple[ModelBundle, ServeSetup]:
    bundle = build_model(cfg)
    params_shapes = jax.eval_shape(bundle.init, jax.random.key(0))
    p_shard = shd.param_shardings(params_shapes, cfg, mesh)
    cache_shapes = jax.eval_shape(lambda: bundle.init_cache(batch, max_len))
    c_shard = shd.cache_shardings(cache_shapes, cfg, mesh)
    rep = NamedSharding(mesh, P())

    serve = _with_profile(
        lambda params, cache, batch_in: bundle.serve_step(params, cache, batch_in),
        cfg, mesh,
    )
    prefill = _with_profile(
        lambda params, batch_in, cache: bundle.prefill(params, batch_in, cache),
        cfg, mesh,
    )
    return bundle, ServeSetup(serve, prefill, p_shard, c_shard)
