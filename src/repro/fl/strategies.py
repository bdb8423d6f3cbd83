"""Server aggregation strategies (paper Table 7: Flame's ✓ column).

A ``ServerStrategy`` consumes the *aggregated client delta* (already reduced
over the clients of its TAG level — by the inproc runtime or by a mesh
collective stage) and produces new global weights. All state is an explicit
pytree so strategies are pjit-traceable and checkpointable.

FedAvg      McMahan et al. 2017          global = mean of client models
FedProx     Li et al. 2020               FedAvg server + proximal client term
FedAdam/
FedAdagrad/
FedYogi     Reddi et al. 2021            adaptive server optimizers on -delta
FedDyn      Acar et al. 2021             dynamic regularizer state h
FedBuff     Nguyen et al. 2022           buffered async aggregation (K of N)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import fold

Tree = Any


def tree_zeros_like(t: Tree) -> Tree:
    return jax.tree_util.tree_map(jnp.zeros_like, t)


def tree_add(a: Tree, b: Tree) -> Tree:
    return jax.tree_util.tree_map(jnp.add, a, b)


def tree_sub(a: Tree, b: Tree) -> Tree:
    return jax.tree_util.tree_map(jnp.subtract, a, b)


def tree_scale(t: Tree, s: float) -> Tree:
    return jax.tree_util.tree_map(lambda x: x * s, t)


class ServerStrategy:
    """Base: ``init(params) -> state`` and
    ``apply(params, agg_delta, state) -> (new_params, new_state)``.

    ``agg_delta`` is mean(client_model) - global (the model-update convention
    of the paper's aggregator roles).
    """

    name = "base"

    def init(self, params: Tree) -> Tree:
        return ()

    def apply(self, params: Tree, agg_delta: Tree, state: Tree) -> Tuple[Tree, Tree]:
        raise NotImplementedError

    # client-side hook: loss regularizer (FedProx/FedDyn need one)
    def client_loss_extra(
        self, params: Tree, global_params: Tree, state: Tree
    ) -> jax.Array:
        return jnp.float32(0.0)


@dataclasses.dataclass
class FedAvg(ServerStrategy):
    server_lr: float = 1.0
    name: str = "fedavg"

    def apply(self, params, agg_delta, state):
        new = jax.tree_util.tree_map(
            lambda p, d: p + self.server_lr * d, params, agg_delta
        )
        return new, state


@dataclasses.dataclass
class FedProx(ServerStrategy):
    """Server side is FedAvg; the proximal mu/2 * ||w - w_g||^2 term is added
    to the client loss via ``client_loss_extra``."""

    mu: float = 0.01
    server_lr: float = 1.0
    name: str = "fedprox"

    def apply(self, params, agg_delta, state):
        new = jax.tree_util.tree_map(
            lambda p, d: p + self.server_lr * d, params, agg_delta
        )
        return new, state

    def client_loss_extra(self, params, global_params, state):
        sq = jax.tree_util.tree_map(
            lambda w, g: jnp.sum((w.astype(jnp.float32) - g.astype(jnp.float32)) ** 2),
            params,
            global_params,
        )
        return 0.5 * self.mu * sum(jax.tree_util.tree_leaves(sq))


class _AdaptiveServer(ServerStrategy):
    """Shared m/v machinery of FedAdam / FedAdagrad / FedYogi (Reddi 2021)."""

    def __init__(self, lr=0.01, beta1=0.9, beta2=0.99, tau=1e-3):
        self.lr, self.beta1, self.beta2, self.tau = lr, beta1, beta2, tau

    def init(self, params: Tree) -> Tree:
        return {
            "m": tree_zeros_like(params),
            "v": jax.tree_util.tree_map(
                lambda p: jnp.full_like(p, self.tau**2), params
            ),
        }

    def _update_v(self, v: jax.Array, d2: jax.Array) -> jax.Array:
        raise NotImplementedError

    def apply(self, params, agg_delta, state):
        m = jax.tree_util.tree_map(
            lambda m_, d: self.beta1 * m_ + (1 - self.beta1) * d, state["m"], agg_delta
        )
        v = jax.tree_util.tree_map(
            lambda v_, d: self._update_v(v_, d * d), state["v"], agg_delta
        )
        new = jax.tree_util.tree_map(
            lambda p, m_, v_: p + self.lr * m_ / (jnp.sqrt(v_) + self.tau),
            params,
            m,
            v,
        )
        return new, {"m": m, "v": v}


class FedAdam(_AdaptiveServer):
    name = "fedadam"

    def _update_v(self, v, d2):
        return self.beta2 * v + (1 - self.beta2) * d2


class FedAdagrad(_AdaptiveServer):
    name = "fedadagrad"

    def _update_v(self, v, d2):
        return v + d2


class FedYogi(_AdaptiveServer):
    name = "fedyogi"

    def _update_v(self, v, d2):
        return v - (1 - self.beta2) * d2 * jnp.sign(v - d2)


@dataclasses.dataclass
class FedDyn(ServerStrategy):
    """FedDyn (Acar et al. 2021): server keeps a running h state that debiases
    partial participation; client adds a linear+proximal dynamic regularizer."""

    alpha: float = 0.01
    name: str = "feddyn"

    def init(self, params: Tree) -> Tree:
        return {"h": tree_zeros_like(params)}

    def apply(self, params, agg_delta, state):
        h = jax.tree_util.tree_map(
            lambda h_, d: h_ - self.alpha * d, state["h"], agg_delta
        )
        new = jax.tree_util.tree_map(
            lambda p, d, h_: p + d - h_ / self.alpha, params, agg_delta, h
        )
        return new, {"h": h}

    def client_loss_extra(self, params, global_params, state):
        # linearized penalty: -<grad_prev, w> + alpha/2 ||w - w_g||^2
        sq = jax.tree_util.tree_map(
            lambda w, g: jnp.sum((w.astype(jnp.float32) - g.astype(jnp.float32)) ** 2),
            params,
            global_params,
        )
        return 0.5 * self.alpha * sum(jax.tree_util.tree_leaves(sq))


class _BufferedBatchMixin:
    """Streaming absorption for the buffered async strategies.

    ``accumulate_stream(state, delta, staleness)`` folds ONE update into the
    strategy state the moment it arrives — the aggregator never buffers
    delta trees, so server memory is O(1) in client count. Where
    ``fold.device_fold`` says so, the scale and the add run through the
    fold's jitted ``scale``/``add`` pair; otherwise the eager ``accumulate``
    chain runs. Both are the same IEEE op sequence, so the bits are the
    same either way.
    """

    def _update_weight(self, staleness: jax.Array) -> jax.Array:
        raise NotImplementedError

    def accumulate_stream(self, state: Tree, delta: Tree, staleness: int) -> Tree:
        """Fold one arriving update into ``state`` (O(1) server memory)."""
        if not fold.device_fold(delta):
            return self.accumulate(state, delta, np.int32(staleness))
        w = self._update_weight(np.int32(staleness))
        scaled = jax.tree_util.tree_map(lambda d: fold.scale(d, w), delta)
        acc = jax.tree_util.tree_map(fold.add, state["acc"], scaled)
        return {"acc": acc, "count": state["count"] + 1}


@dataclasses.dataclass
class FedBuff(_BufferedBatchMixin, ServerStrategy):
    """Buffered asynchronous aggregation: the server applies an update once
    ``buffer_size`` client deltas have arrived (Nguyen et al. 2022). The
    buffering itself happens in the aggregator role / async harness; this
    strategy tracks staleness-weighted averaging state."""

    buffer_size: int = 10
    server_lr: float = 1.0
    staleness_exp: float = 0.5
    name: str = "fedbuff"

    def init(self, params: Tree) -> Tree:
        return {"acc": tree_zeros_like(params), "count": jnp.zeros((), jnp.int32)}

    def staleness_weight(self, staleness: jax.Array) -> jax.Array:
        return 1.0 / jnp.power(1.0 + staleness.astype(jnp.float32), self.staleness_exp)

    def _update_weight(self, staleness: jax.Array) -> jax.Array:
        return self.staleness_weight(staleness)

    def accumulate(self, state: Tree, delta: Tree, staleness: jax.Array) -> Tree:
        w = self.staleness_weight(staleness)
        acc = jax.tree_util.tree_map(lambda a, d: a + w * d, state["acc"], delta)
        return {"acc": acc, "count": state["count"] + 1}

    def ready(self, state: Tree) -> jax.Array:
        return state["count"] >= self.buffer_size

    def apply(self, params, agg_delta, state):
        # agg_delta unused: the buffer IS the aggregate
        count = jnp.maximum(state["count"], 1).astype(jnp.float32)
        new = jax.tree_util.tree_map(
            lambda p, a: p + self.server_lr * a / count, params, state["acc"]
        )
        return new, self.init(params)


@dataclasses.dataclass
class FedAsync(_BufferedBatchMixin, ServerStrategy):
    """FedAsync (Xie et al. 2019): apply every update the moment it arrives,
    mixing it in with a staleness-decayed rate — the ``buffer_size=1`` end of
    the async family. Exposes the same ``accumulate/ready/apply`` surface as
    FedBuff so the async aggregator role can drive either uniformly."""

    alpha: float = 0.6
    staleness_exp: float = 0.5
    name: str = "fedasync"

    def init(self, params: Tree) -> Tree:
        return {"acc": tree_zeros_like(params), "count": jnp.zeros((), jnp.int32)}

    def staleness_weight(self, staleness: jax.Array) -> jax.Array:
        return 1.0 / jnp.power(1.0 + staleness.astype(jnp.float32), self.staleness_exp)

    def _update_weight(self, staleness: jax.Array) -> jax.Array:
        return self.alpha * self.staleness_weight(staleness)

    def accumulate(self, state: Tree, delta: Tree, staleness: jax.Array) -> Tree:
        w = self.alpha * self.staleness_weight(staleness)
        acc = jax.tree_util.tree_map(lambda a, d: a + w * d, state["acc"], delta)
        return {"acc": acc, "count": state["count"] + 1}

    def ready(self, state: Tree) -> jax.Array:
        return state["count"] >= 1

    def apply(self, params, agg_delta, state):
        new = jax.tree_util.tree_map(lambda p, a: p + a, params, state["acc"])
        return new, self.init(params)


_STRATEGIES: Dict[str, Callable[..., ServerStrategy]] = {
    "fedavg": FedAvg,
    "fedprox": FedProx,
    "fedadam": FedAdam,
    "fedadagrad": FedAdagrad,
    "fedyogi": FedYogi,
    "feddyn": FedDyn,
    "fedbuff": FedBuff,
    "fedasync": FedAsync,
}


def register_strategy(
    name: str, factory: Callable[..., ServerStrategy], *, overwrite: bool = False
) -> None:
    """Register a server strategy under ``name`` (mirrors
    ``register_codec``/``register_template``): downstream aggregation rules
    become reachable by name without editing this module."""
    if not overwrite and name in _STRATEGIES:
        raise ValueError(
            f"strategy {name!r} already registered (pass overwrite=True to replace)"
        )
    _STRATEGIES[name] = factory


def registered_strategies() -> List[str]:
    return sorted(_STRATEGIES)


def get_strategy(name: str, **kwargs: Any) -> ServerStrategy:
    try:
        return _STRATEGIES[name](**kwargs)
    except KeyError:
        raise KeyError(
            f"unknown strategy {name!r}; available: {sorted(_STRATEGIES)}"
        ) from None
