"""Trace-time sharding profile shared by model modules.

XLA's SPMD propagation loses the batch sharding through gathers, scatters
and scan carries (observed as "involuntary full rematerialization" and
replicated 100+ GiB remat stashes in the dry-run buffer assignment). The
launcher activates a profile during tracing; model code pins the
layout-critical tensors, the (B, S, d) activations, in their compute and
stash layouts.

On a 1-device mesh (tests) or with no profile active this is a no-op.
"""
from __future__ import annotations

import contextlib
import contextvars
from typing import Optional, Tuple

import jax
from jax.sharding import PartitionSpec as P

_PROFILE: contextvars.ContextVar = contextvars.ContextVar(
    "shard_profile", default=None
)


@contextlib.contextmanager
def shard_profile(act=None, stash=None, axis_sizes=None):
    """Activate sharding constraints during tracing.

    ``act``: per-dim axes for (B, S, d) activations in the COMPUTE layout,
    e.g. ``(("data", "model"), None)``. ``stash``: the layout for scan
    carries / remat stashes, e.g. ``(("data",), ("model",))`` — sequence-
    sharded so the per-layer residual stash stays O(tokens/devices) while
    compute sees full sequences. Indivisible dims trim axes from the right.
    ``axis_sizes``: {axis: size} for divisibility guards.
    """
    token = _PROFILE.set(
        {"act": act, "stash": stash, "axis_sizes": dict(axis_sizes or {})}
    )
    try:
        yield
    finally:
        _PROFILE.reset(token)


def constrain(t: jax.Array, spec) -> jax.Array:
    return jax.lax.with_sharding_constraint(t, P(*spec))


def _fit(axes, dim: int, sizes) -> Optional[Tuple[str, ...]]:
    """Largest prefix of ``axes`` whose shard product divides ``dim``."""
    axes = tuple(axes or ())
    while axes:
        n = 1
        for a in axes:
            n *= sizes.get(a, 1)
        if n > 0 and dim % n == 0:
            return axes
        axes = axes[:-1]
    return None


def _pin(h: jax.Array, layout) -> jax.Array:
    prof = _PROFILE.get()
    if prof is None or not prof.get(layout):
        return h
    a0, a1 = prof[layout]
    sizes = prof["axis_sizes"]
    spec0 = _fit(a0, h.shape[0], sizes)
    spec1 = _fit(a1, h.shape[1], sizes) if h.ndim > 2 else None
    if spec0 is None and spec1 is None:
        return h
    return constrain(h, (spec0, spec1) + (None,) * (h.ndim - 2))


def pin_activation(h: jax.Array) -> jax.Array:
    """Pin a (B, S, d) activation to the COMPUTE layout."""
    return _pin(h, "act")


def pin_stash(h: jax.Array) -> jax.Array:
    """Pin a scan carry / remat residual to the STASH layout."""
    return _pin(h, "stash")
