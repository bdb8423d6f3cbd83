"""Client updates made from ``--seed``, on the device, in one jitted call,
in the type they are used in. The same seed gives the same values on any
device, so the reference can make them again without taking them from the
program.
"""
from __future__ import annotations

from typing import Any, Callable, List

import jax
import jax.numpy as jnp

Tree = Any


def seed_key(seed: int) -> jax.Array:
    """A key from every bit of a seed of up to 64 bits (``jax.random.key``
    keeps only the low 32 without x64)."""
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def updates_fn(shapes: Tree, clients: int) -> Callable[[jax.Array], List[Tree]]:
    """``fn(key) -> [tree] * clients`` of f32 N(0, 1) client updates."""
    flat, treedef = jax.tree_util.tree_flatten(shapes)

    def make(key):
        out = []
        for c in range(clients):
            ck = jax.random.fold_in(key, c)
            out.append(jax.tree_util.tree_unflatten(treedef, [
                jax.random.normal(jax.random.fold_in(ck, i), s.shape, jnp.float32)
                for i, s in enumerate(flat)
            ]))
        return out

    return jax.jit(make)
