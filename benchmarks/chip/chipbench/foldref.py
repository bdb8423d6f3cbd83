"""Plain reference of the sync round's aggregate: the sample-weighted mean
of the client updates, folded in numpy in client order.

Each update is scaled by its sample count, added to the running sum in
worker order, and the sum is divided once by the Python-float total: the
sequential IEEE fold the program claims to reproduce bit for bit.
``bfloat16=True`` is the control: the same fold with the updates, the
products and the sum held in bfloat16, the precision below the float32 the
configuration states.
"""
from __future__ import annotations

from typing import Any, Sequence, Tuple

import jax
import ml_dtypes
import numpy as np

Tree = Any


def sequential_fold(updates: Sequence[Tuple[Tree, float]],
                    bfloat16: bool = False) -> Tuple[Tree, float]:
    dt = ml_dtypes.bfloat16 if bfloat16 else np.float32
    total, acc = 0.0, None
    for tree, n in updates:
        total += n
        scaled = jax.tree_util.tree_map(
            lambda x: np.asarray(x).astype(dt) * dt(n), tree)
        acc = scaled if acc is None else jax.tree_util.tree_map(np.add, acc, scaled)
    mean = jax.tree_util.tree_map(lambda x: (x / dt(total)).astype(np.float32), acc)
    return mean, total


def mismatched(got: Tree, want: Tree) -> int:
    """Elements whose bits differ (NaNs never match)."""
    n = 0
    for a, b in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        a, b = np.asarray(a), np.asarray(b)
        if a.shape != b.shape or a.dtype != b.dtype:
            n += max(a.size, b.size)
            continue
        n += int(np.count_nonzero(a.view(np.uint32) != b.view(np.uint32)))
    return n
