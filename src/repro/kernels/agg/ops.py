"""Public wrapper: weighted aggregation over pytrees of client deltas.

``aggregate_tree`` flattens a batch-of-client pytrees (leaves lead with the
client dim C), runs the bandwidth-optimal reduction per leaf chunk and
restores the structure — the aggregator role's compute hot-spot.

Two modes:

* ``exact=False`` — single fused ``(w @ d) / denom`` pass (fastest; the
  compiler may FMA-contract, shifting results by an ulp).
* ``exact=True`` — the mode the aggregator roles run: the ``w_c * d_c``
  scale pass is compiled *separately* from the add-only fold, so no
  multiply ever sits next to an add inside one XLA computation and nothing
  can be FMA-contracted. The result is bit-identical to the sequential
  per-client ``tree_map`` accumulation the roles used before the fused
  path existed (verified by ``tests/test_fused_agg.py``), at the cost of
  one extra materialized (C, N) buffer. The final division runs on the
  host in numpy, and the result is a numpy array.

Dispatch: on an accelerator the Pallas kernels run natively; on CPU the
wrappers route to plain jnp implementations with the same op structure
(bit-identical; interpret-mode Pallas pays per-grid-step overhead that
dominates on large grids). Pass ``interpret=True`` explicitly to exercise
the kernels themselves on CPU. ``fused_dispatch_default()`` tells callers
whether *auto* size-based dispatch should prefer the fused path at all —
on CPU the per-client numpy loop is already the fast path, and since both
paths produce identical bits the choice is purely about speed.

``denom`` overrides the normalizer (default: sum of weights). The roles
pass the Python-float sample total so the final division matches the
sequential path bit-for-bit.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.agg.kernel import fold_scaled, weighted_aggregate


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def fused_dispatch_default() -> bool:
    """Whether size-based auto-dispatch should route aggregations through
    the fused stacked path. True on accelerators; on CPU the sequential
    numpy loop beats kernel dispatch, so auto stays sequential (forcing
    the fused path remains available and bit-identical)."""
    return not _on_cpu()


def stack_client_trees(trees):
    """Stack per-client pytrees into one tree whose leaves lead with the
    client dim C (the ``aggregate_tree`` input layout).

    Returns None when the trees aren't *uniform float32 pytrees* — mismatched
    treedefs (different keys/structure), ragged shapes, or non-f32 leaves —
    so fused callers fall back to the sequential path, which either handles
    or rejects such inputs with its own error surface. Each tree is
    flattened exactly once."""
    flat0, treedef = jax.tree_util.tree_flatten(trees[0])
    for ref in flat0:
        if getattr(ref, "dtype", None) != np.float32 or not hasattr(ref, "shape"):
            return None
    flats = [flat0]
    for tree in trees[1:]:
        leaves, td = jax.tree_util.tree_flatten(tree)
        if td != treedef:
            return None
        flats.append(leaves)
    stacked = []
    for i, ref in enumerate(flat0):
        rows = []
        for leaves in flats:
            leaf = leaves[i]
            if getattr(leaf, "shape", None) != ref.shape or (
                getattr(leaf, "dtype", None) != np.float32
            ):
                return None
            rows.append(np.asarray(leaf))
        stacked.append(np.stack(rows))
    return jax.tree_util.tree_unflatten(treedef, stacked)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fused_flat(deltas, weights, den, *, interpret):
    if interpret is None:
        if _on_cpu():
            # same math as the kernel ((w @ d) / den), plain XLA dot
            w = weights.astype(jnp.float32)
            return (w @ deltas.astype(jnp.float32)) / den[0]
        interpret = False
    return weighted_aggregate(deltas, weights, den, interpret=interpret)


@functools.partial(jax.jit, static_argnames=())
def _scale_rows(deltas, weights):
    # kept as its own jit entry: compiling this multiply together with the
    # fold would let XLA contract mul+add into FMAs and break the
    # bit-equality of exact mode with sequential accumulation
    return deltas.astype(jnp.float32) * weights.astype(jnp.float32)[:, None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fold_flat(scaled, *, interpret):
    if interpret is None:
        if _on_cpu():
            # same op structure as the fold kernel (adds only, client
            # order), vectorized by XLA — bit-identical, no Pallas
            # interpreter overhead
            acc = scaled[0]
            for c in range(1, scaled.shape[0]):
                acc = acc + scaled[c]
            return acc
        interpret = False
    return fold_scaled(scaled, interpret=interpret)


def aggregate_flat(
    deltas: jax.Array,  # (C, N)
    weights: jax.Array,  # (C,)
    *,
    denom=None,  # scalar normalizer; default sum(weights) (clamped > 0)
    interpret: bool = None,  # type: ignore[assignment]
    exact: bool = False,
) -> jax.Array | np.ndarray:
    deltas = jnp.asarray(deltas)
    weights = jnp.asarray(weights, jnp.float32)
    if denom is None:
        den = jnp.maximum(jnp.sum(weights), 1e-30).reshape(1)
    else:
        den = jnp.asarray(denom, jnp.float32).reshape(1)
    if not exact:
        return _fused_flat(deltas, weights, den, interpret=interpret)
    scaled = _scale_rows(deltas, weights)
    summed = np.asarray(_fold_flat(scaled, interpret=interpret))
    # divide on the host, as the sequential fold does: the TPU's f32
    # division is not correctly rounded (up to 2 ulp on a v5e)
    return summed / np.asarray(den)[0]


def aggregate_tree(client_trees, weights, *, denom=None, interpret=None,
                   exact: bool = False):
    """Leaves of ``client_trees`` lead with the client dim C."""
    leaves, treedef = jax.tree_util.tree_flatten(client_trees)
    C = leaves[0].shape[0]
    flat = jnp.concatenate([l.reshape(C, -1) for l in leaves], axis=1)
    agg = aggregate_flat(
        flat, weights, denom=denom, interpret=interpret, exact=exact
    )
    out, offset = [], 0
    for l in leaves:
        size = l[0].size
        out.append(agg[offset : offset + size].reshape(l.shape[1:]).astype(l.dtype))
        offset += size
    return jax.tree_util.tree_unflatten(treedef, out)
