"""Pallas kernels: weighted multi-client model-delta reduction.

The aggregator role's hot loop (FedAvg-style weighted mean over C client
deltas) is HBM-bandwidth-bound: C·N reads for N writes, zero reuse. Both
kernels tile the flattened parameter axis into VMEM-sized blocks so each
delta element is read exactly once — the roofline for this op. Two flavors:

* ``weighted_aggregate`` — single fused pass: the weights stay resident in
  SMEM and each block computes ``sum_c w_c * d_c / denom``. Fastest, but the
  compiler is free to contract the multiply-add chain into FMAs, so the
  result can differ from a sequential IEEE mul-then-add accumulation by an
  ulp or two.
* ``fold_scaled`` — the order-exact flavor used by the aggregator roles:
  consumes *pre-scaled* rows (the ``w_c * d_c`` products are materialized by
  a separately-compiled elementwise pass, see ``ops.aggregate_flat``) and
  folds them in client order with plain adds. With no multiply adjacent to
  the adds inside the kernel there is nothing to FMA-contract, so the
  accumulation is bit-identical to the sequential per-client ``tree_map``
  loop it replaces — which is what keeps seeded jobs byte-comparable across
  the fused and fallback paths. It returns the sum: the TPU's f32 division
  is not correctly rounded (up to 2 ulp on a v5e), so the caller divides.

Both loops read client row ``c`` from the ref (``d_ref[pl.ds(c, 1), :]``):
indexing a *loaded* block with the loop counter is a ``dynamic_slice``,
which the TPU lowering does not implement. The block width comes from
``block_n_for`` so the double-buffered ``(C, block_n)`` input block fits the
scoped VMEM whatever the client count.

Layout: deltas (C, N) f32, weights (C,) f32, denom (1,) f32 -> out (N,)
f32; ``fold_scaled`` takes pre-scaled rows and no weights or denom.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# VMEM bytes the double-buffered (C, block_n) input block may take: half of
# the 16 MiB default scoped VMEM of a TPU v5e core, leaving the rest for the
# output block and the accumulator
_IN_BLOCK_BUDGET = 8 * 1024 * 1024
_MAX_BLOCK_N = 65_536


def block_n_for(n_clients: int) -> int:
    """Widest lane-aligned power-of-two block (at most 65,536 elements) whose
    double-buffered f32 ``(C, block_n)`` input block fits
    ``_IN_BLOCK_BUDGET``, with C padded to the 8-row sublane tile."""
    rows = -(-n_clients // 8) * 8
    block = _MAX_BLOCK_N
    while block > 128 and 2 * rows * block * 4 > _IN_BLOCK_BUDGET:
        block //= 2
    return block


def _agg_kernel(w_ref, den_ref, d_ref, o_ref, *, n_clients: int):
    def body(c, acc):
        return acc + w_ref[c] * d_ref[pl.ds(c, 1), :]

    acc = jax.lax.fori_loop(
        0, n_clients, body, jnp.zeros(o_ref.shape, jnp.float32)
    )
    o_ref[...] = acc / den_ref[0]


def _fold_kernel(d_ref, o_ref, *, n_clients: int):
    def body(c, acc):
        return acc + d_ref[pl.ds(c, 1), :]

    # init from the first row, not zeros: 0.0 + (-0.0) is +0.0, so a
    # zeros-seeded fold would flip the sign of all-negative-zero elements
    # and break bit-identity with the sequential accumulation
    o_ref[...] = jax.lax.fori_loop(1, n_clients, body, d_ref[pl.ds(0, 1), :])


def _call(kernel, deltas, *scalars, interpret: bool):
    """``scalars`` (weights, denom) ride in SMEM; ``deltas`` is tiled into
    ``(C, block)`` blocks along the parameter axis, zero-padded to a whole
    number of blocks."""
    C, N = deltas.shape
    block = min(block_n_for(C), N)
    pad = (-N) % block
    if pad:
        deltas = jnp.pad(deltas, ((0, 0), (0, pad)))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        functools.partial(kernel, n_clients=C),
        grid=(deltas.shape[1] // block,),
        in_specs=[smem] * len(scalars)
        + [pl.BlockSpec((C, block), lambda i: (0, i))],
        out_specs=pl.BlockSpec((1, block), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, deltas.shape[1]), jnp.float32),
        interpret=interpret,
    )(*scalars, deltas)
    return out[0, :N]


def weighted_aggregate(
    deltas: jax.Array,  # (C, N)
    weights: jax.Array,  # (C,)
    denom: jax.Array = None,  # (1,) f32; default: sum(weights)
    *,
    interpret: bool = False,
) -> jax.Array:
    """Fused single-pass ``sum_c w_c * d_c / denom`` (FMA-contractable)."""
    if denom is None:
        denom = jnp.maximum(
            jnp.sum(weights.astype(jnp.float32)), 1e-30
        ).reshape(1)
    return _call(
        _agg_kernel, deltas.astype(jnp.float32), weights.astype(jnp.float32),
        denom, interpret=interpret,
    )


def fold_scaled(
    scaled: jax.Array,  # (C, N) f32 — already multiplied by client weights
    *,
    interpret: bool = False,
) -> jax.Array:
    """Order-exact fold: ``((s_0 + s_1) + ...) + s_{C-1}``."""
    return _call(_fold_kernel, scaled.astype(jnp.float32), interpret=interpret)
