"""Pallas kernel: blockwise int8 symmetric quantize / dequantize.

The channel payload transform behind the per-channel ``wire_dtype="int8"``
policy (§6.2): before a model update crosses a slow channel (cross-pod DCN),
it is quantized to int8 with one f32 scale per block.
Memory-bound by construction; the kernel fuses absmax + scale + round in a
single VMEM pass per block so HBM sees each element once.

Each grid step takes ``ROWS`` quantization blocks at once: the TPU tiles
the last two dimensions of a block by (8, 128) for f32 and (32, 128) for
int8, so a one-row block is refused. Inputs whose block count is not a
multiple of ``ROWS`` are padded with zero rows, whose scales and codes are
sliced off again.

Layout: x (NB, BLOCK) f32 -> (q (NB, BLOCK) int8, scale (NB, 1) f32).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

ROWS = 32


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)  # (ROWS, BLOCK)
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    scale = jnp.maximum(absmax, 1e-30) / 127.0
    q_ref[...] = jnp.clip(jnp.round(x / scale), -127, 127).astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, x_ref):
    x_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


def _pad_rows(a: jax.Array) -> jax.Array:
    pad = (-a.shape[0]) % ROWS
    return jnp.pad(a, ((0, pad), (0, 0))) if pad else a


def quantize_blocks(x: jax.Array, *, interpret: bool = False):
    """x: (NB, BLOCK) f32 -> (q int8, scale (NB, 1) f32)."""
    NB, BLOCK = x.shape
    xp = _pad_rows(x)
    rows = pl.BlockSpec((ROWS, BLOCK), lambda i: (i, 0))
    q, s = pl.pallas_call(
        _quant_kernel,
        grid=(xp.shape[0] // ROWS,),
        in_specs=[rows],
        out_specs=[rows, pl.BlockSpec((ROWS, 1), lambda i: (i, 0))],
        out_shape=[
            jax.ShapeDtypeStruct(xp.shape, jnp.int8),
            jax.ShapeDtypeStruct((xp.shape[0], 1), jnp.float32),
        ],
        interpret=interpret,
    )(xp)
    return q[:NB], s[:NB]


def dequantize_blocks(q: jax.Array, scale: jax.Array, *, interpret: bool = False):
    NB, BLOCK = q.shape
    qp, sp = _pad_rows(q), _pad_rows(scale)
    rows = pl.BlockSpec((ROWS, BLOCK), lambda i: (i, 0))
    x = pl.pallas_call(
        _dequant_kernel,
        grid=(qp.shape[0] // ROWS,),
        in_specs=[rows, pl.BlockSpec((ROWS, 1), lambda i: (i, 0))],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct(qp.shape, jnp.float32),
        interpret=interpret,
    )(qp, sp)
    return x[:NB]
