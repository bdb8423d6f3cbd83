"""Pallas TPU kernels (compute hot-spots; validated in interpret mode on CPU).

* ``agg`` — weighted multi-client model-delta reduction (aggregator role's
  HBM-bound hot loop).
* ``quant`` — blockwise int8 symmetric quant/dequant (per-channel wire-dtype
  payload transform).

Causal attention's fused kernel is JAX's own splash attention, called from
``repro.models.attention``.
"""
