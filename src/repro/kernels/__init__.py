"""Pallas TPU kernels (compute hot-spots; validated in interpret mode on CPU).

* ``quant`` — blockwise int8 symmetric quant/dequant (per-channel wire-dtype
  payload transform).

Causal attention's fused kernel is JAX's own splash attention, called from
``repro.models.attention``. The aggregators' weighted fold is two jitted
XLA ops in ``repro.core.fold``.
"""
