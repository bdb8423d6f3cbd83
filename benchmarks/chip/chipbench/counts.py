"""Operations and bytes that a cell's work needs, counted from its shapes.

The counts depend only on the configuration and the traffic, never on
which implementation runs, so every PR's shares are judged on the same work.
"""
from __future__ import annotations


def decoder_layer_params(cfg: dict) -> int:
    """Parameters of one Qwen2-style decoder layer: q/k/v with bias, o
    without, a gated MLP of three matrices, two RMSNorm scales."""
    d = cfg["hidden_size"]
    head_dim = d // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * head_dim
    kv = cfg["num_key_value_heads"] * head_dim
    attn = d * q + q + 2 * (d * kv + kv) + q * d
    mlp = 3 * d * cfg["intermediate_size"]
    return attn + mlp + 2 * d


def fold_least_bytes(clients: int, elems: int, itemsize: int = 4) -> int:
    """Least HBM bytes of a weighted fold of ``clients`` updates of ``elems``
    elements: every update read once, the result written once."""
    return clients * elems * itemsize + elems * itemsize
