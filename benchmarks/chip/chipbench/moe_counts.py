"""Model operations of a Qwen3-MoE FL train step, counted from the
configuration and the traffic alone.

Every matrix product counts 2 operations a multiply-add; training counts
the forward pass and twice it for the backward, and nothing recomputed.
Attention counts the causal query-key pairs of each sequence. The held
experts count at their share of the picks under even routing, k · held / E
a token (8 · 8 / 128 = 0.5 at the cut), so the count does not depend on the
routing a seed happens to give.
"""
from __future__ import annotations

TRAIN = 3  # forward, and the backward's two products per forward product


def expert_pick_flops(cfg: dict) -> float:
    """Forward operations of one pick through one expert: gate, up, down."""
    return 2.0 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def token_flops(cfg: dict, seq_len: int) -> float:
    """Forward operations a token, averaged over a sequence of ``seq_len``."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    proj = 2.0 * (d * q + 2 * d * kv + q * d)
    pairs = (seq_len + 1) / 2  # keys a query sees, on average, under the causal mask
    core = 4.0 * cfg["num_attention_heads"] * hd * pairs
    router = 2.0 * d * cfg["num_experts_total"]
    held_picks = cfg["num_experts_per_tok"] * cfg["num_experts"] / cfg["num_experts_total"]
    layer = proj + core + router + held_picks * expert_pick_flops(cfg)
    head = 2.0 * d * cfg["vocab_size"]
    return cfg["num_hidden_layers"] * layer + head


def round_tokens(traffic: dict) -> int:
    return traffic["local_steps"] * traffic["seqs_per_step"] * traffic["seq_len"]


def round_flops(cfg: dict, traffic: dict) -> float:
    """Model operations of one FL round: every local step's tokens, trained."""
    return TRAIN * round_tokens(traffic) * token_flops(cfg, traffic["seq_len"])
