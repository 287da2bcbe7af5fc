"""The work an algorithm requires, counted from its shapes: what the
model FLOP utilization divides by.  Padding, recompute and capacity slack
are never counted, so that a change which removes them reads as a gain and
not as a share above 100%.
"""
from __future__ import annotations

from typing import Dict


def lm_train_flops_per_token(cfg: Dict[str, float], seq_len: int) -> float:
    """FLOPs the forward and backward passes of the cut decoder require per
    token (3 x the forward's 2 x multiply-adds): attention projections,
    causal attention (each position attends to itself and the positions
    before it: (seq_len + 1) / 2 on average), the router, the top-k
    experts' gated FFNs, and the head over the vocabulary held here.  No
    recompute, no capacity padding."""
    d = cfg["hidden_size"]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    layers = cfg["num_hidden_layers"]
    proj = d * (h * hd + 2 * kv * hd) + h * hd * d
    attn = 2 * h * hd * (seq_len + 1) / 2
    router = d * cfg["num_experts"]
    experts = cfg["num_experts_per_tok"] * 3 * d * cfg["moe_intermediate_size"]
    head = d * cfg["vocab_size"]
    macs = layers * (proj + attn + router + experts) + head
    return 3.0 * 2.0 * macs
