"""The work the cut latent-attention MoE decoder (DeepSeek-V3's block, as
Moonlight-16B-A3B) requires, counted from its shapes, for its MFU and the
attention core's roofline share.  Padding, recompute and capacity slack are
never counted, so that a change which removes them reads as a gain and not
as a share above 100%.
"""
from __future__ import annotations

from typing import Dict


def causal_core_macs_per_token(cfg: Dict[str, float], seq_len: int) -> float:
    """Forward multiply-adds a token's causal attention core takes in one
    layer: scores over qk = nope + rope and the weighted sum over v, each
    position attending to itself and those before it ((S + 1) / 2 keys on
    average)."""
    qk = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    return cfg["num_attention_heads"] * (qk + cfg["v_head_dim"]) \
        * (seq_len + 1) / 2


def train_flops_per_token(cfg: Dict[str, float], seq_len: int) -> float:
    """FLOPs the forward and backward passes require per token (3 x the
    forward's 2 x multiply-adds): latent attention's projections and causal
    core in every layer; the dense layers' gated MLP; in each MoE layer the
    router over all the router's experts, the shared block and the held
    experts' share of the top-k (k x held / router experts a token); the
    head over the vocabulary held here."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    r, nope = cfg["kv_lora_rank"], cfg["qk_nope_head_dim"]
    rope, vd = cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    router_experts = cfg["published"]["n_routed_experts"]
    proj = (d * h * (nope + rope) + d * (r + rope) + r * h * (nope + vd)
            + h * vd * d)
    attn = proj + causal_core_macs_per_token(cfg, seq_len)
    mlp = 3 * d * cfg["intermediate_size"]
    f = cfg["moe_intermediate_size"]
    moe = (d * router_experts + 3 * d * f * cfg["n_shared_experts"]
           + cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
           / router_experts * 3 * d * f)
    macs = layers * attn + dense * mlp + (layers - dense) * moe \
        + d * cfg["vocab_size"]
    return 3.0 * 2.0 * macs


def causal_core_train_flops(cfg: Dict[str, float], seq_len: int,
                            tokens: int) -> float:
    """FLOPs the causal core requires in a train step of ``tokens`` tokens
    over all layers: the forward and a backward of twice its work."""
    return 3.0 * 2.0 * cfg["num_hidden_layers"] * tokens \
        * causal_core_macs_per_token(cfg, seq_len)
