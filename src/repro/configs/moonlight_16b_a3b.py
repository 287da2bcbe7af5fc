"""moonlight-16b-a3b [moe] — 27L d_model=2048 16H latent attention (MLA:
kv_lora_rank 512, qk 128 + rope 64, v 128), layer 0 dense (d_ff=11264), then
26 MoE layers of 64 routed experts top-6 (moe_d_ff=1408) plus 2 shared
experts, sigmoid routing with a correction bias (noaux_tc), routed weights
renormalised and scaled by 2.446, vocab=163840.
[hf:moonshotai/Moonlight-16B-A3B config.json, model_type deepseek_v3]

Rope pairs the halves of q_pe and k_pe where the published code interleaves
them: a fixed permutation of the rope columns of w_q and w_kv_a.  The
sequence-wise balance loss and the bias rate follow the DeepSeek-V3 report
(arXiv:2412.19437, sections 2.1.2 and 4.2: alpha 1e-4, gamma 0.001); the
config gives neither.  Serving through a latent cache is not built:
prefill and decode refuse MLA, so only the train cell runs.
"""
from repro.configs.base import BLOCK_MOE, ModelConfig

CONFIG = ModelConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=11264,           # the leading dense layer's MLP
    vocab_size=163840,
    attn_type="mla",
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rope_theta=50000.0,
    block_pattern=(BLOCK_MOE,),
    first_dense_layers=1,
    num_experts=64,
    top_k=6,
    moe_d_ff=1408,
    num_shared_experts=2,
    shared_d_ff=2 * 1408,
    router_scoring="sigmoid",
    router_bias_rate=0.001,
    routed_scaling=2.446,
    aux_loss="sequence",
    aux_loss_weight=1e-4,
    norm_eps=1e-5,
    act="silu",
    skip_shapes=("prefill_32k", "decode_32k", "long_500k"),
)

SMOKE = ModelConfig(
    name="moonlight-smoke",
    family="moe",
    num_layers=3,
    d_model=64,
    num_heads=4,
    num_kv_heads=4,
    d_ff=96,
    vocab_size=256,
    attn_type="mla",
    kv_lora_rank=32,
    qk_nope_head_dim=16,
    qk_rope_head_dim=8,
    v_head_dim=16,
    rope_theta=50000.0,
    block_pattern=(BLOCK_MOE,),
    first_dense_layers=1,
    num_experts=8,
    top_k=2,
    moe_d_ff=32,
    num_shared_experts=2,
    shared_d_ff=64,
    router_scoring="sigmoid",
    router_bias_rate=0.001,
    routed_scaling=2.446,
    aux_loss="sequence",
    aux_loss_weight=1e-4,
    capacity_factor=8.0,   # no-drop for smoke/parity tests
    norm_eps=1e-5,
    act="silu",
    skip_shapes=("prefill_32k", "decode_32k", "long_500k"),
)
