"""The work count MFU divides by, against hand counts at a small size."""
import pytest

import benchutil  # noqa: F401  (puts the repository on the path)
from bench import flops

TINY = {"hidden_size": 4, "num_attention_heads": 2, "num_key_value_heads": 1,
        "head_dim": 2, "num_experts": 4, "num_experts_per_tok": 2,
        "moe_intermediate_size": 3, "vocab_size": 5, "num_hidden_layers": 1}


def test_train_flops_per_token_hand_count():
    # per token: q 4x(2x2) + k,v 2 x 4x(1x2) + o (2x2)x4 = 16+16+16 = 48
    # MACs; causal attention at seq 3: 2 x 2 heads x 2 x (3+1)/2 = 16;
    # router 4x4 = 16; two experts x 3 matrices x 4x3 = 72; head 4x5 =
    # 20.  Total 172 MACs, x2 FLOPs, x3 forward+backward = 1032.
    assert flops.lm_train_flops_per_token(TINY, 3) == pytest.approx(1032)


def test_train_flops_scale_with_layers_but_not_the_head():
    two = dict(TINY, num_hidden_layers=2)
    one = flops.lm_train_flops_per_token(TINY, 3)
    assert flops.lm_train_flops_per_token(two, 3) - one == \
        pytest.approx(one - 6 * 20)


def test_qwen3_cut_flops_per_token():
    import json
    cfg = json.loads((benchutil.ROOT /
                      "bench/configs/qwen3-moe-30b-a3b-1L.json").read_text())
    # 18.87M projection + 16.78M attention + 0.26M router + 37.75M expert
    # + 38.90M head MACs a token at seq 4096
    assert flops.lm_train_flops_per_token(cfg, 4096) == \
        pytest.approx(6 * 95_780_864 + 6 * 16_781_312, rel=1e-9)
