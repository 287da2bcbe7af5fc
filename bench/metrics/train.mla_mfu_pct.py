"""Model FLOP utilization of a latent-attention MoE training window: the
FLOPs the forward and backward passes of the cut model require per token
(``bench/flops_mla.py``: latent attention's projections and causal core,
the dense MLP, the router, the shared block, the held experts' share of
the top-k, the head over the vocabulary held here; no recompute, no
capacity slack) times the window's tokens per second, over the chips' bf16
peak."""
from bench import flops_mla


def read(run, trace, peaks):
    rate = run.e2e.get("train_tokens_per_s")
    if not rate:
        return None
    per_token = flops_mla.train_flops_per_token(run.config,
                                                run.traffic["seq_len"])
    return 100.0 * rate * per_token / (len(run.devices) * peaks["flops_bf16"])
