"""Roofline share of the attention core (score, softmax, weighted sum; the
fused kernel on the chip): the FLOPs the causal core requires in a traced
step, forward and backward (``bench/flops_mla.py``; remat's recompute and
the masked half of the blocks not counted), over the device time of the
ops under the ``attn/core`` name scope (``models/attention.py``) times the
bf16 peak.  The core is bound by compute.  ``None`` where no op carries the
scope."""
from bench import flops_mla, scope_time


def read(run, trace, peaks):
    ms = scope_time.ms_per_step(run, trace, "attn/core")
    if not ms:
        return None
    mix = run.traffic
    need = flops_mla.causal_core_train_flops(
        run.config, mix["seq_len"], mix["global_batch"] * mix["seq_len"])
    need /= len(run.devices)
    return 100.0 * need / (ms * 1e-3 * peaks["flops_bf16"])
