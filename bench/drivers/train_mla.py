"""The latent-attention MoE training cells (Moonlight-16B-A3B,
DeepSeek-V3's block): ``train.py``'s set-up, window and check, with the
program's config built from the configuration's own keys and the plain
reference ``bench/refs/mla_moe_lm.py``.

Beside ``train.py``'s numbers the check compares the router bias after the
set-up steps (``router_bias_gap``), and logs what it saw of the held
experts: how many of their parameters it counted (a held expert that no
token reaches has no gradient to compare) and their widest gaps.  The
window logs to standard error what the bias balances: the routed counts
over all the router's experts as max/mean per layer, the share of routed
assignments that reach the experts held here, and the spread of the bias.
"""
from __future__ import annotations

import dataclasses
import re
from pathlib import Path

import numpy as np

from bench.harness import load_module

HERE = Path(__file__).resolve().parent
base = load_module(HERE / "train.py", "bench_train_for_mla")
base.REF = HERE.parent / "refs" / "mla_moe_lm.py"


def program_config(cfg: dict):
    """The program's model config for this configuration file; refuses a
    program whose published widths differ from the file's."""
    from repro import configs
    arch = cfg["program_config"]
    full = (configs.get_smoke_config(arch) if cfg.get("program_smoke")
            else configs.get_config(arch))
    pub = {**cfg, **cfg["published"]}
    pairs = {"d_model": "hidden_size", "num_heads": "num_attention_heads",
             "kv_lora_rank": "kv_lora_rank",
             "qk_nope_head_dim": "qk_nope_head_dim",
             "qk_rope_head_dim": "qk_rope_head_dim",
             "v_head_dim": "v_head_dim", "d_ff": "intermediate_size",
             "moe_d_ff": "moe_intermediate_size",
             "num_experts": "n_routed_experts", "top_k": "num_experts_per_tok",
             "num_shared_experts": "n_shared_experts",
             "first_dense_layers": "first_k_dense_replace",
             "routed_scaling": "routed_scaling_factor",
             "rope_theta": "rope_theta",
             "norm_eps": "rms_norm_eps", "router_scoring": "scoring_func",
             "router_bias_rate": "bias_update_speed",
             "aux_loss_weight": "aux_loss_alpha"}
    for ours, theirs in pairs.items():
        if getattr(full, ours) != pub[theirs]:
            raise ValueError(f"the program's {ours} {getattr(full, ours)} is "
                             f"not the configuration's {pub[theirs]}")
    if full.attn_type != "mla" or full.aux_loss != "sequence" or \
            not cfg["norm_topk_prob"] or full.shared_width != \
            cfg["n_shared_experts"] * cfg["moe_intermediate_size"]:
        raise ValueError(f"{full.name} is not the configuration's block")
    return dataclasses.replace(full, num_layers=cfg["num_hidden_layers"],
                               vocab_size=cfg["vocab_size"],
                               experts_held=cfg["n_routed_experts"],
                               first_held_expert=cfg["first_held_expert"],
                               capacity_factor=cfg["capacity_factor"])


base.program_config = program_config


def setup(run):
    st = base.setup(run)
    if run.control:
        return st
    st.readings["router_bias"] = st.ref.router_bias(st.params)
    step = st.step_fn

    def keep_metrics(p, o, b):
        p, o, metrics = step(p, o, b)
        st.last_metrics = metrics
        return p, o, metrics

    st.step_fn = keep_metrics
    return st


def window(run, state):
    base.window(run, state)
    if run.control:
        return
    cfg = state.mcfg
    counts = np.asarray(state.last_metrics["expert_counts"], np.float64)
    bias = np.asarray(state.params["scan"]["b0"]["moe"]["router_bias"])
    lo, hi = cfg.first_held_expert, cfg.first_held_expert + cfg.held_experts
    held = counts[:, lo:hi].sum() / counts.sum()
    run.counters.update(routed_max_over_mean=(counts.max(1)
                                              / counts.mean(1)).tolist(),
                        held_share=float(held))
    run.log(f"[train] last step's routed counts over {counts.shape[1]} "
            f"experts, max/mean by layer "
            f"{np.round(counts.max(1) / counts.mean(1), 4).tolist()}; "
            f"held experts {lo}-{hi - 1} take {100 * held:.3f}% of routed "
            f"assignments ({100 * (hi - lo) / counts.shape[1]:.3f}% even)")
    run.log(f"[train] router bias spread (max - min) by layer "
            f"{np.round(bias.max(1) - bias.min(1), 6).tolist()}, after "
            f"{state.step} steps")


HELD = re.compile(r"/moe/w_\w+\[l\d+\.e\d+\]$")


class _HeldGaps:
    """The reference module, its ``gaps`` also logging the held experts'
    part of the comparison."""

    def __init__(self, ref, run, state):
        self._ref, self._run, self._state = ref, run, state

    def __getattr__(self, name):
        return getattr(self._ref, name)

    def gaps(self, prog, ref, rel_floor=1e-3):
        out, kept, worst = self._ref.gaps(prog, ref, rel_floor)
        names = self._ref.parameter_names(base._shapes(self._run,
                                                       self._state))
        held = [i for i, n in enumerate(names) if HELD.search(n)]
        counted = sorted(set(held) & set(kept))
        widest = {}
        for key, gap in (("grad_norms", "grad"), ("change_norms", "update")):
            p, r = np.asarray(prog[key]), np.asarray(ref[key])
            scale = np.maximum(r, np.median(r[kept]))
            widest[gap] = float(np.max(np.abs(p - r)[counted]
                                       / scale[counted])) if counted else None
        self._run.counters.update(held_counted=len(counted),
                                  held_gaps=widest)
        self._run.log(f"[train] held experts: {len(counted)} of {len(held)} "
                      f"parameters counted; widest gaps among them {widest}")
        return out, kept, worst


def check(run, state):
    state.ref = _HeldGaps(state.ref, run, state)
    base.check(run, state)
