"""End-to-end driver: train a ~100M-parameter MoE LM for a few hundred steps
with the full substrate — data pipeline, AdamW, checkpointing, fault-tolerant
restart, and CCM-LB expert re-placement from live router statistics.

  PYTHONPATH=src python examples/train_moe_ccm.py [--steps 300]
"""
import argparse
import dataclasses

import jax

from repro.configs.base import BLOCK_MOE, ModelConfig
from repro.launch.mesh import make_local_mesh
from repro.launch.train import train_loop
from repro.runtime.fault import FaultInjector, run_with_restarts

# ~100M params: 2*16k*512 embed + 8 layers x (attn ~1.3M + 16 experts x
# 3*512*512 + shared mlp) ~= 118M
CONFIG_100M = ModelConfig(
    name="moe-100m",
    family="moe",
    num_layers=8,
    d_model=512,
    num_heads=8,
    num_kv_heads=4,
    d_ff=1536,
    vocab_size=16384,
    head_dim=64,
    block_pattern=(BLOCK_MOE,),
    num_experts=16,
    top_k=2,
    moe_d_ff=512,
    act="silu",
)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/moe_ccm_ckpt")
    ap.add_argument("--fail-at", type=int, default=0,
                    help="inject a node failure at this step (0 = off)")
    args = ap.parse_args()

    mesh = make_local_mesh(1, 1)
    n = CONFIG_100M.param_count()
    print(f"[example] ~{n / 1e6:.0f}M params, {args.steps} steps, "
          f"CCM expert re-placement every 50 steps")
    inj = FaultInjector(fail_at_steps=(args.fail_at,) if args.fail_at else ())

    losses_all = []

    def once():
        _, _, losses = train_loop(
            CONFIG_100M, mesh, steps=args.steps, seq_len=args.seq_len,
            global_batch=args.global_batch, ckpt_dir=args.ckpt_dir,
            ckpt_every=50, rebalance_every=50, fault=inj, lr=1e-3,
            log_every=20)
        losses_all.append(losses)

    stats = run_with_restarts(once)
    losses = losses_all[-1]
    print(f"[example] done: loss {losses[0]:.3f} -> {losses[-1]:.3f} "
          f"(restarts={stats.restarts}, wall={stats.wall_s:.0f}s)")
    assert losses[-1] < losses[0], "loss did not decrease"


if __name__ == "__main__":
    main()
