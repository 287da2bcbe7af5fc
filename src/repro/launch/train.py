"""Training launcher.

Runs any --arch (smoke configs on CPU; full configs are for the production
meshes) with: checkpoint/restart fault tolerance and — for MoE archs —
periodic CCM-LB expert re-placement applied as function-preserving slot
permutations.  Under ``jax.profiler.trace`` each step shows as a ``train``
step span, and a replan as ``ccm_lb.plan`` and ``rebalance.permute`` spans.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-moe-30b-a3b \
      --smoke --steps 50 --rebalance-every 20
"""
from __future__ import annotations

import argparse
import functools
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.balance.expert_placement import plan_expert_placement
from repro.checkpoint import CheckpointManager
from repro.data.pipeline import make_batch
from repro.launch.mesh import axes_for, make_local_mesh, \
    make_production_mesh
from repro.launch.steps import abstract_opt, abstract_params, make_train_step
from repro.models.layers import split_lp_tree
from repro.models.model import build_model
from repro.models.moe import expert_dropped
from repro.optim import adamw_init
from repro.runtime.fault import FaultInjector, run_with_restarts


def train_loop(cfg, mesh, *, steps: int, seq_len: int, global_batch: int,
               ckpt_dir: Optional[str] = None, ckpt_every: int = 50,
               rebalance_every: int = 0, fault: Optional[FaultInjector] = None,
               lr: float = 3e-4, log_every: int = 10, seed: int = 0,
               hbm_budget_bytes: Optional[float] = None):
    """``hbm_budget_bytes`` is the per-device budget of the expert replan;
    ``None`` reads it from the device (see ``rebalance_experts``)."""
    model = build_model(cfg, mesh)
    params_sds, p_sh = abstract_params(model)
    opt_sds, o_sh = abstract_opt(params_sds, p_sh)
    # the step returns params and moments placed as they came in, so every
    # step after the first reuses its program
    step_fn = jax.jit(make_train_step(model, lr=lr,
                                      warmup_steps=max(1, steps // 10),
                                      total_steps=steps),
                      out_shardings=(p_sh, o_sh, None),
                      donate_argnums=(0, 1))

    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    start = 0
    if mgr and mgr.latest() is not None:
        (params, opt_state), start = mgr.restore((params_sds, opt_sds),
                                                 (p_sh, o_sh))
        print(f"[train] restored step {start}")
    else:
        lp = model.init(jax.random.key(seed))
        params, _ = split_lp_tree(lp)
        params = jax.device_put(params, p_sh)
        opt_state = jax.device_put(adamw_init(params), o_sh)

    losses = []
    for step in range(start, steps):
        if fault is not None:
            fault.maybe_fail(step)
        batch = make_batch(cfg, seq_len, global_batch, step, seed=seed)
        t0 = time.time()
        with jax.profiler.StepTraceAnnotation("train", step_num=step):
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            loss = float(metrics["loss"])
        dt = time.time() - t0
        losses.append(loss)
        if step % log_every == 0 or step == steps - 1:
            print(f"[train] step {step} loss {loss:.4f} ({dt:.2f}s)"
                  + _routing_note(metrics, cfg, mesh, seq_len, global_batch),
                  flush=True)
        if mgr and ((step + 1) % ckpt_every == 0 or step == steps - 1):
            mgr.save(step + 1, (params, opt_state))
        if (rebalance_every and cfg.is_moe and (step + 1) % rebalance_every == 0
                and "expert_counts" in metrics):
            counts = np.asarray(metrics["expert_counts"])  # (periods, E)
            params, opt_state, _ = rebalance_experts(
                params, opt_state, counts, cfg, mesh,
                hbm_budget_bytes=hbm_budget_bytes)
    if mgr:
        mgr.wait()
    return params, opt_state, losses


def _routing_note(metrics, cfg, mesh, seq_len: int, global_batch: int) -> str:
    """The step's routed assignments and those its held experts dropped at
    capacity (``moe.expert_dropped``), for the log; empty without an
    expert layer."""
    if "expert_counts" not in metrics:
        return ""
    counts = np.asarray(metrics["expert_counts"])
    shards = int(np.prod([mesh.shape[a] for a in axes_for(mesh).batch]))
    dropped = expert_dropped(counts, cfg, global_batch * seq_len // shards,
                             shards)
    return (f"; routed {int(counts.sum())}, expert_dropped {dropped}, "
            f"max/mean count {float(counts.max() / counts.mean()):.3f}")


def _permute_experts(params, opt_state, perms, cfg):
    """Apply the per-period slot permutations ``perms`` (periods, held) to
    the held expert weights of every MoE block, to the router's columns and
    router bias of the held experts, and to their AdamW moments, so each
    moment stays with the weight it belongs to."""
    lo = cfg.first_held_expert
    hi = lo + cfg.held_experts

    def take(leaf, axis, cols=None):
        def one(sl, p):
            if cols is None:
                return jnp.take(sl, p, axis=axis)
            idx = jnp.arange(sl.shape[axis]).at[cols[0]:cols[1]].set(
                p + cols[0])
            return jnp.take(sl, idx, axis=axis)
        return jax.vmap(one)(leaf, perms)

    def one_tree(tree):
        scan = dict(tree["scan"])
        for i, kind in enumerate(cfg.block_pattern):
            if kind != "moe":
                continue
            blk = dict(scan[f"b{i}"])
            moe = dict(blk["moe"])
            for name in ("w_gate", "w_up", "w_down"):
                moe[name] = take(moe[name], 0)
            moe["router"] = take(moe["router"], 1, (lo, hi))
            if "router_bias" in moe:
                moe["router_bias"] = take(moe["router_bias"], 0, (lo, hi))
            blk["moe"] = moe
            scan[f"b{i}"] = blk
        out = dict(tree)
        out["scan"] = scan
        return out

    return one_tree(params), opt_state._replace(m=one_tree(opt_state.m),
                                                v=one_tree(opt_state.v))


@functools.lru_cache(maxsize=8)
def _permute_program(cfg, shardings, treedef):
    """The jitted permutation for one config and placement, built once, so
    that a replan after the first compiles nothing."""
    return jax.jit(functools.partial(_permute_experts, cfg=cfg),
                   out_shardings=jax.tree_util.tree_unflatten(
                       treedef, shardings),
                   donate_argnums=(0, 1))


def apply_expert_permutation(params, opt_state, perms, cfg):
    """``_permute_experts`` in one program that keeps each leaf's sharding,
    so the experts stay spread over the model axis; built once per config
    and placement."""
    leaves, treedef = jax.tree.flatten((params, opt_state))
    return _permute_program(cfg, tuple(a.sharding for a in leaves), treedef)(
        params, opt_state, jnp.asarray(perms))


def rebalance_experts(params, opt_state, counts, cfg, mesh, *,
                      hbm_budget_bytes: Optional[float] = None):
    """CCM-LB plan -> per-layer slot permutation applied to live params and
    to the optimizer state.  Returns ``(params, opt_state, plan)``; ``plan``
    is ``None`` when nothing was applied.

    ``counts`` (periods, num_experts) are the router's counts of every
    expert; the plan places the experts this layer holds
    (``cfg.experts_held``) over the model axis.  The plan's per-device
    budget is ``hbm_budget_bytes`` or, when that is ``None``, the limit the
    device reports; a backend that reports none needs it passed."""
    n_model = int(mesh.shape["model"])
    n_dev = max(n_model, 1)
    held = cfg.held_experts
    if n_dev == 1 or held % n_dev:
        return params, opt_state, None
    if hbm_budget_bytes is None:
        stats = mesh.devices.flat[0].memory_stats() or {}
        if "bytes_limit" not in stats:
            raise ValueError("the backend reports no device memory limit: "
                             "pass hbm_budget_bytes")
        hbm_budget_bytes = float(stats["bytes_limit"])
    lo = cfg.first_held_expert
    with jax.profiler.TraceAnnotation("ccm_lb.plan"):
        plan = plan_expert_placement(
            np.asarray(counts)[:, lo:lo + held], cfg, n_dev,
            hbm_budget_bytes=hbm_budget_bytes,
            rank_speed=None)
    if plan.max_work_after >= plan.max_work_before:
        return params, opt_state, None
    with jax.profiler.TraceAnnotation("rebalance.permute"):
        params, opt_state = apply_expert_permutation(
            params, opt_state, plan.permutations, cfg)
    print(f"[ccm-lb] expert re-placement: imbalance "
          f"{plan.imbalance_before:.3f} -> {plan.imbalance_after:.3f} "
          f"(replication suggested on {plan.replicated_blocks} blocks)",
          flush=True)
    return params, opt_state, plan


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--rebalance-every", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--production-mesh", action="store_true")
    args = ap.parse_args()

    cfg = (configs.get_smoke_config(args.arch) if args.smoke
           else configs.get_config(args.arch))
    mesh = (make_production_mesh() if args.production_mesh
            else make_local_mesh(1, 1))

    def once():
        train_loop(cfg, mesh, steps=args.steps, seq_len=args.seq_len,
                   global_batch=args.global_batch, ckpt_dir=args.ckpt_dir,
                   ckpt_every=args.ckpt_every,
                   rebalance_every=args.rebalance_every, lr=args.lr)

    stats = run_with_restarts(once)
    print(f"[train] done: restarts={stats.restarts} wall={stats.wall_s:.1f}s")


if __name__ == "__main__":
    main()
