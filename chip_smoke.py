"""Smoke run of the balancer and the MoE trainer it places, on a TPU.

  python3 chip_smoke.py             # one chip: the plan and train phases
  python3 chip_smoke.py --chips 4   # four chips: the expert-parallel replan

One chip runs two phases in this one process:

1. plan: CCM-LB on the ``ccmlb_scaling`` instance at 256 ranks (6400
   tasks, 12799 comm edges), once per scorer backend.  The numpy engine is
   the reference.  ``pallas_compiled`` (f32, the kernel compiled for the
   chip) must give its assignment or, where f32 near-ties make it
   diverge, a plan that replays from its transfer log, keeps every rank
   under its memory cap and has max/mean work within 1% of the reference.
   ``jit`` (f64) must give the reference's assignment bit for bit, or
   refuse the platform; the TPU's emulated f64 makes it refuse there.
2. train: ``train_loop`` on a one-chip mesh for a few steps of
   qwen3-moe-30b-a3b at every published width, cut to one layer (the
   whole layer period) and a 1/8 share of the vocabulary, at 4096 tokens
   per sequence.  Every loss must be finite.

``--chips 4`` runs only the path that exists across chips: the same cut
model on a (data=1, model=4) mesh with 32 experts per chip, a replan
inside ``train_loop``, and a check on one fixed batch that a CCM expert
re-placement changes neither the loss (within bf16 tolerance) nor the
optimizer state, printed beside each chip's routed tokens.

The last line of standard output is one JSON object naming the device.
The script exits non-zero, with no such line, when JAX finds no TPU or
any check fails.  With ``JAX_COMPILATION_CACHE_DIR`` unset the compile
cache lives in ``.jax_cache/`` next to this file.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
from pathlib import Path
from time import perf_counter

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

PLAN_RANKS = 256
PLAN_KW = dict(n_iter=4, k_rounds=2, fanout=4, seed=0)
PLAN_MAX_MEAN_SLACK = 0.01      # pallas_compiled vs numpy, when they diverge
SEQ_LEN = 4096                  # the repo's train_4k cell
# the largest global batch whose train step the TPU compiler fits in one
# v5e's memory at the cut config: memory_analysis gives 15.99 GB at 3, of
# which 7.01 GB are parameters and AdamW moments; at 4 the compiler refuses
# (16.83 GiB of 15.75 GiB)
GLOBAL_BATCH = 3
TRAIN_STEPS = 4
REPLAN_STEPS = 3                # train_loop replans after step 1 of 0..2
# the loss on one batch before and after a replan: the expert outputs are
# summed in another order and rounded to bf16 once more, so allow two bf16
# ulps of the loss
REPLAN_LOSS_RTOL = 2.0 ** -7


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def qwen3_moe_cut():
    """qwen3-moe-30b-a3b at every published width, cut to one layer and a
    1/8 share of the vocabulary; returns ``(config, cuts)``."""
    from repro import configs
    full = configs.get_config("qwen3-moe-30b-a3b")
    cfg = dataclasses.replace(full, num_layers=len(full.block_pattern),
                              vocab_size=full.vocab_size // 8)
    cuts = [f"num_layers {full.num_layers} -> {cfg.num_layers} "
            "(one whole layer period)",
            f"vocab_size {full.vocab_size} -> {cfg.vocab_size} (1/8 share)"]
    return cfg, cuts


# ------------------------------------------------------------------ plan
def _replay(a0, transfer_log):
    a = a0.copy()
    for tasks, _src, dst in transfer_log:
        a[list(tasks)] = dst
    return a


def _first_divergence(log_a, log_b):
    for i, (x, y) in enumerate(zip(log_a, log_b)):
        if x != y:
            return i, x, y
    n = min(len(log_a), len(log_b))
    return n, (log_a[n] if n < len(log_a) else None), \
        (log_b[n] if n < len(log_b) else None)


def _warm_scorer(backend: str) -> None:
    """Compile the shape buckets a run at this phase's knobs touches, so
    the timed run holds no compiles."""
    import numpy as np

    from repro.kernels.ccm_scorer import jit as scorer_jit
    from repro.kernels.ccm_scorer.layout import N_AV, N_PM, N_SC, SC
    if backend == "jit":
        scorer_jit.warmup(max_batch=1)
        return
    # pallas_compiled: one event, A on the 8-sublane grid up to 12
    # candidates plus the empty one, B on the 128-lane boundary
    for a_n in (8, 16):
        sc = np.zeros((1, N_SC))
        sc[:, SC.speed_a] = sc[:, SC.speed_b] = 1.0
        scorer_jit.score_tiles_f32(np.zeros((1, N_AV, a_n)),
                                   np.zeros((1, N_AV, 128)),
                                   np.zeros((1, N_PM, a_n, 128)), sc)


def plan_phase(ranks: int = PLAN_RANKS,
               backends=("pallas_compiled", "jit"), log=print) -> dict:
    """CCM-LB on ``scaling_phase(ranks)`` per backend against the numpy
    reference.  Returns per-backend seconds and whether the f32 kernel
    was interpreted."""
    import numpy as np

    from repro.core import CCMParams, CCMState, ccm_lb
    from repro.core.problem import initial_assignment, scaling_phase
    from repro.kernels.ccm_scorer import jit as scorer_jit

    params = CCMParams(delta=1e-9)
    phase = scaling_phase(ranks)
    a0 = initial_assignment(phase)
    mean = phase.task_load.sum() / ranks
    log(f"[plan] scaling_phase({ranks}): {phase.num_ranks} ranks, "
        f"{phase.num_tasks} tasks, {phase.num_comms} comm edges; "
        f"n_iter={PLAN_KW['n_iter']} k_rounds={PLAN_KW['k_rounds']} "
        f"fanout={PLAN_KW['fanout']} seed={PLAN_KW['seed']}")

    def run(backend):
        t0 = perf_counter()
        res = ccm_lb(phase, a0, params, backend=backend, **PLAN_KW)
        dt = perf_counter() - t0
        mm = float(res.max_work[-1] / mean)
        log(f"[plan] {backend}: {dt:.3f} s to plan, {res.transfers} "
            f"transfers, max/mean {mm:.6f}")
        return res, dt, mm

    ref, ref_s, ref_mm = run("numpy")
    out = {"numpy_s": ref_s}
    for backend in backends:
        t0 = perf_counter()
        try:
            _warm_scorer(backend)
        except NotImplementedError as e:
            if backend != "jit":
                raise
            # the f64 backend refuses platforms where it cannot be bitwise
            log(f"[plan] jit: refused: {e}")
            out["jit_s"] = None
            continue
        log(f"[plan] {backend}: compile {perf_counter() - t0:.3f} s")
        res, dt, mm = run(backend)
        out[f"{backend}_s"] = dt
        same = np.array_equal(res.assignment, ref.assignment)
        if backend == "jit" or same:
            _require(same, f"{backend} assignment differs from numpy")
            _require(res.transfer_log == ref.transfer_log,
                     f"{backend} transfer log differs from numpy")
            log(f"[plan] {backend}: assignment and transfer log identical "
                "to numpy")
            continue
        i, mine, theirs = _first_divergence(res.transfer_log,
                                            ref.transfer_log)
        log(f"[plan] {backend}: diverges from numpy at transfer {i}: "
            f"{mine} vs numpy {theirs}")
        _require(np.array_equal(_replay(a0, res.transfer_log),
                                res.assignment),
                 f"{backend} transfer log does not replay to its plan")
        st = CCMState.build(phase, res.assignment, params)
        _require(all(st.memory_feasible(r) for r in range(ranks)),
                 f"{backend} plan breaks a rank's memory cap")
        _require(abs(mm - ref_mm) <= PLAN_MAX_MEAN_SLACK * ref_mm,
                 f"{backend} max/mean {mm} not within 1% of {ref_mm}")
        log(f"[plan] {backend}: plan replays, every rank within its "
            f"memory cap, max/mean {mm:.6f} vs {ref_mm:.6f}")
    out["interpreted"] = scorer_jit.pallas_compiled_fallback()
    return out


# ----------------------------------------------------------------- train
def _memory_line(device) -> str:
    stats = device.memory_stats()
    if not stats:
        return "device reports no memory stats"
    return (f"peak_bytes_in_use {stats.get('peak_bytes_in_use')} of "
            f"bytes_limit {stats.get('bytes_limit')}")


def train_phase(cfg, *, seq_len: int, global_batch: int, steps: int,
                log=print) -> list:
    """A few steps of ``train_loop`` on a one-chip mesh; returns losses."""
    import numpy as np

    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import train_loop

    mesh = make_local_mesh(1, 1)
    log(f"[train] {cfg.name}: {cfg.param_count() / 1e9:.3f}B params, "
        f"seq_len {seq_len}, global_batch {global_batch}, {steps} steps")
    t0 = perf_counter()
    _, _, losses = train_loop(cfg, mesh, steps=steps, seq_len=seq_len,
                              global_batch=global_batch, log_every=1)
    log(f"[train] {steps} steps in {perf_counter() - t0:.3f} s, "
        "first step compiling")
    _require(len(losses) == steps and bool(np.all(np.isfinite(losses))),
             f"train losses not all finite: {losses}")
    log(f"[train] {_memory_line(mesh.devices.flat[0])}")
    return losses


# ---------------------------------------------------------------- replan
def _per_chip(counts, n_chips: int):
    """(periods, E) routed tokens -> tokens per chip (slot s lives on chip
    s // (E / n_chips))."""
    return counts.sum(0).reshape(n_chips, -1).sum(1)


def _slot_fingerprint(tree, cfg):
    """Per-(period, slot) sums of every MoE leaf of a params-shaped tree;
    a slot permutation permutes them."""
    import jax.numpy as jnp
    out = []
    for i, kind in enumerate(cfg.block_pattern):
        if kind != "moe":
            continue
        moe = tree["scan"][f"b{i}"]["moe"]
        for name in ("w_gate", "w_up", "w_down"):
            out.append(jnp.sum(moe[name].astype(jnp.float32), axis=(2, 3)))
        out.append(jnp.sum(moe["router"].astype(jnp.float32), axis=1))
    return jnp.stack(out)               # (leaves, periods, E)


def replan_phase(cfg, *, n_chips: int, seq_len: int, global_batch: int,
                 steps: int, hbm_budget_bytes=None, log=print) -> dict:
    """Expert parallelism over ``n_chips``: ``train_loop`` with a replan,
    then one CCM re-placement checked on a fixed batch for the loss, the
    optimizer state and each chip's routed tokens."""
    import jax
    import numpy as np

    from repro.data.pipeline import make_batch
    from repro.launch.mesh import make_local_mesh
    from repro.launch.train import rebalance_experts, train_loop
    from repro.models.model import build_model

    mesh = make_local_mesh(1, n_chips)
    e_loc = cfg.num_experts // n_chips
    log(f"[replan] {cfg.name} on a (data=1, model={n_chips}) mesh, "
        f"{e_loc} experts per chip, seq_len {seq_len}, global_batch "
        f"{global_batch}, {steps} steps, replan after step {steps - 2}")
    params, opt_state, losses = train_loop(
        cfg, mesh, steps=steps, seq_len=seq_len, global_batch=global_batch,
        rebalance_every=steps - 1, log_every=1,
        hbm_budget_bytes=hbm_budget_bytes)
    _require(bool(np.all(np.isfinite(losses))),
             f"train losses not all finite: {losses}")

    w_gate = params["scan"]["b0"]["moe"]["w_gate"]
    shards = w_gate.addressable_shards
    _require(len({s.device for s in shards}) == n_chips
             and all(s.data.shape[1] == e_loc for s in shards),
             f"w_gate shards {[s.data.shape for s in shards]} are not "
             f"{e_loc} experts on each of {n_chips} devices")
    log(f"[replan] w_gate {w_gate.shape}: one {shards[0].data.shape} shard "
        f"on each of {n_chips} devices")

    loss_fn = jax.jit(build_model(cfg, mesh).loss_fn)
    batch = make_batch(cfg, seq_len, global_batch, steps, seed=1)
    loss0, m0 = loss_fn(params, batch)
    counts0 = np.asarray(m0["expert_counts"])
    fp_before = [np.asarray(_slot_fingerprint(t, cfg))
                 for t in (params, opt_state.m, opt_state.v)]
    params, opt_state, plan = rebalance_experts(
        params, opt_state, counts0, cfg, mesh,
        hbm_budget_bytes=hbm_budget_bytes)
    _require(plan is not None, "the CCM plan did not improve the placement")
    loss1, m1 = loss_fn(params, batch)
    counts1 = np.asarray(m1["expert_counts"])
    loss0, loss1 = float(loss0), float(loss1)
    log(f"[replan] fixed batch loss {loss0:.6f} before, {loss1:.6f} after "
        f"(rtol {REPLAN_LOSS_RTOL})")
    _require(abs(loss1 - loss0) <= REPLAN_LOSS_RTOL * abs(loss0),
             "the replan changed the loss")
    perms = np.asarray(plan.permutations)           # (periods, E)
    for name, t, before in zip(("params", "adamw m", "adamw v"),
                               (params, opt_state.m, opt_state.v),
                               fp_before):
        after = np.asarray(_slot_fingerprint(t, cfg))
        want = np.take_along_axis(before, perms[None], axis=2)
        _require(np.allclose(after, want, rtol=1e-6, atol=0),
                 f"{name} did not follow the slot permutation")
    log("[replan] expert weights, router and both AdamW moments follow "
        "the permutation")
    _require(np.array_equal(np.take_along_axis(counts0, perms, axis=1),
                            counts1),
             "routed tokens did not follow the permutation")
    chips0, chips1 = _per_chip(counts0, n_chips), _per_chip(counts1, n_chips)
    mm0, mm1 = chips0.max() / chips0.mean(), chips1.max() / chips1.mean()
    log(f"[replan] routed tokens per chip before {chips0.tolist()} "
        f"(max/mean {mm0:.4f}), after {chips1.tolist()} "
        f"(max/mean {mm1:.4f}); plan imbalance "
        f"{plan.imbalance_before:.4f} -> {plan.imbalance_after:.4f}")
    return {"loss_before": loss0, "loss_after": loss1,
            "tokens_max_mean_before": float(mm0),
            "tokens_max_mean_after": float(mm1)}


# ------------------------------------------------------------------ main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip expert-parallel replan")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no repro package in {SRC}", file=sys.stderr)
        return 2
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform "
              f"{devices[0].platform!r})", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, {len(devices)} found",
              file=sys.stderr)
        return 1
    if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
        jax.config.update("jax_compilation_cache_dir",
                          str(ROOT / ".jax_cache"))
    print(f"[device] {devices[0].platform} {devices[0].device_kind} x "
          f"{len(devices)}, jax {jax.__version__}", flush=True)

    def log(msg):
        print(msg, flush=True)

    cfg, cuts = qwen3_moe_cut()
    for cut in cuts:
        log(f"[cut] {cut}")
    log(f"[cut] seq_len {SEQ_LEN}, global_batch {GLOBAL_BATCH} (the largest "
        "the compiler fits on one chip)")
    if args.chips == 4:
        replan_phase(cfg, n_chips=4, seq_len=SEQ_LEN,
                     global_batch=GLOBAL_BATCH, steps=REPLAN_STEPS, log=log)
    else:
        plan = plan_phase(log=log)
        _require(not plan["interpreted"],
                 "the f32 scorer kernel ran in interpret mode")
        train_phase(cfg, seq_len=SEQ_LEN, global_batch=GLOBAL_BATCH,
                    steps=TRAIN_STEPS, log=log)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
