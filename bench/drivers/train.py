"""Driver of the training cells: the program's train step, fed host
batches, for a window of steps.

The step is the one ``launch/train.py``'s ``train_loop`` builds:
``make_train_step`` jitted with ``out_shardings=(params, opt, None)`` and
``donate_argnums=(0, 1)``, one host batch a step and the loss read back each
step.  ``train_loop`` itself runs a fixed number of steps, not a time
window, so the driver builds the same step and drives it.

Set-up makes the weights from the seed on the device in one jitted call,
builds the step and drives it through its first ``CHECK_STEPS`` steps on
batches that all differ; those steps are the warm-up and give the
readings the check compares with the plain reference (the loss of each
step, the per-leaf norms of the first gradient as the optimizer holds it
after one step, the per-leaf norms of the parameters' change after the
last).

``train_tokens_per_s`` is all tokens trained in the window over the
window's time.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from pathlib import Path

import numpy as np

from bench import traffic as gen

REF = Path(__file__).resolve().parents[1] / "refs" / "moe_lm.py"
CHECK_STEPS = 3


def program_config(cfg: dict):
    """The program's model config for this configuration file; refuses a
    program whose published widths differ from the file's."""
    from repro import configs
    arch = cfg["program_config"]
    full = (configs.get_smoke_config(arch) if cfg.get("program_smoke")
            else configs.get_config(arch))
    pairs = {"d_model": "hidden_size", "num_heads": "num_attention_heads",
             "num_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
             "num_experts": "num_experts", "top_k": "num_experts_per_tok",
             "moe_d_ff": "moe_intermediate_size", "rope_theta": "rope_theta",
             "norm_eps": "rms_norm_eps"}
    for ours, theirs in pairs.items():
        if getattr(full, ours) != cfg[theirs]:
            raise ValueError(f"the program's {ours} {getattr(full, ours)} is "
                             f"not the configuration's {cfg[theirs]}")
    return dataclasses.replace(full, num_layers=cfg["num_hidden_layers"],
                               vocab_size=cfg["vocab_size"],
                               capacity_factor=cfg["capacity_factor"])


@dataclasses.dataclass
class State:
    ref: object
    key: object
    shapes: object
    batches: list                 # host batches, cycled by the window
    ref_batches: list             # what the reference trains on
    mcfg: object = None
    mesh: object = None
    step_fn: object = None
    params: object = None
    opt: object = None
    step: int = 0
    readings: dict = None
    losses: list = dataclasses.field(default_factory=list)


def _batches(run, mcfg_vocab: int):
    mix = run.traffic
    b, s, n = mix["global_batch"], mix["seq_len"], mix["distinct_batches"]
    tok = gen.zipf_tokens(mcfg_vocab, (n, b, s + 1), mix["zipf_exponent"],
                          run.seed)
    ref = [(t[:, :-1], t[:, 1:]) for t in tok]
    prog = []
    for t, tgt in ref:
        tgt = tgt.copy()
        if run.fault == "half":
            tgt[b // 2:] = -1        # half the batch left out of the mean
        prog.append({"tokens": np.ascontiguousarray(t),
                     "targets": np.ascontiguousarray(tgt)})
    return prog, ref


def setup(run):
    import jax
    from bench.harness import load_module

    cfg, mix, tr = run.config, run.traffic, run.config["training"]
    ref = load_module(REF, "bench_ref_moe_lm")
    key = ref.key_from_words(gen.seed_words(run.seed))
    prog, ref_batches = _batches(run, cfg["vocab_size"])
    st = State(ref=ref, key=key, shapes=None, batches=prog,
               ref_batches=ref_batches[:CHECK_STEPS])
    if run.control:
        return st
    from repro.launch.mesh import make_local_mesh
    from repro.launch.steps import abstract_opt, abstract_params, \
        make_train_step
    from repro.models.model import build_model
    from repro.optim import adamw_init

    st.mcfg = program_config(cfg)
    st.mesh = make_local_mesh(mix["mesh"]["data"], mix["mesh"]["model"])
    model = build_model(st.mcfg, st.mesh)
    st.shapes, p_sh = abstract_params(model)
    _, o_sh = abstract_opt(st.shapes, p_sh)
    std = float(cfg["initializer_range"])
    st.params = ref.init_on(st.shapes, key, std, shardings=p_sh)
    st.opt = jax.jit(adamw_init, out_shardings=o_sh)(st.params)
    step = make_train_step(model, lr=tr["lr"], weight_decay=tr["weight_decay"],
                           warmup_steps=tr["warmup_steps"],
                           total_steps=tr["total_steps"])
    if run.fault == "unchanged":
        def unchanged(p, o, b):
            return p, o, step(p, o, b)[2]
        st.step_fn = jax.jit(unchanged, out_shardings=(p_sh, o_sh, None))
    else:
        st.step_fn = jax.jit(step, out_shardings=(p_sh, o_sh, None),
                             donate_argnums=(0, 1))
    run.log(f"[train] {st.mcfg.name} cut to {st.mcfg.num_layers} layer(s), "
            f"vocab {st.mcfg.vocab_size}; mesh {dict(st.mesh.shape)}; "
            f"batch {mix['global_batch']} x {mix['seq_len']}")

    losses = []
    for i in range(CHECK_STEPS):
        st.params, st.opt, metrics = st.step_fn(st.params, st.opt,
                                                st.batches[i])
        losses.append(float(metrics["loss"]))
        if i == 0:
            g1 = np.asarray(jax.jit(ref.leaf_norms)(st.opt.m))
            g1 = [float(x) / (1.0 - tr["beta1"]) for x in g1]
    st.step = CHECK_STEPS
    st.readings = {"losses": losses, "grad_norms": g1,
                   "change_norms": ref.change_norms(st.params, key, st.shapes,
                                                    std)}
    run.log(f"[train] set-up steps: losses {losses}")
    return st


def window(run, state):
    import jax
    if run.control:
        return
    mix = run.traffic
    st = state
    tokens_per_step = mix["global_batch"] * mix["seq_len"]
    trace_from, trace_steps = mix["trace_from_step"], mix["trace_steps"]
    n = len(st.batches)
    steps, ends = 0, []
    stack = contextlib.ExitStack()
    t0 = time.perf_counter()
    try:
        while True:
            if steps == trace_from:
                stack.enter_context(run.profiled())
            batch = st.batches[st.step % n]
            with jax.profiler.TraceAnnotation("step"):
                st.params, st.opt, metrics = st.step_fn(st.params, st.opt,
                                                        batch)
            with jax.profiler.TraceAnnotation("loss_readback"):
                st.losses.append(float(metrics["loss"]))
            ends.append(time.perf_counter())
            st.step += 1
            steps += 1
            if steps == trace_from + trace_steps:
                stack.close()
            if time.perf_counter() - t0 >= run.seconds:
                break
    finally:
        stack.close()
    elapsed = time.perf_counter() - t0 - run.profiler_s
    # host-clock seconds of each step with its read-back (the traced steps
    # include the profiler's start and stop)
    dt = np.diff([t0] + ends)
    run.attempted = steps
    run.failed = int(np.sum(~np.isfinite(st.losses)))
    run.e2e["train_tokens_per_s"] = steps * tokens_per_step / elapsed
    run.counters.update(
        steps=steps, window_s=elapsed, tokens_per_step=tokens_per_step,
        traced_steps=min(trace_steps, max(0, steps - trace_from)),
        chips=len(run.devices), step_s=dt.tolist())
    run.log(f"[train] {steps} steps in {elapsed:.3f} s "
            f"({run.e2e['train_tokens_per_s']:.1f} tokens/s); last loss "
            f"{st.losses[-1] if st.losses else None}")
    # a slow window is either every step slower or a few stalls
    run.log(f"[train] step seconds: median {np.median(dt):.5f}, p10 "
            f"{np.quantile(dt, 0.1):.5f}, p90 {np.quantile(dt, 0.9):.5f}, "
            f"max {dt.max():.5f}")


def check(run, state):
    import jax
    st = state
    cfg = run.config
    if run.control:
        prog = st.ref.readings(cfg, cfg["training"], _shapes(run, st),
                               st.key, st.ref_batches, CHECK_STEPS,
                               quant=True)
        shapes = _shapes(run, st)
    else:
        prog = st.readings
        shapes = st.shapes
        st.params = st.opt = st.step_fn = None   # free the program's state
        jax.clear_caches()
    ref = st.ref.readings(cfg, cfg["training"], shapes, st.key,
                          st.ref_batches, CHECK_STEPS)
    gaps, kept, worst = st.ref.gaps(prog, ref)
    names = st.ref.parameter_names(shapes)
    run.log(f"[train] program losses {prog['losses']}, reference "
            f"{ref['losses']}; {len(kept)} of {len(names)} parameters "
            f"counted")
    for gap, i in worst.items():
        run.log(f"[train] widest {gap}: {names[i]}, grad "
                f"{prog['grad_norms'][i]:.6g} vs {ref['grad_norms'][i]:.6g}, "
                f"change {prog['change_norms'][i]:.6g} vs "
                f"{ref['change_norms'][i]:.6g}")
    run.log(f"[train] gaps {gaps}")
    # the configuration names the numbers compared: a gap that neither the
    # control nor a fault separates from sound runs is read, not compared
    for k, limit in cfg["limits"].items():
        run.check(k, gaps[k], limit)


def _shapes(run, st):
    """The parameter tree's shapes without building the step (control)."""
    if st.shapes is not None:
        return st.shapes
    import jax

    from repro.launch.steps import abstract_params
    from repro.models.model import build_model
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2,
                         devices=jax.devices()[:1])
    st.shapes, _ = abstract_params(build_model(program_config(run.config),
                                               mesh))
    return st.shapes
