"""The expert-parallel training cells: ``train.py``'s set-up,
window and check on a mesh whose ``model`` axis shares the experts, with
CCM-LB re-placing them inside the window.

Every ``replan_every`` window steps the step's caller hands the routed
counts of the steps since the last replan to ``rebalance_experts``
(``launch/train.py``, as ``train_loop`` calls it): the plan, then the
permutation of the experts, the router and the AdamW moments on the chips.
Each replan's host time, ended by ``block_until_ready``, is recorded for
``ep.replan_ms``; it stays inside the window, so the rate pays for it.
The plan's memory budget is the chip's limit (or the traffic's
``hbm_budget_bytes`` where the backend reports none).

Set-up replans too, between the first and the second of the steps the
check compares: ``rebalance_experts`` on counts skewed by the seed, so that
CCM-LB moves experts across chips, with their router columns and AdamW
moments, after a step has filled the moments.  The readings of the third
step are put back in the published experts' order by the driver's own
indexing, so the reference, which never permutes, checks the replan as the
window runs it; it also compiles the permutation, so no replan compiles
inside the window.

The check is ``train.py``'s, with the reference split over two of the
cell's chips (the loss and gradients on one, AdamW's state on the other),
since the global batch's activations and the cut model's float32
parameters, gradients and moments do not fit one chip together.  Planted
faults: ``nopsum``, an expert layer whose cross-chip sum of the expert
outputs is left out, by tracing the program with a ``psum`` over the model
axis that returns its input; ``norouter``, a set-up replan that moves the
experts but leaves the router's columns where they were.
"""
from __future__ import annotations

import contextlib
import functools
import time
from pathlib import Path

import numpy as np

from bench import traffic as gen
from bench.harness import load_module

HERE = Path(__file__).resolve().parent
base = load_module(HERE / "train.py", "bench_train_for_replan")
CHECK_STEPS = base.CHECK_STEPS


class _Shim:
    """A module's attributes, some of them replaced."""

    def __init__(self, module, **replaced):
        self._module = module
        self.__dict__.update(replaced)

    def __getattr__(self, name):
        return getattr(self._module, name)


@contextlib.contextmanager
def _without_model_psum():
    """The MoE layer's module sees a ``jax`` whose ``lax.psum`` over the
    ``model`` axis is the identity (the planted fault)."""
    import jax

    from repro.models import moe as moe_lib

    def psum(x, axis_name, **kw):
        if axis_name == "model":
            return x
        return jax.lax.psum(x, axis_name, **kw)

    shim = _Shim(jax, lax=_Shim(jax.lax, psum=psum))
    moe_lib.jax = shim
    try:
        yield
    finally:
        moe_lib.jax = jax


def _skewed_counts(seed, layers: int, experts: int) -> np.ndarray:
    """Routed counts for the set-up replan: Zipf(1) weights dealt to the
    experts by the seed, a different deal on each layer."""
    r = gen.rng(seed, stream=1)
    w = 1e6 / np.arange(1, experts + 1)
    return np.stack([r.permutation(w) for _ in range(layers)])


def _in_published_order(params, perms, cfg, experts: bool = True):
    """``params`` with each MoE block's held experts (unless ``experts`` is
    false) and the router's columns and bias for them put back in the
    published experts' order; after a replan slot ``j`` of layer ``l``
    holds expert ``perms[l, j]``."""
    import jax
    import jax.numpy as jnp
    lo = cfg.first_held_expert
    inv = np.argsort(perms, axis=1)
    cols = np.tile(np.arange(cfg.num_experts), (len(perms), 1))
    cols[:, lo:lo + cfg.held_experts] = inv + lo

    def take(x, idx, axis):
        return jax.vmap(lambda a, i: jnp.take(a, i, axis=axis))(
            x, jnp.asarray(idx))

    def put_back(tree):
        scan = dict(tree["scan"])
        for name, blk in scan.items():
            if "moe" not in blk:
                continue
            moe = dict(blk["moe"])
            if experts:
                for w in ("w_gate", "w_up", "w_down"):
                    moe[w] = take(moe[w], inv, 0)
            moe["router"] = take(moe["router"], cols, 1)
            if "router_bias" in moe:
                moe["router_bias"] = take(moe["router_bias"], cols, 0)
            scan[name] = {**blk, "moe": moe}
        return {**tree, "scan": scan}

    return jax.jit(put_back, out_shardings=jax.tree.map(
        lambda a: a.sharding, params))(params)


def setup(run):
    import jax

    # a program without the held-expert replan API fails here, before any
    # compile
    from repro.launch.train import rebalance_experts
    stack = contextlib.ExitStack()
    if run.fault == "nopsum":
        stack.enter_context(_without_model_psum())
    with stack:
        if run.control:
            return base.setup(run)
        # the first compared step in train.py's set-up; the replan; the
        # other two here
        base.CHECK_STEPS = 1
        try:
            st = base.setup(run)
        finally:
            base.CHECK_STEPS = CHECK_STEPS
        cfg, mesh = st.mcfg, st.mesh
        st.ref_batches = base._batches(run, run.config["vocab_size"])[1][
            :CHECK_STEPS]
        layers = st.opt.m["scan"]["b0"]["moe"]["w_gate"].shape[0]
        st.params, st.opt, plan = rebalance_experts(
            st.params, st.opt,
            _skewed_counts(run.seed, layers, cfg.num_experts), cfg, mesh,
            hbm_budget_bytes=run.traffic.get("hbm_budget_bytes"))
        if plan is None:
            raise RuntimeError("the set-up replan found no better placement, "
                               "so the check would not cover a replan")
        perms = np.asarray(plan.permutations)
        per_chip = cfg.held_experts // int(mesh.shape["model"])
        slots = np.arange(perms.shape[1])
        moved = (slots // per_chip != perms // per_chip).sum(1)
        run.counters["setup_moved"] = moved.tolist()
        run.log(f"[replan] set-up: {moved.tolist()} of {perms.shape[1]} "
                f"experts a layer moved across chips")
        if run.fault == "norouter":
            st.params = _in_published_order(st.params, perms, cfg,
                                            experts=False)
        losses = st.readings["losses"]
        for i in range(1, CHECK_STEPS):
            st.params, st.opt, metrics = st.step_fn(st.params, st.opt,
                                                    st.batches[i])
            losses.append(float(metrics["loss"]))
        st.step = CHECK_STEPS
    std = float(run.config["initializer_range"])
    st.readings["change_norms"] = st.ref.change_norms(
        _in_published_order(st.params, perms, cfg), st.key, st.shapes, std)
    run.log(f"[train] set-up steps with the replan: losses {losses}")
    every = run.traffic["replan_every"]
    step = st.step_fn
    since = {"steps": 0, "counts": 0.0}
    run.counters["replan_s"] = []

    def step_and_replan(p, o, b):
        p, o, metrics = step(p, o, b)
        since["counts"] = since["counts"] + np.asarray(
            metrics["expert_counts"], np.float64)
        since["steps"] += 1
        if since["steps"] == every:
            t0 = time.perf_counter()
            with jax.profiler.TraceAnnotation("replan"):
                p, o, plan = rebalance_experts(
                    p, o, since["counts"], cfg, mesh,
                    hbm_budget_bytes=run.traffic.get("hbm_budget_bytes"))
                jax.block_until_ready((p, o))
            dt = time.perf_counter() - t0
            run.counters["replan_s"].append(dt)
            run.log(f"[replan] after {every} steps: {dt * 1e3:.3f} ms, "
                    + ("no better plan" if plan is None else
                       f"max work {plan.max_work_before:.6g} -> "
                       f"{plan.max_work_after:.6g}"))
            since.update(steps=0, counts=0.0)
        return p, o, metrics

    st.step_fn = step_and_replan
    return st


def window(run, state):
    base.window(run, state)
    run.log(f"[replan] {len(run.counters.get('replan_s', []))} replans in "
            f"the window")


class _SplitReference:
    """``moe_lm``'s reference, its readings computed over two chips: its
    ``_ref_step`` cut in two, the loss and the clipped gradients on the
    first chip, AdamW on the second."""

    def __init__(self, ref, devices):
        import jax
        self._ref = ref
        self._devices = devices
        self._grads = jax.jit(functools.partial(_grads, ref),
                              static_argnames=("cfg_items", "tr_items",
                                               "quant"))
        self._adamw = jax.jit(functools.partial(_adamw, ref),
                              static_argnames=("tr_items",),
                              donate_argnums=(0, 1, 2))

    def __getattr__(self, name):
        return getattr(self._ref, name)

    def readings(self, cfg, tr, shapes, key, batches, steps=3, quant=False):
        import jax
        import jax.numpy as jnp
        from jax.sharding import SingleDeviceSharding

        ref = self._ref
        on_g, on_u = (SingleDeviceSharding(d) for d in self._devices[:2])
        std = float(cfg["initializer_range"])
        run_cfg = ref.as_run(cfg)
        cfg_items = tuple((k, run_cfg[k]) for k in ref.MODEL_KEYS)
        tr_items = tuple((k, tr[k]) for k in ref.TRAIN_KEYS)
        p = ref.init_on(shapes, key, std,
                        shardings=jax.tree.map(lambda _: on_g, shapes))
        zeros = jax.jit(lambda: jax.tree.map(
            lambda a: jnp.zeros(a.shape, jnp.float32), shapes),
            out_shardings=jax.tree.map(lambda _: on_u, shapes))
        m, v = zeros(), zeros()
        losses, g1 = [], None
        for i in range(steps):
            tok, tgt = (jax.device_put(np.asarray(a), on_g)
                        for a in batches[i])
            loss, g, gn = self._grads(p, tok, tgt, cfg_items=cfg_items,
                                      tr_items=tr_items, quant=quant)
            losses.append(float(loss))
            if g1 is None:
                g1 = [float(x) for x in np.asarray(gn)]
            p, m, v = self._adamw(jax.device_put(p, on_u), m, v,
                                  jax.device_put(g, on_u), i + 1,
                                  tr_items=tr_items)
            del g
            p = jax.device_put(p, on_g)
        del m, v
        return {"losses": losses, "grad_norms": g1,
                "change_norms": ref.change_norms(p, key, shapes, std)}


def _grads(ref, stored, tokens, targets, cfg_items, tr_items, quant):
    """The first half of ``moe_lm``'s ``_ref_step``: the loss and its
    clipped gradients, in float32."""
    import jax
    import jax.numpy as jnp
    cfg, tr = dict(cfg_items), dict(tr_items)
    params = jax.tree.map(lambda x: x.astype(jnp.float32), stored)
    loss, g = jax.value_and_grad(ref.loss_fn)(params, tokens, targets, cfg,
                                              quant)
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, tr["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    g = jax.tree.map(lambda x: x * scale, g)
    return loss, g, ref.leaf_norms(g)


def _adamw(ref, stored, m, v, g, step, tr_items):
    """The second half: AdamW in float32, the result stored in the
    parameters' dtypes."""
    import jax
    import jax.numpy as jnp
    tr = dict(tr_items)
    params = jax.tree.map(lambda x: x.astype(jnp.float32), stored)
    b1, b2, eps, wd = tr["beta1"], tr["beta2"], tr["eps"], tr["weight_decay"]
    t = jnp.float32(step)
    lr = ref.lr_at(step, tr)
    m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
    v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
    params = jax.tree.map(
        lambda p, a, c: p - lr * ((a / (1 - b1 ** t))
                                  / (jnp.sqrt(c / (1 - b2 ** t)) + eps)
                                  + wd * p), params, m, v)
    return jax.tree.map(lambda x, s: x.astype(s.dtype), params, stored), m, v


def check(run, state):
    state.ref = _SplitReference(state.ref, run.devices)
    base.check(run, state)
