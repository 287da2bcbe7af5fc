"""The expert replan of ``launch.train``: CCM-LB re-places the held experts
over the ``model`` axis, and the permutation moves each expert's weights,
router column, router bias and AdamW moments together, so the model
computes the same function and the optimizer carries on where it was."""
import functools
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch.mesh import make_local_mesh
from repro.launch.train import apply_expert_permutation
from repro.models.layers import split_lp_tree
from repro.models.model import build_model
from repro.optim import adamw_init

ROOT = Path(__file__).resolve().parents[1]
ARCHS = ("qwen3-moe-30b-a3b", "moonlight-16b-a3b")

# On two virtual CPU devices, the smoke model cut to one MoE layer (as the
# benchmark's configurations are), at 4096 tokens a step: train_loop with a
# replan after its second step, then checks on one fixed batch around a
# replan of that batch's counts, around one of uniform counts, and around
# one with no budget.
TWO_DEVICES = r"""
import dataclasses, json, sys
import jax, numpy as np
from repro import configs
from repro.data.pipeline import make_batch
from repro.launch.mesh import make_local_mesh
from repro.launch.train import rebalance_experts, train_loop
from repro.models.model import build_model

cfg = configs.get_smoke_config(sys.argv[1])
cfg = dataclasses.replace(cfg, num_layers=cfg.first_dense_layers + 1)
mesh = make_local_mesh(1, 2)
seq, batch_size, steps, budget = 512, 8, 3, 16e9
params, opt, losses = train_loop(cfg, mesh, steps=steps, seq_len=seq,
                                 global_batch=batch_size, rebalance_every=2,
                                 log_every=100, hbm_budget_bytes=budget)
out = {"losses": losses}
shards = params["scan"]["b0"]["moe"]["w_gate"].addressable_shards
out["shards"] = [[str(s.device), s.data.shape[1]] for s in shards]


def host(tree):
    return jax.tree.map(np.asarray, tree)


def moe_leaves(params, opt):
    # (tree, leaf) -> (periods, ...) array with the expert axis at 1
    out = {}
    for tree, t in (("params", params), ("m", opt.m), ("v", opt.v)):
        moe = host(t["scan"]["b0"]["moe"])
        for name in ("w_gate", "w_up", "w_down", "router_bias"):
            if name in moe:
                out[f"{tree}.{name}"] = moe[name]
        out[f"{tree}.router"] = np.moveaxis(moe["router"], 2, 1)
    return out


def per_chip_max_mean(counts):
    chips = counts.sum(0).reshape(2, -1).sum(1)
    return float(chips.max() / chips.mean())


loss_fn = jax.jit(build_model(cfg, mesh).loss_fn)
fixed = make_batch(cfg, seq, batch_size, steps, seed=1)
loss0, m0 = loss_fn(params, fixed)
counts0 = np.asarray(m0["expert_counts"])
out["max_mean"] = [per_chip_max_mean(counts0)]
before = moe_leaves(params, opt)
everything = host((params, opt))

try:
    rebalance_experts(params, opt, counts0, cfg, mesh, hbm_budget_bytes=None)
    out["no_budget"] = "planned"
except ValueError as e:
    out["no_budget"] = str(e)

p_u, o_u, plan_u = rebalance_experts(params, opt, np.ones_like(counts0), cfg,
                                     mesh, hbm_budget_bytes=budget)
out["uniform_plan_none"] = plan_u is None
out["uniform_untouched"] = all(
    np.array_equal(a, b) for a, b in zip(jax.tree.leaves(everything),
                                         jax.tree.leaves(host((p_u, o_u)))))

params, opt, plan = rebalance_experts(params, opt, counts0, cfg, mesh,
                                      hbm_budget_bytes=budget)
out["planned"] = plan is not None
if plan is None:
    print(json.dumps(out))
    sys.exit()
perms = np.asarray(plan.permutations)                    # (periods, E)
loss1, m1 = loss_fn(params, fixed)
counts1 = np.asarray(m1["expert_counts"])
after = moe_leaves(params, opt)
out["loss"] = [float(loss0), float(loss1)]
out["perms"] = perms.tolist()
out["follow"] = {
    k: bool(np.array_equal(
        after[k], np.stack([np.take(b, p, axis=0)
                            for b, p in zip(before[k], perms)])))
    for k in before}
out["counts_follow"] = bool(np.array_equal(
    np.take_along_axis(counts0, perms, axis=1), counts1))
out["max_mean"].append(per_chip_max_mean(counts1))
print(json.dumps(out))
"""


@functools.lru_cache(maxsize=None)
def _two_devices(arch: str) -> dict:
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", TWO_DEVICES, arch], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("arch", ARCHS)
def test_replan_preserves_function_and_optimizer_on_two_devices(arch):
    """A replan inside ``train_loop``, then one of a fixed batch's counts:
    the experts stay split over both devices, each expert's weights,
    router column, router bias and moments move with it bit for bit, and
    the fixed batch's loss and routing are the same."""
    out = _two_devices(arch)
    assert len(out["losses"]) == 3 and np.all(np.isfinite(out["losses"]))
    cfg = configs.get_smoke_config(arch)
    assert len({d for d, _ in out["shards"]}) == 2
    assert all(n == cfg.num_experts // 2 for _, n in out["shards"])
    assert out["planned"]
    assert any(p != sorted(p) for p in out["perms"])
    assert out["follow"] and all(out["follow"].values()), out["follow"]
    assert out["counts_follow"]
    np.testing.assert_allclose(out["loss"][1], out["loss"][0], rtol=1e-5)


@pytest.mark.parametrize("arch", ARCHS)
def test_replan_lowers_the_busiest_devices_routed_tokens(arch):
    """The permutation a replan applies gives the busier device fewer of
    the fixed batch's routed tokens than before (max over mean of the two
    devices' counts)."""
    out = _two_devices(arch)
    assert out["planned"]
    before, after = out["max_mean"]
    assert after < before


@pytest.mark.parametrize("arch", ARCHS)
def test_replan_of_uniform_counts_changes_nothing(arch):
    """Counts that every placement balances: no plan is applied, and the
    parameters and moments come back as they went in."""
    out = _two_devices(arch)
    assert out["uniform_plan_none"]
    assert out["uniform_untouched"]


def test_replan_without_a_budget_on_the_cpu_refuses():
    """The CPU reports no device memory limit, so a replan that is given
    no ``hbm_budget_bytes`` raises rather than plan without one."""
    assert "hbm_budget_bytes" in _two_devices(ARCHS[0])["no_budget"]


@pytest.mark.parametrize("arch", ARCHS)
def test_permutation_then_its_inverse_restores_everything(arch):
    """``apply_expert_permutation`` by a permutation and then by its
    inverse gives back every parameter and moment bit for bit, and the
    first alone moves the held experts."""
    cfg = configs.get_smoke_config(arch)
    model = build_model(cfg, make_local_mesh(1, 1))
    params, _ = split_lp_tree(model.init(jax.random.key(0)))
    opt = adamw_init(params)
    rng = np.random.default_rng(0)
    opt = opt._replace(
        m=jax.tree.map(lambda a: jnp.asarray(
            rng.standard_normal(a.shape, np.float32)), opt.m),
        v=jax.tree.map(lambda a: jnp.asarray(rng.random(a.shape, np.float32)),
                       opt.v))
    want = jax.tree.map(np.asarray, (params, opt))
    periods = want[0]["scan"]["b0"]["moe"]["w_gate"].shape[0]
    perms = np.stack([rng.permutation(cfg.held_experts)
                      for _ in range(periods)])
    p1, o1 = apply_expert_permutation(params, opt, perms, cfg)
    moved = np.asarray(p1["scan"]["b0"]["moe"]["w_gate"])
    assert not np.array_equal(moved, want[0]["scan"]["b0"]["moe"]["w_gate"])
    p2, o2 = apply_expert_permutation(p1, o1, np.argsort(perms, axis=1), cfg)
    got = jax.tree.map(np.asarray, (p2, o2))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(a, b)
