"""End-to-end behaviour tests: training convergence, data determinism,
sharding rules, and the serving loop."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.data.pipeline import SyntheticLMData, make_batch
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import serve_batch
from repro.launch.train import train_loop
from repro.models.layers import split_lp_tree
from repro.models.model import build_model
from repro.sharding import MeshAxes, spec_for

MESH = make_local_mesh(1, 1)


def test_training_reduces_loss():
    cfg = configs.get_smoke_config("tinyllama-1.1b")
    _, _, losses = train_loop(cfg, MESH, steps=40, seq_len=64,
                              global_batch=4, lr=3e-3, log_every=100)
    first = np.mean(losses[:5])
    last = np.mean(losses[-5:])
    assert last < first - 0.2, (first, last)


def test_moe_training_reduces_loss_and_reports_stats():
    # config pinned by an lr/warmup/steps sweep: the default
    # make_train_step warmup (100 steps) never ramped the lr within a
    # 20-step run, leaving the loss flat.  With warmup_steps=3 the measured
    # first5-last5 drops were lr 3e-3/20 steps: 0.06, 3e-3/30: 0.13,
    # 1e-2/30: 0.28 — the last gives a deterministic ~3x margin over the
    # 0.1 threshold asserted below.
    cfg = configs.get_smoke_config("qwen3-moe-30b-a3b")
    from repro.launch.steps import make_train_step
    from repro.optim import adamw_init
    n_steps = 30
    model = build_model(cfg, MESH)
    params, _ = split_lp_tree(model.init(jax.random.key(0)))
    opt = adamw_init(params)
    step = jax.jit(make_train_step(model, lr=1e-2, warmup_steps=3,
                                   total_steps=n_steps))
    losses = []
    for i in range(n_steps):
        batch = make_batch(cfg, 64, 4, i)
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["loss"]))
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.1, (
        np.mean(losses[:5]), np.mean(losses[-5:]))
    counts = np.asarray(m["expert_counts"])
    assert counts.shape[-1] == cfg.num_experts
    # every token routed top_k times
    assert counts.sum() == pytest.approx(2 * 4 * 64 * cfg.top_k, rel=1e-6)


def test_local_mesh_axes_are_auto():
    """Auto axes: with_sharding_constraint refuses Explicit ones."""
    from jax.sharding import AxisType
    assert MESH.axis_types == (AxisType.Auto, AxisType.Auto)
    assert MESH.axis_names == ("data", "model")


def test_data_pipeline_deterministic():
    d1 = SyntheticLMData(1024, 64, 4, seed=3)
    d2 = SyntheticLMData(1024, 64, 4, seed=3)
    b1, b2 = d1.batch(17), d2.batch(17)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = d1.batch(18)
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    # targets are next-token shifted
    np.testing.assert_array_equal(b1["tokens"][:, 1:], b1["targets"][:, :-1])


def test_sharding_rules_divisibility_and_dedupe():
    mesh = MESH  # 1x1 — sizes 1, everything divisible
    axes = MeshAxes.for_mesh(mesh)
    # square matrix mapping two dims to the same axis -> deduped
    spec = spec_for(mesh, axes, ("rnn", "rnn"), (64, 64))
    named = [s for s in spec if s is not None]
    assert len(named) <= 1
    # non-divisible dim replicated (simulate with a fake larger mesh need:
    # on a 1-sized axis everything divides; check rule table instead)
    spec2 = spec_for(mesh, axes, ("vocab", "embed"), (100, 64))
    assert len(spec2) == 2


RNN_SHARDING = r"""
import json, sys
import jax
from repro import configs
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import abstract_params
from repro.models.model import build_model

mesh = make_local_mesh(1, 2)
_, shardings = abstract_params(
    build_model(configs.get_smoke_config(sys.argv[1]), mesh))
out = [[[getattr(k, "key", None) for k in path],
        [s if s is None or isinstance(s, str) else list(s) for s in sh.spec]]
       for path, sh in jax.tree_util.tree_leaves_with_path(shardings)]
print(json.dumps(out))
"""

# each block's parameters with a recurrent-width dimension
RNN_LEAVES = {
    "rwkv6-7b": ("time_mix", {"w0", "u", "w_r", "w_k", "w_v", "w_g", "w_o",
                              "ln_w", "ln_b"}),
    "recurrentgemma-9b": ("rec", {"w_in_x", "w_in_gate", "conv_w", "conv_b",
                                  "w_a", "b_a", "w_x", "b_x", "lambda_p",
                                  "w_out"}),
}


@pytest.mark.parametrize("arch", sorted(RNN_LEAVES))
def test_recurrent_width_is_sharded_over_model(arch):
    """On a (data 1, model 2) mesh every parameter of the recurrent blocks
    that has a recurrent-width dimension splits it over ``model``."""
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=str(root / "src"))
    p = subprocess.run([sys.executable, "-c", RNN_SHARDING, arch], env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    block, names = RNN_LEAVES[arch]
    seen = set()
    for path, spec in json.loads(p.stdout.strip().splitlines()[-1]):
        if block in path and path[-1] in names:
            seen.add(path[-1])
            assert "model" in spec, (path, spec)
    assert seen == names


def test_serve_batch_all_families():
    rng = np.random.default_rng(0)
    for arch in ("smollm-360m", "rwkv6-7b"):
        cfg = configs.get_smoke_config(arch)
        model = build_model(cfg, MESH)
        params, _ = split_lp_tree(model.init(jax.random.key(0)))
        prompts = rng.integers(0, cfg.vocab_size, (2, 16)).astype(np.int32)
        out = serve_batch(model, params, prompts, max_new=8)
        assert out.shape == (2, 8)
        assert (out >= 0).all() and (out < cfg.vocab_size).all()


def test_greedy_decode_is_deterministic():
    cfg = configs.get_smoke_config("tinyllama-1.1b")
    model = build_model(cfg, MESH)
    params, _ = split_lp_tree(model.init(jax.random.key(0)))
    prompts = np.ones((2, 12), np.int32)
    o1 = serve_batch(model, params, prompts, max_new=6)
    o2 = serve_batch(model, params, prompts, max_new=6)
    np.testing.assert_array_equal(o1, o2)
