"""Compiles of the main path for a described TPU v5e, with no chip attached.

The TPU compiler refuses what the Pallas interpreter and XLA:CPU accept:
blocks off the (8, 128) tiling, non-integer iotas, programs that do not
fit the chip.  These tests make it look at the scorer kernel, at one
full-width qwen3-moe expert layer and at the fused attention path at the
train cell's shapes, on one chip and on the 2x2 mesh.  The topology is
described inside a fixture, never at import, so that every test worker
collects the same tests and only the worker that runs this file loads the
TPU library.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import (AxisType, Mesh, NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro import configs
from repro.kernels.ccm_scorer.kernel import score_tiles_fwd
from repro.kernels.ccm_scorer.layout import N_AV, N_OUT, N_PM, N_SC
from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models.layers import split_lp_tree
from repro.sharding import MeshAxes, specs_for_lp_tree


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("events", [1, 8])
def test_scorer_kernel_compiles_for_v5e(one_chip, no_compile_cache, events):
    """The f32 scorer at the launcher's buckets: A on the 8-sublane grid,
    B on the 128-lane boundary, one grid step per lock event."""
    a_n, b_n = 8, 128

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    compiled = score_tiles_fwd.lower(
        sds((events, N_AV, a_n)), sds((events, N_AV, b_n)),
        sds((events, N_PM, a_n, b_n)), sds((events, N_SC)),
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
    out = compiled.out_info
    assert out.shape == (events, N_OUT, a_n, b_n)


def test_qwen3_expert_layer_compiles_for_v5e(topo, no_compile_cache):
    """One qwen3-moe-30b-a3b expert layer forward at its published widths
    (d_model 2048, 128 experts, top-8, moe_d_ff 768) on one chip."""
    cfg = configs.get_config("qwen3-moe-30b-a3b")
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    axes = MeshAxes.for_mesh(mesh)
    params = split_lp_tree(jax.eval_shape(
        lambda k: moe_lib.init_moe(k, cfg), jax.random.key(0)))[0]
    one = SingleDeviceSharding(topo.devices[0])
    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one), params)
    x = jax.ShapeDtypeStruct((1, 512, cfg.d_model), jnp.bfloat16, sharding=one)

    def fwd(p, x):
        return moe_lib.moe_forward(p, x, cfg, mesh, axes, cfg.act)

    compiled = jax.jit(fwd).lower(params, x).compile()
    y, stats = compiled.out_info
    assert y.shape == x.shape
    assert stats["expert_counts"].shape == (cfg.num_experts,)
    mem = compiled.memory_analysis()
    weights = 3 * cfg.num_experts * cfg.d_model * cfg.moe_d_ff * 2
    assert mem.argument_size_in_bytes >= weights
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_qwen3_expert_layer_routes_in_a_few_sorts_on_v5e(topo,
                                                         no_compile_cache):
    """Forward, remat's recompute and backward of one qwen3-moe-30b-a3b
    expert layer at the train cell's (3, 4096) on one v5e: the routing is a
    handful of sorts (the router's top-k, the held experts' batched top-k
    and each token's slot order, in the forward and the recompute; a loop of
    one top-k per expert had 268), and the temporaries stay under 4 GB
    (2.1 GB; the loop's 2.73)."""
    cfg = configs.get_config("qwen3-moe-30b-a3b")
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    axes = MeshAxes.for_mesh(mesh)
    lp = jax.eval_shape(lambda k: moe_lib.init_moe(k, cfg), jax.random.key(0))
    specs = specs_for_lp_tree(mesh, axes, lp)
    params = jax.tree.map(
        lambda s, spec: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, spec)),
        split_lp_tree(lp)[0], specs)
    x = jax.ShapeDtypeStruct((3, 4096, cfg.d_model), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("data")))

    def loss(p, x):
        y, stats = moe_lib.moe_forward(p, x, cfg, mesh, axes, cfg.act)
        return y.astype(jnp.float32).sum() + stats["aux_loss"]

    compiled = jax.jit(jax.value_and_grad(jax.checkpoint(loss),
                                          argnums=(0, 1))).lower(
        params, x).compile()
    sorts = re.findall(r" sort\(", compiled.as_text())
    assert 0 < len(sorts) <= 16
    assert compiled.memory_analysis().temp_size_in_bytes < 4e9


def _attention_train_compile(mesh, batch):
    """Forward, remat's recompute and backward of one qwen3-moe-30b-a3b
    attention layer (32 q heads, 4 kv heads, head_dim 128) at seq 4096,
    batch-sharded on ``mesh``."""
    cfg = configs.get_config("qwen3-moe-30b-a3b")
    axes = MeshAxes.for_mesh(mesh)
    seq = 4096
    lp = jax.eval_shape(lambda k: attn_lib.init_attention(k, cfg),
                        jax.random.key(0))
    specs = specs_for_lp_tree(mesh, axes, lp)
    params = jax.tree.map(
        lambda s, spec: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=NamedSharding(mesh, spec)),
        split_lp_tree(lp)[0], specs)
    x = jax.ShapeDtypeStruct((batch, seq, cfg.d_model), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("data")))
    pos = jnp.broadcast_to(jnp.arange(seq)[None], (batch, seq))

    def loss(p, x):
        out, _, _ = attn_lib.attention_forward_kv(
            p, x, cfg, mask_kind="causal", positions=pos, mesh=mesh,
            axes=axes)
        return out.astype(jnp.float32).sum()

    step = jax.jit(jax.value_and_grad(jax.checkpoint(loss), argnums=(0, 1)))
    return step.lower(params, x).compile()


def test_attention_takes_the_kernel_on_one_chip(topo, no_compile_cache):
    """The train cell's attention at (3, 4096, 32/4, 128) on one v5e runs
    on the fused kernel: its temporaries stay under 1 GB, where the
    materialised (3, 4, 8, 4096, 4096) f32 scores need 6.7 GB."""
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    compiled = _attention_train_compile(mesh, 3)
    text = compiled.as_text()
    # the forward, remat's recompute of it and the fused backward
    assert text.count("tpu_custom_call") == 3
    assert compiled.memory_analysis().temp_size_in_bytes < 1e9


def test_attention_kernel_is_sharded_on_the_2x2_mesh(topo, no_compile_cache):
    """On (data 2, model 2) the kernel runs per shard under ``shard_map``:
    batch on ``data``, the 32 q and 4 kv heads on ``model``."""
    mesh = Mesh(np.array(topo.devices).reshape(2, 2), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    compiled = _attention_train_compile(mesh, 4)
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 3
    # each chip holds a quarter of the work: 2 of the 4 sequences, 16 of
    # the 32 heads
    assert compiled.memory_analysis().temp_size_in_bytes < 0.5e9


def test_moonlight_cut_step_fits_one_v5e_at_batch_4(topo, no_compile_cache):
    """The train step of the Moonlight-16B-A3B cell (five layers: the dense
    one and four MoE layers of 8 held experts of 64; a 20480-id vocabulary
    slice) at seq 8192 and batch 4 compiles for one v5e and fits it: about
    5.7 GB of bf16 weights and f32 moments and 6.8 GB of temporaries.
    Latent attention's core runs on the fused kernel at qk 192 / v 128 in
    the dense layer and the scanned MoE layer (forward, recompute,
    backward)."""
    import dataclasses

    from repro.launch.steps import abstract_opt, abstract_params, \
        make_train_step
    from repro.models.model import build_model
    cfg = dataclasses.replace(configs.get_config("moonlight-16b-a3b"),
                              num_layers=5, vocab_size=20480, experts_held=8)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    model = build_model(cfg, mesh)
    p_sds, p_sh = abstract_params(model)
    o_sds, o_sh = abstract_opt(p_sds, p_sh)

    def placed(tree, shardings):
        return jax.tree.map(lambda s, sh: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sh), tree, shardings)

    one = NamedSharding(mesh, P())
    batch = {k: jax.ShapeDtypeStruct((4, 8192), jnp.int32, sharding=one)
             for k in ("tokens", "targets")}
    compiled = jax.jit(make_train_step(model), out_shardings=(p_sh, o_sh, None),
                       donate_argnums=(0, 1)).lower(
        placed(p_sds, p_sh), placed(o_sds, o_sh), batch).compile()
    assert compiled.as_text().count("tpu_custom_call") == 6
    mem = compiled.memory_analysis()
    n = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(p_sds))
    assert 5.6e8 < n < 5.8e8                       # 568.5M parameters held
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.75 * 2**30
