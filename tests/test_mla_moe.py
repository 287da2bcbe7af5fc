"""Latent attention and the sigmoid-routed, shared-expert MoE layer
(Moonlight-16B-A3B, DeepSeek-V3's block) against the plain float32
reference ``bench/refs/mla_moe_lm.py``; the splash kernel at qk 192 / v
128; a layer that holds only its share of the experts; the router bias the
step moves and AdamW leaves alone; and qwen3's softmax path, unchanged."""
import dataclasses
import importlib.util
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
from repro.launch.steps import make_train_step
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models.layers import split_lp_tree
from repro.models.model import build_model
from repro.optim import adamw_init
from repro.sharding import MeshAxes

ROOT = Path(__file__).resolve().parents[1]
MESH = make_local_mesh(1, 1)
AXES = MeshAxes.for_mesh(MESH)
SMOKE = configs.get_smoke_config("moonlight-16b-a3b")


@pytest.fixture(scope="module")
def ref():
    name = "bench_ref_mla_moe_lm"
    spec = importlib.util.spec_from_file_location(
        name, ROOT / "bench/refs/mla_moe_lm.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _ref_cfg(cfg, **kw):
    """The reference's configuration keys for a program config."""
    out = {"hidden_size": cfg.d_model, "num_attention_heads": cfg.num_heads,
           "kv_lora_rank": cfg.kv_lora_rank,
           "qk_nope_head_dim": cfg.qk_nope_head_dim,
           "qk_rope_head_dim": cfg.qk_rope_head_dim,
           "v_head_dim": cfg.v_head_dim, "rope_theta": cfg.rope_theta,
           "rms_norm_eps": cfg.norm_eps, "router_experts": cfg.num_experts,
           "n_routed_experts": cfg.held_experts,
           "first_held_expert": cfg.first_held_expert,
           "num_experts_per_tok": cfg.top_k, "norm_topk_prob": True,
           "routed_scaling_factor": cfg.routed_scaling,
           "capacity_factor": cfg.capacity_factor}
    out.update(kw)
    return out


def _f32(tree):
    return jax.tree.map(lambda x: x.astype(jnp.float32), tree)


# --------------------------------------------------------------- attention
def test_mla_forward_and_gradients_match_the_reference(ref):
    cfg = SMOKE
    params = _f32(split_lp_tree(attn.init_attention(jax.random.key(1), cfg))[0])
    x = jax.random.normal(jax.random.key(2), (2, 32, cfg.d_model))
    pos = jnp.broadcast_to(jnp.arange(32)[None], (2, 32))
    rc = _ref_cfg(cfg)

    def prog(p, x):
        out, _, _ = attn.attention_forward_kv(p, x, cfg, mask_kind="causal",
                                              positions=pos)
        return out

    def plain(p, x):
        return jax.vmap(lambda r: ref.attention_row(p, r, rc, False))(x)

    ct = jax.random.normal(jax.random.key(3), (2, 32, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        out_p, vjp_p = jax.vjp(prog, params, x)
        out_r, vjp_r = jax.vjp(plain, params, x)
        g_p, g_r = vjp_p(ct), vjp_r(ct)
    np.testing.assert_allclose(out_p, out_r, rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree.leaves(g_p), jax.tree.leaves(g_r)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


def test_splash_kernel_at_qk192_v128_matches_sdpa(monkeypatch):
    """The kernel at latent attention's head sizes (scores over 192, values
    of 128), causal, in the interpreter, against ``_sdpa`` in f32."""
    monkeypatch.setattr(attn, "_block", lambda seq: 128)
    s, h = 256, 2
    ks = jax.random.split(jax.random.key(0), 4)
    q = jax.random.normal(ks[0], (1, s, h, 192), jnp.bfloat16)
    k = jax.random.normal(ks[1], (1, s, h, 192), jnp.bfloat16)
    v = jax.random.normal(ks[2], (1, s, h, 128), jnp.bfloat16)
    ct = jax.random.normal(ks[3], (1, s, h, 128), jnp.bfloat16)
    pos = jnp.arange(s)[None]

    def plain(q, k, v):
        bias = attn._mask_bias(pos, pos, "causal", 0)[:, None]
        return attn._sdpa(q, k, v, bias, 0.0)

    def fused(q, k, v):
        return attn._splash(q, k, v, SMOKE, "causal", None, None, True)

    f32 = [t.astype(jnp.float32) for t in (q, k, v)]
    out_t, vjp_t = jax.vjp(plain, *f32)
    out_f, vjp_f = jax.jit(lambda *a: jax.vjp(fused, *a))(q, k, v)
    assert out_f.shape == (1, s, h, 128)
    for got, want in zip([out_f, *vjp_f(ct)],
                         [out_t, *vjp_t(ct.astype(jnp.float32))]):
        got, want = np.asarray(got, np.float32), np.asarray(want)
        assert np.abs(got - want).max() / np.abs(want).mean() < 0.3
        assert np.abs(got - want).mean() / np.abs(want).mean() < 0.01


# ----------------------------------------------------------------- experts
def _layer(cfg, key):
    return _f32(split_lp_tree(moe_lib.init_moe(key, cfg))[0])


def test_shares_of_a_64_expert_layer_add_up_to_the_whole(ref):
    """Eight layers, each told it holds 8 of the 64 experts, give parts
    that, with the shared block counted once, add up to the uncut
    reference layer."""
    cfg = dataclasses.replace(SMOKE, num_experts=64, top_k=6, moe_d_ff=16,
                              capacity_factor=64.0)
    whole = _layer(cfg, jax.random.key(4))
    whole["router_bias"] = 0.1 * jax.random.normal(jax.random.key(5), (64,))
    x = jax.random.normal(jax.random.key(6), (2, 16, cfg.d_model))
    shared = ref.gated_mlp(whole["shared"], x.reshape(32, -1), False)
    total = shared
    with jax.default_matmul_precision("highest"):
        for i in range(8):
            share = dataclasses.replace(cfg, experts_held=8,
                                        first_held_expert=8 * i)
            p = dict(whole, **{n: whole[n][8 * i:8 * i + 8]
                               for n in ("w_gate", "w_up", "w_down")})
            y, stats = jax.jit(lambda p, x, share=share: moe_lib.moe_forward(
                p, x, share, MESH, AXES, "silu"))(p, x)
            total = total + y.reshape(32, -1) - shared
        y_ref, _, counts = jax.jit(lambda p, x: ref.moe(
            p, x, _ref_cfg(cfg), False, 2))(whole, x.reshape(32, -1))
    np.testing.assert_allclose(total, y_ref, rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(stats["expert_counts"], counts)
    assert float(counts.sum()) == 32 * 6


def test_sigmoid_routing_picks_by_bias_and_weighs_by_score():
    logits = jnp.array([[0.0, 1.0, 2.0, -1.0, 0.5, 0.2, -0.3, 0.1]])
    bias = jnp.zeros(8).at[3].set(10.0)
    vals, idx, _ = moe_lib._route(jnp.ones((1, 1)), logits, bias, SMOKE)
    assert sorted(idx[0].tolist()) == [2, 3]   # expert 3 by its bias alone
    # weighed by the sigmoid alone, renormalised, times 2.446
    s = jax.nn.sigmoid(logits[0, idx[0]])
    np.testing.assert_allclose(vals[0], 2.446 * s / s.sum(), rtol=1e-6)


def test_sequence_balance_loss_by_hand():
    cfg = dataclasses.replace(SMOKE, num_experts=2, top_k=1,
                              aux_loss="sequence")
    # one sequence of two tokens: both routed to expert 0
    top_idx = jnp.array([[0], [0]])
    scores = jnp.array([[0.6, 0.2], [0.2, 0.2]])
    # f = (2 / (1 x 2)) x (2, 0); P = mean((0.75, 0.25), (0.5, 0.5))
    aux = moe_lib._aux_loss(top_idx, scores, cfg, 1)
    np.testing.assert_allclose(aux, 2 * 0.625, rtol=1e-6)


# ------------------------------------------------------- the router's bias
def test_bias_moves_by_gamma_and_adamw_leaves_it_alone():
    model = build_model(SMOKE, MESH)
    params, _ = split_lp_tree(model.init(jax.random.key(0)))
    params["scan"]["b0"]["moe"]["router_bias"] = jnp.full((2, 8), 0.5)
    rng = np.random.default_rng(0)
    batch = {k: jnp.asarray(rng.integers(0, SMOKE.vocab_size, (2, 32)),
                            jnp.int32) for k in ("tokens", "targets")}
    _, metrics = jax.jit(model.loss_fn)(params, batch)
    counts = np.asarray(metrics["expert_counts"])
    step = jax.jit(make_train_step(model, weight_decay=0.1))
    p2, o2, _ = step(params, adamw_init(params), batch)
    want = 0.5 + 0.001 * np.sign(counts.mean(-1, keepdims=True) - counts)
    np.testing.assert_allclose(p2["scan"]["b0"]["moe"]["router_bias"], want,
                               rtol=0, atol=1e-7)
    assert np.any(want != 0.5)
    for moments in (o2.m, o2.v):
        assert not np.any(np.asarray(moments["scan"]["b0"]["moe"]
                                     ["router_bias"]))
    # the router itself is a parameter: AdamW moves it
    assert np.any(np.asarray(o2.m["scan"]["b0"]["moe"]["router"]))


def test_qwen3_softmax_path_is_unchanged():
    """qwen3-smoke's loss and gradient norms, as computed before the router
    learned sigmoid scores, held experts and a weight from the config."""
    cfg = configs.get_smoke_config("qwen3-moe-30b-a3b")
    model = build_model(cfg, MESH)
    params, _ = split_lp_tree(model.init(jax.random.key(0)))
    rng = np.random.default_rng(0)
    batch = {k: jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 32)),
                            jnp.int32) for k in ("tokens", "targets")}
    (loss, _), g = jax.jit(jax.value_and_grad(model.loss_fn, has_aux=True))(
        params, batch)
    assert "router_bias" not in params["scan"]["b0"]["moe"]
    norms = {k: float(jnp.linalg.norm(g["scan"]["b0"]["moe"][k]))
             for k in ("router", "w_gate", "w_down")}
    np.testing.assert_allclose(float(loss), GOLDEN_LOSS, rtol=1e-6)
    for k, v in GOLDEN_NORMS.items():
        np.testing.assert_allclose(norms[k], v, rtol=1e-5)


GOLDEN_LOSS = 6.118075847625732
GOLDEN_NORMS = {"router": 0.3235764503479004, "w_gate": 0.8671875,
                "w_down": 0.546875}


def test_train_loop_runs_moonlight():
    """The normal path: ``train_loop`` builds, steps and returns it."""
    from repro.launch.train import train_loop
    params, _, losses = train_loop(SMOKE, MESH, steps=3, seq_len=32,
                                   global_batch=2, log_every=100)
    assert len(losses) == 3 and np.all(np.isfinite(losses))
    assert np.any(np.asarray(params["scan"]["b0"]["moe"]["router_bias"]))


# --------------------------------------------------------------- serving
def test_serving_refuses_latent_attention():
    model = build_model(SMOKE, MESH)
    params, _ = split_lp_tree(model.init(jax.random.key(0)))
    with pytest.raises(NotImplementedError, match="latent"):
        model.prefill_fn(params, {"tokens": jnp.zeros((1, 8), jnp.int32)})


# ---------------------------------------------------------------- replans
REPLAN = r"""
import dataclasses, json
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.launch.mesh import make_local_mesh
from repro.launch.train import rebalance_experts
from repro.models.layers import split_lp_tree
from repro.models.model import build_model
from repro.launch.steps import abstract_opt, abstract_params
from repro.optim import adamw_init

cfg = dataclasses.replace(configs.get_smoke_config("moonlight-16b-a3b"),
                          experts_held=4, first_held_expert=2)
mesh = make_local_mesh(1, 2)
model = build_model(cfg, mesh)
p_sds, p_sh = abstract_params(model)
_, o_sh = abstract_opt(p_sds, p_sh)
params, _ = split_lp_tree(model.init(jax.random.key(0)))
params["scan"]["b0"]["moe"]["router_bias"] = jnp.arange(16.0).reshape(2, 8) / 100
params = jax.device_put(params, p_sh)
rng = np.random.default_rng(0)
batch = {k: jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 32)), jnp.int32)
         for k in ("tokens", "targets")}
loss = jax.jit(lambda p: model.loss_fn(p, batch)[0])
opt = adamw_init(params)
opt = jax.device_put(opt._replace(m=jax.tree.map(lambda a: a + 1.0, opt.m)),
                     o_sh)
before = float(loss(params))
# skewed routed counts of the held experts (a Zipf draw the plan improves)
held = np.random.default_rng(3).zipf(1.5, (2, 4)).astype(float)
counts = np.zeros((2, 8))
counts[:, 2:6] = held / held.sum(1, keepdims=True) * 8192
p2, o2, plan = rebalance_experts(params, opt, counts, cfg, mesh,
                                 hbm_budget_bytes=16e9)
after = float(loss(p2))
moved = [int(x) for x in np.asarray(plan.permutations).ravel()]
bias = np.asarray(p2["scan"]["b0"]["moe"]["router_bias"])
print(json.dumps({"before": before, "after": after, "moved": moved,
                  "bias": bias.tolist(),
                  "n_dev": len(p2["scan"]["b0"]["moe"]["w_gate"].sharding.device_set)}))
"""


def test_replan_preserves_the_loss_on_two_devices():
    """A replan of a sigmoid-routed model holding 4 of its 8 experts over 2
    virtual devices permutes experts, router columns and router bias
    together: the loss of a fixed batch is the same."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=str(ROOT / "src"))
    p = subprocess.run([sys.executable, "-c", REPLAN], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    import json
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["moved"] != [0, 1, 2, 3] * 2      # the plan moved experts
    assert out["n_dev"] == 2
    np.testing.assert_allclose(out["after"], out["before"], rtol=1e-5)
    bias = np.asarray(out["bias"])
    perm = np.asarray(out["moved"]).reshape(2, 4)
    want = (np.arange(16.0).reshape(2, 8) / 100)
    for layer in range(2):
        np.testing.assert_allclose(bias[layer, 2:6],
                                   want[layer, 2 + perm[layer]])
        np.testing.assert_allclose(bias[layer, [0, 1, 6, 7]],
                                   want[layer, [0, 1, 6, 7]])
