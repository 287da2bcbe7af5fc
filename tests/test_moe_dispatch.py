"""The expert layer's sorted dispatch against the per-expert loop it replaced.

The oracle, ``_loop_moe``, is the layer's body as a Python loop: for each
held expert, a ``top_k`` over all tokens for its capacity rows, a gather,
its three GEMMs and a scatter-add into an f32 (T, d) buffer.  The layer
fills every held expert's slots from one batched ``top_k``, runs them in one
batched GEMM and returns the rows to their tokens by gathers or one
scatter-add; it must keep exactly the same (token, slot) pairs with the
same weights, and give the same output and gradients bit for bit on the
CPU.
"""
import dataclasses
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
from repro.models import moe as moe_lib
from repro.models.layers import activation, split_lp_tree
from repro.sharding import MeshAxes

QWEN3 = configs.get_smoke_config("qwen3-moe-30b-a3b")
MOONLIGHT = configs.get_smoke_config("moonlight-16b-a3b")


# ------------------------------------------------------------------ oracle
def _loop_select(top_vals, top_idx, e_id, cap):
    """The loop's choice for expert ``e_id``: its top-``cap`` tokens by
    weight, and whether each row holds a routed token."""
    w_e = jnp.where(top_idx == e_id, top_vals, 0.0).sum(-1)  # (T,)
    sel_w, sel_i = jax.lax.top_k(jnp.where(w_e > 0, w_e, -1.0), cap)
    return sel_w, sel_i, (sel_w > 0).astype(jnp.float32)


def _loop_moe(router_w, bias, w_gate, w_up, w_down, x, *, cfg, axes,
              act_name, model_size, data_size):
    """``moe._local_moe`` as a Python loop over the held experts."""
    b, s, d = x.shape
    t = b * s
    x_flat = x.reshape(t, d)
    e = cfg.num_experts
    e_loc = cfg.held_experts // model_size
    if data_size > 1:
        w_gate = jax.lax.all_gather(w_gate, axes.data, axis=2, tiled=True)
        w_up = jax.lax.all_gather(w_up, axes.data, axis=2, tiled=True)
        w_down = jax.lax.all_gather(w_down, axes.data, axis=1, tiled=True)
    top_vals, top_idx, scores = moe_lib._route(x_flat, router_w, bias, cfg)
    cap = moe_lib._capacity(cfg, t)
    act = activation(act_name)
    out = jnp.zeros((t, d), jnp.float32)
    offset = cfg.first_held_expert + jax.lax.axis_index(axes.model) * e_loc
    for e_local in range(e_loc):
        sel_w, sel_i, valid = _loop_select(top_vals, top_idx,
                                           offset + e_local, cap)
        xg = x_flat[sel_i]
        g = act(xg @ w_gate[e_local])
        u = xg @ w_up[e_local]
        h = ((g * u) @ w_down[e_local]).astype(jnp.float32)
        h = h * (sel_w * valid)[:, None]
        out = out.at[sel_i].add(h)
    out = jax.lax.psum(out, axes.model)
    aux = moe_lib._aux_loss(top_idx, scores, cfg, b)
    counts = jnp.zeros((e,), jnp.float32)
    for k in range(cfg.top_k):
        counts = counts + jax.nn.one_hot(top_idx[:, k], e,
                                         dtype=jnp.float32).sum(0)
    aux = jax.lax.pmean(aux, axes.batch)
    counts = jax.lax.psum(counts, axes.batch)
    return out.reshape(b, s, d).astype(x.dtype), aux, counts


# ------------------------------------------------------------ the selection
def _kept_by_loop(top_vals, top_idx, offset, e_loc, cap):
    """{(local expert, rank, token): weight} as the loop keeps them."""
    kept = {}
    for l in range(e_loc):
        sel_w, sel_i, valid = map(np.asarray, _loop_select(
            top_vals, top_idx, offset + l, cap))
        for r in np.flatnonzero(valid):
            kept[(l, int(r), int(sel_i[r]))] = float(sel_w[r])
    return kept


def _kept_by_plan(top_vals, top_idx, offset, e_loc, cap):
    """The same, from ``_dispatch_plan``."""
    t, k = top_idx.shape
    slot_token, slot_weight = map(np.asarray, moe_lib._dispatch_plan(
        top_vals, top_idx, offset, e_loc, cap))
    filled = slot_token < t
    # an expert's filled slots come first; an empty slot weighs 0, and
    # its token, out of range, is no other slot's
    assert np.all(np.diff(filled.astype(int), axis=1) <= 0)
    assert np.all(slot_weight[~filled] == 0)
    assert len(np.unique(slot_token[~filled])) == int((~filled).sum())
    # each token's slots, ascending, name the slots that hold it
    slot = np.asarray(moe_lib._token_slots(top_idx, jnp.asarray(slot_token),
                                           offset, cap))
    assert np.all(np.diff(slot, axis=1) > 0)
    mine = {(int(v) // cap, int(v) % cap, int(tok))
            for tok, row in enumerate(slot) for v in row if v < e_loc * cap}
    assert mine == {(l, r, int(slot_token[l, r]))
                    for l, r in zip(*np.nonzero(filled))}
    return {(l, r, int(slot_token[l, r])): float(slot_weight[l, r])
            for l, r in zip(*np.nonzero(filled))}


def _routing(seed, t, e, k, *, tie_hot=False, unreached=(), zero_weight=0):
    """(top_vals, top_idx) of ``t`` tokens over ``e`` experts: expert 0 hot;
    ``tie_hot`` gives every token the same weight on it; the experts in
    ``unreached`` are chosen by no token; ``zero_weight`` assignments have
    weight 0."""
    rng = np.random.default_rng(seed)
    free = [x for x in range(e) if x not in unreached]
    idx = np.stack([rng.choice(free, k, replace=False) for _ in range(t)])
    hot = rng.random(t) < 0.6
    for i in np.flatnonzero(hot):
        if 0 not in idx[i] and 0 in free:
            idx[i, rng.integers(k)] = 0
    vals = rng.random((t, k)).astype(np.float32)
    vals = np.round(vals * 8) / 8          # coarse weights: ties everywhere
    vals[vals == 0] = 0.125
    if tie_hot:
        vals[idx == 0] = 0.5
    if zero_weight:
        flat = vals.reshape(-1)
        flat[rng.choice(flat.size, zero_weight, replace=False)] = 0.0
    return jnp.asarray(vals), jnp.asarray(idx, jnp.int32)


@pytest.mark.parametrize("case", [
    dict(seed=0, t=64, e=8, k=2, offset=0, e_loc=8, cap=12, tie_hot=True),
    dict(seed=1, t=64, e=8, k=2, offset=0, e_loc=8, cap=12, unreached=(5,)),
    dict(seed=2, t=96, e=16, k=4, offset=4, e_loc=8, cap=20, tie_hot=True,
         unreached=(6, 9)),
    dict(seed=3, t=50, e=8, k=3, offset=2, e_loc=4, cap=50, zero_weight=9),
    dict(seed=4, t=128, e=16, k=2, offset=8, e_loc=8, cap=8, tie_hot=True,
         zero_weight=5),
], ids=["tied-cut", "unreached", "held-subset", "under-filled", "foreign-hot"])
def test_the_plan_keeps_what_the_loop_keeps(case):
    """Over-full experts with tied weights at the cut, under-filled and
    unreached experts, weights of 0, a held subset that starts past 0."""
    case = dict(case)
    offset, e_loc, cap = case.pop("offset"), case.pop("e_loc"), case.pop("cap")
    top_vals, top_idx = _routing(**case)
    want = _kept_by_loop(top_vals, top_idx, offset, e_loc, cap)
    got = _kept_by_plan(top_vals, top_idx, offset, e_loc, cap)
    assert got == want
    if case.get("tie_hot") and offset == 0:
        # the hot expert is over-full: the cut falls among tied weights
        assert sum(1 for (l, _, _) in want if l == 0) == cap
        assert int((np.asarray(top_idx) == 0).sum()) > cap


# ------------------------------------------------------- the whole layer
def _layer_inputs(cfg, seed, t):
    """Parameters and input of one expert layer over ``t`` tokens, with
    repeated tokens (tied weights), expert 0 made hot and the last expert
    out of every token's reach."""
    params, _ = split_lp_tree(moe_lib.init_moe(jax.random.key(seed), cfg))
    # every token's feature 0 is 1: its router row makes expert 0 hot and
    # the last expert lose to every other
    router = np.asarray(params["router"]).copy()
    router[0, 0] += 3.0
    router[0, -1] -= 30.0
    params["router"] = jnp.asarray(router)
    if "router_bias" in params:
        params["router_bias"] = jnp.linspace(-0.01, 0.01, cfg.num_experts)
    x = jax.random.normal(jax.random.key(seed + 1), (2, t // 2, cfg.d_model))
    x = x.at[..., 0].set(1.0)
    x = x.at[:, 1::4].set(x[:, ::4][:, : x[:, 1::4].shape[1]])
    return params, x.astype(jnp.bfloat16)


def _value_and_grads(cfg, mesh, params, x, body):
    axes = MeshAxes.for_mesh(mesh)
    r = jax.random.normal(jax.random.key(7), x.shape, jnp.float32)

    def loss(p, x):
        y, stats = moe_lib.moe_forward(p, x, cfg, mesh, axes, cfg.act)
        return (jnp.sum(y.astype(jnp.float32) * r)
                + stats["aux_loss"]), (y, stats)

    old = moe_lib._local_moe
    moe_lib._local_moe = body
    try:
        fn = jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))
        return jax.device_get(fn(params, x))
    finally:
        moe_lib._local_moe = old


def _assert_same_layer(cfg, mesh, seed=0, t=64):
    params, x = _layer_inputs(cfg, seed, t)
    want = _value_and_grads(cfg, mesh, params, x, _loop_moe)
    got = _value_and_grads(cfg, mesh, params, x, moe_lib._local_moe)
    leaves_w, tree_w = jax.tree.flatten(want)
    leaves_g, tree_g = jax.tree.flatten(got)
    assert tree_w == tree_g
    names = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(want)[0]]
    for name, a, b in zip(names, leaves_w, leaves_g):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=name)
    return want


def _dropping(cfg, **kw):
    """A config whose capacity drops tokens (1.0 against the smoke's 8)."""
    return dataclasses.replace(cfg, capacity_factor=1.0, **kw)


def _gathers(cfg, t, model_size=1):
    """Whether the layer returns rows to tokens by gathers (no more
    assignments than slots) or by a scatter-add."""
    e_loc = cfg.held_experts // model_size
    return t * cfg.top_k <= e_loc * moe_lib._capacity(cfg, t)


@pytest.mark.parametrize("cfg,gathers", [
    (_dropping(QWEN3), True),
    (_dropping(QWEN3, experts_held=4, first_held_expert=2), False),
    (_dropping(QWEN3, top_k=4), True),
    (_dropping(QWEN3, top_k=4, experts_held=4, first_held_expert=4), False),
    (_dropping(MOONLIGHT), True),
    (_dropping(MOONLIGHT, experts_held=4, first_held_expert=2), False),
    (QWEN3, True),
], ids=["softmax", "softmax-held", "top-4", "top-4-held", "sigmoid-bias",
        "sigmoid-held", "no-drops"])
def test_the_layer_is_the_loop_bit_for_bit(cfg, gathers):
    """Output, router statistics and the gradients of router, experts and
    input, on one device, with rows returned by gathers and by a
    scatter-add."""
    assert _gathers(cfg, 64) == gathers
    (_, (y, stats)), grads = _assert_same_layer(cfg, make_local_mesh(1, 1))
    assert np.all(np.isfinite(np.asarray(y, np.float32)))
    counts = np.asarray(stats["expert_counts"])
    assert counts[-1] == 0                        # an unreached expert
    if cfg.capacity_factor == 1.0:
        cap = moe_lib._capacity(cfg, 64)
        assert counts[0] > cap                    # an over-full one
        assert counts[cfg.first_held_expert:][:cfg.held_experts].min() < cap


SHARDED = r"""
import sys
sys.path.insert(0, sys.argv[1])
import test_moe_dispatch as t
from repro.launch.mesh import make_local_mesh
for cfg in (t._dropping(t.QWEN3, top_k=4),
            t._dropping(t.MOONLIGHT, routed_scaling=1.0),
            t._dropping(t.MOONLIGHT, routed_scaling=1.0, experts_held=4,
                        first_held_expert=2)):
    assert not t._gathers(cfg, 64, model_size=2)
    t._assert_same_layer(cfg, make_local_mesh(1, 2))
    assert t._gathers(cfg, 32) or cfg.experts_held
    t._assert_same_layer(cfg, make_local_mesh(2, 1))
print("ok")
"""


def test_the_layer_is_the_loop_bit_for_bit_over_two_devices():
    """The held experts split over ``model`` (each shard its own offset,
    outputs summed by the psum), and the tokens over ``data``.  Moonlight's
    routed scale is 1 here: on two CPU devices XLA fuses the loop's
    per-expert weight cotangents with the scale's and rounds the router's
    f32 gradient in its last bit otherwise (on one device, above, the
    scaled path is bit for bit too)."""
    here = Path(__file__).resolve().parent
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=str(here.parent / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    p = subprocess.run([sys.executable, "-c", SHARDED, str(here)], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    assert p.stdout.strip().endswith("ok")


@pytest.mark.parametrize("seed,shards", [(0, 1), (1, 1), (2, 2)])
def test_expert_dropped_counts_what_the_plan_leaves_out(seed, shards):
    """``expert_dropped`` from the step's counts alone: on one shard, the
    routed assignments to held experts that found no slot."""
    cfg = _dropping(QWEN3, experts_held=6)
    t, cap = 64, moe_lib._capacity(cfg, 64)
    top_vals, top_idx = _routing(seed, t, cfg.num_experts, cfg.top_k,
                                 tie_hot=True, unreached=(7,))
    counts = np.bincount(np.asarray(top_idx).ravel(),
                         minlength=cfg.num_experts)
    slot_token, _ = moe_lib._dispatch_plan(top_vals, top_idx, 0, 6, cap)
    held = int(counts[:6].sum())
    left_out = held - int((np.asarray(slot_token) < t).sum())
    assert left_out > 0                           # the hot expert 0
    if shards == 1:
        assert moe_lib.expert_dropped(counts[None], cfg, t, 1) == left_out
    else:
        # two shards with these counts each: twice the drops, and no more
        both = 2 * counts[None]
        assert moe_lib.expert_dropped(both, cfg, t, 2) == 2 * left_out
