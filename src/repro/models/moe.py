"""Mixture-of-Experts FFN with explicit expert parallelism (shard_map).

Baseline collective schedule ("replicated-token EP"): activations are batch-
sharded over the data axes and replicated over the model axis (standard TP
layout between blocks), experts are sharded over the model axis, and each
model-shard processes the tokens routed to *its* experts via per-expert
top-capacity gather -> GEMM -> scatter; results combine with a single psum
over the model axis.  Expert weights are FSDP-sharded over the data axis on
the hidden dim and all-gathered at use.

Router statistics (tokens-per-expert) are returned so the CCM load balancer
(repro.balance.expert_placement) can re-plan expert placement: experts are CCM
*shared blocks*, per-expert token loads are task loads, and dispatch volume is
the communication term.

The layer runs under the name scope ``moe``, and its parts under ``router``,
``dispatch``, ``experts``, ``combine`` and ``stats``, so that each op of a
profiler trace names the part it belongs to.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import activation, dense_init
from repro.sharding import MeshAxes


def init_moe(key, cfg: ModelConfig, dtype=jnp.bfloat16):
    kr, k1, k2, k3, ks = jax.random.split(key, 5)
    d, e, f = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    params = {
        "router": dense_init(kr, (d, e), ("embed", None), dtype=jnp.float32),
        "w_gate": dense_init(k1, (e, d, f), ("expert", "embed", "expert_mlp"),
                             in_axis=1, dtype=dtype),
        "w_up": dense_init(k2, (e, d, f), ("expert", "embed", "expert_mlp"),
                           in_axis=1, dtype=dtype),
        "w_down": dense_init(k3, (e, f, d), ("expert", "expert_mlp", "embed"),
                             in_axis=1, dtype=dtype),
    }
    if cfg.num_shared_experts:
        from repro.models.layers import init_mlp
        params["shared"] = init_mlp(ks, d, cfg.d_ff * cfg.num_shared_experts,
                                    dtype=dtype)
    return params


def _capacity(cfg: ModelConfig, tokens: int) -> int:
    c = int(cfg.capacity_factor * tokens * cfg.top_k / cfg.num_experts) + 1
    c = (c + 7) // 8 * 8
    return max(1, min(c, tokens))


def _local_moe(router_w, w_gate, w_up, w_down, x, *, cfg: ModelConfig,
               axes: MeshAxes, act_name: str, model_size: int, data_size: int):
    """Per-device body under shard_map.

    x: (B_loc, S, d) — identical across the model axis, sharded over batch.
    w_*: (E_loc, d, f_loc) — expert-sharded over model, fsdp over data.
    """
    b, s, d = x.shape
    t = b * s
    x_flat = x.reshape(t, d)
    e = cfg.num_experts
    e_loc = e // model_size
    assert e % model_size == 0, (e, model_size)

    # FSDP all-gather of this shard's expert weights over the data axis.
    if data_size > 1:
        with jax.named_scope("dispatch"):
            w_gate = jax.lax.all_gather(w_gate, axes.data, axis=2, tiled=True)
            w_up = jax.lax.all_gather(w_up, axes.data, axis=2, tiled=True)
            w_down = jax.lax.all_gather(w_down, axes.data, axis=1, tiled=True)

    with jax.named_scope("router"):
        logits = (x_flat.astype(jnp.float32) @ router_w)  # (T, E)
        probs = jax.nn.softmax(logits, axis=-1)
        top_vals, top_idx = jax.lax.top_k(probs, cfg.top_k)  # (T, k)
        top_vals = top_vals / jnp.maximum(top_vals.sum(-1, keepdims=True),
                                          1e-9)

    cap = _capacity(cfg, t)
    act = activation(act_name)
    out = jnp.zeros((t, d), jnp.float32)
    offset = jax.lax.axis_index(axes.model) * e_loc
    for e_local in range(e_loc):
        e_id = offset + e_local
        with jax.named_scope("dispatch"):
            w_e = jnp.where(top_idx == e_id, top_vals, 0.0).sum(-1)  # (T,)
            sel_w, sel_i = jax.lax.top_k(jnp.where(w_e > 0, w_e, -1.0), cap)
            valid = (sel_w > 0).astype(jnp.float32)
            xg = x_flat[sel_i]  # (C, d)
        with jax.named_scope("experts"):
            g = act(xg @ w_gate[e_local])
            u = xg @ w_up[e_local]
            h = ((g * u) @ w_down[e_local]).astype(jnp.float32)
        with jax.named_scope("combine"):
            h = h * (sel_w * valid)[:, None]
            out = out.at[sel_i].add(h)

    with jax.named_scope("combine"):
        out = jax.lax.psum(out, axes.model)

    # Router stats: tokens-per-expert counts + Switch-style aux loss.
    with jax.named_scope("stats"):
        assign = jax.nn.one_hot(top_idx[:, 0], e, dtype=jnp.float32)  # top-1
        f_frac = assign.mean(0)
        p_mean = probs.mean(0)
        aux = e * jnp.sum(f_frac * p_mean)
        counts = jnp.zeros((e,), jnp.float32)
        for k in range(cfg.top_k):
            counts = counts + jax.nn.one_hot(top_idx[:, k], e,
                                             dtype=jnp.float32).sum(0)
        aux = jax.lax.pmean(aux, axes.batch)
        counts = jax.lax.psum(counts, axes.batch)
    return out.reshape(b, s, d).astype(x.dtype), aux, counts


def moe_forward(params, x, cfg: ModelConfig, mesh: Mesh, axes: MeshAxes,
                act_name: str):
    """Returns (y, stats) where stats = {'aux_loss','expert_counts'}."""
    bspec = axes.batch if len(axes.batch) > 1 else axes.batch[0]
    fn = functools.partial(
        _local_moe, cfg=cfg, axes=axes, act_name=act_name,
        model_size=int(mesh.shape[axes.model]),
        data_size=int(mesh.shape[axes.data]))
    with jax.named_scope("moe"):
        y, aux, counts = jax.shard_map(
            fn,
            mesh=mesh,
            in_specs=(
                P(None, None),                       # router (d, E) replicated
                P(axes.model, None, axes.data),      # w_gate (E, d, f)
                P(axes.model, None, axes.data),      # w_up
                P(axes.model, axes.data, None),      # w_down (E, f, d)
                P(bspec, None, None),                # x
            ),
            out_specs=(P(bspec, None, None), P(), P()),
            check_vma=False,
        )(params["router"], params["w_gate"], params["w_up"],
          params["w_down"], x)

        if cfg.num_shared_experts:
            from repro.models.layers import mlp_forward
            y = y + mlp_forward(params["shared"], x, act_name)
    return y, {"aux_loss": aux, "expert_counts": counts}
