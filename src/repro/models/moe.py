"""Mixture-of-Experts FFN with explicit expert parallelism (shard_map).

Baseline collective schedule ("replicated-token EP"): activations are batch-
sharded over the data axes and replicated over the model axis (standard TP
layout between blocks), experts are sharded over the model axis, and each
model-shard processes the tokens routed to *its* experts: one ``top_k``
over the held experts' token weights fills each expert's capacity slots, one
gather moves the tokens there, one batched GEMM runs every held expert, and
the weighted rows return to their tokens; results combine with a single psum
over the model axis.  Expert weights are FSDP-sharded over the data axis on
the hidden dim and all-gathered at use.

Router statistics (tokens-per-expert) are returned so the CCM load balancer
(repro.balance.expert_placement) can re-plan expert placement: experts are CCM
*shared blocks*, per-expert token loads are task loads, and dispatch volume is
the communication term.

A layer may hold only some of the experts (``cfg.experts_held`` from
``cfg.first_held_expert``): it still routes over all ``num_experts``,
computes the part of the output that its own experts give and counts the
tokens routed to every expert.  The router scores by softmax, or by sigmoid
with a ``router_bias`` that picks the experts but does not weigh them
(DeepSeek-V3's aux-loss-free balancing; the train step moves the bias
against the routed load, see ``update_router_bias``).

The layer runs under the name scope ``moe``, and its parts under ``router``,
``dispatch``, ``experts``, ``combine``, ``shared`` and ``stats``, so that
each op of a profiler trace names the part it belongs to.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import activation, dense_init, zeros_init
from repro.sharding import MeshAxes


def is_router_state(path) -> bool:
    """Whether the parameter leaf at key path ``path`` is the router bias,
    which the train step moves and no gradient or optimizer does."""
    return getattr(path[-1], "key", None) == "router_bias"


def init_moe(key, cfg: ModelConfig, dtype=jnp.bfloat16):
    kr, k1, k2, k3, ks = jax.random.split(key, 5)
    d, e, f = cfg.d_model, cfg.held_experts, cfg.moe_d_ff
    params = {
        "router": dense_init(kr, (d, cfg.num_experts), ("embed", None),
                             dtype=jnp.float32),
        "w_gate": dense_init(k1, (e, d, f), ("expert", "embed", "expert_mlp"),
                             in_axis=1, dtype=dtype),
        "w_up": dense_init(k2, (e, d, f), ("expert", "embed", "expert_mlp"),
                           in_axis=1, dtype=dtype),
        "w_down": dense_init(k3, (e, f, d), ("expert", "expert_mlp", "embed"),
                             in_axis=1, dtype=dtype),
    }
    if cfg.router_scoring == "sigmoid":
        params["router_bias"] = zeros_init((cfg.num_experts,), (None,),
                                           dtype=jnp.float32)
    if cfg.num_shared_experts:
        from repro.models.layers import init_mlp
        params["shared"] = init_mlp(ks, d, cfg.shared_width, dtype=dtype)
    return params


def _capacity(cfg: ModelConfig, tokens: int) -> int:
    c = int(cfg.capacity_factor * tokens * cfg.top_k / cfg.num_experts) + 1
    c = (c + 7) // 8 * 8
    return max(1, min(c, tokens))


def expert_dropped(counts, cfg: ModelConfig, tokens: int, shards: int) -> int:
    """Routed assignments the held experts drop at capacity: over layers
    and held experts, ``max(count - shards * capacity, 0)``, from a step's
    ``expert_counts`` (..., E), summed over ``shards`` data shards of
    ``tokens`` tokens each.  Exact on one shard; a lower bound on more,
    where one shard may drop what another has room for."""
    held = np.asarray(counts, np.float64)[
        ..., cfg.first_held_expert:cfg.first_held_expert + cfg.held_experts]
    cap = shards * _capacity(cfg, tokens)
    return int(np.maximum(held - cap, 0).sum())


def _route(x_flat, router_w, bias, cfg: ModelConfig):
    """(top_vals, top_idx, scores): the chosen experts' weights and ids
    (T, k), and the per-token scores the balance loss reads (T, E)."""
    logits = (x_flat.astype(jnp.float32) @ router_w)  # (T, E)
    if cfg.router_scoring == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        _, top_idx = jax.lax.top_k(scores + bias, cfg.top_k)
        top_vals = jnp.take_along_axis(scores, top_idx, axis=-1)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        top_vals, top_idx = jax.lax.top_k(scores, cfg.top_k)  # (T, k)
    top_vals = top_vals / jnp.maximum(top_vals.sum(-1, keepdims=True), 1e-9)
    if cfg.routed_scaling != 1.0:
        top_vals = top_vals * cfg.routed_scaling
    return top_vals, top_idx, scores


def _aux_loss(top_idx, scores, cfg: ModelConfig, batch: int):
    """``switch``: experts x the sum over experts of the top-1 share times
    the mean score.  ``sequence`` (DeepSeek-V3, arXiv:2412.19437 2.1.2):
    per sequence, the sum over experts of f_i (experts / (k x S) x the
    tokens routed to i) times P_i (the mean of the scores normalised over
    the experts), averaged over the sequences."""
    e = cfg.num_experts
    if cfg.aux_loss == "sequence":
        t, k = top_idx.shape
        s = t // batch
        chosen = jax.nn.one_hot(top_idx, e, dtype=jnp.float32).sum(1)
        f = chosen.reshape(batch, s, e).sum(1) * (e / (k * s))
        p = scores / jnp.maximum(scores.sum(-1, keepdims=True), 1e-9)
        p = p.reshape(batch, s, e).mean(1)
        return jnp.mean(jnp.sum(f * p, -1))
    assign = jax.nn.one_hot(top_idx[:, 0], e, dtype=jnp.float32)  # top-1
    return e * jnp.sum(assign.mean(0) * scores.mean(0))


def _dispatch_plan(top_vals, top_idx, offset, e_loc: int, cap: int):
    """Each held expert's capacity slots: its ``cap`` highest-weighted
    tokens, ties to the lower token, from one ``top_k`` over the (E_loc, T)
    weights of all held experts.

    Returns ``(slot_token, slot_weight)`` (E_loc, C): each slot's token and
    weight; where fewer tokens reach the expert, a token out of range (each
    a different one) and weight 0.
    """
    t = top_idx.shape[0]
    held = offset + jnp.arange(e_loc)
    w = jnp.where(top_idx[None] == held[:, None, None], top_vals[None],
                  0.0).sum(-1)  # (E_loc, T)
    sel_w, sel_i = jax.lax.top_k(jnp.where(w > 0, w, -1.0), cap)
    filled = sel_w > 0
    empty = jnp.arange(e_loc * cap, dtype=jnp.int32).reshape(e_loc, cap)
    return (jnp.where(filled, sel_i, t + empty),
            jnp.where(filled, sel_w, 0.0))


def _token_slots(top_idx, slot_token, offset, cap: int):
    """(T, k): each token's slots in ascending order, which is ascending
    expert order; a dropped or foreign assignment's slot is out of range
    (each a different one) and sorts last."""
    t, k = top_idx.shape
    e_loc = slot_token.shape[0]
    rank = jnp.full((t, e_loc), -1, jnp.int32).at[
        slot_token, jnp.arange(e_loc)[:, None]].set(
        jnp.broadcast_to(jnp.arange(cap, dtype=jnp.int32), slot_token.shape),
        mode="drop", unique_indices=True)
    loc = top_idx - offset
    r = jnp.take_along_axis(rank, jnp.clip(loc, 0, e_loc - 1), axis=1)
    kept = (loc >= 0) & (loc < e_loc) & (r >= 0)
    out = e_loc * cap + jnp.arange(t * k, dtype=jnp.int32).reshape(t, k)
    return jnp.sort(jnp.where(kept, loc * cap + r, out), axis=-1)


def _token_sums(rows, slot_token, slot, t: int, descending: bool):
    """(T, d): each token's rows of ``rows`` (E_loc, C, d), added from 0 in
    ``rows``' dtype in its experts' order, ascending or descending.  With
    the tokens' ``slot`` (T, k), by one gather per choice; without, by one
    scatter-add of the slots, which are expert-major."""
    if slot is None:
        if descending:
            rows, slot_token = rows[::-1], slot_token[::-1]
        return jnp.zeros((t, rows.shape[-1]), rows.dtype).at[slot_token].add(
            rows, mode="drop")
    flat = rows.reshape(-1, rows.shape[-1])
    out = jnp.zeros((t, rows.shape[-1]), rows.dtype)
    for j in (reversed(range(slot.shape[1])) if descending
              else range(slot.shape[1])):
        out = out + flat.at[slot[:, j]].get(mode="fill", fill_value=0,
                                            unique_indices=True)
    return out


def _take_tokens(x_flat, slot_token):
    """(E_loc, C, d): each slot's token row; an empty slot reads the last
    token, whose row its weight of 0 keeps out of every sum."""
    return x_flat.at[slot_token].get(mode="clip")


# Rows move between tokens (T, d) and capacity slots (E_loc, C, d) by a
# gather one way and a sum over each token's slots the other; each move is
# the other's transpose.  The combine adds a token's rows in ascending
# expert order; the dispatch's transpose in descending order, the order in
# which reverse mode accumulates the cotangents of one gather per expert,
# so that the sums round as a per-expert formulation's do.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _to_slots(x_flat, slot_token, slot, t: int):
    return _take_tokens(x_flat, slot_token)


def _to_slots_fwd(x_flat, slot_token, slot, t):
    return _take_tokens(x_flat, slot_token), (slot_token, slot)


def _to_slots_bwd(t, res, g):
    slot_token, slot = res
    return _token_sums(g, slot_token, slot, t, descending=True), None, None


_to_slots.defvjp(_to_slots_fwd, _to_slots_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _to_tokens(rows, slot_token, slot, t: int):
    return _token_sums(rows, slot_token, slot, t, descending=False)


def _to_tokens_fwd(rows, slot_token, slot, t):
    return (_token_sums(rows, slot_token, slot, t, descending=False),
            (slot_token, slot))


def _to_tokens_bwd(t, res, g):
    slot_token, _ = res
    return _take_tokens(g, slot_token), None, None


_to_tokens.defvjp(_to_tokens_fwd, _to_tokens_bwd)


def _local_moe(router_w, bias, w_gate, w_up, w_down, x, *, cfg: ModelConfig,
               axes: MeshAxes, act_name: str, model_size: int, data_size: int):
    """Per-device body under shard_map.

    x: (B_loc, S, d) — identical across the model axis, sharded over batch.
    w_*: (E_loc, d, f_loc) — the held experts sharded over model, fsdp over
    data; this shard holds experts ``offset .. offset + E_loc - 1``.

    Each held expert runs its ``cap`` highest-weighted tokens (ties to the
    lower token), all experts in one batched GEMM; a token's weighted
    outputs are added in f32 in ascending expert order.
    """
    b, s, d = x.shape
    t = b * s
    x_flat = x.reshape(t, d)
    e = cfg.num_experts
    e_loc = cfg.held_experts // model_size
    assert cfg.held_experts % model_size == 0, (cfg.held_experts, model_size)

    # FSDP all-gather of this shard's expert weights over the data axis.
    if data_size > 1:
        with jax.named_scope("dispatch"):
            w_gate = jax.lax.all_gather(w_gate, axes.data, axis=2, tiled=True)
            w_up = jax.lax.all_gather(w_up, axes.data, axis=2, tiled=True)
            w_down = jax.lax.all_gather(w_down, axes.data, axis=1, tiled=True)

    with jax.named_scope("router"):
        top_vals, top_idx, scores = _route(x_flat, router_w, bias, cfg)

    cap = _capacity(cfg, t)
    act = activation(act_name)
    offset = cfg.first_held_expert + jax.lax.axis_index(axes.model) * e_loc
    with jax.named_scope("dispatch"):
        slot_token, slot_weight = _dispatch_plan(top_vals, top_idx, offset,
                                                 e_loc, cap)
        # a token's rows are gathered from its slots where there are no more
        # assignments than slots; where most are foreign (an expert-parallel
        # shard) or dropped, the slots are scattered to their tokens
        slot = (_token_slots(top_idx, slot_token, offset, cap)
                if t * cfg.top_k <= e_loc * cap else None)
        xg = _to_slots(x_flat, slot_token, slot, t)  # (E_loc, C, d)
    with jax.named_scope("experts"):
        g = act(jnp.einsum("ecd,edf->ecf", xg, w_gate))
        u = jnp.einsum("ecd,edf->ecf", xg, w_up)
        h = jnp.einsum("ecf,efd->ecd", g * u, w_down).astype(jnp.float32)
    with jax.named_scope("combine"):
        out = _to_tokens(h * slot_weight[..., None], slot_token, slot, t)
        out = jax.lax.psum(out, axes.model)

    # Router stats: tokens-per-expert counts (all experts, held or not) and
    # the balance loss.
    with jax.named_scope("stats"):
        aux = _aux_loss(top_idx, scores, cfg, b)
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
    bias = params.get("router_bias")
    if bias is None:
        bias = jnp.zeros((cfg.num_experts,), jnp.float32)

    def body(*args):
        # on a mesh of several devices the body's names gain a ``shard_map``
        # segment after ``moe``: open ``moe`` again inside, so that its parts
        # read ``moe/router``, ``moe/experts``, ... there too
        if mesh.size == 1:
            return fn(*args)
        with jax.named_scope("moe"):
            return fn(*args)

    with jax.named_scope("moe"):
        y, aux, counts = jax.shard_map(
            body,
            mesh=mesh,
            in_specs=(
                P(None, None),                       # router (d, E) replicated
                P(None),                             # router_bias (E,)
                P(axes.model, None, axes.data),      # w_gate (E, d, f)
                P(axes.model, None, axes.data),      # w_up
                P(axes.model, axes.data, None),      # w_down (E, f, d)
                P(bspec, None, None),                # x
            ),
            out_specs=(P(bspec, None, None), P(), P()),
            check_vma=False,
        )(params["router"], jax.lax.stop_gradient(bias), params["w_gate"],
          params["w_up"], params["w_down"], x)

        if cfg.num_shared_experts:
            from repro.models.layers import mlp_forward
            with jax.named_scope("shared"):
                y = y + mlp_forward(params["shared"], x, act_name)
    return y, {"aux_loss": aux, "expert_counts": counts}


def update_router_bias(bias, counts, rate: float):
    """DeepSeek-V3's aux-loss-free balancing: each expert's bias moves by
    ``rate`` towards the mean load, ``b_i + rate * sign(mean - load_i)``,
    from the step's routed counts (..., E)."""
    load = counts.astype(jnp.float32)
    return bias + rate * jnp.sign(load.mean(-1, keepdims=True) - load)
