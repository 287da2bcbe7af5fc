"""AdamW in pure JAX, used by both LM training and the paper's cost-model FNN
(§VI-D cites AdamW [36] for better generalization/convergence).

Moments are f32 and mirror the parameter tree (and therefore its sharding —
ZeRO-1 falls out of the params being FSDP+TP sharded already).
"""
from __future__ import annotations

from typing import Any, Dict, NamedTuple

import jax
import jax.numpy as jnp


class AdamWState(NamedTuple):
    step: jnp.ndarray
    m: Any
    v: Any


def adamw_init(params) -> AdamWState:
    zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
    return AdamWState(step=jnp.zeros((), jnp.int32), m=zeros,
                      v=jax.tree.map(jnp.copy, zeros))


def global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree.leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
                        for l in leaves))


def adamw_update(grads, state: AdamWState, params, lr, *, b1=0.9, b2=0.95,
                 eps=1e-8, weight_decay=0.0, clip_norm=1.0, skip=None):
    """Returns (new_params, new_state).  ``lr`` may be a scalar or schedule(step).

    ``skip(path)``, given a leaf's key path, marks state that is no
    parameter of the optimizer: it is neither moved nor decayed, and its
    moments stay zero."""
    with jax.named_scope("optimizer"):
        step = state.step + 1
        if callable(lr):
            lr = lr(step)
        gnorm = global_norm(grads)
        scale = jnp.minimum(1.0, clip_norm / jnp.maximum(gnorm, 1e-9)) \
            if clip_norm else 1.0

        def upd(g, m, v, p):
            g = g.astype(jnp.float32) * scale
            m2 = b1 * m + (1 - b1) * g
            v2 = b2 * v + (1 - b2) * jnp.square(g)
            mhat = m2 / (1 - b1 ** step.astype(jnp.float32))
            vhat = v2 / (1 - b2 ** step.astype(jnp.float32))
            delta = mhat / (jnp.sqrt(vhat) + eps)
            if weight_decay:
                delta = delta + weight_decay * p.astype(jnp.float32)
            p2 = p.astype(jnp.float32) - lr * delta
            return p2.astype(p.dtype), m2, v2

        paths, treedef = jax.tree_util.tree_flatten_with_path(params)
        flat_p = [p for _, p in paths]
        flat_g = treedef.flatten_up_to(grads)
        flat_m = treedef.flatten_up_to(state.m)
        flat_v = treedef.flatten_up_to(state.v)
        out = [(p, m, v) if skip and skip(path) else upd(g, m, v, p)
               for (path, p), g, m, v in zip(paths, flat_g, flat_m, flat_v)]
        new_p = treedef.unflatten([o[0] for o in out])
        new_m = treedef.unflatten([o[1] for o in out])
        new_v = treedef.unflatten([o[2] for o in out])
        return new_p, AdamWState(step=step, m=new_m, v=new_v)
