"""Step builders: abstract (ShapeDtypeStruct) parameter and optimizer trees
with matching NamedShardings, and the train step that the trainer and the
benchmark's drivers jit.
"""
from __future__ import annotations

import re
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models.layers import split_lp_tree
from repro.models.model import Model
from repro.models.moe import is_router_state
from repro.models.transformer import update_router_bias
from repro.optim import adamw_update, warmup_cosine
from repro.sharding import shardings_for_lp_tree

# The model names its layers (jax.named_scope) for profiles.  By default the
# persistent compile cache leaves the op metadata out of its key, so a step
# of the same computation compiled from other source (such as a version
# without the scopes) is loaded with that source's op names.  Keying on the
# metadata keeps a profile's names true; file names are taken relative to
# the checkout, so that where a checkout lies does not change the key.
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
if jax.config.jax_hlo_source_file_canonicalization_regex is None:
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      "^" + re.escape(str(Path(__file__).resolve().parents[3]))
                      + "/")


def abstract_params(model: Model):
    """(params SDS tree, NamedSharding tree) without allocating anything."""
    lp_tree = jax.eval_shape(model.init, jax.random.key(0))
    params_sds, _ = split_lp_tree(lp_tree)
    shardings = shardings_for_lp_tree(model.mesh, model.axes, lp_tree)
    return params_sds, shardings


def abstract_opt(params_sds, param_shardings):
    """AdamW state SDS + shardings mirroring the params (ZeRO-1)."""
    f32 = lambda sds: jax.ShapeDtypeStruct(sds.shape, jnp.float32)
    m = jax.tree.map(f32, params_sds)
    from repro.optim.adamw import AdamWState
    state = AdamWState(step=jax.ShapeDtypeStruct((), jnp.int32), m=m,
                       v=jax.tree.map(f32, params_sds))
    mesh = jax.tree.leaves(param_shardings)[0].mesh
    shardings = AdamWState(step=NamedSharding(mesh, P()),
                           m=param_shardings, v=param_shardings)
    return state, shardings


def make_train_step(model: Model, *, lr=3e-4, weight_decay=0.1,
                    warmup_steps=100, total_steps=10000):
    """The step: gradients, AdamW on every parameter but the router state
    (``moe.is_router_state``), then, where the config has a bias rate, the
    router bias moved by the step's own routed counts."""
    schedule = warmup_cosine(lr, warmup_steps, total_steps)
    cfg = model.cfg

    def train_step(params, opt_state, batch):
        (loss, metrics), grads = jax.value_and_grad(
            model.loss_fn, has_aux=True)(params, batch)
        new_params, new_opt = adamw_update(
            grads, opt_state, params, schedule, weight_decay=weight_decay,
            skip=is_router_state)
        if cfg.router_bias_rate:
            new_params = update_router_bias(new_params,
                                            metrics["expert_counts"], cfg)
        metrics = dict(metrics)
        metrics["loss"] = loss
        return new_params, new_opt, metrics

    return train_step
