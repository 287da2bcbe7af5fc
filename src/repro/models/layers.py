"""Shared layer primitives and the logical-parameter convention.

Every parameter leaf is created as an ``LP(value, axes)`` — a value plus a
tuple of *logical* axis names ("embed", "heads", "mlp", "expert", ...).  The
launcher maps logical axes onto mesh axes (see launch/shardings.py); models
never hardcode mesh names, so the same code serves the 1-device smoke tests,
the (16,16) single-pod mesh and the (2,16,16) multi-pod mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass
class LP:
    """Logical param: array (or ShapeDtypeStruct) + logical axis names."""

    value: Any
    axes: Tuple[Optional[str], ...]

    def __post_init__(self):
        shape = getattr(self.value, "shape", None)
        if shape is not None:
            assert len(self.axes) == len(shape), (self.axes, shape)


# Registered as a pytree node so jax.eval_shape / vmap can trace through LP
# trees; axes ride along as static aux data.
jax.tree_util.register_pytree_node(
    LP,
    lambda p: ((p.value,), p.axes),
    lambda axes, children: LP(children[0], axes),
)


def is_lp(x) -> bool:
    return isinstance(x, LP)


def lp_map(fn, tree):
    return jax.tree.map(fn, tree, is_leaf=is_lp)


def split_lp_tree(tree):
    """LP tree -> (values tree, logical-axes tree)."""
    values = lp_map(lambda p: p.value, tree)
    axes = lp_map(lambda p: p.axes, tree)
    return values, axes


def merge_lp_tree(values, axes):
    return jax.tree.map(LP, values, axes,
                        is_leaf=lambda x: isinstance(x, tuple) and all(
                            a is None or isinstance(a, str) for a in x))


# --------------------------------------------------------------------- init
def dense_init(key, shape, axes, in_axis=0, scale=1.0, dtype=jnp.bfloat16) -> LP:
    """Truncated-normal fan-in init (LeCun-ish)."""
    fan_in = int(np.prod([shape[i] for i in np.atleast_1d(in_axis)]))
    std = scale / np.sqrt(max(fan_in, 1))
    v = std * jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
    return LP(v.astype(dtype), axes)


def zeros_init(shape, axes, dtype=jnp.bfloat16) -> LP:
    return LP(jnp.zeros(shape, dtype), axes)


def ones_init(shape, axes, dtype=jnp.bfloat16) -> LP:
    return LP(jnp.ones(shape, dtype), axes)


def const_init(value, axes, dtype=jnp.float32) -> LP:
    return LP(jnp.asarray(value, dtype), axes)


# --------------------------------------------------------------------- norms
def rms_norm(x, weight, eps: float = 1e-6, offset: float = 1.0):
    """RMSNorm in f32 (gemma convention: weight is a delta around 1)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    normed = xf * jax.lax.rsqrt(var + eps)
    return (normed * (offset + weight.astype(jnp.float32))).astype(x.dtype)


def layer_norm(x, weight, bias, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    normed = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (normed * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def group_norm(x, weight, bias, num_groups: int, eps: float = 1e-5):
    """GroupNorm over the last dim (used by RWKV6 output)."""
    *lead, d = x.shape
    xf = x.astype(jnp.float32).reshape(*lead, num_groups, d // num_groups)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    normed = ((xf - mu) * jax.lax.rsqrt(var + eps)).reshape(*lead, d)
    return (normed * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------- activations
def activation(name: str):
    return {
        "silu": jax.nn.silu,
        "gelu": lambda x: jax.nn.gelu(x, approximate=True),
        "relu": jax.nn.relu,
        "relu2": lambda x: jnp.square(jax.nn.relu(x)),
    }[name]


def softcap(x, cap: float):
    if cap and cap > 0.0:
        return cap * jnp.tanh(x / cap)
    return x


# --------------------------------------------------------------------- RoPE
def rope_freqs(head_dim: int, theta: float):
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    return 1.0 / (theta ** exponent)  # (head_dim/2,)


def apply_rope(x, positions, theta: float):
    """x: (..., seq, heads, head_dim); positions: (..., seq)."""
    head_dim = x.shape[-1]
    freqs = rope_freqs(head_dim, theta)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]  # broadcast over heads
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ----------------------------------------------------------------- gated MLP
def init_mlp(key, d_model: int, d_ff: int, dtype=jnp.bfloat16):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(k1, (d_model, d_ff), ("embed", "mlp"), dtype=dtype),
        "w_up": dense_init(k2, (d_model, d_ff), ("embed", "mlp"), dtype=dtype),
        "w_down": dense_init(k3, (d_ff, d_model), ("mlp", "embed"), dtype=dtype),
    }


def mlp_forward(params, x, act_name: str):
    act = activation(act_name)
    with jax.named_scope("mlp"):
        gate = act(jnp.einsum("bsd,df->bsf", x, params["w_gate"]))
        up = jnp.einsum("bsd,df->bsf", x, params["w_up"])
        return jnp.einsum("bsf,fd->bsd", gate * up, params["w_down"])
