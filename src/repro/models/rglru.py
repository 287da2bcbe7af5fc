"""Griffin / RecurrentGemma recurrent block: temporal conv1d + RG-LRU gated
diagonal linear recurrence.  [arXiv:2402.19427]

Training uses ``jax.lax.associative_scan`` (the recurrence is diagonal, so the
(a, b) affine composition is elementwise and cheap); decode is an O(1)-state
step.  State = (B, d_rnn) h-state + (B, conv_width-1, d_rnn) conv tail — O(1)
in sequence length, which is why recurrentgemma runs the long_500k cell.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import LP, dense_init, zeros_init


def init_rglru_block(key, cfg: ModelConfig, dtype=jnp.bfloat16):
    d = cfg.d_model
    dr = d  # rnn width = d_model
    ks = jax.random.split(key, 6)
    lam = jax.random.uniform(ks[5], (dr,), jnp.float32, 0.9 ** 2, 0.999 ** 2)
    # parameterize a = sigmoid(lambda_p); init so sigmoid(lambda_p)=lam^(1/c) —
    # standard Griffin init: a ~ uniform in [0.9, 0.999].
    lambda_p = jnp.log(lam ** (1.0 / cfg.rglru_c) /
                       (1.0 - lam ** (1.0 / cfg.rglru_c)))
    return {
        "w_in_x": dense_init(ks[0], (d, dr), ("embed", "rnn"), dtype=dtype),
        "w_in_gate": dense_init(ks[1], (d, dr), ("embed", "rnn"), dtype=dtype),
        "conv_w": zeros_init((cfg.rglru_conv_width, dr), ("conv", "rnn"),
                             dtype=jnp.float32),
        "conv_b": zeros_init((dr,), ("rnn",), dtype=jnp.float32),
        "w_a": dense_init(ks[2], (dr, dr), ("rnn", "rnn"), dtype=dtype),
        "b_a": zeros_init((dr,), ("rnn",), dtype=jnp.float32),
        "w_x": dense_init(ks[3], (dr, dr), ("rnn", "rnn"), dtype=dtype),
        "b_x": zeros_init((dr,), ("rnn",), dtype=jnp.float32),
        "lambda_p": LP(lambda_p, ("rnn",)),
        "w_out": dense_init(ks[4], (dr, d), ("rnn", "embed"), dtype=dtype),
    }


def _conv1d(p, y, tail=None):
    """Causal depthwise conv, width W.  y: (B,S,dr); tail: (B,W-1,dr)."""
    w = p["conv_w"]
    width = w.shape[0]
    if tail is None:
        tail = jnp.zeros((y.shape[0], width - 1, y.shape[2]), y.dtype)
    ypad = jnp.concatenate([tail.astype(y.dtype), y], axis=1)
    out = sum(ypad[:, i:i + y.shape[1]] * w[i].astype(y.dtype)
              for i in range(width))
    new_tail = ypad[:, ypad.shape[1] - (width - 1):]
    return out + p["conv_b"].astype(y.dtype), new_tail


def _gates(p, y, cfg: ModelConfig):
    """RG-LRU gate computation in f32.  y: (..., dr)."""
    yf = y.astype(jnp.float32)
    r = jax.nn.sigmoid(yf @ p["w_a"].astype(jnp.float32) + p["b_a"])
    i = jax.nn.sigmoid(yf @ p["w_x"].astype(jnp.float32) + p["b_x"])
    log_a0 = jax.nn.log_sigmoid(p["lambda_p"])  # log a in (-inf, 0)
    log_a = cfg.rglru_c * r * log_a0            # a_t = a^(c*r_t)
    a = jnp.exp(log_a)
    mult = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-12))
    b = mult * (i * yf)
    return a, b


def rglru_scan(p, y, cfg: ModelConfig, h0=None):
    """Full-sequence RG-LRU via associative scan.  y: (B,S,dr) -> (B,S,dr)."""
    a, b = _gates(p, y, cfg)
    if h0 is not None:
        b = b.at[:, 0].add(a[:, 0] * h0.astype(jnp.float32))
    def combine(c1, c2):
        a1, b1 = c1
        a2, b2 = c2
        return a1 * a2, a2 * b1 + b2
    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    return h.astype(y.dtype), h[:, -1]


def rglru_block_forward(p, x, cfg: ModelConfig, state=None):
    """Griffin recurrent block.  x: (B,S,d).  state=(h, conv_tail) or None.

    Returns (out, new_state).
    """
    h0, tail = state if state is not None else (None, None)
    y = x @ p["w_in_x"]
    gate = jax.nn.gelu((x @ p["w_in_gate"]).astype(jnp.float32))
    y, new_tail = _conv1d(p, y, tail)
    h, h_last = rglru_scan(p, y, cfg, h0=h0)
    out = (h.astype(jnp.float32) * gate).astype(x.dtype)
    return out @ p["w_out"], (h_last, new_tail)
