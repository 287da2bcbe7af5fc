"""RWKV6 ("Finch") block: data-dependent token-shift + WKV6 recurrence with
per-channel data-dependent decay, plus squared-ReLU channel mix.
[arXiv:2404.05892]

Training uses a chunked form (lax.scan over chunks; within-chunk pairwise
contraction in f32 log-decay space) so the HLO stays compact and stable; the
Pallas kernel (repro.kernels.rwkv6) mirrors the same chunking for TPU.  Decode
is the O(1)-state recurrence — the "KV cache" of this family is a constant
(B, H, hd, hd) state regardless of sequence length, which is why rwkv6 runs
the long_500k cell.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import LP, dense_init, group_norm, zeros_init

MIX_LORA = 32
DECAY_LORA = 64
_MIX_NAMES = ("r", "k", "v", "w", "g")


def init_time_mix(key, cfg: ModelConfig, dtype=jnp.bfloat16):
    d = cfg.d_model
    h = d // cfg.rwkv_head_dim
    hd = cfg.rwkv_head_dim
    ks = jax.random.split(key, 12)
    return {
        "mu_x": zeros_init((d,), ("embed",), dtype=jnp.float32),
        "mu": zeros_init((5, d), (None, "embed"), dtype=jnp.float32),
        "mix_a": dense_init(ks[0], (d, 5 * MIX_LORA), ("embed", "lora"),
                            scale=0.1, dtype=jnp.float32),
        "mix_b": zeros_init((5, MIX_LORA, d), (None, "lora", "embed"),
                            dtype=jnp.float32),
        "w0": LP(jnp.full((h, hd), -6.0, jnp.float32), ("rnn", "head_dim")),
        "w_a": dense_init(ks[1], (d, DECAY_LORA), ("embed", "lora"),
                          scale=0.1, dtype=jnp.float32),
        "w_b": zeros_init((DECAY_LORA, d), ("lora", "embed"), dtype=jnp.float32),
        "u": zeros_init((h, hd), ("rnn", "head_dim"), dtype=jnp.float32),
        "w_r": dense_init(ks[2], (d, d), ("embed", "rnn"), dtype=dtype),
        "w_k": dense_init(ks[3], (d, d), ("embed", "rnn"), dtype=dtype),
        "w_v": dense_init(ks[4], (d, d), ("embed", "rnn"), dtype=dtype),
        "w_g": dense_init(ks[5], (d, d), ("embed", "rnn"), dtype=dtype),
        "w_o": dense_init(ks[6], (d, d), ("rnn", "embed"), dtype=dtype),
        "ln_w": LP(jnp.ones((d,), jnp.float32), ("rnn",)),
        "ln_b": zeros_init((d,), ("rnn",), dtype=jnp.float32),
    }


def init_channel_mix(key, cfg: ModelConfig, dtype=jnp.bfloat16):
    d, f = cfg.d_model, cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "mu_k": zeros_init((d,), ("embed",), dtype=jnp.float32),
        "mu_r": zeros_init((d,), ("embed",), dtype=jnp.float32),
        "w_k": dense_init(k1, (d, f), ("embed", "mlp"), dtype=dtype),
        "w_v": dense_init(k2, (f, d), ("mlp", "embed"), dtype=dtype),
        "w_r": dense_init(k3, (d, d), ("embed", "embed"), dtype=dtype),
    }


def _token_shift(x, prev=None):
    """Shift sequence right by one; ``prev`` (B, d) fills slot 0 (decode carry)."""
    if prev is None:
        pad = jnp.zeros_like(x[:, :1])
    else:
        pad = prev[:, None, :].astype(x.dtype)
    return jnp.concatenate([pad, x[:, :-1]], axis=1)


def _ddlerp(p, x, shifted):
    """RWKV6 data-dependent interpolation -> (5, B, S, d) mixed inputs."""
    dx = (shifted - x).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    xxx = xf + dx * p["mu_x"]
    lora = jnp.tanh(xxx @ p["mix_a"])  # (B,S,5*r)
    b, s, _ = lora.shape
    lora = lora.reshape(b, s, 5, MIX_LORA)
    delta = jnp.einsum("bsnr,nrd->nbsd", lora, p["mix_b"])
    mixed = xf[None] + dx[None] * (p["mu"][:, None, None, :] + delta)
    return mixed  # f32


def _projections(p, x, shifted, cfg: ModelConfig):
    mixed = _ddlerp(p, x, shifted)
    xr, xk, xv, xw, xg = [mixed[i].astype(x.dtype) for i in range(5)]
    b, s, d = x.shape
    h, hd = d // cfg.rwkv_head_dim, cfg.rwkv_head_dim
    r = (xr @ p["w_r"]).reshape(b, s, h, hd)
    k = (xk @ p["w_k"]).reshape(b, s, h, hd)
    v = (xv @ p["w_v"]).reshape(b, s, h, hd)
    g = jax.nn.silu(xg @ p["w_g"])
    # data-dependent log-decay, guaranteed < 0 (w = exp(-exp(z)))
    z = p["w0"].reshape(-1) + (jnp.tanh(xw @ p["w_a"]) @ p["w_b"])
    log_w = -jnp.exp(jnp.clip(z, -20.0, 8.0)).reshape(b, s, h, hd)
    return r, k, v, g, log_w


def _pick_chunk(s: int, target: int) -> int:
    """Largest divisor of s that is <= target (sequence lengths are usually
    powers of two; odd prompt lengths degrade gracefully)."""
    c = min(target, s)
    while s % c:
        c -= 1
    return max(c, 1)


def wkv6_chunked(r, k, v, log_w, u, chunk: int = 16):
    """Chunked WKV6.  r,k,v,log_w: (B,S,H,hd) — returns (B,S,H,hd), final state.

    Within a chunk all decay factors appear as exp(non-positive) ratios, so the
    computation is stable in f32 without log-space matmuls.
    """
    b, s, h, hd = r.shape
    chunk = _pick_chunk(s, chunk)
    nc = s // chunk
    rf = r.astype(jnp.float32).reshape(b, nc, chunk, h, hd)
    kf = k.astype(jnp.float32).reshape(b, nc, chunk, h, hd)
    vf = v.astype(jnp.float32).reshape(b, nc, chunk, h, hd)
    lw = log_w.astype(jnp.float32).reshape(b, nc, chunk, h, hd)

    state0 = jnp.zeros((b, h, hd, hd), jnp.float32)

    def step(state, inputs):
        rc, kc, vc, lwc = inputs  # (B, c, H, hd)
        cs = jnp.cumsum(lwc, axis=1)            # inclusive (B,c,H,hd)
        cse = cs - lwc                          # exclusive
        # inter-chunk: y1[t] = (r_t * exp(cse_t)) @ state
        q1 = rc * jnp.exp(cse)
        y1 = jnp.einsum("bthk,bhkv->bthv", q1, state)
        # intra-chunk: pair[t,s,i] = r_t[i] k_s[i] exp(cse_t - cs_s), s<t
        ratio = cse[:, :, None] - cs[:, None, :]          # (B,t,s,H,hd)
        tri = jnp.tril(jnp.ones((chunk, chunk), jnp.float32), -1)
        pair = rc[:, :, None] * kc[:, None, :] * jnp.exp(
            jnp.minimum(ratio, 0.0))
        scores = pair.sum(-1) * tri[None, :, :, None]     # (B,t,s,H)
        y2 = jnp.einsum("btsh,bshv->bthv", scores, vc)
        # diagonal (current-token bonus u)
        diag = (rc * u[None, None] * kc).sum(-1, keepdims=True) * vc
        # state update
        decay_to_end = jnp.exp(cs[:, -1:] - cs)           # (B,c,H,hd)
        new_state = state * jnp.exp(cs[:, -1])[:, :, :, None] + jnp.einsum(
            "bshk,bshv->bhkv", kc * decay_to_end, vc)
        return new_state, y1 + y2 + diag

    inputs = tuple(jnp.moveaxis(t, 1, 0) for t in (rf, kf, vf, lw))
    state, y = jax.lax.scan(step, state0, inputs)
    y = jnp.moveaxis(y, 0, 1).reshape(b, s, h, hd)
    return y, state


def wkv6_step(state, r, k, v, log_w, u):
    """O(1) decode step.  state: (B,H,hd,hd); r,k,v,log_w: (B,H,hd)."""
    sf = state
    rf, kf, vf = (t.astype(jnp.float32) for t in (r, k, v))
    # y[j] = sum_i r_i (S[i,j] + u_i k_i v_j)
    y = jnp.einsum("bhk,bhkv->bhv", rf, sf) + (
        (rf * u[None] * kf).sum(-1, keepdims=True) * vf)
    new_state = sf * jnp.exp(log_w.astype(jnp.float32))[..., None] + (
        kf[..., :, None] * vf[..., None, :])
    return new_state, y


def time_mix_forward(p, x, cfg: ModelConfig, chunk: int = 16):
    """Training/prefill path.  x: (B,S,d) -> (B,S,d), final (state, last_x)."""
    b, s, d = x.shape
    h, hd = d // cfg.rwkv_head_dim, cfg.rwkv_head_dim
    shifted = _token_shift(x)
    r, k, v, g, log_w = _projections(p, x, shifted, cfg)
    y, state = wkv6_chunked(r, k, v, log_w, p["u"], chunk=chunk)
    y = y.reshape(b, s, d)
    y = group_norm(y.astype(x.dtype), p["ln_w"], p["ln_b"], num_groups=h)
    y = (y.astype(jnp.float32) * g).astype(x.dtype)
    return y @ p["w_o"], (state, x[:, -1, :])


def time_mix_step(p, x, state, prev_x, cfg: ModelConfig):
    """Decode step.  x: (B,1,d); state: (B,H,hd,hd); prev_x: (B,d)."""
    b, _, d = x.shape
    h = d // cfg.rwkv_head_dim
    shifted = _token_shift(x, prev=prev_x)
    r, k, v, g, log_w = _projections(p, x, shifted, cfg)
    new_state, y = wkv6_step(state, r[:, 0], k[:, 0], v[:, 0], log_w[:, 0],
                             p["u"])
    y = y.reshape(b, 1, d)
    y = group_norm(y.astype(x.dtype), p["ln_w"], p["ln_b"], num_groups=h)
    y = (y.astype(jnp.float32) * g).astype(x.dtype)
    return y @ p["w_o"], (new_state, x[:, -1, :])


def channel_mix_forward(p, x, prev_x=None):
    """Squared-relu channel mix.  Returns (out, last_x carry)."""
    shifted = _token_shift(x, prev=prev_x)
    dx = (shifted - x).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    xk = (xf + dx * p["mu_k"]).astype(x.dtype)
    xr = (xf + dx * p["mu_r"]).astype(x.dtype)
    kk = jnp.square(jax.nn.relu(xk @ p["w_k"]))
    out = jax.nn.sigmoid((xr @ p["w_r"]).astype(jnp.float32)).astype(x.dtype) \
        * (kk @ p["w_v"])
    return out, x[:, -1, :]
