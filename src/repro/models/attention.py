"""GQA and latent (MLA) attention: full/sliding-window masks, logit softcap,
cross-attention, and decode with an updatable KV cache (GQA only).

MLA (DeepSeek-V2/V3) projects q from x, and k and v from a normed latent
of ``kv_lora_rank`` (scope ``latent``); q and k carry ``qk_nope_head_dim``
plain and ``qk_rope_head_dim`` rotary columns, the rotary key one for all
heads, and v has ``v_head_dim``.  From there it runs the same core as GQA,
with one kv head per q head.

On a TPU, causal and sliding-window self-attention over a sequence that is a
multiple of 128 runs its score-softmax-PV core on JAX's fused Pallas kernel
(splash attention, forward and backward): the (S, S) scores never reach HBM.
Everything else (cross-attention, the unmasked encoder, decode, ragged
lengths, other backends) takes the jnp path ``_sdpa``, which materialises
the scores in f32; the tests hold the kernel to it.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu import splash_attention as splash
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.models.layers import (LP, apply_rope, dense_init, rms_norm,
                                 softcap, zeros_init)
from repro.sharding import MeshAxes


def init_attention(key, cfg: ModelConfig, dtype=jnp.bfloat16):
    if cfg.attn_type == "mla":
        return init_mla(key, cfg, dtype)
    kq, kk, kv, ko = jax.random.split(key, 4)
    d, h, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return {
        "w_q": dense_init(kq, (d, h, hd), ("embed", "heads", "head_dim"), dtype=dtype),
        "w_k": dense_init(kk, (d, hkv, hd), ("embed", "kv_heads", "head_dim"), dtype=dtype),
        "w_v": dense_init(kv, (d, hkv, hd), ("embed", "kv_heads", "head_dim"), dtype=dtype),
        "w_o": dense_init(ko, (h, hd, d), ("heads", "head_dim", "embed"),
                          in_axis=(0, 1), dtype=dtype),
    }


def init_mla(key, cfg: ModelConfig, dtype=jnp.bfloat16):
    kq, ka, kb, ko = jax.random.split(key, 4)
    d, h, r = cfg.d_model, cfg.num_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return {
        "w_q": dense_init(kq, (d, h, nope + rope),
                          ("embed", "heads", "head_dim"), dtype=dtype),
        "w_kv_a": dense_init(ka, (d, r + rope), ("embed", "lora"),
                             dtype=dtype),
        "kv_norm": zeros_init((r,), ("lora",), dtype=jnp.float32),
        "w_kv_b": dense_init(kb, (r, h, nope + vd),
                             ("lora", "heads", "head_dim"), dtype=dtype),
        "w_o": dense_init(ko, (h, vd, d), ("heads", "head_dim", "embed"),
                          in_axis=(0, 1), dtype=dtype),
    }


def mla_qkv(params, x, positions, cfg: ModelConfig):
    """q, k: (B,S,H,nope+rope), v: (B,S,H,v_head_dim) of latent attention.
    The rotary key is one per position, broadcast to every head."""
    nope, r = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    q = jnp.einsum("bsd,dhe->bshe", x, params["w_q"])
    q = jnp.concatenate(
        [q[..., :nope], apply_rope(q[..., nope:], positions, cfg.rope_theta)],
        -1)
    with jax.named_scope("latent"):
        ckv = jnp.einsum("bsd,dr->bsr", x, params["w_kv_a"])
        c = rms_norm(ckv[..., :r], params["kv_norm"], cfg.norm_eps)
        k_pe = apply_rope(ckv[..., None, r:], positions, cfg.rope_theta)
        kv = jnp.einsum("bsr,rhe->bshe", c, params["w_kv_b"])
        h = kv.shape[2]
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_pe, k_pe.shape[:2] + (h, k_pe.shape[-1]))],
            -1)
        v = kv[..., nope:]
    return q, k, v


def _mask_bias(q_pos, k_pos, kind: str, window: int):
    """(q, k) additive mask bias in f32.  q_pos: (...,Sq), k_pos: (...,Sk)."""
    q = q_pos[..., :, None]
    k = k_pos[..., None, :]
    if kind == "causal":
        ok = k <= q
    elif kind == "local":
        ok = (k <= q) & (k > q - window)
    elif kind == "none":
        ok = jnp.ones(jnp.broadcast_shapes(q.shape, k.shape), bool)
    else:
        raise ValueError(kind)
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)


def _sdpa(q, k, v, bias, logit_cap: float):
    """q, k: (B,Sq|Sk,H|Hkv,hd)  v: (B,Sk,Hkv,hd_v)  bias: broadcastable
    (B,1,Sq,Sk)."""
    b, sq, h, hd = q.shape
    hkv = k.shape[2]
    g = h // hkv
    q = q.reshape(b, sq, hkv, g, hd)
    scores = jnp.einsum("bqhgd,bkhd->bhgqk", q, k).astype(jnp.float32)
    scores = scores / jnp.sqrt(jnp.float32(hd))
    scores = softcap(scores, logit_cap)
    scores = scores + bias[:, :, None, :, :]
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)
    return out.reshape(b, sq, h, v.shape[-1])


# ------------------------------------------------------------ fused kernel
def use_fused_kernel(platform: str, sq: int, sk: int, mask_kind: str,
                     cross: bool) -> bool:
    """Whether attention over these inputs runs on the fused kernel: TPU
    self-attention, causal or sliding-window, over a multiple of 128."""
    return (platform == "tpu" and not cross and sq == sk and sq % 128 == 0
            and mask_kind in ("causal", "local"))


def _block(seq: int) -> int:
    """q and kv block of every kernel phase: the largest of 1024, 512, 256
    and 128 that divides the sequence (1024 was the fastest on a v5e at
    S=4096, head_dim 128; PERF.md)."""
    return next(b for b in (1024, 512, 256, 128) if seq % b == 0)


@functools.lru_cache(maxsize=32)
def _splash_kernel(seq: int, block: int, mask_kind: str, window: int,
                   logit_cap: float, group: int, interpret: bool):
    """The MQA kernel for one kv head's ``group`` q heads over ``seq``
    positions: its mask and block tables, built once per shape."""
    if mask_kind == "causal":
        mask = splash.CausalMask((seq, seq))
    else:   # key k is seen by query q iff q - window < k <= q, as _mask_bias
        mask = splash.LocalMask((seq, seq), window_size=(window - 1, 0),
                                offset=0)
    # one backward kernel computes dq, dk and dv together
    blocks = splash.BlockSizes(
        block_q=block, block_kv=block, block_kv_compute=block,
        block_q_dkv=block, block_kv_dkv=block, block_kv_dkv_compute=block,
        use_fused_bwd_kernel=True)
    # the block tables are numpy constants, never tracers of the caller's jit
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mqa_single_device(
            splash.MultiHeadMask([mask] * group), block_sizes=blocks,
            attn_logits_soft_cap=logit_cap if logit_cap > 0 else None,
            interpret=interpret)


def _splash_local(q, k, v, *, mask_kind: str, window: int, logit_cap: float,
                  interpret: bool):
    """Attention core of one device's shard.  q: (B,S,H,hd) bf16, already
    scaled; k: (B,S,Hkv,hd), v: (B,S,Hkv,hd_v).  q head h reads kv head
    h // G."""
    b, s, h, hd = q.shape
    hkv = k.shape[2]
    g = h // hkv
    kernel = _splash_kernel(s, _block(s), mask_kind, window,
                            float(logit_cap), g, interpret)
    qt = q.reshape(b, s, hkv, g, hd).transpose(0, 2, 3, 1, 4)  # (B,Hkv,G,S,hd)
    kt = k.transpose(0, 2, 1, 3)                                # (B,Hkv,S,hd)
    vt = v.transpose(0, 2, 1, 3)
    out = jax.vmap(jax.vmap(kernel))(qt, kt, vt)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, s, h, v.shape[-1])


def _splash(q, k, v, cfg: ModelConfig, mask_kind: str, mesh: Optional[Mesh],
            axes: Optional[MeshAxes], interpret: bool):
    """The fused core over (B,S,H,hd) q and (B,S,Hkv,hd) k, v, or None
    where a mesh of several devices cannot split the inputs by batch and
    heads.  The 1/sqrt(hd) scale is applied to q in f32, then q is cast
    back to its dtype; the kernel accumulates and keeps its softmax
    statistics in f32."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    fn = functools.partial(_splash_local, mask_kind=mask_kind,
                           window=cfg.window_size,
                           logit_cap=cfg.logit_softcap, interpret=interpret)
    if mesh is None or mesh.size == 1:
        return fn(q, k, v)
    # a pallas_call is not partitioned by the compiler: run it per shard
    n_batch = int(np.prod([mesh.shape[a] for a in axes.batch]))
    n_model = mesh.shape[axes.model]
    if q.shape[0] % n_batch or q.shape[2] % n_model or k.shape[2] % n_model:
        return None
    bspec = axes.batch if len(axes.batch) > 1 else axes.batch[0]
    spec = P(bspec, None, axes.model, None)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def _platform(mesh: Optional[Mesh]) -> str:
    if mesh is not None:
        return mesh.devices.flat[0].platform
    return jax.default_backend()


def attention_forward_kv(params, x, cfg: ModelConfig, *, mask_kind: str,
                         positions, kv_x=None, kv_positions=None,
                         mesh: Optional[Mesh] = None,
                         axes: Optional[MeshAxes] = None,
                         interpret: bool = False):
    """Training/prefill attention.  ``kv_x`` set => cross-attention.

    ``mesh``/``axes`` are the activations' mesh, for the fused kernel's
    per-shard call.  ``interpret`` runs the fused kernel in the Pallas
    interpreter whatever the backend (tests on the CPU).  The kernel masks
    by index: it takes ``positions`` to be 0..S-1, as every caller passes.

    Returns (out, k, v) so prefill can populate the KV cache for free.
    """
    with jax.named_scope("attn"):
        kv_in = x if kv_x is None else kv_x
        if cfg.attn_type == "mla":
            q, k, v = mla_qkv(params, x, positions, cfg)
            kv_pos = positions
        else:
            q = jnp.einsum("bsd,dhe->bshe", x, params["w_q"])
            k = jnp.einsum("bsd,dhe->bshe", kv_in, params["w_k"])
            v = jnp.einsum("bsd,dhe->bshe", kv_in, params["w_v"])
            if kv_x is None:  # self-attention -> RoPE
                q = apply_rope(q, positions, cfg.rope_theta)
                k = apply_rope(k, positions, cfg.rope_theta)
                kv_pos = positions
            else:
                kv_pos = kv_positions
        with jax.named_scope("core"):
            out = None
            platform = "tpu" if interpret else _platform(mesh)
            if use_fused_kernel(platform, q.shape[1], k.shape[1], mask_kind,
                                kv_x is not None):
                out = _splash(q, k, v, cfg, mask_kind, mesh, axes, interpret)
            if out is None:
                bias = _mask_bias(positions, kv_pos, mask_kind,
                                  cfg.window_size)[:, None]
                out = _sdpa(q, k, v, bias, cfg.logit_softcap)
        return jnp.einsum("bshe,hed->bsd", out, params["w_o"]), k, v


def attention_forward(params, x, cfg: ModelConfig, *, mask_kind: str,
                      positions, kv_x=None, kv_positions=None):
    out, _, _ = attention_forward_kv(params, x, cfg, mask_kind=mask_kind,
                                     positions=positions, kv_x=kv_x,
                                     kv_positions=kv_positions)
    return out


# ------------------------------------------------------------------- decode
def init_kv_cache(cfg: ModelConfig, num_layers: int, batch: int, max_len: int,
                  dtype=jnp.bfloat16):
    shape = (num_layers, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    return {
        "k": jnp.zeros(shape, dtype),
        "v": jnp.zeros(shape, dtype),
    }


def kv_cache_spec(cfg: ModelConfig, num_layers: int, batch: int, max_len: int,
                  dtype=jnp.bfloat16):
    shape = (num_layers, batch, max_len, cfg.num_kv_heads, cfg.head_dim)
    return {
        "k": jax.ShapeDtypeStruct(shape, dtype),
        "v": jax.ShapeDtypeStruct(shape, dtype),
    }


def attention_decode(params, x, cache_k, cache_v, pos, cfg: ModelConfig, *,
                     mask_kind: str, cross: bool = False, ring: bool = False):
    """One-token decode.  x: (B,1,d); cache_{k,v}: (B,S,Hkv,hd); pos: scalar.

    For ``cross=True`` the caches hold precomputed encoder K/V and are not
    updated; ``pos`` masks nothing (full visibility).

    ``ring=True`` (local_attn + cfg.window_kv_cache, §Perf): the cache holds
    only ``window`` slots; position p lives in slot p % window.  K is stored
    with RoPE already applied at its true position, so ring indexing only
    changes the masking: slot s currently holds position
    pos - ((pos - s) mod window), masked out while still negative.

    Returns (out, new_cache_k, new_cache_v).
    """
    with jax.named_scope("attn"):
        b = x.shape[0]
        s_max = cache_k.shape[1]
        q = jnp.einsum("bsd,dhe->bshe", x, params["w_q"])
        if not cross:
            k_new = jnp.einsum("bsd,dhe->bshe", x, params["w_k"])
            v_new = jnp.einsum("bsd,dhe->bshe", x, params["w_v"])
            q = apply_rope(q, jnp.full((b, 1), pos), cfg.rope_theta)
            k_new = apply_rope(k_new, jnp.full((b, 1), pos), cfg.rope_theta)
            write_at = jnp.mod(pos, s_max) if ring else pos
            cache_k = jax.lax.dynamic_update_slice_in_dim(
                cache_k, k_new.astype(cache_k.dtype), write_at, axis=1)
            cache_v = jax.lax.dynamic_update_slice_in_dim(
                cache_v, v_new.astype(cache_v.dtype), write_at, axis=1)
        if cross:
            bias = jnp.zeros((b, 1, 1, s_max), jnp.float32)
        elif ring:
            slots = jnp.arange(s_max)[None, :]
            k_pos = pos - jnp.mod(pos - slots, s_max)  # true position of a slot
            ok = k_pos >= 0
            bias = jnp.where(ok, 0.0,
                             -1e30).astype(jnp.float32)[:, None, None, :]
            bias = jnp.broadcast_to(bias, (b, 1, 1, s_max))
        else:
            q_pos = jnp.full((b, 1), pos)
            k_pos = jnp.arange(s_max)[None, :]
            bias = _mask_bias(q_pos, k_pos,
                              "local" if mask_kind == "local" else "causal",
                              cfg.window_size)[:, None]
        out = _sdpa(q, cache_k, cache_v, bias, cfg.logit_softcap)
        out = jnp.einsum("bshe,hed->bsd", out, params["w_o"])
        return out, cache_k, cache_v
