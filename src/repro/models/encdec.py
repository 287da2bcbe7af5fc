"""Encoder-decoder assembly (whisper-large-v3 backbone).

The audio frontend is a STUB per the assignment: the batch carries
precomputed frame embeddings (B, S_enc, d_model); the encoder is a
bidirectional transformer stack over frames; the decoder is a causal stack
with cross-attention.  ``seq_len`` of a shape cell = encoder frame count;
decoder length = min(448, seq_len // 8) (whisper's 448-token label budget).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models.layers import init_mlp, mlp_forward, rms_norm, zeros_init
from repro.models.transformer import (Ctx, dense_init, embed_tokens,
                                      masked_cross_entropy, stack_periods,
                                      unembed)
from repro.sharding import MeshAxes


def decoder_len(cfg: ModelConfig, seq_len: int) -> int:
    return max(8, min(448, seq_len // 8))


def init_enc_block(key, cfg: ModelConfig, dtype=jnp.bfloat16):
    k1, k2 = jax.random.split(key)
    return {
        "norm_attn": zeros_init((cfg.d_model,), ("embed",), dtype=jnp.float32),
        "attn": attn.init_attention(k1, cfg, dtype=dtype),
        "norm_mlp": zeros_init((cfg.d_model,), ("embed",), dtype=jnp.float32),
        "mlp": init_mlp(k2, cfg.d_model, cfg.d_ff, dtype=dtype),
    }


def init_dec_block(key, cfg: ModelConfig, dtype=jnp.bfloat16):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "norm_self": zeros_init((cfg.d_model,), ("embed",), dtype=jnp.float32),
        "self_attn": attn.init_attention(k1, cfg, dtype=dtype),
        "norm_cross": zeros_init((cfg.d_model,), ("embed",), dtype=jnp.float32),
        "cross_attn": attn.init_attention(k2, cfg, dtype=dtype),
        "norm_mlp": zeros_init((cfg.d_model,), ("embed",), dtype=jnp.float32),
        "mlp": init_mlp(k3, cfg.d_model, cfg.d_ff, dtype=dtype),
    }


def init_encdec(key, cfg: ModelConfig, dtype=jnp.bfloat16):
    keys = jax.random.split(key, 4)
    ekeys = jax.random.split(keys[0], cfg.num_layers)
    dkeys = jax.random.split(keys[1], cfg.num_decoder_layers)
    return {
        "embed": dense_init(keys[2], (cfg.vocab_size, cfg.d_model),
                            ("vocab", "embed"), in_axis=1, dtype=dtype),
        "enc_scan": stack_periods(
            [{"b0": init_enc_block(k, cfg, dtype)} for k in ekeys]),
        "enc_norm": zeros_init((cfg.d_model,), ("embed",), dtype=jnp.float32),
        "dec_scan": stack_periods(
            [{"b0": init_dec_block(k, cfg, dtype)} for k in dkeys]),
        "final_norm": zeros_init((cfg.d_model,), ("embed",), dtype=jnp.float32),
        "lm_head": dense_init(keys[3], (cfg.d_model, cfg.vocab_size),
                              ("embed", "vocab"), dtype=dtype),
    }


def run_encoder(params, audio_embed, cfg: ModelConfig, ctx: Ctx):
    x = ctx.bconstrain(audio_embed)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))

    def period_fn(x, p):
        blk = p["b0"]
        h = rms_norm(x, blk["norm_attn"], cfg.norm_eps)
        a = attn.attention_forward(blk["attn"], h, cfg, mask_kind="none",
                                   positions=positions)
        x = x + a
        h = rms_norm(x, blk["norm_mlp"], cfg.norm_eps)
        x = ctx.bconstrain(x + mlp_forward(blk["mlp"], h, cfg.act))
        return x, None

    x, _ = jax.lax.scan(jax.checkpoint(period_fn), x, params["enc_scan"])
    return rms_norm(x, params["enc_norm"], cfg.norm_eps)


def run_decoder(params, tokens, enc_out, cfg: ModelConfig, ctx: Ctx,
                collect_cache: bool = False):
    x = embed_tokens(params, tokens, cfg)
    x = ctx.bconstrain(x)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    b_e, s_e, _ = enc_out.shape
    kv_positions = jnp.broadcast_to(jnp.arange(s_e)[None, :], (b_e, s_e))

    def period_fn(x, p):
        blk = p["b0"]
        h = rms_norm(x, blk["norm_self"], cfg.norm_eps)
        a, sk, sv = attn.attention_forward_kv(
            blk["self_attn"], h, cfg, mask_kind="causal", positions=positions,
            mesh=ctx.mesh, axes=ctx.axes)
        x = x + a
        h = rms_norm(x, blk["norm_cross"], cfg.norm_eps)
        a, ck, cv = attn.attention_forward_kv(
            blk["cross_attn"], h, cfg, mask_kind="none", positions=positions,
            kv_x=enc_out, kv_positions=kv_positions)
        x = x + a
        h = rms_norm(x, blk["norm_mlp"], cfg.norm_eps)
        x = ctx.bconstrain(x + mlp_forward(blk["mlp"], h, cfg.act))
        cache = ({"sk": sk, "sv": sv, "ck": ck, "cv": cv}
                 if collect_cache else None)
        return x, cache

    x, caches = jax.lax.scan(jax.checkpoint(period_fn), x,
                             params["dec_scan"])
    return rms_norm(x, params["final_norm"], cfg.norm_eps), caches


def encdec_loss(params, batch, cfg: ModelConfig, mesh: Mesh, axes: MeshAxes):
    ctx = Ctx(cfg, mesh, axes)
    enc_out = run_encoder(params, batch["audio_embed"], cfg, ctx)
    x, _ = run_decoder(params, batch["tokens"], enc_out, cfg, ctx)
    loss, denom = masked_cross_entropy(params, x, batch["targets"], cfg, ctx)
    return loss, {"ce_loss": loss, "tokens": denom}


def encdec_prefill(params, batch, cfg: ModelConfig, mesh: Mesh, axes: MeshAxes):
    ctx = Ctx(cfg, mesh, axes)
    enc_out = run_encoder(params, batch["audio_embed"], cfg, ctx)
    x, caches = run_decoder(params, batch["tokens"], enc_out, cfg, ctx,
                            collect_cache=True)
    logits = unembed(params, x[:, -1:], cfg)
    return caches, logits


def encdec_decode(params, caches, token, pos, cfg: ModelConfig, mesh: Mesh,
                  axes: MeshAxes):
    """token: (B,1).  caches: stacked {'sk','sv','ck','cv'} over layers."""
    ctx = Ctx(cfg, mesh, axes)
    x = embed_tokens(params, token, cfg)

    def body(x, scanned):
        p, cache = scanned
        blk = p["b0"]
        h = rms_norm(x, blk["norm_self"], cfg.norm_eps)
        a, sk, sv = attn.attention_decode(blk["self_attn"], h, cache["sk"],
                                          cache["sv"], pos, cfg,
                                          mask_kind="causal")
        x = x + a
        h = rms_norm(x, blk["norm_cross"], cfg.norm_eps)
        a, _, _ = attn.attention_decode(blk["cross_attn"], h, cache["ck"],
                                        cache["cv"], pos, cfg,
                                        mask_kind="none", cross=True)
        x = x + a
        h = rms_norm(x, blk["norm_mlp"], cfg.norm_eps)
        x = x + mlp_forward(blk["mlp"], h, cfg.act)
        return x, {"sk": sk, "sv": sv, "ck": cache["ck"], "cv": cache["cv"]}

    x, new_caches = jax.lax.scan(body, x, (params["dec_scan"], caches))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return new_caches, unembed(params, x, cfg)
