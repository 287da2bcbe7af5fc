"""Decoder-only LM assembly shared by all assigned architectures.

Layer stacks are ``jax.lax.scan``s over stacked period params (period = the
repeating ``block_pattern``; gemma2 = (local, full), recurrentgemma =
(rglru, rglru, local)); leading dense layers (``first_dense_layers``,
"lead") run before the scan and layers beyond the last full period after it
("tail"), both unrolled.  This keeps HLO size, and with it compile time,
O(1) in depth.  Every period runs under ``jax.checkpoint``: the backward
pass recomputes its activations.

Three paths per architecture: ``lm_loss`` (training), ``lm_prefill`` and
``lm_decode`` (serving with per-family state: KV cache for attention blocks,
(B,H,hd,hd) WKV state for rwkv6, (h, conv-tail) for rglru).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import (BLOCK_ATTN, BLOCK_LOCAL, BLOCK_MOE, BLOCK_REC,
                                BLOCK_RWKV, ModelConfig)
from repro.models import attention as attn
from repro.models import moe as moe_lib
from repro.models import rglru as rglru_lib
from repro.models import rwkv6 as rwkv_lib
from repro.models.layers import (LP, dense_init, init_mlp, is_lp, mlp_forward,
                                 rms_norm, softcap, zeros_init)
from repro.sharding import MeshAxes, constrain


@dataclasses.dataclass
class Ctx:
    cfg: ModelConfig
    mesh: Mesh
    axes: MeshAxes

    @property
    def bspec(self):
        return self.axes.batch if len(self.axes.batch) > 1 else self.axes.batch[0]

    def bconstrain(self, x):
        """Constrain (B, S, d) activations: batch-sharded, rest replicated."""
        return constrain(x, self.mesh, P(self.bspec, *([None] * (x.ndim - 1))))


# ---------------------------------------------------------------------- init
def init_block(key, kind: str, cfg: ModelConfig, dtype=jnp.bfloat16):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    p: Dict[str, Any] = {
        "norm_attn": zeros_init((cfg.d_model,), ("embed",), dtype=jnp.float32),
        "norm_mlp": zeros_init((cfg.d_model,), ("embed",), dtype=jnp.float32),
    }
    if kind in (BLOCK_ATTN, BLOCK_LOCAL, BLOCK_MOE):
        p["attn"] = attn.init_attention(k1, cfg, dtype=dtype)
    if kind == BLOCK_MOE:
        p["moe"] = moe_lib.init_moe(k2, cfg, dtype=dtype)
    elif kind == BLOCK_RWKV:
        p["time_mix"] = rwkv_lib.init_time_mix(k1, cfg, dtype=dtype)
        p["channel_mix"] = rwkv_lib.init_channel_mix(k2, cfg, dtype=dtype)
    elif kind == BLOCK_REC:
        p["rec"] = rglru_lib.init_rglru_block(k3, cfg, dtype=dtype)
        p["mlp"] = init_mlp(k4, cfg.d_model, cfg.d_ff, dtype=dtype)
    else:
        p["mlp"] = init_mlp(k4, cfg.d_model, cfg.d_ff, dtype=dtype)
    return p


def init_period(key, kinds, cfg: ModelConfig, dtype=jnp.bfloat16):
    keys = jax.random.split(key, len(kinds))
    return {f"b{i}": init_block(k, kind, cfg, dtype=dtype)
            for i, (k, kind) in enumerate(zip(keys, kinds))}


def stack_periods(trees):
    """List of per-period LP trees -> single tree with leading 'layers' axis."""
    def stack_lp(*lps):
        vals = jnp.stack([p.value for p in lps])
        return LP(vals, ("layers",) + lps[0].axes)
    return jax.tree.map(stack_lp, *trees, is_leaf=is_lp)


def split_layers(cfg: ModelConfig, num_layers: Optional[int] = None):
    """(periods scanned, kinds of the tail) of the layers after the lead."""
    n = num_layers if num_layers is not None else cfg.num_layers
    lead = min(cfg.first_dense_layers, n)
    period = cfg.pattern_period
    n_periods = (n - lead) // period
    tail_kinds = cfg.layer_kinds(n)[lead + n_periods * period:]
    return n_periods, tuple(tail_kinds)


def lead_kinds(cfg: ModelConfig) -> Tuple[str, ...]:
    return cfg.layer_kinds()[:min(cfg.first_dense_layers, cfg.num_layers)]


def init_lm(key, cfg: ModelConfig, dtype=jnp.bfloat16):
    """Full LM param LP-tree (decoder-only archs)."""
    keys = jax.random.split(key, 8)
    n_periods, tail_kinds = split_layers(cfg)
    period_keys = jax.random.split(keys[0], n_periods)
    params: Dict[str, Any] = {
        "embed": dense_init(keys[1], (cfg.vocab_size, cfg.d_model),
                            ("vocab", "embed"), in_axis=1, scale=1.0,
                            dtype=dtype),
        "scan": stack_periods([
            init_period(k, cfg.block_pattern, cfg, dtype) for k in period_keys]),
        "final_norm": zeros_init((cfg.d_model,), ("embed",), dtype=jnp.float32),
    }
    if lead_kinds(cfg):
        lkeys = jax.random.split(keys[4], len(lead_kinds(cfg)))
        params["lead"] = {f"l{i}": init_block(k, kind, cfg, dtype)
                          for i, (k, kind) in enumerate(zip(lkeys,
                                                            lead_kinds(cfg)))}
    if tail_kinds:
        tkeys = jax.random.split(keys[2], len(tail_kinds))
        params["tail"] = {f"t{i}": init_block(k, kind, cfg, dtype)
                          for i, (k, kind) in enumerate(zip(tkeys, tail_kinds))}
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(keys[3], (cfg.d_model, cfg.vocab_size),
                                       ("embed", "vocab"), dtype=dtype)
    return params


# ------------------------------------------------------------------- forward
def block_train(p, kind: str, x, positions, ctx: Ctx, return_kv=False):
    """One block, full-sequence.  Returns (x, stats, kv_or_None)."""
    cfg = ctx.cfg
    stats = {}
    kv = None
    if kind in (BLOCK_ATTN, BLOCK_LOCAL, BLOCK_MOE):
        mask_kind = "local" if kind == BLOCK_LOCAL else "causal"
        h = rms_norm(x, p["norm_attn"], cfg.norm_eps)
        a, k_c, v_c = attn.attention_forward_kv(
            p["attn"], h, cfg, mask_kind=mask_kind, positions=positions,
            mesh=ctx.mesh, axes=ctx.axes)
        if return_kv:
            kv = (k_c, v_c)
        x = x + a
        h = rms_norm(x, p["norm_mlp"], cfg.norm_eps)
        if kind == BLOCK_MOE:
            y, stats = moe_lib.moe_forward(p["moe"], h, cfg, ctx.mesh, ctx.axes,
                                           cfg.act)
        else:
            y = mlp_forward(p["mlp"], h, cfg.act)
        x = x + y
    elif kind == BLOCK_RWKV:
        h = rms_norm(x, p["norm_attn"], cfg.norm_eps)
        y, (wkv_state, tm_last) = rwkv_lib.time_mix_forward(p["time_mix"], h, cfg)
        x = x + y
        h = rms_norm(x, p["norm_mlp"], cfg.norm_eps)
        y, cm_last = rwkv_lib.channel_mix_forward(p["channel_mix"], h)
        x = x + y
        if return_kv:
            kv = (wkv_state, tm_last, cm_last)
    elif kind == BLOCK_REC:
        h = rms_norm(x, p["norm_attn"], cfg.norm_eps)
        y, (h_last, conv_tail) = rglru_lib.rglru_block_forward(p["rec"], h, cfg)
        x = x + y
        h = rms_norm(x, p["norm_mlp"], cfg.norm_eps)
        x = x + mlp_forward(p["mlp"], h, cfg.act)
        if return_kv:
            kv = (h_last, conv_tail)
    else:
        raise ValueError(kind)
    return ctx.bconstrain(x), stats, kv


def block_decode(p, kind: str, x, cache, pos, ctx: Ctx):
    """One block, one-token decode.  cache is the per-block state entry."""
    cfg = ctx.cfg
    if kind in (BLOCK_ATTN, BLOCK_LOCAL, BLOCK_MOE):
        mask_kind = "local" if kind == BLOCK_LOCAL else "causal"
        ring = kind == BLOCK_LOCAL and cfg.window_kv_cache
        h = rms_norm(x, p["norm_attn"], cfg.norm_eps)
        a, ck, cv = attn.attention_decode(p["attn"], h, cache["k"], cache["v"],
                                          pos, cfg, mask_kind=mask_kind,
                                          ring=ring)
        new_cache = {"k": ck, "v": cv}
        x = x + a
        h = rms_norm(x, p["norm_mlp"], cfg.norm_eps)
        if kind == BLOCK_MOE:
            y, _ = moe_lib.moe_forward(p["moe"], h, cfg, ctx.mesh, ctx.axes,
                                       cfg.act)
        else:
            y = mlp_forward(p["mlp"], h, cfg.act)
        x = x + y
    elif kind == BLOCK_RWKV:
        h = rms_norm(x, p["norm_attn"], cfg.norm_eps)
        y, (wkv, tm_last) = rwkv_lib.time_mix_step(
            p["time_mix"], h, cache["wkv"], cache["tm_shift"], cfg)
        x = x + y
        h = rms_norm(x, p["norm_mlp"], cfg.norm_eps)
        y, cm_last = rwkv_lib.channel_mix_forward(p["channel_mix"], h,
                                                  prev_x=cache["cm_shift"])
        x = x + y
        new_cache = {"wkv": wkv, "tm_shift": tm_last, "cm_shift": cm_last}
    elif kind == BLOCK_REC:
        h = rms_norm(x, p["norm_attn"], cfg.norm_eps)
        y, (h_last, tail) = rglru_lib.rglru_block_forward(
            p["rec"], h, cfg, state=(cache["h"], cache["conv"]))
        x = x + y
        h = rms_norm(x, p["norm_mlp"], cfg.norm_eps)
        x = x + mlp_forward(p["mlp"], h, cfg.act)
        new_cache = {"h": h_last, "conv": tail}
    else:
        raise ValueError(kind)
    return x, new_cache


def _merge_stats(stats_list):
    out: Dict[str, Any] = {}
    for st in stats_list:
        for k, v in st.items():
            out[k] = out[k] + v if k in out else v
    return out


def run_stack(params, x, positions, ctx: Ctx, kinds, tail_kinds,
              collect_cache: bool = False):
    """Unrolled lead + scan over periods + unrolled tail.  Returns (x,
    stats, caches)."""
    for i, kind in enumerate(lead_kinds(ctx.cfg)):
        x = jax.checkpoint(lambda x, p, kind=kind: block_train(
            p, kind, x, positions, ctx)[0])(x, params["lead"][f"l{i}"])

    def period_fn(x, p_period):
        stats, caches = [], {}
        for i, kind in enumerate(kinds):
            x, st, kv = block_train(p_period[f"b{i}"], kind, x, positions, ctx,
                                    return_kv=collect_cache)
            stats.append(st)
            if collect_cache:
                caches[f"b{i}"] = _pack_cache(kind, kv)
        return x, (_merge_stats(stats), caches)

    x, (stats, caches) = jax.lax.scan(jax.checkpoint(period_fn), x,
                                      params["scan"])
    # scan stacks stats over periods: total the aux loss, keep per-layer counts.
    if "aux_loss" in stats:
        stats = {"aux_loss": stats["aux_loss"].sum(),
                 "expert_counts": stats["expert_counts"]}
    tail_caches = {}
    for i, kind in enumerate(tail_kinds):
        x, st, kv = block_train(params["tail"][f"t{i}"], kind, x, positions,
                                ctx, return_kv=collect_cache)
        stats = _merge_stats([stats, st])
        if collect_cache:
            tail_caches[f"t{i}"] = _pack_cache(kind, kv)
    return x, stats, {"scan": caches, "tail": tail_caches}


def _pack_cache(kind: str, kv):
    if kind in (BLOCK_ATTN, BLOCK_LOCAL, BLOCK_MOE):
        return {"k": kv[0], "v": kv[1]}
    if kind == BLOCK_RWKV:
        return {"wkv": kv[0], "tm_shift": kv[1], "cm_shift": kv[2]}
    if kind == BLOCK_REC:
        return {"h": kv[0], "conv": kv[1]}
    raise ValueError(kind)


# ----------------------------------------------------------------- embedding
def embed_tokens(params, tokens, cfg: ModelConfig):
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
        if cfg.tie_embeddings:
            x = x * jnp.sqrt(jnp.float32(cfg.d_model)).astype(x.dtype)
        return x


def unembed(params, x, cfg: ModelConfig):
    table = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,dv->bsv", x, table).astype(jnp.float32)
    return softcap(logits, cfg.final_softcap)


def lm_inputs(params, batch, cfg: ModelConfig):
    """Token (+ stub-frontend media/audio) embedding -> (x, positions)."""
    tokens = batch["tokens"]
    x = embed_tokens(params, tokens, cfg)
    if cfg.frontend == "vision" and "media_embed" in batch:
        x = jnp.concatenate([batch["media_embed"].astype(x.dtype), x], axis=1)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    return x, positions


# -------------------------------------------------------------------- losses
def masked_cross_entropy(params, x, targets, cfg: ModelConfig, ctx: Ctx):
    """CE over the vocab without materializing a one-hot: logsumexp - label
    logit (label logits via an lm_head gather, SPMD-friendly), averaged
    over the targets >= 0.  Returns (loss, token count)."""
    with jax.named_scope("head"):
        table = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        logits = jnp.einsum("bsd,dv->bsv", x, table).astype(jnp.float32)
        logits = softcap(logits, cfg.final_softcap)
        logits = constrain(logits, ctx.mesh, P(ctx.bspec, None, "model"))
        lse = jax.nn.logsumexp(logits, axis=-1)  # (B,S)
        mask = (targets >= 0)
        safe = jnp.maximum(targets, 0)
        lbl_w = jnp.take(table, safe, axis=1)            # (d, B, S)
        lbl_logit = jnp.einsum("bsd,dbs->bs", x, lbl_w).astype(jnp.float32)
        lbl_logit = softcap(lbl_logit, cfg.final_softcap)
        nll = ((lse - lbl_logit) * mask).sum()
        denom = jnp.maximum(mask.sum(), 1)
        return nll / denom, denom


def lm_loss(params, batch, cfg: ModelConfig, mesh: Mesh, axes: MeshAxes):
    ctx = Ctx(cfg, mesh, axes)
    x, positions = lm_inputs(params, batch, cfg)
    x = ctx.bconstrain(x)
    _, tail_kinds = split_layers(cfg)
    x, stats, _ = run_stack(params, x, positions, ctx, cfg.block_pattern,
                            tail_kinds)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    targets = batch["targets"]
    if cfg.frontend == "vision" and "media_embed" in batch:
        pad = -jnp.ones((targets.shape[0], batch["media_embed"].shape[1]),
                        targets.dtype)
        targets = jnp.concatenate([pad, targets], axis=1)
    loss, denom = masked_cross_entropy(params, x, targets, cfg, ctx)
    metrics = {"ce_loss": loss, "tokens": denom}
    if "aux_loss" in stats:
        aux = cfg.aux_loss_weight * stats["aux_loss"]
        metrics["moe_aux_loss"] = stats["aux_loss"]
        metrics["expert_counts"] = stats["expert_counts"]
        loss = loss + aux
    return loss, metrics


# -------------------------------------------------------------- router state
def update_router_bias(params, expert_counts, cfg: ModelConfig):
    """Move every MoE layer's ``router_bias`` against the load the step
    routed to each expert (``moe.update_router_bias``).  ``expert_counts``
    is the loss's (periods, E): one row per scanned layer, so the pattern
    must be one MoE block with no MoE layer outside the scan."""
    if (cfg.block_pattern != (BLOCK_MOE,)
            or BLOCK_MOE in split_layers(cfg)[1] + lead_kinds(cfg)):
        raise NotImplementedError("the router bias is updated for a "
                                  "pattern of one MoE block only")
    with jax.named_scope("router_bias"):
        blk = params["scan"]["b0"]
        moe = dict(blk["moe"])
        moe["router_bias"] = moe_lib.update_router_bias(
            moe["router_bias"], expert_counts, cfg.router_bias_rate)
        scan = dict(params["scan"], b0=dict(blk, moe=moe))
        return dict(params, scan=scan)


# ------------------------------------------------------------------- serving
def _refuse_serving(cfg: ModelConfig):
    if cfg.attn_type == "mla" or cfg.first_dense_layers:
        raise NotImplementedError(
            f"{cfg.name}: serving (prefill and decode) has no latent-attention "
            "cache and no leading dense layers; only training runs it")


def lm_prefill(params, batch, cfg: ModelConfig, mesh: Mesh, axes: MeshAxes):
    """Prompt pass: returns (cache, last-position logits)."""
    _refuse_serving(cfg)
    ctx = Ctx(cfg, mesh, axes)
    x, positions = lm_inputs(params, batch, cfg)
    x = ctx.bconstrain(x)
    _, tail_kinds = split_layers(cfg)
    x, _, caches = run_stack(params, x, positions, ctx, cfg.block_pattern,
                             tail_kinds, collect_cache=True)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params, x[:, -1:], cfg)
    return caches, logits


def lm_decode(params, caches, token, pos, cfg: ModelConfig, mesh: Mesh,
              axes: MeshAxes):
    """One-token decode.  token: (B,1) int32; pos: int32 scalar."""
    _refuse_serving(cfg)
    ctx = Ctx(cfg, mesh, axes)
    x = embed_tokens(params, token, cfg)
    _, tail_kinds = split_layers(cfg)

    def body(x, scanned):
        p_period, cache_period = scanned
        new_caches = {}
        for i, kind in enumerate(cfg.block_pattern):
            x, nc = block_decode(p_period[f"b{i}"], kind, x,
                                 cache_period[f"b{i}"], pos, ctx)
            new_caches[f"b{i}"] = nc
        return x, new_caches

    x, new_scan = jax.lax.scan(body, x, (params["scan"], caches["scan"]))
    new_tail = {}
    for i, kind in enumerate(tail_kinds):
        x, nc = block_decode(params["tail"][f"t{i}"], kind, x,
                             caches["tail"][f"t{i}"], pos, ctx)
        new_tail[f"t{i}"] = nc
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params, x, cfg)
    return {"scan": new_scan, "tail": new_tail}, logits
