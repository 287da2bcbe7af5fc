"""Plain float32 reference of the cut Moonlight-16B-A3B decoder's training
step (DeepSeek-V3 block): the loss, its gradients, AdamW and the router
bias update, in ``jax.numpy`` at the highest matmul precision, computed in
blocks (attention by row and query chunk, every MLP and the head by row
chunk, the experts one at a time) so that it fits one chip.  Weights are
stored in the configuration's dtypes between steps (bf16 matrices; f32
norms, router, router bias and AdamW moments).

Independent of the program: it reads only the configuration's keys and the
parameter tree :func:`init_params` makes from the seed.  It reuses the
plain helpers of ``moe_lm.py`` (rope, RMSNorm, the float8 control, the
head's cross-entropy, the learning-rate schedule, the gaps).  The model, as
the configuration states it:

- token embedding; ``first_k_dense_replace`` dense layers, then MoE layers;
  each layer: RMSNorm (weight a delta around 1), latent attention, a
  residual add, RMSNorm, the FFN, a residual add; a final RMSNorm and the
  head over the vocabulary held here;
- latent attention (no q-LoRA): ``q = x w_q`` (heads x (nope + rope));
  ``[c, k_pe] = x w_kv_a``, ``c`` RMS-normed (``kv_norm``), ``[k_nope, v] =
  c w_kv_b``; rope on ``q_pe`` and on the one ``k_pe`` all heads share;
  causal softmax of ``q k^T / sqrt(nope + rope)``, times v, through ``w_o``;
- the dense FFN: a gated SiLU of ``intermediate_size``;
- the MoE FFN: sigmoid scores of a float32 router over all the published
  ``n_routed_experts`` (64); the top ``num_experts_per_tok`` of scores +
  ``router_bias`` chosen, weighed by their scores alone, renormalised to
  sum 1 and scaled by ``routed_scaling_factor``; of the experts, only the
  ``n_routed_experts`` held here (from ``first_held_expert``) compute, each
  on at most ``capacity`` tokens (the program's departure), and the shared
  block (``n_shared_experts`` x ``moe_intermediate_size`` wide) on all;
- the loss: mean cross-entropy plus ``aux_loss_alpha`` x the sequence-wise
  balance loss summed over the MoE layers (DeepSeek-V3, arXiv:2412.19437
  2.1.2): per sequence, the sum over experts of ``f_i`` (experts / (k x S)
  x the tokens routed to i) times ``P_i`` (the mean over the sequence of
  the scores normalised over the experts), averaged over the sequences;
- after each AdamW step, which leaves the router bias alone, each layer's
  bias moves by ``bias_update_speed`` x sign(mean load - load of i), from
  the step's routed counts.

Departures the program shares, under ``departures`` in the configuration:
the capacity with drops, and rope rotating the halves of q_pe and k_pe
where the published code pairs them interleaved (a fixed permutation of
the rope columns of ``w_q`` and ``w_kv_a``).
"""
from __future__ import annotations

import functools
from pathlib import Path
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_HELPERS = Path(__file__).resolve().parent / "moe_lm.py"


def _load_helpers():
    import importlib.util
    import sys
    name = "bench_ref_moe_lm_helpers"
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, _HELPERS)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[name] = mod
        spec.loader.exec_module(mod)
    return sys.modules[name]


H = _load_helpers()
key_from_words = H.key_from_words
leaf_name = H.leaf_name
rms_norm = H.rms_norm
rope = H.rope
_mm = H._mm
lr_at = H.lr_at
cross_entropy_sum = H.cross_entropy_sum

Q_CHUNK = 512          # attention query rows per block
ROW_CHUNK = 4096       # MLP and shared-block rows per block
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")
ROUTER_STATE = "router_bias"


# ------------------------------------------------------------- parameters
def init_params(shapes, key, std: float):
    """As ``moe_lm.init_params`` (norms 0, every other leaf a truncated
    normal by its name), with the router bias 0, as a model starts."""
    p = H.init_params(shapes, key, std)
    return jax.tree_util.tree_map_with_path(
        lambda path, x: jnp.zeros_like(x)
        if leaf_name(path).endswith(ROUTER_STATE) else x, p)


def init_on(shapes, key, std, shardings=None):
    return jax.jit(lambda k: init_params(shapes, k, std),
                   out_shardings=shardings)(key)


def _is_expert(name: str) -> bool:
    """An expert's stacked matrix: ``.../moe/w_gate`` (not the shared
    block's ``.../moe/shared/w_gate`` or a dense ``mlp``)."""
    parts = name.split("/")
    return len(parts) >= 2 and parts[-2] == "moe" and parts[-1] in \
        EXPERT_LEAVES


def _parameters(x, name):
    """A leaf as the published model's parameters, one a row: the layer
    axis of a leaf under ``scan`` and the experts' axis of an expert matrix
    are split, since the published model has one tensor per layer and per
    expert."""
    lead = int(name.startswith("scan/")) + int(_is_expert(name))
    return x.reshape(int(np.prod(x.shape[:lead])), -1)


def parameter_names(shapes) -> list:
    """The published parameters' names in the order of :func:`leaf_norms`
    (``scan/b0/moe/w_up[l0.e5]``: scanned layer 0, held expert 5)."""
    names = []
    for path, sds in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        name = leaf_name(path)
        if not name.startswith("scan/"):
            names.append(name)
        elif _is_expert(name):
            names += [f"{name}[l{li}.e{e}]" for li in range(sds.shape[0])
                      for e in range(sds.shape[1])]
        else:
            names += [f"{name}[l{li}]" for li in range(sds.shape[0])]
    return names


def leaf_norms(tree):
    return jnp.concatenate([
        jnp.sqrt(jnp.sum(jnp.square(_parameters(x, leaf_name(path))
                                    .astype(jnp.float32)), axis=1))
        for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]])


def change_norms(params, key, shapes, std):
    def norms(p, k):
        p0 = init_params(shapes, k, std)
        return leaf_norms(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            p, p0))
    return [float(x) for x in np.asarray(jax.jit(norms)(params, key))]


def router_bias(params) -> list:
    """Every MoE layer's router bias, flattened (layers x experts)."""
    return [float(x) for x in
            np.asarray(params["scan"]["b0"]["moe"][ROUTER_STATE]).ravel()]


# ------------------------------------------------------------------ layers
def attention_row(p, x, cfg, quant):
    """Causal latent attention of one sequence x (S, d), by query chunks."""
    s = x.shape[0]
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rp = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    theta = cfg["rope_theta"]
    pos = jnp.arange(s)
    q = _mm("sd,dhe->she", x, p["w_q"], quant)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, theta)], -1)
    ckv = _mm("sd,dr->sr", x, p["w_kv_a"], quant)
    c = rms_norm(ckv[:, :r], p["kv_norm"], cfg["rms_norm_eps"])
    k_pe = rope(ckv[:, None, r:], pos, theta)
    kv = _mm("sr,rhe->she", c, p["w_kv_b"], quant)
    k = jnp.concatenate([kv[..., :nope],
                         jnp.broadcast_to(k_pe, (s, h, rp))], -1)
    v = kv[..., nope:]
    chunk = min(Q_CHUNK, s)
    n = s // chunk
    qc = q.reshape(n, chunk, h, nope + rp)

    @jax.checkpoint
    def block(args):
        qi, i = args
        sc = _mm("qhd,khd->hqk", qi, k, quant) \
            / jnp.sqrt(jnp.float32(nope + rp))
        qpos = i * chunk + jnp.arange(chunk)
        sc = jnp.where(pos[None, :] <= qpos[:, None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        return _mm("hqk,khe->qhe", pr, v, quant)

    out = jax.lax.map(block, (qc, jnp.arange(n))).reshape(s, h, -1)
    return _mm("she,hed->sd", out, p["w_o"], quant)


def gated_mlp(p, x, quant):
    """The gated SiLU FFN over rows of x (T, d), by row chunks."""
    t = x.shape[0]
    chunk = min(ROW_CHUNK, t)

    @jax.checkpoint
    def block(xi):
        hdn = jax.nn.silu(_mm("td,df->tf", xi, p["w_gate"], quant)) \
            * _mm("td,df->tf", xi, p["w_up"], quant)
        return _mm("tf,fd->td", hdn, p["w_down"], quant)

    return jax.lax.map(block, x.reshape(t // chunk, chunk, -1)).reshape(t, -1)


def capacity(cfg, tokens: int) -> int:
    c = int(cfg["capacity_factor"] * tokens * cfg["num_experts_per_tok"]
            / cfg["router_experts"]) + 1
    c = (c + 7) // 8 * 8
    return max(1, min(c, tokens))


def moe(p, x, cfg, quant, batch: int):
    """x (T, d) -> (y (T, d), balance loss, tokens routed to each of the
    router's experts)."""
    t = x.shape[0]
    e_n, k = cfg["router_experts"], cfg["num_experts_per_tok"]
    scores = jax.nn.sigmoid(_mm("td,de->te", x, p["router"], quant))
    _, top_i = jax.lax.top_k(scores + p[ROUTER_STATE], k)
    top_v = jnp.take_along_axis(scores, top_i, -1)
    if cfg["norm_topk_prob"]:
        top_v = top_v / jnp.maximum(top_v.sum(-1, keepdims=True), 1e-9)
    top_v = top_v * cfg["routed_scaling_factor"]
    cap = capacity(cfg, t)

    @jax.checkpoint
    def ffn(xs, sel_i, sel_w, wg, wu, wd):
        xg = xs[sel_i]
        hdn = jax.nn.silu(_mm("cd,df->cf", xg, wg, quant)) \
            * _mm("cd,df->cf", xg, wu, quant)
        return _mm("cf,fd->cd", hdn, wd, quant) * sel_w[:, None]

    def expert(out, args):
        e, wg, wu, wd = args
        w_e = jnp.where(top_i == e, top_v, 0.0).sum(-1)
        sel_w, sel_i = jax.lax.top_k(jnp.where(w_e > 0, w_e, -1.0), cap)
        sel_w = jnp.where(sel_w > 0, sel_w, 0.0)
        return out.at[sel_i].add(ffn(x, sel_i, sel_w, wg, wu, wd)), None

    held = cfg["n_routed_experts"]
    ids = cfg["first_held_expert"] + jnp.arange(held)
    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (ids, p["w_gate"], p["w_up"], p["w_down"]))
    y = y + gated_mlp(p["shared"], x, quant)
    chosen = jax.nn.one_hot(top_i, e_n).sum(1)                 # (T, E)
    s = t // batch
    f = jax.lax.stop_gradient(chosen.reshape(batch, s, e_n).sum(1)
                              * (e_n / (k * s)))
    pn = scores / scores.sum(-1, keepdims=True)
    aux = jnp.mean(jnp.sum(f * pn.reshape(batch, s, e_n).mean(1), -1))
    return y, aux, chosen.sum(0)


def loss_fn(params, tokens, targets, cfg, quant=False):
    """Mean CE + alpha x the balance loss; params in f32.  Returns (loss,
    routed counts of each MoE layer (layers, E))."""
    b, s = tokens.shape
    d, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
    x = params["embed"][tokens]
    aux_total = 0.0
    counts = []
    dense = cfg["first_k_dense_replace"]
    for li in range(cfg["num_hidden_layers"]):
        if li < dense:
            p = params["lead"][f"l{li}"]
        else:
            p = jax.tree.map(lambda a: a[li - dense], params["scan"]["b0"])
        h = rms_norm(x, p["norm_attn"], eps)
        x = x + jax.lax.map(jax.checkpoint(
            lambda r: attention_row(p["attn"], r, cfg, quant)), h)
        h = rms_norm(x, p["norm_mlp"], eps).reshape(b * s, d)
        if li < dense:
            y = gated_mlp(p["mlp"], h, quant)
        else:
            y, aux, c = moe(p["moe"], h, cfg, quant, b)
            aux_total = aux_total + aux
            counts.append(c)
        x = x + y.reshape(b, s, d)
    x = rms_norm(x, params["final_norm"], eps)
    ce = cross_entropy_sum(x.reshape(b * s, d), params["lm_head"],
                           targets.reshape(b * s), quant) / (b * s)
    return ce + cfg["aux_loss_alpha"] * aux_total, jnp.stack(counts)


def _is_state(path) -> bool:
    return leaf_name(path).endswith(ROUTER_STATE)


@functools.partial(jax.jit, static_argnames=("cfg_items", "tr_items",
                                             "quant"),
                   donate_argnums=(0, 1, 2))
def _ref_step(stored, m, v, tokens, targets, step, cfg_items, tr_items,
              quant):
    """One step from the parameters as stored to the next stored ones:
    AdamW on every parameter but the router bias, then the bias update."""
    cfg, tr = dict(cfg_items), dict(tr_items)
    params = jax.tree.map(lambda x: x.astype(jnp.float32), stored)
    (loss, counts), g = jax.value_and_grad(loss_fn, has_aux=True)(
        params, tokens, targets, cfg, quant)
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, tr["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    g = jax.tree.map(lambda x: x * scale, g)
    b1, b2, eps, wd = tr["beta1"], tr["beta2"], tr["eps"], tr["weight_decay"]
    t = jnp.float32(step)
    lr = lr_at(step, tr)

    def adamw(path, p, a, c, x):
        if _is_state(path):
            return p, a, c
        a = b1 * a + (1 - b1) * x
        c = b2 * c + (1 - b2) * x * x
        p = p - lr * ((a / (1 - b1 ** t)) / (jnp.sqrt(c / (1 - b2 ** t))
                                             + eps) + wd * p)
        return p, a, c

    out = jax.tree_util.tree_map_with_path(adamw, params, m, v, g)
    is_triple = lambda x: isinstance(x, tuple)
    params = jax.tree.map(lambda o: o[0], out, is_leaf=is_triple)
    m = jax.tree.map(lambda o: o[1], out, is_leaf=is_triple)
    v = jax.tree.map(lambda o: o[2], out, is_leaf=is_triple)
    moe_p = params["scan"]["b0"]["moe"]
    load = counts
    moe_p[ROUTER_STATE] = moe_p[ROUTER_STATE] + cfg["bias_update_speed"] \
        * jnp.sign(load.mean(-1, keepdims=True) - load)
    stored = jax.tree.map(lambda x, s: x.astype(s.dtype), params, stored)
    return stored, m, v, loss, leaf_norms(g)


MODEL_KEYS = ("hidden_size", "num_attention_heads", "kv_lora_rank",
              "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
              "n_routed_experts", "router_experts", "first_held_expert",
              "num_experts_per_tok", "norm_topk_prob",
              "routed_scaling_factor", "first_k_dense_replace",
              "vocab_size", "num_hidden_layers", "rope_theta",
              "rms_norm_eps", "capacity_factor", "aux_loss_alpha",
              "bias_update_speed")
TRAIN_KEYS = H.TRAIN_KEYS


def as_run(cfg: Dict) -> Dict:
    """The configuration as the program runs it: the router scores all the
    published experts (``router_experts``), of which ``n_routed_experts``
    are held here."""
    return {**cfg, "router_experts": cfg["published"]["n_routed_experts"]}


def readings(cfg: Dict, tr: Dict, shapes, key, batches, steps: int = 3,
             quant: bool = False) -> Dict[str, object]:
    """As ``moe_lm.readings``, with each MoE layer's router bias after the
    last step."""
    std = float(cfg["initializer_range"])
    run_cfg = as_run(cfg)
    cfg_items = tuple((k, run_cfg[k]) for k in MODEL_KEYS)
    tr_items = tuple((k, tr[k]) for k in TRAIN_KEYS)
    p = init_on(shapes, key, std)
    m = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), p)
    v = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), p)
    losses, g1 = [], None
    for i in range(steps):
        tok, tgt = batches[i]
        p, m, v, loss, gn = _ref_step(p, m, v, jnp.asarray(tok),
                                      jnp.asarray(tgt), i + 1, cfg_items,
                                      tr_items, quant)
        losses.append(float(loss))
        if g1 is None:
            g1 = [float(x) for x in np.asarray(gn)]
    del m, v
    return {"losses": losses, "grad_norms": g1,
            "change_norms": change_norms(p, key, shapes, std),
            "router_bias": router_bias(p)}


def gaps(prog: Dict[str, list], ref: Dict[str, list],
         rel_floor: float = 1e-3) -> Tuple[Dict[str, float], list, dict]:
    """``moe_lm.gaps``, and ``router_bias_gap``: the share of router bias
    entries (layers x experts) on which the program and the reference
    differ after the last step by more than a quarter of their largest
    magnitude.  The bias moves by the sign of a count's distance from the
    mean, so a sound run differs only on experts whose load sat at the
    mean within rounding's reach."""
    out, kept, worst = H.gaps(prog, ref, rel_floor)
    bp, br = np.asarray(prog["router_bias"]), np.asarray(ref["router_bias"])
    tol = 0.25 * max(np.max(np.abs(br)), 1e-30)
    out["router_bias_gap"] = float(np.mean(np.abs(bp - br) > tol))
    return out, kept, worst
