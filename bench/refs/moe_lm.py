"""Plain float32 reference of the cut qwen3-moe decoder's training step:
the loss, its gradients and AdamW, in ``jax.numpy`` at the highest matmul
precision, computed in blocks (attention by row and query chunk, the head
by row chunk, the experts one at a time) so that it fits one chip.  The
weights are stored in the configuration's dtypes (bf16 matrices; f32 norms,
router and AdamW moments): every update is computed in float32 and rounded
to the weight's dtype, as the configuration states.

Independent of the program: it reads only the configuration's keys and
the parameter tree that :func:`init_params` makes from the seed (the same
tree, leaf for leaf, that the benchmark hands the program).  The model, as
the configuration states it:

- token embedding; one decoder layer per ``num_hidden_layers``:
  RMSNorm (weight stored as a delta around 1), grouped-query causal
  attention with rotary embedding (``rope_theta``; halves rotated), a
  residual add, RMSNorm, the MoE layer, a residual add; a final RMSNorm and
  the head over the vocabulary held here;
- the MoE layer: a float32 router, softmax, the top-``num_experts_per_tok``
  experts with their weights renormalized to sum to 1; each expert takes at
  most ``capacity`` tokens (``capacity_factor`` x tokens x k / experts,
  plus one, rounded up to 8), the ones it weighs highest, and adds its
  gated SiLU FFN output times the token's weight; tokens past capacity are
  dropped for that expert;
- the loss: mean cross-entropy over the tokens plus
  ``router_aux_loss_coef`` x the Switch balance loss (experts x the sum
  over experts of the top-1 share times the mean router probability).

Departures from the published layer, which the program shares: no per-head
q/k RMSNorm, a capacity with drops where the published routing is
dropless, and the aux coefficient.  The configuration keeps the published
``router_aux_loss_coef`` and gives the value the program runs under
``departures``; :func:`as_run` puts it in the published one's place.

``quant`` (the control) rounds every matmul operand and result to float8
e4m3 with a per-tensor scale, and every cotangent to float8 e5m2: the
reference computed one precision below the configuration's bfloat16, in
which the program holds its matmul operands and results.
"""
from __future__ import annotations

import functools
import zlib
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
EXPERT_LEAVES = ("w_gate", "w_up", "w_down")   # (layers, experts, ...)
Q_CHUNK = 512          # attention query rows per block
HEAD_CHUNK = 2048      # head rows per block


# ------------------------------------------------------------- parameters
def leaf_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)


def init_params(shapes, key, std: float):
    """A parameter tree shaped like ``shapes`` (ShapeDtypeStructs): norm
    weights 0 (their scale is 1 + weight), every other leaf a normal
    truncated at 2 sigma with ``std``, in the leaf's dtype.  Each leaf's
    values depend only on the key and the leaf's name."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)
    leaves = []
    for path, sds in flat:
        name = leaf_name(path)
        if "norm" in name.rsplit("/", 1)[-1]:
            leaves.append(jnp.zeros(sds.shape, sds.dtype))
            continue
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
        v = std * jax.random.truncated_normal(k, -2.0, 2.0, sds.shape,
                                              jnp.float32)
        leaves.append(v.astype(sds.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def key_from_words(words) -> jax.Array:
    """A threefry key from two uint32 words (the seed may exceed 32
    bits)."""
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


# --------------------------------------------------------------- the control
def _fp8(x, dtype):
    top = float(jnp.finfo(dtype).max)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, top / amax, 1.0)
    # clipped: a scaled value a rounding above the largest finite one would
    # otherwise cast to NaN (e4m3fn has no infinity)
    y = jnp.clip(x * scale, -top, top)
    return y.astype(dtype).astype(jnp.float32) / scale


@jax.custom_vjp
def fp8_operand(x):
    return _fp8(x, jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return fp8_operand(x), None


def _fp8_bwd(_, g):
    return (_fp8(g, jnp.float8_e5m2),)


fp8_operand.defvjp(_fp8_fwd, _fp8_bwd)


def _mm(eq, a, b, quant):
    if not quant:
        return jnp.einsum(eq, a, b, precision=HI)
    # the control: operands and result in float8, as the program holds
    # its matmul operands and results in bfloat16
    out = jnp.einsum(eq, fp8_operand(a), fp8_operand(b), precision=HI)
    return fp8_operand(out)


# ------------------------------------------------------------------ layers
def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * (1.0 + w)


def rope(x, pos, theta):
    """x (S, H, D): rotate the halves of each head by position."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None, None].astype(jnp.float32) * inv
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def attention_row(p, x, cfg, quant):
    """Causal GQA attention of one sequence x (S, d), by query chunks."""
    s = x.shape[0]
    h, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    pos = jnp.arange(s)
    q = rope(_mm("sd,dhe->she", x, p["w_q"], quant), pos, cfg["rope_theta"])
    k = rope(_mm("sd,dhe->she", x, p["w_k"], quant), pos, cfg["rope_theta"])
    v = _mm("sd,dhe->she", x, p["w_v"], quant)
    chunk = min(Q_CHUNK, s)
    n = s // chunk
    qc = q.reshape(n, chunk, kv, h // kv, hd)

    @jax.checkpoint
    def block(args):
        qi, i = args
        sc = _mm("qhgd,khd->hgqk", qi, k, quant) / jnp.sqrt(jnp.float32(hd))
        qpos = i * chunk + jnp.arange(chunk)
        sc = jnp.where(pos[None, :] <= qpos[:, None], sc, -jnp.inf)
        pr = jax.nn.softmax(sc, axis=-1)
        return _mm("hgqk,khd->qhgd", pr, v, quant).reshape(chunk, h, hd)

    out = jax.lax.map(block, (qc, jnp.arange(n))).reshape(s, h, hd)
    return _mm("she,hed->sd", out, p["w_o"], quant)


def capacity(cfg, tokens: int) -> int:
    c = int(cfg["capacity_factor"] * tokens * cfg["num_experts_per_tok"]
            / cfg["num_experts"]) + 1
    c = (c + 7) // 8 * 8
    return max(1, min(c, tokens))


def moe(p, x, cfg, quant):
    """x (T, d) -> (y (T, d), aux, tokens routed to each expert)."""
    t = x.shape[0]
    e_n, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    probs = jax.nn.softmax(_mm("td,de->te", x, p["router"], quant), -1)
    top_v, top_i = jax.lax.top_k(probs, k)
    top_v = top_v / jnp.maximum(top_v.sum(-1, keepdims=True), 1e-9)
    cap = capacity(cfg, t)

    @jax.checkpoint
    def ffn(xs, sel_i, sel_w, wg, wu, wd):
        # recomputed in the backward pass: a scan over the experts then
        # keeps only each expert's selection, not its activations
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

    y, _ = jax.lax.scan(expert, jnp.zeros_like(x),
                        (jnp.arange(e_n), p["w_gate"], p["w_up"],
                         p["w_down"]))
    top1 = jax.nn.one_hot(top_i[:, 0], e_n).mean(0)
    aux = e_n * jnp.sum(jax.lax.stop_gradient(top1) * probs.mean(0))
    counts = jax.nn.one_hot(top_i, e_n).sum((0, 1))
    return y, aux, counts


def cross_entropy_sum(x, head, targets, quant):
    """Summed NLL over rows of x (T, d), by row chunks."""
    t = x.shape[0]
    chunk = min(HEAD_CHUNK, t)
    n = t // chunk

    @jax.checkpoint
    def block(args):
        xi, ti = args
        logits = _mm("td,dv->tv", xi, head, quant)
        lse = jax.nn.logsumexp(logits, -1)
        lbl = jnp.take_along_axis(logits, ti[:, None], -1)[:, 0]
        return jnp.sum(lse - lbl)

    return jnp.sum(jax.lax.map(block, (x.reshape(n, chunk, -1),
                                       targets.reshape(n, chunk))))


def loss_fn(params, tokens, targets, cfg, quant=False):
    """Mean CE over all tokens + the aux loss; params in f32."""
    b, s = tokens.shape
    d, eps = cfg["hidden_size"], cfg["rms_norm_eps"]
    x = params["embed"][tokens]
    aux_total = 0.0
    layers = params["scan"]["b0"]
    for li in range(cfg["num_hidden_layers"]):
        p = jax.tree.map(lambda a: a[li], layers)
        h = rms_norm(x, p["norm_attn"], eps)
        att = jax.lax.map(jax.checkpoint(
            lambda r: attention_row(p["attn"], r, cfg, quant)), h)
        x = x + att
        h = rms_norm(x, p["norm_mlp"], eps)
        y, aux, _ = moe(p["moe"], h.reshape(b * s, d), cfg, quant)
        x = x + y.reshape(b, s, d)
        aux_total = aux_total + aux
    x = rms_norm(x, params["final_norm"], eps)
    ce = cross_entropy_sum(x.reshape(b * s, d), params["lm_head"],
                           targets.reshape(b * s), quant) / (b * s)
    return ce + cfg["router_aux_loss_coef"] * aux_total


# ------------------------------------------------------------------ AdamW
def lr_at(step, tr):
    """Warm-up then cosine to ``floor`` x peak, at optimizer step ``step``
    (1-based)."""
    peak, warm, total = tr["lr"], tr["warmup_steps"], tr["total_steps"]
    step = jnp.float32(step)
    frac = jnp.clip((step - warm) / max(total - warm, 1), 0.0, 1.0)
    cos = peak * (tr["lr_floor"] + (1 - tr["lr_floor"]) * 0.5
                  * (1 + jnp.cos(jnp.pi * frac)))
    return jnp.where(step < warm, peak * step / max(warm, 1), cos)


def _parameters(x, name):
    """A leaf of the tree as the published model's parameters, one a row:
    the layer axis of a leaf under ``scan`` and the experts' axis of an
    expert matrix are split, since the published model has one tensor per
    layer and per expert."""
    lead = int(name.startswith("scan/")) + int(name.rsplit("/", 1)[-1]
                                               in EXPERT_LEAVES)
    return x.reshape(int(np.prod(x.shape[:lead])), -1)


def parameter_names(shapes) -> list:
    """The names of the published parameters, in the order of
    :func:`leaf_norms` (``scan/b0/moe/w_up[l0.e5]``: layer 0, expert 5)."""
    names = []
    for path, sds in jax.tree_util.tree_flatten_with_path(shapes)[0]:
        name = leaf_name(path)
        if not name.startswith("scan/"):
            names.append(name)
        elif name.rsplit("/", 1)[-1] in EXPERT_LEAVES:
            names += [f"{name}[l{li}.e{e}]" for li in range(sds.shape[0])
                      for e in range(sds.shape[1])]
        else:
            names += [f"{name}[l{li}]" for li in range(sds.shape[0])]
    return names


def leaf_norms(tree):
    """The norm of each published parameter (see :func:`_parameters`), one
    float32 vector."""
    return jnp.concatenate([
        jnp.sqrt(jnp.sum(jnp.square(_parameters(x, leaf_name(path))
                                    .astype(jnp.float32)), axis=1))
        for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]])


@functools.partial(jax.jit, static_argnames=("cfg_items", "tr_items",
                                             "quant"),
                   donate_argnums=(0, 1, 2))
def _ref_step(stored, m, v, tokens, targets, step, cfg_items, tr_items,
              quant):
    """One step from the parameters as stored (the configuration's dtypes)
    to the next stored parameters; everything between in float32."""
    cfg, tr = dict(cfg_items), dict(tr_items)
    params = jax.tree.map(lambda x: x.astype(jnp.float32), stored)
    loss, g = jax.value_and_grad(loss_fn)(params, tokens, targets, cfg,
                                          quant)
    gnorm = jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g)))
    scale = jnp.minimum(1.0, tr["clip_norm"] / jnp.maximum(gnorm, 1e-9))
    g = jax.tree.map(lambda x: x * scale, g)
    b1, b2, eps, wd = tr["beta1"], tr["beta2"], tr["eps"], tr["weight_decay"]
    t = jnp.float32(step)
    lr = lr_at(step, tr)
    m = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
    v = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, v, g)
    params = jax.tree.map(
        lambda p, a, c: p - lr * ((a / (1 - b1 ** t))
                                  / (jnp.sqrt(c / (1 - b2 ** t)) + eps)
                                  + wd * p), params, m, v)
    # stored in the configuration's dtypes (bf16 matrices, f32 norms and
    # router).  The rounding happens where the step's result is written:
    # inside one program the compiler may drop a round trip through bf16
    # (excess precision), as it did on the TPU
    stored = jax.tree.map(lambda x, s: x.astype(s.dtype), params, stored)
    return stored, m, v, loss, leaf_norms(g)


MODEL_KEYS = ("hidden_size", "num_attention_heads", "num_key_value_heads",
              "head_dim", "num_experts", "num_experts_per_tok",
              "moe_intermediate_size", "vocab_size", "num_hidden_layers",
              "rope_theta", "rms_norm_eps", "capacity_factor",
              "router_aux_loss_coef")
TRAIN_KEYS = ("lr", "warmup_steps", "total_steps", "lr_floor", "beta1",
              "beta2", "eps", "weight_decay", "clip_norm")


def as_run(cfg: Dict) -> Dict:
    """The configuration as the program runs it: a model key that
    ``departures`` holds takes the value given there."""
    dep = cfg.get("departures", {})
    return {**cfg, **{k: v for k, v in dep.items() if k in MODEL_KEYS}}


def init_on(shapes, key, std, shardings=None):
    """The initial parameters made on the device in one jitted call, in
    their own dtypes, placed by ``shardings``."""
    return jax.jit(lambda k: init_params(shapes, k, std),
                   out_shardings=shardings)(key)


def change_norms(params, key, shapes, std):
    """The norm of each published parameter's change from the initial
    parameters, which are made again from ``key`` (never kept)."""
    def norms(p, k):
        p0 = init_params(shapes, k, std)
        return leaf_norms(jax.tree.map(
            lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
            p, p0))
    return [float(x) for x in np.asarray(jax.jit(norms)(params, key))]


def readings(cfg: Dict, tr: Dict, shapes, key, batches, steps: int = 3,
             quant: bool = False) -> Dict[str, object]:
    """Train ``steps`` steps from the initial parameters on ``batches``
    (tokens, targets) and return the loss before each update, the per-leaf
    norms of the first (clipped) gradient, and the per-leaf norms of the
    parameters' change after the last step.  ``shapes`` gives the tree and
    the dtypes the parameters are made in; the reference computes in
    float32."""
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
            "change_norms": change_norms(p, key, shapes, std)}


def gaps(prog: Dict[str, list], ref: Dict[str, list],
         rel_floor: float = 1e-3) -> Tuple[Dict[str, float], list, dict]:
    """The numbers that can be compared: the widest relative gap of the
    three losses, and, over the parameters whose reference gradient is not
    nought to rounding (at least ``rel_floor`` x the median parameter's),
    the gap of the first gradient's norm and of the norm of the parameters'
    change, each against the reference's norm of that parameter or of the
    median parameter, whichever is larger: the widest (``grad_gap``,
    ``update_gap``) and the median parameter's (``grad_median_gap``,
    ``update_median_gap``).  Returns the gaps, the indices of the
    parameters counted and the index of the widest gap of each kind."""
    lp, lr = np.asarray(prog["losses"]), np.asarray(ref["losses"])
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    gr = np.asarray(ref["grad_norms"])
    keep = np.flatnonzero(gr >= rel_floor * np.median(gr))
    out, worst = {"loss_gap": loss_gap}, {}
    for key, name in (("grad_norms", "grad"), ("change_norms", "update")):
        p, r = np.asarray(prog[key])[keep], np.asarray(ref[key])[keep]
        scale = np.maximum(r, np.median(r))
        gap = np.abs(p - r) / scale
        out[f"{name}_gap"] = float(np.max(gap))
        out[f"{name}_median_gap"] = float(np.median(gap))
        worst[f"{name}_gap"] = int(keep[np.argmax(gap)])
    return out, keep.tolist(), worst
