"""The fused attention path (JAX's splash kernel) against the jnp path it
replaces on a TPU, in the Pallas interpreter on the CPU, and the rule that
picks one path or the other.

The kernel runs at S=256 in 128-blocks, so that its block tables hold
skipped, partial and full blocks."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.models import attention as attn
from repro.models.layers import apply_rope, split_lp_tree

S = 256
B = 2
HD = 128


@pytest.fixture
def blocks_of_128(monkeypatch):
    monkeypatch.setattr(attn, "_block", lambda seq: 128)


def _cfg(**kw):
    return dataclasses.replace(configs.get_smoke_config("qwen3-moe-30b-a3b"),
                               **kw)


def _qkv(h, hkv, q_scale=1.0, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    q = q_scale * jax.random.normal(ks[0], (B, S, h, HD))
    q = q.astype(jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, hkv, HD), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, hkv, HD), jnp.bfloat16)
    ct = jax.random.normal(ks[3], (B, S, h, HD), jnp.bfloat16)
    return q, k, v, ct


def _gap(a, b):
    """Widest and mean absolute difference, over the reference's mean
    magnitude."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    scale = np.abs(b).mean()
    return np.abs(a - b).max() / scale, np.abs(a - b).mean() / scale


CASES = {
    # name: (mask kind, window, soft-cap, q heads, kv heads, q scale)
    "causal-g1": ("causal", 0, 0.0, 4, 4, 1.0),
    "causal-g4": ("causal", 0, 0.0, 8, 2, 1.0),
    "causal-g8": ("causal", 0, 0.0, 8, 1, 1.0),
    "local-w64": ("local", 64, 0.0, 8, 2, 1.0),
    # scores of ~N(0, 36): the cap of 50 bends the largest of them
    "softcap-50": ("causal", 0, 50.0, 8, 2, 6.0),
}


def _core_gaps(kind, cfg, q, k, v, ct, kernel_cfg=None, kernel_kind=None):
    """Gaps of the kernel path's output and q/k/v gradients, and of
    ``_sdpa``'s on the same bf16 inputs, to ``_sdpa`` on those values in
    f32: {name: ((widest, mean) kernel, (widest, mean) _sdpa)}."""
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))

    def ref(q, k, v):
        bias = attn._mask_bias(pos, pos, kind, cfg.window_size)[:, None]
        return attn._sdpa(q, k, v, bias, cfg.logit_softcap)

    def fused(q, k, v):
        return attn._splash(q, k, v, kernel_cfg or cfg, kernel_kind or kind,
                            None, None, True)

    f32 = lambda *a: [t.astype(jnp.float32) for t in a]
    out_t, vjp_t = jax.vjp(ref, *f32(q, k, v))
    out_r, vjp_r = jax.vjp(ref, q, k, v)
    out_f, vjp_f = jax.jit(lambda *a: jax.vjp(fused, *a))(q, k, v)
    assert out_f.dtype == q.dtype and out_f.shape == out_r.shape
    truth = [out_t, *vjp_t(*f32(ct))]
    kernel = [out_f, *vjp_f(ct)]
    sdpa = [out_r, *vjp_r(ct)]
    return {name: (_gap(f, t), _gap(r, t))
            for name, f, r, t in zip(("out", "q", "k", "v"), kernel, sdpa,
                                     truth)}


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_matches_materialised_scores(blocks_of_128, case):
    """Output and q/k/v gradients of the kernel path against ``_sdpa``:
    the same mask, soft-cap and GQA grouping.  Both are held to ``_sdpa``
    on the same values in f32; the kernel, which accumulates the scores in
    f32, is closer on the mean than ``_sdpa`` in bf16, which rounds them."""
    kind, window, cap, h, hkv, q_scale = CASES[case]
    cfg = _cfg(window_size=window or 4096, logit_softcap=cap)
    q, k, v, ct = _qkv(h, hkv, q_scale)
    for name, ((widest, mean), (_, sdpa_mean)) in _core_gaps(
            kind, cfg, q, k, v, ct).items():
        assert widest < 0.3 and mean <= sdpa_mean, (case, name, widest,
                                                     mean, sdpa_mean)


@pytest.mark.parametrize("wrong", ["mask", "softcap"])
def test_kernel_sees_the_mask_and_the_cap(blocks_of_128, wrong):
    """A kernel with the wrong mask or no soft-cap is far outside the gaps
    allowed above."""
    if wrong == "mask":    # causal where the reference has a 64-wide window
        kind, cfg, kernel_kind, kernel_cfg = ("local", _cfg(window_size=64),
                                              "causal", None)
        q, k, v, ct = _qkv(8, 2)
    else:
        kind, cfg, kernel_kind, kernel_cfg = ("causal",
                                              _cfg(logit_softcap=50.0),
                                              None, _cfg())
        q, k, v, ct = _qkv(8, 2, CASES["softcap-50"][-1])
    gaps = _core_gaps(kind, cfg, q, k, v, ct, kernel_cfg, kernel_kind)
    assert gaps["out"][0][1] > 3 * gaps["out"][1][1], gaps["out"]


def test_kernel_is_built_once_per_shape(blocks_of_128):
    attn._splash_kernel.cache_clear()
    cfg = _cfg()
    q, k, v, _ = _qkv(8, 2)
    f = jax.jit(lambda q, k, v: attn._splash(q, k, v, cfg, "causal", None,
                                             None, True))
    f(q, k, v)
    f(q * 2, k, v)
    jax.jit(lambda q, k, v: attn._splash(q, k, v, cfg, "causal", None, None,
                                         True) * 2)(q, k, v)
    info = attn._splash_kernel.cache_info()
    assert info.misses == 1 and info.hits == 1


# ----------------------------------------------------------------- dispatch
DISPATCH = {
    # name: (platform, Sq, Sk, mask kind, cross-attention, fused)
    "tpu-causal": ("tpu", 256, 256, "causal", False, True),
    "tpu-local": ("tpu", 4096, 4096, "local", False, True),
    "tpu-cross": ("tpu", 256, 256, "none", True, False),
    "tpu-cross-causal": ("tpu", 256, 256, "causal", True, False),
    "tpu-unmasked-encoder": ("tpu", 256, 256, "none", False, False),
    "tpu-ragged-200": ("tpu", 200, 200, "causal", False, False),
    "tpu-decode": ("tpu", 1, 256, "causal", False, False),
    "cpu-causal": ("cpu", 256, 256, "causal", False, False),
    "gpu-causal": ("gpu", 256, 256, "causal", False, False),
}


@pytest.mark.parametrize("case", list(DISPATCH))
def test_dispatch_rule(case):
    platform, sq, sk, kind, cross, fused = DISPATCH[case]
    assert attn.use_fused_kernel(platform, sq, sk, kind, cross) is fused


def _attention_inputs(s, seed=0):
    cfg = _cfg(d_model=256, num_heads=8, num_kv_heads=2, head_dim=HD)
    p = split_lp_tree(attn.init_attention(jax.random.key(seed), cfg))[0]
    x = jax.random.normal(jax.random.key(seed + 1), (B, s, cfg.d_model),
                          jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (B, s))
    return cfg, p, x, pos


CALLS = {
    # name: (platform, S, cross-attention, kernel called)
    "tpu-self": ("tpu", 256, False, True),
    "tpu-cross": ("tpu", 256, True, False),
    "tpu-ragged-200": ("tpu", 200, False, False),
    "cpu-self": ("cpu", 256, False, False),
}


@pytest.mark.parametrize("case", list(CALLS))
def test_attention_takes_the_path_its_inputs_show(monkeypatch, case):
    """``attention_forward_kv`` calls the kernel exactly where the rule
    says; elsewhere its result is ``_sdpa``'s, bit for bit."""
    platform, s, cross, called = CALLS[case]
    calls = []
    monkeypatch.setattr(attn, "_platform", lambda mesh: platform)
    monkeypatch.setattr(attn, "_splash",
                        lambda *a, **k: calls.append(a) or None)
    cfg, p, x, pos = _attention_inputs(s)
    kw = dict(kv_x=x[:, ::2], kv_positions=pos[:, ::2]) if cross else {}
    out, k, v = attn.attention_forward_kv(
        p, x, cfg, mask_kind="none" if cross else "causal", positions=pos,
        **kw)
    assert bool(calls) is called
    # the spy declined, so every case computed the jnp path
    q = jnp.einsum("bsd,dhe->bshe", x, p["w_q"])
    if not cross:
        q = apply_rope(q, pos, cfg.rope_theta)
    bias = attn._mask_bias(pos, kw.get("kv_positions", pos),
                           "none" if cross else "causal",
                           cfg.window_size)[:, None]
    ref = jnp.einsum("bshe,hed->bsd", attn._sdpa(q, k, v, bias, 0.0),
                     p["w_o"])
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_decode_keeps_the_jnp_path(monkeypatch):
    monkeypatch.setattr(attn, "_platform", lambda mesh: "tpu")
    monkeypatch.setattr(attn, "_splash", lambda *a, **k: pytest.fail(
        "decode reached the fused kernel"))
    cfg, p, x, _ = _attention_inputs(S)
    cache = attn.init_kv_cache(cfg, 1, B, S)
    out, _, _ = attn.attention_decode(p, x[:, :1], cache["k"][0],
                                      cache["v"][0], 3, cfg,
                                      mask_kind="causal")
    assert out.shape == (B, 1, cfg.d_model)


def test_forward_kv_in_the_interpreter_matches_the_cpu_path(blocks_of_128):
    """The whole attention layer, projections and RoPE included, through
    the kernel (``interpret=True``) and through ``_sdpa`` (the CPU's
    path)."""
    cfg, p, x, pos = _attention_inputs(S, seed=3)
    ref, k_r, v_r = jax.jit(lambda p, x: attn.attention_forward_kv(
        p, x, cfg, mask_kind="causal", positions=pos))(p, x)
    out, k_f, v_f = jax.jit(lambda p, x: attn.attention_forward_kv(
        p, x, cfg, mask_kind="causal", positions=pos, interpret=True))(p, x)
    np.testing.assert_array_equal(np.asarray(k_f), np.asarray(k_r))
    np.testing.assert_array_equal(np.asarray(v_f), np.asarray(v_r))
    widest, mean = _gap(out, ref)
    assert widest < 0.25 and mean < 0.01, (widest, mean)
