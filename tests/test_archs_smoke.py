"""Deliverable (f): per-architecture smoke tests — reduced config of the same
family, one forward/train step on CPU, asserting shapes + no NaNs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import make_train_step
from repro.models.layers import split_lp_tree
from repro.models.model import build_model
from repro.optim import adamw_init

MESH = make_local_mesh(1, 1)


def _batch(cfg, b=2, s=32):
    rng = np.random.default_rng(0)
    if cfg.arch_type == "encdec":
        return {
            "audio_embed": jnp.asarray(
                rng.standard_normal((b, s, cfg.d_model)) * 0.1, jnp.bfloat16),
            "tokens": jnp.zeros((b, 8), jnp.int32),
            "targets": jnp.ones((b, 8), jnp.int32),
        }
    if cfg.frontend == "vision":
        return {
            "media_embed": jnp.asarray(
                rng.standard_normal((b, cfg.num_media_positions, cfg.d_model))
                * 0.1, jnp.bfloat16),
            "tokens": jnp.zeros((b, s), jnp.int32),
            "targets": jnp.ones((b, s), jnp.int32),
        }
    return {"tokens": jnp.zeros((b, s), jnp.int32),
            "targets": jnp.ones((b, s), jnp.int32)}


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_arch_smoke_forward_and_train_step(arch):
    cfg = configs.get_smoke_config(arch)
    model = build_model(cfg, MESH)
    params, _ = split_lp_tree(model.init(jax.random.key(0)))
    batch = _batch(cfg)
    loss, metrics = jax.jit(model.loss_fn)(params, batch)
    assert loss.shape == ()
    assert np.isfinite(float(loss)), arch
    # one full train step (grads + AdamW) — params move, no NaNs
    opt = adamw_init(params)
    step = jax.jit(make_train_step(model))
    p2, o2, m = step(params, opt, batch)
    assert np.isfinite(float(m["loss"]))
    moved = jax.tree.map(
        lambda a, b: float(jnp.abs(a.astype(jnp.float32)
                                   - b.astype(jnp.float32)).max()),
        params, p2)
    assert max(jax.tree.leaves(moved)) > 0.0
    for leaf in jax.tree.leaves(p2):
        assert np.isfinite(np.asarray(leaf, np.float32)).all(), arch


@pytest.mark.parametrize("arch", configs.ARCH_IDS)
def test_full_config_matches_assignment(arch):
    """The FULL configs carry the exact assigned hyperparameters."""
    cfg = configs.get_config(arch)
    expected = {
        "whisper-large-v3": (32, 1280, 20, 20, 5120, 51866),
        "llama3.2-3b": (28, 3072, 24, 8, 8192, 128256),
        "gemma2-27b": (46, 4608, 32, 16, 36864, 256000),
        "smollm-360m": (32, 960, 15, 5, 2560, 49152),
        "tinyllama-1.1b": (22, 2048, 32, 4, 5632, 32000),
        "llava-next-mistral-7b": (32, 4096, 32, 8, 14336, 32000),
        "qwen3-moe-30b-a3b": (48, 2048, 32, 4, None, 151936),
        "llama4-scout-17b-a16e": (48, 5120, 40, 8, 8192, 202048),
        "rwkv6-7b": (32, 4096, None, None, 14336, 65536),
        "recurrentgemma-9b": (38, 4096, 16, 1, 12288, 256000),
        "moonlight-16b-a3b": (27, 2048, 16, 16, 11264, 163840),
    }[arch]
    layers, d, h, kv, ff, vocab = expected
    assert cfg.num_layers == layers and cfg.d_model == d
    assert cfg.vocab_size == vocab
    if h is not None:
        assert cfg.num_heads == h and cfg.num_kv_heads == kv
    if ff is not None:
        assert cfg.d_ff == ff
    if arch == "qwen3-moe-30b-a3b":
        assert cfg.num_experts == 128 and cfg.top_k == 8 and cfg.moe_d_ff == 768
    if arch == "llama4-scout-17b-a16e":
        assert cfg.num_experts == 16 and cfg.top_k == 1
    if arch == "moonlight-16b-a3b":
        assert (cfg.num_experts, cfg.top_k, cfg.moe_d_ff) == (64, 6, 1408)
        assert (cfg.attn_type, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
                cfg.qk_rope_head_dim, cfg.v_head_dim) == ("mla", 512, 128,
                                                          64, 128)
        assert cfg.first_dense_layers == 1 and cfg.shared_width == 2816
        assert (cfg.router_scoring, cfg.routed_scaling) == ("sigmoid", 2.446)


def test_shape_cells_cover_assignment():
    cells = list(configs.cells())
    # 10 archs x 4 shapes - 7 long_500k skips (DESIGN.md) = 33, and
    # moonlight's train_4k (serving has no latent-attention cache)
    assert len(cells) == 34
    long_runners = {a for a, s in cells if s == "long_500k"}
    assert long_runners == {"gemma2-27b", "rwkv6-7b", "recurrentgemma-9b"}


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "gemma2-27b"])
def test_masked_cross_entropy_is_the_plain_one(arch):
    """The head's loss is a float32 log-softmax cross-entropy averaged over
    the targets >= 0: through an untied ``lm_head`` (tinyllama), and
    through the tied embedding with gemma2's final-logit soft cap."""
    from repro.models.transformer import Ctx, masked_cross_entropy
    cfg = configs.get_smoke_config(arch)
    assert cfg.tie_embeddings == (arch == "gemma2-27b")
    model = build_model(cfg, MESH)
    params, _ = split_lp_tree(model.init(jax.random.key(0)))
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    rng = np.random.default_rng(4)
    x = rng.standard_normal((2, 16, cfg.d_model)).astype(np.float32)
    targets = rng.integers(0, cfg.vocab_size, (2, 16)).astype(np.int32)
    targets[0, :5] = -1
    targets[1, -3:] = -1
    ctx = Ctx(cfg, MESH, model.axes)
    loss, count = jax.jit(lambda p, x, t: masked_cross_entropy(
        p, x, t, cfg, ctx))(params, x, targets)

    table = np.asarray(params["embed"]).T if cfg.tie_embeddings \
        else np.asarray(params["lm_head"])
    logits = x.astype(np.float64) @ table.astype(np.float64)
    if cfg.final_softcap:
        logits = cfg.final_softcap * np.tanh(logits / cfg.final_softcap)
    logp = logits - np.log(np.exp(logits - logits.max(-1, keepdims=True))
                           .sum(-1, keepdims=True)) - logits.max(-1,
                                                                 keepdims=True)
    mask = targets >= 0
    nll = -np.take_along_axis(logp, np.maximum(targets, 0)[..., None],
                              axis=-1)[..., 0]
    assert int(count) == mask.sum() == 24
    np.testing.assert_allclose(float(loss), nll[mask].mean(), rtol=1e-5)
