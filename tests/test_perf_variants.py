"""The windowed ring KV cache of local-attention layers must decode as the
full cache does."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.launch.mesh import make_local_mesh
from repro.models.layers import split_lp_tree
from repro.models.model import build_model

MESH = make_local_mesh(1, 1)


def test_window_ring_cache_matches_full_cache():
    """gemma2-style local layers: ring cache decode == full-cache decode."""
    cfg = configs.get_smoke_config("gemma2-27b")   # window_size 16
    model_full = build_model(cfg, MESH)
    params, _ = split_lp_tree(model_full.init(jax.random.key(0)))
    cfg_ring = dataclasses.replace(cfg, window_kv_cache=True)
    model_ring = build_model(cfg_ring, MESH)

    rng = np.random.default_rng(3)
    prompt, extra = 20, 12                         # crosses the window=16 edge
    tokens = rng.integers(0, cfg.vocab_size, (2, prompt + extra)).astype(np.int32)

    from repro.launch.serve import pad_caches
    caches, logits_f = jax.jit(model_full.prefill_fn)(
        params, {"tokens": jnp.asarray(tokens[:, :prompt])})
    caches = pad_caches(caches, prompt + extra)

    # build the ring cache from the full prefill caches: slot = p % window
    w = cfg.window_size
    def to_ring(path, leaf):
        key = path[-1].key if hasattr(path[-1], "key") else None
        if key not in ("k", "v"):
            return leaf
        return leaf  # converted per-entry below
    ring_caches = jax.tree_util.tree_map_with_path(to_ring, caches)
    # manual conversion for local layers (b0 of each period is local in the
    # (local, full) gemma2 pattern)
    import jax.tree_util as jtu
    ring = jax.tree.map(lambda x: x, caches)
    for name, entry in ring["scan"].items():
        kind = cfg.block_pattern[int(name[1:])]
        if kind != "local_attn":
            continue
        for kk in ("k", "v"):
            full = entry[kk]                        # (P, B, S, hkv, hd)
            ringbuf = jnp.zeros(full.shape[:2] + (w,) + full.shape[3:],
                                full.dtype)
            for p in range(max(0, prompt - w), prompt):
                ringbuf = ringbuf.at[:, :, p % w].set(full[:, :, p])
            entry[kk] = ringbuf

    dec_f = jax.jit(model_full.decode_fn)
    dec_r = jax.jit(model_ring.decode_fn)
    cf, cr = caches, ring
    for i in range(extra):
        tok = jnp.asarray(tokens[:, prompt + i: prompt + i + 1])
        cf, lf = dec_f(params, cf, tok, jnp.int32(prompt + i))
        cr, lr = dec_r(params, cr, tok, jnp.int32(prompt + i))
        a = np.asarray(lf, np.float32)
        b = np.asarray(lr, np.float32)
        np.testing.assert_allclose(a, b, atol=0.05, rtol=0.05)
        np.testing.assert_array_equal(a.argmax(-1), b.argmax(-1))
