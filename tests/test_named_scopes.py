"""The train step names its layers with ``jax.named_scope``: the compiled
HLO's ``op_name``s carry each scope the configuration reaches, on forward
ops and on the backward (``transpose(...)``) ops.  The benchmark reads the
device time of each scope from these names (``bench/scopes.py``)."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import abstract_opt, abstract_params, make_train_step
from repro.models import attention as attn
from repro.models.layers import split_lp_tree
from repro.models.model import build_model

MOE_SCOPES = ("embed", "attn", "moe", "moe/router", "moe/dispatch",
              "moe/experts", "moe/combine", "moe/stats", "head", "optimizer")
DIFFERENTIATED = ("embed", "attn", "moe/router", "moe/dispatch",
                  "moe/experts", "moe/combine", "head")


def _op_names(arch):
    cfg = configs.get_smoke_config(arch)
    model = build_model(cfg, make_local_mesh(1, 1))
    params, p_sh = abstract_params(model)
    opt, _ = abstract_opt(params, p_sh)
    batch = {k: jax.ShapeDtypeStruct((2, 32), jnp.int32)
             for k in ("tokens", "targets")}
    text = jax.jit(make_train_step(model)).lower(
        params, opt, batch).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


def _carries(name, scope):
    # whole segments, with transformation wrappers such as
    # ``transpose(jvp(head))`` split off
    tokens = [t for t in re.split(r"[/()]", name) if t]
    want = scope.split("/")
    return any(tokens[i:i + len(want)] == want
               for i in range(len(tokens) - len(want) + 1))


@pytest.fixture(scope="module")
def moe_names():
    return _op_names("qwen3-moe-30b-a3b")


@pytest.mark.parametrize("scope", MOE_SCOPES)
def test_forward_ops_carry_the_scope(moe_names, scope):
    assert any(_carries(n, scope) for n in moe_names
               if "transpose(" not in n), scope


@pytest.mark.parametrize("scope", DIFFERENTIATED)
def test_backward_ops_carry_the_scope(moe_names, scope):
    assert any(_carries(n, scope) for n in moe_names
               if "transpose(" in n), scope


def test_recomputed_ops_carry_the_scope(moe_names):
    for scope in ("attn", "moe/experts"):
        assert any(_carries(n, "rematted_computation/" + scope)
                   for n in moe_names), scope


def test_a_dense_model_names_its_mlp():
    names = _op_names("tinyllama-1.1b")
    assert any(_carries(n, "mlp") for n in names if "transpose(" not in n)
    assert any(_carries(n, "mlp") for n in names if "transpose(" in n)
    assert not any(_carries(n, "moe") for n in names)


def test_segments_are_whole():
    assert _carries("jit(s)/transpose(jvp(head))/dot_general", "head")
    assert not _carries("jit(s)/moe_x/experts/dot_general", "moe/experts")
    assert not _carries("jit(s)/moe/stats/experts/mul", "moe/experts")


# the fused attention kernel's phases, by the kernel names splash gives them
KERNEL_PHASES = {
    "forward": ("splash_mqa_fwd", lambda n: "transpose(" not in n),
    "recomputed": ("splash_mqa_fwd",
                   lambda n: "rematted_computation" in n),
    # one fused backward kernel computes dq, dk and dv
    "backward": ("splash_mqa_dkv", lambda n: "transpose(" in n),
}


@pytest.fixture(scope="module")
def kernel_names():
    """Op names of one attention layer on the fused kernel (in the Pallas
    interpreter), under full remat, value and gradient, as in the train
    step."""
    cfg = dataclasses.replace(configs.get_smoke_config("qwen3-moe-30b-a3b"),
                              d_model=256, num_heads=8, num_kv_heads=2,
                              head_dim=128)
    p = split_lp_tree(attn.init_attention(jax.random.key(0), cfg))[0]
    b, s = 2, 256
    x = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16)
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    def loss(p, x):
        out, _, _ = attn.attention_forward_kv(p, x, cfg, mask_kind="causal",
                                              positions=pos, interpret=True)
        return out.astype(jnp.float32).sum()

    text = jax.jit(jax.value_and_grad(jax.checkpoint(loss), argnums=(0, 1))
                   ).lower(p, x).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("phase", list(KERNEL_PHASES))
def test_the_fused_kernel_carries_attn(kernel_names, phase):
    kernel, in_phase = KERNEL_PHASES[phase]
    ops = [n for n in kernel_names if in_phase(n)
           and any(t.startswith(kernel) for t in re.split(r"[/()]", n))]
    assert ops, phase
    scope = "rematted_computation/attn" if phase == "recomputed" else "attn"
    assert all(_carries(n, scope) for n in ops), phase


MLA_SCOPES = ("attn/core", "attn/latent", "moe/shared", "router_bias")


@pytest.fixture(scope="module")
def mla_names():
    return _op_names("moonlight-16b-a3b")


@pytest.mark.parametrize("scope", MLA_SCOPES)
def test_latent_attention_and_shared_experts_carry_their_scopes(mla_names,
                                                                scope):
    """Moonlight's step names the attention core, the compressed-KV path,
    the shared experts and the router bias's update; the first three in
    the backward pass too."""
    assert any(_carries(n, scope) for n in mla_names), scope
    if scope != "router_bias":
        assert any(_carries(n, scope) for n in mla_names
                   if "transpose(" in n), scope


SHARDED = r"""
import re, jax, jax.numpy as jnp
from repro import configs
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import abstract_opt, abstract_params, make_train_step
from repro.models.model import build_model
model = build_model(configs.get_smoke_config("qwen3-moe-30b-a3b"),
                    make_local_mesh(1, 2))
params, p_sh = abstract_params(model)
opt, _ = abstract_opt(params, p_sh)
batch = {k: jax.ShapeDtypeStruct((2, 32), jnp.int32)
         for k in ("tokens", "targets")}
text = jax.jit(make_train_step(model)).lower(params, opt, batch).compile().as_text()
print("\n".join(sorted(set(re.findall(r'op_name="([^"]*)"', text)))))
"""


def test_the_expert_layer_keeps_its_scopes_on_a_sharded_mesh():
    """With the experts over two devices the body's names gain a
    ``shard_map`` segment; each part still reads ``moe/<part>``."""
    import os
    import subprocess
    import sys
    from pathlib import Path
    src = str(Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=src,
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    p = subprocess.run([sys.executable, "-c", SHARDED], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    names = p.stdout.splitlines()
    assert any("shard_map" in n for n in names)
    for scope in MOE_SCOPES:
        assert any(_carries(n, scope) for n in names), scope
