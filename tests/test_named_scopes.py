"""The train step names its layers with ``jax.named_scope``: the compiled
HLO's ``op_name``s carry each scope the configuration reaches, on forward
ops and on the backward (``transpose(...)``) ops.  The benchmark reads the
device time of each scope from these names (``bench/scopes.py``)."""
import re

import jax
import jax.numpy as jnp
import pytest

from repro import configs
from repro.launch.mesh import make_local_mesh
from repro.launch.steps import abstract_opt, abstract_params, make_train_step
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
