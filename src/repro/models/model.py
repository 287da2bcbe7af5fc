"""Uniform model API over all assigned architectures.

``build_model(cfg, mesh)`` returns a ``Model`` with init / loss / prefill /
decode closures.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

from jax.sharding import Mesh

from repro.configs.base import ModelConfig
from repro.models import encdec as encdec_lib
from repro.models import transformer as tf_lib
from repro.sharding import MeshAxes


@dataclasses.dataclass
class Model:
    cfg: ModelConfig
    mesh: Mesh
    axes: MeshAxes
    init: Callable            # key -> LP tree
    loss_fn: Callable         # (params, batch) -> (loss, metrics)
    prefill_fn: Callable      # (params, batch) -> (cache, logits)
    decode_fn: Callable       # (params, cache, token, pos) -> (cache, logits)


def build_model(cfg: ModelConfig, mesh: Mesh,
                axes: Optional[MeshAxes] = None) -> Model:
    axes = axes or MeshAxes.for_mesh(mesh)
    if cfg.arch_type == "encdec":
        return Model(
            cfg, mesh, axes,
            init=functools.partial(encdec_lib.init_encdec, cfg=cfg),
            loss_fn=lambda p, b: encdec_lib.encdec_loss(p, b, cfg, mesh, axes),
            prefill_fn=lambda p, b: encdec_lib.encdec_prefill(p, b, cfg, mesh, axes),
            decode_fn=lambda p, c, t, pos: encdec_lib.encdec_decode(
                p, c, t, pos, cfg, mesh, axes),
        )
    return Model(
        cfg, mesh, axes,
        init=functools.partial(tf_lib.init_lm, cfg=cfg),
        loss_fn=lambda p, b: tf_lib.lm_loss(p, b, cfg, mesh, axes),
        prefill_fn=lambda p, b: tf_lib.lm_prefill(p, b, cfg, mesh, axes),
        decode_fn=lambda p, c, t, pos: tf_lib.lm_decode(
            p, c, t, pos, cfg, mesh, axes),
    )
