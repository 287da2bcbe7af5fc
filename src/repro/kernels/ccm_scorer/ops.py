"""Packing, dispatch and the shared work combine for the CCM scorer tiles.

Tile / mask layout
------------------
A *lock event* is one (rank a, rank b) exchange negotiation; scoring it
means evaluating every candidate cluster pair ``(A_ia a->b, B_ib b->a)``
with ``ia in 0..na``, ``ib in 0..nb`` (index 0 = the empty cluster, i.e.
one-sided gives).  A *batched* lock event packs E such events — with
pairwise-disjoint rank sets — into fixed-size device tiles:

  av  (E, N_AV, A)     per-a-candidate feature planes (layout.AV rows)
  bv  (E, N_AV, B)     per-b-candidate feature planes (same row meanings)
  pm  (E, N_PM, A, B)  pairwise planes: counter-flow volumes x_ab/x_ba and
                       the shared-block corrections cs/ch (layout.PM)
  sc  (E, N_SC)        per-event scalars: current rank-to-rank flows,
                       CCMState volume bases, load/mem/homing bases, the
                       mask bounds na/nb, and the combine-only scalars
                       speed/mem-cap (layout.SC)

``A``/``B`` are fixed pad sizes >= max(na)+1 / max(nb)+1 over the batch
(the engine rounds them up to a multiple of 8 for the kernel path; a real
TPU deployment would pad B to the 128-lane boundary).  Candidate slots past
``na``/``nb`` are the *masked tail*: feature planes are zero-padded, and
the scorer forces tail outputs to 0 (flow/load/homing planes) or +inf
(memory planes, so tail pairs can never appear feasible).  Events are
independent grid steps — the flow decomposition is block-diagonal across
the batch, assembled by ``PhaseEngine`` with one flat bincount.

The scorer itself (ref.score_tiles / kernel.score_tiles_fwd) produces the
ten *work components* per pair (layout.OUT): loads, off-/on-rank volumes,
homing bytes and memory highs after the exchange.  It deliberately contains
no multiplications — XLA's FMA contraction would re-round them and break
the bitwise NumPy/Pallas parity contract (kernel.py) — so applying the CCM
coefficients is a separate, backend-shared host step:

  ``combine_work``: W = alpha*L/speed + beta*Voff + gamma*Von + delta*M_H,
  feasibility from the memory planes vs the per-event caps (eq. 9), and
  infeasible pairs forced to +inf — the exact expression the scalar
  reference evaluates, applied to whole tiles at once (``combine_work``)
  or to the (N_OUT, P) planes gathered at one event's shortlisted pairs
  (``combine_work_pairs`` — the hot path; elementwise ops commute with
  the gather, so the two are bitwise-interchangeable).

The per-event dispatch itself (shape-bucket padding, the compiled f64
pipeline, the f32 128-lane path, pair gathering) lives in jit.py; this
module keeps the raw full-tile API and the shared combine.  See README.md
for the backend matrix and the two parity tiers.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np

from repro.kernels.ccm_scorer import ref
from repro.kernels.ccm_scorer.layout import (AV, N_AV, N_OUT, N_PM, N_SC,
                                             OUT, PM, SC)

__all__ = ["ccm_score_tiles", "combine_work", "combine_work_pairs", "AV",
           "PM", "SC", "OUT", "N_AV", "N_PM", "N_SC", "N_OUT", "BACKENDS"]

INF = float("inf")

#: the scorer backend matrix (see kernels/ccm_scorer/README.md):
#: f64-bitwise tier: numpy / jit / pallas (interpret);
#: f32 assignment-identity tier: pallas_compiled.
BACKENDS = ("numpy", "jit", "pallas", "pallas_compiled")


def ccm_score_tiles(av: np.ndarray, bv: np.ndarray, pm: np.ndarray,
                    sc: np.ndarray, *, backend: str = "numpy",
                    interpret: bool = True) -> np.ndarray:
    """Dispatch packed tiles to a scorer backend (full-tile API).

    ``numpy`` (the reference), ``jit`` (bucketed compiled f64) and
    ``pallas`` (interpret mode) return (E, N_OUT, A, B) float64 and agree
    BITWISE on the CPU backend, and refuse any other.  ``pallas_compiled``
    scores in f32 on 128-lane tiles (interpreted on the CPU backend only)
    and returns the exact f32 values upcast to float64 — ulp-level
    approximate, assignment-identity parity tier.
    """
    if backend == "numpy":
        return ref.score_tiles(av, bv, pm, sc)
    if backend == "jit":
        from repro.kernels.ccm_scorer import jit as scorer_jit
        return scorer_jit.score_tiles_jit(av, bv, pm, sc)
    if backend == "pallas":
        from repro.kernels.ccm_scorer import jit as scorer_jit
        return scorer_jit._pallas_score(av, bv, pm, sc, interpret=interpret)
    if backend == "pallas_compiled":
        from repro.kernels.ccm_scorer import jit as scorer_jit
        return scorer_jit.score_tiles_f32(av, bv, pm, sc)
    raise ValueError(f"unknown ccm_scorer backend: {backend!r}")


def combine_work(out: np.ndarray, sc: np.ndarray, params,
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Backend-shared affine combine: work components -> (w_a, w_b, feas).

    Mirrors ``CCMState.work`` / the scalar ``exchange_eval`` tail exactly
    (same expression tree, so the NumPy engine stays bitwise-compatible
    with the pre-kernel implementation).
    """
    speed_a = sc[:, SC.speed_a, None, None]
    speed_b = sc[:, SC.speed_b, None, None]
    # the SC cap slots are packed pre-scaled through
    # repro.core.ccm.effective_mem_cap (relative tolerance + optional
    # pressure headroom), so the combines compare plain <=
    if params.memory_constraint:
        feas = ((out[:, OUT.mem_a] <= sc[:, SC.mem_cap_a, None, None])
                & (out[:, OUT.mem_b] <= sc[:, SC.mem_cap_b, None, None]))
    else:
        feas = np.ones(out.shape[0:1] + out.shape[2:], bool)
    w_a = (params.alpha * out[:, OUT.load_a] / speed_a
           + params.beta * out[:, OUT.off_a]
           + params.gamma * out[:, OUT.on_a]
           + params.delta * out[:, OUT.hom_a])
    w_b = (params.alpha * out[:, OUT.load_b] / speed_b
           + params.beta * out[:, OUT.off_b]
           + params.gamma * out[:, OUT.on_b]
           + params.delta * out[:, OUT.hom_b])
    w_a = np.where(feas, w_a, INF)
    w_b = np.where(feas, w_b, INF)
    return w_a, w_b, feas


def combine_terms(terms: np.ndarray, sc_row: np.ndarray, params,
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Host tail of the combine when the products were computed in the
    compiled region (jit pairs path): ``terms`` is (10, P) — the eight
    coefficient-scaled work terms (a: load/off/on/hom, then b) followed by
    the two memory planes.  Only ADDS happen here (XLA:CPU would
    FMA-contract them; lone muls in the compiled region are safe), in the
    exact association order of ``combine_work``, so the results are
    bitwise-identical to the all-host combine."""
    if params.memory_constraint:
        feas = ((terms[8] <= sc_row[SC.mem_cap_a])
                & (terms[9] <= sc_row[SC.mem_cap_b]))
    else:
        feas = np.ones(terms.shape[1], bool)
    w_a = terms[0] + terms[1] + terms[2] + terms[3]
    w_b = terms[4] + terms[5] + terms[6] + terms[7]
    w_a = np.where(feas, w_a, INF)
    w_b = np.where(feas, w_b, INF)
    return w_a, w_b, feas


def combine_work_pairs(outp: np.ndarray, sc_row: np.ndarray, params,
                       ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Work combine on (N_OUT, P) planes already gathered at one event's
    shortlisted pairs.  Elementwise ops commute with the gather, so this is
    bitwise-identical per pair to ``combine_work`` on the full tile followed
    by the gather — the hot path just skips combining lanes it will never
    read.  ``sc_row`` is the event's (N_SC,) scalar row."""
    if params.memory_constraint:
        feas = ((outp[OUT.mem_a] <= sc_row[SC.mem_cap_a])
                & (outp[OUT.mem_b] <= sc_row[SC.mem_cap_b]))
    else:
        feas = np.ones(outp.shape[1], bool)
    w_a = (params.alpha * outp[OUT.load_a] / sc_row[SC.speed_a]
           + params.beta * outp[OUT.off_a]
           + params.gamma * outp[OUT.on_a]
           + params.delta * outp[OUT.hom_a])
    w_b = (params.alpha * outp[OUT.load_b] / sc_row[SC.speed_b]
           + params.beta * outp[OUT.off_b]
           + params.gamma * outp[OUT.on_b]
           + params.delta * outp[OUT.hom_b])
    w_a = np.where(feas, w_a, INF)
    w_b = np.where(feas, w_b, INF)
    return w_a, w_b, feas
