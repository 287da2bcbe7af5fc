"""Pallas kernel for the CCM stage-2 exchange-scorer tiles.

One grid step scores one lock event: the (A, B) candidate-pair tile of a
single (rank a, rank b) exchange, where A/B are the padded candidate counts
(empty candidate at index 0, masked tail past ``na``/``nb``).  A batched
lock event of E disjoint rank pairs is a single ``pallas_call`` with
``grid=(E,)`` — the block-diagonal flow decomposition means events never
read each other's planes, so the launch is embarrassingly parallel.

Bitwise contract (see ref.py): the kernel body uses ONLY additions,
subtractions, maxima, compares and selects — never a multiply or divide —
because XLA contracts ``mul+add`` into FMA and rewrites division by
constants into reciprocal multiplies, either of which would break the
bit-for-bit parity with the NumPy backend that the CCM-LB trajectory
guarantee rests on.  The affine work combine (alpha/beta/gamma/delta and
the speed divide) therefore lives in shared host code (ops.combine_work)
for BOTH backends.  Keep every expression tree here in lockstep with
ref.score_tiles.

On TPU the natural deployment pads B to the 128-lane boundary and runs in
f32; tier-1 CI runs the kernel with ``interpret=True`` on CPU in f64, where
it is held bitwise-equal to the reference.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ccm_scorer.layout import AV, N_OUT, OUT, PM, SC


class _Scalars:
    """``sc[i]`` reads scalar i of this grid step's event.  The whole
    (E, N_SC) scalar array sits in SMEM: a (1, N_SC) block would break the
    TPU's (8, 128) block tiling, and the kernel only ever needs the
    scalars one at a time."""

    def __init__(self, ref):
        self._ref = ref
        self._e = pl.program_id(0)

    def __getitem__(self, i):
        return self._ref[self._e, i]


def _scorer_kernel(av_ref, bv_ref, pm_ref, sc_ref, o_ref):
    av = av_ref[0]          # (N_AV, A)
    bv = bv_ref[0]          # (N_AV, B)
    pm = pm_ref[0]          # (N_PM, A, B)
    sc = _Scalars(sc_ref)   # (N_SC,) in SMEM, read one scalar at a time
    a_n = av.shape[1]
    b_n = bv.shape[1]

    def col(i):
        return av[i][:, None]

    def row(i):
        return bv[i][None, :]

    def colv(v):
        return v[:, None]

    def rowv(v):
        return v[None, :]

    x_ab, x_ba = pm[PM.x_ab], pm[PM.x_ba]
    cs_a, ch_a = pm[PM.cs_a], pm[PM.ch_a]
    cs_b, ch_b = pm[PM.cs_b], pm[PM.ch_b]

    # --- flows after the exchange (expression trees == ref.py) -----------
    sent_a = (x_ba + rowv(bv[AV.out_own] - bv[AV.intra] + bv[AV.out_other])
              + colv(av[AV.in_own] - av[AV.intra])
              + (sc[SC.f_ab] - col(AV.out_peer) - row(AV.in_peer) + x_ab)
              + (sc[SC.f_ao] - col(AV.out_other)))
    recv_a = (x_ab + rowv(bv[AV.in_own] - bv[AV.intra] + bv[AV.in_other])
              + colv(av[AV.out_own] - av[AV.intra])
              + (sc[SC.f_ba] - row(AV.out_peer) - col(AV.in_peer) + x_ba)
              + (sc[SC.f_oa] - col(AV.in_other)))
    on_a = (row(AV.intra) + (row(AV.out_peer) - x_ba)
            + (row(AV.in_peer) - x_ab)
            + (sc[SC.f_aa] - colv(av[AV.out_own] + av[AV.in_own]
                                  - av[AV.intra])))
    sent_b = (x_ab + colv(av[AV.out_own] - av[AV.intra] + av[AV.out_other])
              + rowv(bv[AV.in_own] - bv[AV.intra])
              + (sc[SC.f_ba] - row(AV.out_peer) - col(AV.in_peer) + x_ba)
              + (sc[SC.f_bo] - row(AV.out_other)))
    recv_b = (x_ba + colv(av[AV.in_own] - av[AV.intra] + av[AV.in_other])
              + rowv(bv[AV.out_own] - bv[AV.intra])
              + (sc[SC.f_ab] - col(AV.out_peer) - row(AV.in_peer) + x_ab)
              + (sc[SC.f_ob] - row(AV.in_other)))
    on_b = (col(AV.intra) + (col(AV.out_peer) - x_ab)
            + (col(AV.in_peer) - x_ba)
            + (sc[SC.f_bb] - rowv(bv[AV.out_own] + bv[AV.in_own]
                                  - bv[AV.intra])))

    off_a = jnp.maximum(
        sc[SC.base_sent_a] + (sent_a - (sc[SC.f_ab] + sc[SC.f_ao])),
        sc[SC.base_recv_a] + (recv_a - (sc[SC.f_ba] + sc[SC.f_oa])))
    off_b = jnp.maximum(
        sc[SC.base_sent_b] + (sent_b - (sc[SC.f_ba] + sc[SC.f_bo])),
        sc[SC.base_recv_b] + (recv_b - (sc[SC.f_ab] + sc[SC.f_ob])))
    on_a = sc[SC.vol_aa] + (on_a - sc[SC.f_aa])
    on_b = sc[SC.vol_bb] + (on_b - sc[SC.f_bb])

    load_a = sc[SC.load_a] - col(AV.load) + row(AV.load)
    load_b = sc[SC.load_b] + col(AV.load) - row(AV.load)

    shared_a = sc[SC.shared_a] - col(AV.s_rm) + row(AV.s_add_peer) + cs_a
    shared_b = sc[SC.shared_b] - row(AV.s_rm) + col(AV.s_add_peer) + cs_b
    hom_a = sc[SC.hom_a] - col(AV.h_rm) + row(AV.h_add_peer) + ch_a
    hom_b = sc[SC.hom_b] - row(AV.h_rm) + col(AV.h_add_peer) + ch_b

    mem_a = (sc[SC.mem_base_a] + sc[SC.mem_task_a] - col(AV.mem)
             + row(AV.mem) + shared_a
             + jnp.maximum(sc[SC.ovh_a], row(AV.ovh)))
    mem_b = (sc[SC.mem_base_b] + sc[SC.mem_task_b] + col(AV.mem)
             - row(AV.mem) + shared_b
             + jnp.maximum(sc[SC.ovh_b], col(AV.ovh)))

    # --- masked tail -----------------------------------------------------
    # Mosaic builds iotas in int32 only
    ia = jax.lax.broadcasted_iota(jnp.int32, (a_n, b_n), 0).astype(av.dtype)
    ib = jax.lax.broadcasted_iota(jnp.int32, (a_n, b_n), 1).astype(av.dtype)
    mask = (ia <= sc[SC.na]) & (ib <= sc[SC.nb])
    zero = jnp.zeros((), av.dtype)
    inf = jnp.full((), jnp.inf, av.dtype)

    o_ref[0, OUT.load_a] = jnp.where(mask, load_a, zero)
    o_ref[0, OUT.load_b] = jnp.where(mask, load_b, zero)
    o_ref[0, OUT.off_a] = jnp.where(mask, off_a, zero)
    o_ref[0, OUT.off_b] = jnp.where(mask, off_b, zero)
    o_ref[0, OUT.on_a] = jnp.where(mask, on_a, zero)
    o_ref[0, OUT.on_b] = jnp.where(mask, on_b, zero)
    o_ref[0, OUT.hom_a] = jnp.where(mask, hom_a, zero)
    o_ref[0, OUT.hom_b] = jnp.where(mask, hom_b, zero)
    o_ref[0, OUT.mem_a] = jnp.where(mask, mem_a, inf)
    o_ref[0, OUT.mem_b] = jnp.where(mask, mem_b, inf)


@functools.partial(jax.jit, static_argnames=("interpret",))
def score_tiles_fwd(av, bv, pm, sc, *, interpret: bool = True):
    """av: (E, N_AV, A), bv: (E, N_AV, B), pm: (E, N_PM, A, B),
    sc: (E, N_SC) -> (E, N_OUT, A, B), one grid step per event."""
    e_n, n_av, a_n = av.shape
    b_n = bv.shape[2]
    n_pm = pm.shape[1]
    return pl.pallas_call(
        _scorer_kernel,
        grid=(e_n,),
        in_specs=[
            pl.BlockSpec((1, n_av, a_n), lambda e: (e, 0, 0)),
            pl.BlockSpec((1, n_av, b_n), lambda e: (e, 0, 0)),
            pl.BlockSpec((1, n_pm, a_n, b_n), lambda e: (e, 0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, N_OUT, a_n, b_n), lambda e: (e, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((e_n, N_OUT, a_n, b_n), av.dtype),
        interpret=interpret,
    )(av, bv, pm, sc)
