"""Compiled shape-bucketed scorer runtime: the jitted event pipeline.

Why buckets
-----------
``jax.jit`` specializes on input shapes: every distinct (E, A, B, P)
quadruple triggers a fresh trace + XLA compile.  Lock-event tiles are small
but their shapes churn (candidate counts vary per rank pair, shortlists
vary per event), so naive jitting would re-trace on the hot path — worse
than the numpy dispatch it replaces.  The launcher therefore pads every
tile into a small, fixed grid of *shape buckets*:

  * lane dims A/B (padded candidate counts): powers of two in
    [8, 128], then multiples of 128 — ``bucket_lanes``.  128 is the TPU
    lane boundary, so a bucket that reaches it stops specializing and
    grows in whole lanes instead.
  * the event dim E and the shortlist dim P: powers of two
    (``bucket_events`` / ``bucket_pairs``; P additionally floors at 32,
    the default shortlist cap, so one P bucket serves every
    normally-sized event).

With ``max_candidates=12`` and ``shortlist=32`` a whole CCM-LB trajectory
touches a handful of buckets; each compiles exactly once
(tests/test_scorer_jit.py guards the recompile count via
:func:`trace_count`).

What is fused
-------------
One jitted function per bucket evaluates the full scorer expression tree
(ref.score_tiles_xp traced with ``xp=jax.numpy`` — the SAME source
expressions as the numpy backend) and gathers the shortlisted (ia, ib)
pairs, so the host receives (E, P, N_OUT) instead of (E, N_OUT, A, B).
Padding is invariant by construction: every op in the tree is elementwise
over the (A, B) tile, so padded lanes cannot perturb real ones, and the
f64 outputs on real lanes are BITWISE-equal to the unpadded numpy backend
(adds/subs/maxima/selects only — nothing XLA can re-round).

The affine work combine stays on the host (ops.combine_work_pairs, shared
by every backend) for the same reason it is not in the Pallas kernel:
XLA:CPU compiles with ``FPOpFusion::Fast`` at instruction selection, so any
``mul`` feeding an ``add`` becomes an FMA **regardless of IR-level
fast-math flags** — measured on this tree: ``jit(0.37*x + 0.21*y)`` equals
``fma(0.37, x, 0.21*y)``, and neither ``lax.optimization_barrier`` nor
bitcast round-trips survive the simplifier to block it.  A fused combine
therefore cannot meet the bitwise f64 parity bar on CPU; combining on the
(P,)-gathered host side costs ~10 tiny numpy ops per event and keeps the
contract exact.

The speculative-scan path
-------------------------
``kind="spec"`` buckets compile the OTHER direction of the same trade: one
launch scores a whole *window* of upcoming lock events, and the per-event
feature assembly itself — the group-flow matrix bincount and every slice
sum ``PhaseEngine._flow_matrices`` / ``_event_features`` used to run on the
host — moves into the traced body.  The host ships raw ingredients (edge
bins + volumes, the non-flow feature rows, a scalar row with the flow
slots zeroed) as ONE flat f64 row per event; the traced body scatter-adds
the flow matrix, derives all flow-dependent features, scores the shortlist
through the SAME ``ref.score_planes`` expression tree, applies the work
combine and the selection rule in-trace, and returns only the winning pair
per event.  A ``jax.lax.scan`` over the window axis (``mode="scan"``) or a
``jax.vmap`` over independent instances (``mode="vmap"``) wraps one shared
per-event body, so every window/fleet size reuses the same trace.

This path CANNOT meet the bitwise f64 bar: the scatter-add segment sums
combine duplicate bins in an XLA-chosen order, while the host reference's
``np.bincount`` accumulates sequentially (and numpy's ``.sum()`` pairwise
summation differs from XLA's reduce order), so the flow features differ by
summation-order ulps.  It therefore sits in its own *compiled-vs-host*
parity tier — end-to-end assignment identity on the ccmlb_scaling
instances plus a tracked ulp budget, with the host engine path kept as
the reference twin (README.md documents the full ladder).

The f32 compiled path
---------------------
``backend="pallas_compiled"`` packs the same tiles in float32 with B padded
to the 128-lane boundary (A to the 8-sublane boundary) and launches the
Pallas kernel with ``interpret=False``.  On the CPU backend, which has no
Pallas compile target, the launcher runs the same f32 kernel in interpret
mode — same dtype, same layout, same masked tail — and records it in
:func:`pallas_compiled_fallback`; on any other backend a lowering error
raises.  The f32 path's parity bar is
*assignment identity* on well-separated instances (scores differ from f64
by ulps of f32), not bitwise equality; tests/test_scorer_jit.py implements
the bar and reports the ulp budget on adversarial tiles.
"""
from __future__ import annotations

from typing import List, Optional, Sequence, Tuple

import numpy as np

from repro.kernels.ccm_scorer import ref
from repro.kernels.ccm_scorer.layout import N_AV, N_OUT, N_PM, N_SC, OUT, SC

__all__ = ["bucket_lanes", "bucket_events", "bucket_pairs", "bucket_edges",
           "score_events", "score_spec", "spec_warmup",
           "score_tiles_jit", "score_tiles_f32", "trace_count",
           "bucket_cache_size", "pallas_compiled_fallback", "LANE_CAP"]

LANE_CAP = 128      # TPU lane boundary: buckets stop doubling here
_LANE_FLOOR = 8     # sublane quantum; also the smallest useful tile

_TRACE_COUNT = 0          # incremented inside every traced body
_FN_CACHE: dict = {}      # bucket key -> compiled callable
_COMPILED_FALLBACK = False


# ------------------------------------------------------------- bucket grid
def bucket_lanes(n: int, *, floor: int = _LANE_FLOOR,
                 cap: int = LANE_CAP) -> int:
    """Round a lane count up to the bucket grid: powers of two in
    [floor, cap], multiples of ``cap`` beyond it."""
    n = max(int(n), 1)
    if n <= floor:
        return floor
    if n >= cap:
        return -(-n // cap) * cap
    return 1 << (n - 1).bit_length()


def bucket_events(e: int) -> int:
    """Event-axis bucket: next power of two (E is small — the
    ``batch_lock_events`` cap)."""
    e = max(int(e), 1)
    return 1 << (e - 1).bit_length()


def bucket_pairs(p: int) -> int:
    """Shortlist-axis bucket: powers of two with a floor of 32 (the default
    shortlist cap) — one bucket serves every normally-sized event, so P
    churn cannot multiply the compile count."""
    p = max(int(p), 1)
    return max(32, 1 << (p - 1).bit_length())


def bucket_edges(n: int) -> int:
    """Edge-axis bucket for the speculative-scan rows: powers of two with a
    floor of 32.  Incident-edge counts churn per rank pair, so without the
    pow2 grid every distinct count would be a fresh compile; with it a whole
    trajectory touches at most log2(max incident edges) edge buckets."""
    n = max(int(n), 1)
    return max(32, 1 << (n - 1).bit_length())


def trace_count() -> int:
    """How many times a bucketed scorer body has been TRACED (== compiled,
    barring jax's persistent cache).  The recompile-count guard asserts this
    stays bounded by the number of distinct buckets."""
    return _TRACE_COUNT


def bucket_cache_size() -> int:
    return len(_FN_CACHE)


def bucket_keys() -> list:
    """The distinct compiled bucket keys, stringified (kind plus the static
    shape info).  Each key traces exactly once per process, so together
    with ``trace_count()`` this is the per-bucket compile ledger the
    benchmarks record PR to PR."""
    return sorted(str(k) for k in _FN_CACHE)


# --------------------------------------------------------- compiled bodies
def _pair_offsets(p: int) -> Tuple[int, ...]:
    """Cumulative offsets of [avp | bvp | pmp | sc | iaf | ibf | coeffs]
    in one flat per-event row of the pair-gathered layout (coeffs =
    alpha/beta/gamma/delta).  A single input array keeps the host->device
    transfer to ONE numpy conversion per launch — with several separate
    small arrays the per-array ingest dominates the whole dispatch
    (~30 us each on CPU)."""
    o_av = N_AV * p
    o_bv = o_av + N_AV * p
    o_pm = o_bv + N_PM * p
    o_sc = o_pm + N_SC
    o_ia = o_sc + p
    o_ib = o_ia + p
    o_cf = o_ib + 4
    return o_av, o_bv, o_pm, o_sc, o_ia, o_ib, o_cf


def _spec_offsets(eb: int, a_n: int, b_n: int, p_n: int) -> Tuple[int, ...]:
    """Cumulative offsets of
    ``[bins | w | avh | bvh | pmh | sch | iaf | ibf | misc]`` in one flat
    per-event row of the speculative-scan layout (misc = alpha, beta,
    gamma, delta, w_before, p_count).  ``bins``/``w`` are the flow-matrix
    scatter inputs (eb edge slots each), ``avh``/``bvh`` the seven host-side
    candidate feature rows (AV.load..AV.h_add_peer), ``pmh`` the four
    host-side pairwise correction planes gathered at the shortlist, ``sch``
    the scalar row with the eight flow slots zeroed (filled in-trace).
    One flat f64 row per event for the same reason as ``_pair_offsets``:
    per-array device ingest would dominate the launch."""
    o_w = eb
    o_av = o_w + eb
    o_bv = o_av + 7 * a_n
    o_pm = o_bv + 7 * b_n
    o_sc = o_pm + 4 * p_n
    o_ia = o_sc + N_SC
    o_ib = o_ia + p_n
    o_ms = o_ib + p_n
    return o_w, o_av, o_bv, o_pm, o_sc, o_ia, o_ib, o_ms, o_ms + 6


def _get_fn(key):
    """Per-bucket compiled function.  key = (kind, *static shape info)."""
    fn = _FN_CACHE.get(key)
    if fn is None:
        import jax
        import jax.numpy as jnp

        kind = key[0]
        if kind == "pairs":
            # the hot path: pair-gathered scoring.  Tiles are gathered at
            # the shortlist on the host, so the compiled work is O(P) per
            # event — independent of the candidate counts — and the bucket
            # grid collapses to (E, P) keys.  The combine's multiplies and
            # divides also run here: a lone mul whose result feeds an
            # OUTPUT (not an add) cannot be FMA-contracted, so the bits
            # match the host products exactly; only the adds (which XLA
            # would contract) remain on the host (ops.combine_terms).
            _, e_n, p_n = key
            o_av, o_bv, o_pm, o_sc, o_ia, o_ib, o_cf = _pair_offsets(p_n)

            def body(buf):
                global _TRACE_COUNT
                _TRACE_COUNT += 1           # runs at trace time only
                avp = buf[:, :o_av].reshape(e_n, N_AV, p_n)
                bvp = buf[:, o_av:o_bv].reshape(e_n, N_AV, p_n)
                pmp = buf[:, o_bv:o_pm].reshape(e_n, N_PM, p_n)
                sc = buf[:, o_pm:o_sc]
                iaf = buf[:, o_sc:o_ia]
                ibf = buf[:, o_ia:o_ib]
                out = ref.score_pairs_xp(avp, bvp, pmp, sc, iaf, ibf,
                                         xp=jnp)     # (E, N_OUT, P)
                al = buf[:, o_ib + 0, None]
                be = buf[:, o_ib + 1, None]
                ga = buf[:, o_ib + 2, None]
                de = buf[:, o_ib + 3, None]
                terms = [
                    al * out[:, OUT.load_a] / sc[:, SC.speed_a, None],
                    be * out[:, OUT.off_a],
                    ga * out[:, OUT.on_a],
                    de * out[:, OUT.hom_a],
                    al * out[:, OUT.load_b] / sc[:, SC.speed_b, None],
                    be * out[:, OUT.off_b],
                    ga * out[:, OUT.on_b],
                    de * out[:, OUT.hom_b],
                    out[:, OUT.mem_a],
                    out[:, OUT.mem_b],
                ]
                return jnp.stack(terms, axis=1)      # (E, 10, P)
        elif kind == "spec":
            # the speculative-scan path: the WHOLE per-event pipeline —
            # flow-matrix assembly (scatter-add over the fixed group-label
            # layout), slice-sum feature derivation, the score_planes
            # expression tree, the work combine AND the selection rule —
            # runs in-trace, once per window row.  Only the winning pair
            # index and its scores leave the device, so a window of W
            # events costs one dispatch instead of W.
            _, mode, w_n, eb, a_n, b_n, p_n = key
            (o_w, o_av, o_bv, o_pm, o_sc, o_ia, o_ib, o_ms,
             _row_len) = _spec_offsets(eb, a_n, b_n, p_n)
            # fixed group-label layout (mirrors PhaseEngine.spec_raw):
            # 0 = other ranks, 1 = stays on a, 2 = stays on b,
            # a-candidate i at sa + (i - 1), b-candidate j at sb + (j - 1).
            sa = 3
            sb = 3 + (a_n - 1)
            g_n = sb + (b_n - 1)

            def one(row):
                bins = row[:o_w].astype(jnp.int32)
                wgt = row[o_w:o_av]
                F = (jnp.zeros(g_n * g_n, row.dtype).at[bins].add(wgt)
                     .reshape(g_n, g_n))
                # slice sums over the fixed layout; unused candidate groups
                # received no edges, so their contribution is exactly zero
                row_to_a = F[:, 1] + F[:, sa:sb].sum(1)     # -> rank a
                row_to_b = F[:, 2] + F[:, sb:].sum(1)       # -> rank b
                col_from_a = F[1, :] + F[sa:sb, :].sum(0)   # rank a ->
                col_from_b = F[2, :] + F[sb:, :].sum(0)     # rank b ->
                ar = jnp.arange(sa, sb)
                br = jnp.arange(sb, g_n)
                z1 = jnp.zeros((1,), row.dtype)
                # in-trace AV rows 0..6 (flow-derived); rows 7..13 ride in
                # from the host (avh) — same split as _event_features
                avf = jnp.stack([
                    jnp.concatenate([z1, F[ar, ar]]),            # intra
                    jnp.concatenate([z1, row_to_a[sa:sb]]),      # out_own
                    jnp.concatenate([z1, col_from_a[sa:sb]]),    # in_own
                    jnp.concatenate([z1, row_to_b[sa:sb]]),      # out_peer
                    jnp.concatenate([z1, col_from_b[sa:sb]]),    # in_peer
                    jnp.concatenate([z1, F[ar, 0]]),             # out_other
                    jnp.concatenate([z1, F[0, ar]]),             # in_other
                ])
                bvf = jnp.stack([
                    jnp.concatenate([z1, F[br, br]]),
                    jnp.concatenate([z1, row_to_b[sb:]]),
                    jnp.concatenate([z1, col_from_b[sb:]]),
                    jnp.concatenate([z1, row_to_a[sb:]]),
                    jnp.concatenate([z1, col_from_a[sb:]]),
                    jnp.concatenate([z1, F[br, 0]]),
                    jnp.concatenate([z1, F[0, br]]),
                ])
                av = jnp.concatenate(
                    [avf, row[o_av:o_bv].reshape(7, a_n)], axis=0)
                bv = jnp.concatenate(
                    [bvf, row[o_bv:o_pm].reshape(7, b_n)], axis=0)
                flows = jnp.stack([
                    row_to_b[1] + row_to_b[sa:sb].sum(),    # f_ab
                    row_to_a[2] + row_to_a[sb:].sum(),      # f_ba
                    row_to_a[1] + row_to_a[sa:sb].sum(),    # f_aa
                    row_to_b[2] + row_to_b[sb:].sum(),      # f_bb
                    F[1, 0] + F[sa:sb, 0].sum(),            # f_ao
                    F[0, 1] + F[0, sa:sb].sum(),            # f_oa
                    F[2, 0] + F[sb:, 0].sum(),              # f_bo
                    F[0, 2] + F[0, sb:].sum(),              # f_ob
                ])
                sc = row[o_sc:o_ia].at[:8].set(flows)
                ia = row[o_ia:o_ib].astype(jnp.int32)
                ib = row[o_ib:o_ms].astype(jnp.int32)
                avp = av[:, ia]                             # (14, P)
                bvp = bv[:, ib]
                on_pair = (ia >= 1) & (ib >= 1)
                x_ab = jnp.where(on_pair, F[sa - 1 + ia, sb - 1 + ib], 0.0)
                x_ba = jnp.where(on_pair, F[sb - 1 + ib, sa - 1 + ia], 0.0)
                pm = jnp.concatenate(
                    [jnp.stack([x_ab, x_ba]),
                     row[o_pm:o_sc].reshape(4, p_n)], axis=0)   # (6, P)
                planes = ref.score_planes(
                    col=lambda i: avp[i], row=lambda i: bvp[i],
                    scal=lambda i: sc[i], pmp=lambda i: pm[i], xp=jnp)
                # in-trace combine + selection.  FMA contraction is fine
                # here: this path's parity bar is compiled-vs-host (ulp
                # budget + assignment identity), not bitwise f64.
                al, be = row[o_ms + 0], row[o_ms + 1]
                ga, de = row[o_ms + 2], row[o_ms + 3]
                w_before = row[o_ms + 4]
                p_cnt = row[o_ms + 5]
                w_a = (al * planes[OUT.load_a] / sc[SC.speed_a]
                       + be * planes[OUT.off_a] + ga * planes[OUT.on_a]
                       + de * planes[OUT.hom_a])
                w_b = (al * planes[OUT.load_b] / sc[SC.speed_b]
                       + be * planes[OUT.off_b] + ga * planes[OUT.on_b]
                       + de * planes[OUT.hom_b])
                # spec_raw packs the caps pre-scaled by effective_mem_cap
                # (inf when the constraint is off), so compare plain <=
                feas = ((planes[OUT.mem_a] <= sc[SC.mem_cap_a])
                        & (planes[OUT.mem_b] <= sc[SC.mem_cap_b]))
                valid = jnp.arange(p_n) < p_cnt
                diff = w_before - jnp.maximum(w_a, w_b)
                # argmax picks the FIRST max over the same candidate order
                # select_best walks, so selection matches the host rule
                score = jnp.where(valid & feas & (diff > 1e-12),
                                  diff, -jnp.inf)
                j = jnp.argmax(score)
                return jnp.stack([j.astype(row.dtype), score[j],
                                  w_a[j], w_b[j]])

            if mode == "scan":
                def body(buf):
                    global _TRACE_COUNT
                    _TRACE_COUNT += 1
                    _, out = jax.lax.scan(
                        lambda c, r: (c, one(r)),
                        jnp.zeros((), jnp.int32), buf)
                    return out                      # (W, 4)
            elif mode == "vmap":
                def body(buf):
                    global _TRACE_COUNT
                    _TRACE_COUNT += 1
                    return jax.vmap(one)(buf)       # (W, 4)
            else:                           # pragma: no cover
                raise ValueError(f"unknown spec mode: {mode!r}")
            del w_n                         # shape carried by buf itself
        elif kind == "full":
            def body(av, bv, pm, sc):
                global _TRACE_COUNT
                _TRACE_COUNT += 1
                return ref.score_tiles_xp(av, bv, pm, sc, xp=jnp)
        else:                               # pragma: no cover
            raise ValueError(f"unknown bucketed fn kind: {kind!r}")
        fn = jax.jit(body)
        _FN_CACHE[key] = fn
    return fn


def _x64():
    import jax
    return jax.enable_x64(True)


def _bitwise_x64():
    """x64 mode for the f64 bitwise tier (``jit``, ``pallas`` interpret),
    whose bar is bit-for-bit equality with the numpy reference.  That bar
    holds on XLA:CPU.  The TPU has no f64 unit and XLA emulates f64 there
    with pairs of f32, which does not round like numpy: measured on a v5e,
    2735 of 2880 finite lanes of random full tiles differed from numpy, by
    up to 1.4e-12 relative.  Off the CPU these backends therefore raise;
    ``pallas_compiled`` is the accelerator path."""
    import jax
    backend = jax.default_backend()
    if backend != "cpu":
        raise NotImplementedError(
            f"the f64 scorer backends ('jit', 'pallas') are bitwise-equal "
            f"to numpy only on XLA:CPU; {backend!r} emulates f64 and rounds "
            "differently: use backend='pallas_compiled'")
    return jax.enable_x64(True)


# -------------------------------------------------------------- f32 Pallas
def pallas_compiled_fallback() -> bool:
    """True when a ``pallas_compiled`` launch has run the f32 kernel in
    interpret mode, which happens only on the CPU backend."""
    return _COMPILED_FALLBACK


def _pallas_score(av, bv, pm, sc, *, interpret: bool):
    import jax

    from repro.kernels.ccm_scorer.kernel import score_tiles_fwd
    if av.dtype == np.float64:
        with _bitwise_x64():
            return np.asarray(score_tiles_fwd(av, bv, pm, sc,
                                              interpret=interpret))
    return np.asarray(score_tiles_fwd(av, bv, pm, sc, interpret=interpret))


def _f32_pads(a_n: int, b_n: int) -> Tuple[int, int]:
    """The f32 deployment tile rounding: A to the 8-sublane boundary, B to
    the 128-lane boundary — ONE definition for both f32 entry points (the
    launcher and the raw full-tile API), so the layout contract the README
    documents cannot fork."""
    return (bucket_lanes(a_n, floor=_LANE_FLOOR, cap=_LANE_FLOOR),
            bucket_lanes(b_n, floor=LANE_CAP, cap=LANE_CAP))


def _pallas_compiled_score(av32, bv32, pm32, sc32):
    """The f32 kernel, compiled for the default backend.  Only the CPU
    backend, which has no Pallas compile target, interprets it; anywhere
    else a lowering error raises."""
    global _COMPILED_FALLBACK
    import jax
    interpret = jax.default_backend() == "cpu"
    _COMPILED_FALLBACK |= interpret
    return _pallas_score(av32, bv32, pm32, sc32, interpret=interpret)


# ------------------------------------------------------------ tile packing
def _pack(feats, a_pad: int, b_pad: int, e_pad: int, dtype) -> Tuple:
    av = np.zeros((e_pad, N_AV, a_pad), dtype)
    bv = np.zeros((e_pad, N_AV, b_pad), dtype)
    pm = np.zeros((e_pad, N_PM, a_pad, b_pad), dtype)
    sc = np.zeros((e_pad, N_SC), dtype)
    for k, (av_k, bv_k, pm_k, sc_k) in enumerate(feats):
        av[k, :, :av_k.shape[1]] = av_k
        bv[k, :, :bv_k.shape[1]] = bv_k
        pm[k, :, :pm_k.shape[1], :pm_k.shape[2]] = pm_k
        sc[k] = sc_k
    # pad events are never returned (the launcher slices to real events)
    # and their na = nb = 0 mask leaves only the (0, 0) lane live; give
    # them unit speeds so a full-tile combine doesn't divide by zero
    if len(feats) < e_pad:
        sc[len(feats):, SC.speed_a] = 1.0
        sc[len(feats):, SC.speed_b] = 1.0
    return av, bv, pm, sc


# ------------------------------------------------------------ full tiles
def score_tiles_jit(av: np.ndarray, bv: np.ndarray, pm: np.ndarray,
                    sc: np.ndarray) -> np.ndarray:
    """Full-tile f64 scoring through the bucketed compiled path: pads the
    tiles into their shape bucket, scores, and slices back to the caller's
    shape.  Bitwise-equal to ``ref.score_tiles`` on every returned lane."""
    e_n, _, a_n = av.shape
    b_n = bv.shape[2]
    a_pad, b_pad = bucket_lanes(a_n), bucket_lanes(b_n)
    e_pad = bucket_events(e_n) if e_n else 1
    feats = [(av[k], bv[k], pm[k], sc[k]) for k in range(e_n)]
    avp, bvp, pmp, scp = _pack(feats, a_pad, b_pad, e_pad, np.float64)
    fn = _get_fn(("full", e_pad, a_pad, b_pad))
    with _bitwise_x64():
        out = np.asarray(fn(avp, bvp, pmp, scp))
    return out[:e_n, :, :a_n, :b_n]


def score_tiles_f32(av: np.ndarray, bv: np.ndarray, pm: np.ndarray,
                    sc: np.ndarray) -> np.ndarray:
    """Full-tile scoring through the f32 compiled-Pallas path (B padded to
    the 128-lane boundary, A to the sublane boundary; interpreted on the
    CPU backend only).  Returns float64 holding the exact f32
    values (upcast is lossless)."""
    e_n, _, a_n = av.shape
    b_n = bv.shape[2]
    a_pad, b_pad = _f32_pads(a_n, b_n)
    e_pad = bucket_events(e_n) if e_n else 1
    feats = [(av[k], bv[k], pm[k], sc[k]) for k in range(e_n)]
    avp, bvp, pmp, scp = _pack(feats, a_pad, b_pad, e_pad, np.float32)
    out = _pallas_compiled_score(avp, bvp, pmp, scp)
    return np.asarray(out[:e_n, :, :a_n, :b_n], np.float64)


def warmup(max_candidates: int = 12, shortlist: int = 32,
           max_batch: int = 1) -> int:
    """Pre-compile the jit buckets a CCM-LB run with these knobs can touch
    (the shortlist P bucket and the event buckets up to ``max_batch``; the
    pair-gathered hot path is lane-free, so candidate counts do not add
    buckets).  Benchmarks call this so the timed region measures the
    steady-state runtime, not one-off XLA compiles; a persistent jax
    compilation cache (CI) makes even the first warmup cheap.  Returns the
    number of buckets now compiled."""
    del max_candidates      # lane-free: kept for call-site readability
    p_pad = bucket_pairs(shortlist)
    e = 1
    e_buckets = []
    while e <= bucket_events(max_batch):
        e_buckets.append(e)
        e *= 2
    import jax

    # the throwaway warm inputs are meaningless, and XLA's speculative
    # evaluation can surface transient NaNs from them that the real hot
    # path never produces — mask the nan checker for the warm calls only
    debug_nans = jax.config.jax_debug_nans
    jax.config.update("jax_debug_nans", False)
    try:
        with _bitwise_x64():
            for e_pad in e_buckets:
                fn = _get_fn(("pairs", e_pad, p_pad))
                o_pm = _pair_offsets(p_pad)[2]       # sc row starts here
                buf = np.zeros((e_pad, _pair_offsets(p_pad)[-1]))
                buf[:, o_pm + SC.speed_a] = 1.0      # no 0/0 lanes
                buf[:, o_pm + SC.speed_b] = 1.0
                fn(buf)
    finally:
        jax.config.update("jax_debug_nans", debug_nans)
    return bucket_cache_size()


# ------------------------------------------------- the speculative launcher
def score_spec(raws: Sequence[Tuple[np.ndarray, int]], *, a_lanes: int,
               b_lanes: int, p_n: int, mode: str = "scan",
               window: Optional[int] = None) -> np.ndarray:
    """Score a window of speculative lock events in ONE compiled launch.

    ``raws``: per-event ``(row, eb)`` pairs as built by
    ``PhaseEngine.spec_raw`` — ``row`` a complete launch row in the
    ``_spec_offsets(eb, a_lanes, b_lanes, p_n)`` layout (params columns,
    pair count and the driver's w_before already baked in), ``eb`` its
    edge bucket.  Rows sharing the window's edge bucket stack verbatim;
    a smaller row lands with three slice copies, since everything after
    its ``[bins | w]`` head is eb-independent.  Returns ``(len(raws), 4)``
    float64 rows ``[pair slot, diff, w_a, w_b]``: the in-trace selection's
    winning shortlist slot, its work improvement (``-inf`` when no
    feasible improving pair exists — the event is a no-op), and the
    winner's resulting per-rank works.

    ``mode="scan"`` compiles a ``lax.scan`` over the window axis (the solo
    speculative driver), ``mode="vmap"`` a ``jax.vmap`` (the fleet mode);
    both share the identical per-event body.  Outputs sit in the
    compiled-vs-host parity tier (see module docstring), NOT the bitwise
    f64 tier.
    """
    n = len(raws)
    if n == 0:
        return np.zeros((0, 4))
    # bucket on the FILL, not the configured window: a short disjoint
    # prefix then runs a correspondingly small compiled scan instead of
    # padding to the window bucket (pad rows compute in-trace, so window-
    # sized buckets made large windows net losers).  ``window`` remains
    # the warmup hint for the bucket ladder's top.
    del window
    w_n = bucket_events(n)
    eb = max(r[1] for r in raws)
    o_sc, row_len = _spec_offsets(eb, a_lanes, b_lanes, p_n)[4::4]
    buf = np.zeros((w_n, row_len))
    if all(r[1] == eb for r in raws):
        for k, (row, _) in enumerate(raws):
            buf[k] = row
    else:
        for k, (row, e_k) in enumerate(raws):
            buf[k, :e_k] = row[:e_k]            # bins (pad bins stay in
            buf[k, eb:eb + e_k] = row[e_k:2 * e_k]  # (0, 0)); w
            buf[k, 2 * eb:] = row[2 * e_k:]     # the eb-independent tail
    # pad event rows: unit speeds so the in-trace divide cannot 0/0
    # (their p_count stays 0, masking them out of the in-trace argmax)
    buf[n:, o_sc + SC.speed_a] = 1.0
    buf[n:, o_sc + SC.speed_b] = 1.0
    fn = _get_fn(("spec", mode, w_n, eb, a_lanes, b_lanes, p_n))
    with _x64():
        out = np.asarray(fn(buf))
    return out[:n]


def spec_warmup(*, max_candidates: int = 12, shortlist: int = 32,
                window: int = 8, edges: Sequence[int] = (256,),
                modes: Sequence[str] = ("scan",)) -> int:
    """Pre-compile the speculative-scan buckets a run with these knobs can
    touch: the power-of-two fill ladder up to the window bucket, per
    (mode, edge bucket) — the lane and pair buckets are pinned by
    ``max_candidates``/``shortlist``.  (``score_spec`` buckets on the
    actual fill, so a run with window W touches every ladder rung, not
    just the top.)  Pass the edge buckets the instance family reaches
    (``bucket_edges`` of typical incident-edge counts); benchmarks call
    this so the timed region holds no XLA compiles.  Returns the number
    of buckets now compiled."""
    import jax

    lanes = bucket_lanes(max_candidates + 1)
    p_n = bucket_pairs(min(max_candidates * (max_candidates + 2),
                           shortlist))
    debug_nans = jax.config.jax_debug_nans
    jax.config.update("jax_debug_nans", False)
    try:
        for mode in modes:
            for e in edges:
                eb = bucket_edges(e)
                o_sc, row_len = _spec_offsets(eb, lanes, lanes, p_n)[4::4]
                row = np.zeros(row_len)
                row[o_sc + SC.speed_a] = 1.0    # no 0/0 lanes
                row[o_sc + SC.speed_b] = 1.0
                w = 1
                while w <= bucket_events(window):
                    score_spec([(row, eb)] * w, a_lanes=lanes,
                               b_lanes=lanes, p_n=p_n, mode=mode,
                               window=window)
                    w *= 2
    finally:
        jax.config.update("jax_debug_nans", debug_nans)
    return bucket_cache_size()


# -------------------------------------------------------- the event launcher
def score_events(feats: Sequence[Tuple], pairs_list: Sequence[np.ndarray],
                 params, *, backend: str = "numpy", interpret: bool = True,
                 ) -> List[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Score a batch of lock events through one backend launch.

    ``feats``: per-event unpadded feature tuples ``(av, bv, pm, sc)`` as
    built by ``PhaseEngine._event_features`` (av: (N_AV, na+1), ...);
    ``pairs_list``: per-event (P, 2) int64 shortlists.  Returns per-event
    ``(w_a, w_b, feasible)`` aligned with each event's pairs.  The combine
    finishes on the host either way: ``ops.combine_work_pairs`` for the
    tile backends, ``ops.combine_terms`` for the jit path (whose products
    were already computed, contraction-safe, in the compiled region) —
    bitwise-identical results.

    Backends: ``numpy`` (reference tiles, exact shapes), ``jit`` (bucketed
    f64 compiled pipeline, bitwise-equal to numpy), ``pallas`` (interpret
    kernel, bitwise-equal), ``pallas_compiled`` (f32, 128-lane tiles,
    assignment-identity bar).
    """
    from repro.kernels.ccm_scorer import ops as scorer_ops

    e_n = len(feats)
    if e_n == 0:
        return []
    results: List[Optional[Tuple]] = [None] * e_n
    live = [k for k in range(e_n) if pairs_list[k].shape[0]]
    for k in range(e_n):
        if pairs_list[k].shape[0] == 0:
            z = np.zeros(0)
            results[k] = (z, z, np.zeros(0, bool))
    if not live:
        return results

    lf = [feats[k] for k in live]

    if backend == "jit":
        e_pad = bucket_events(len(lf))
        p_pad = bucket_pairs(max(pairs_list[k].shape[0] for k in live))
        o_av, o_bv, o_pm, o_sc, o_ia, o_ib, o_cf = _pair_offsets(p_pad)
        buf = np.zeros((e_pad, o_cf))
        coeffs = (params.alpha, params.beta, params.gamma, params.delta)
        for j, k in enumerate(live):
            av_k, bv_k, pm_k, sc_k = feats[k]
            pr = pairs_list[k]                      # pad rows read (0, 0)
            p = pr.shape[0]
            ia, ib = pr[:, 0], pr[:, 1]
            buf[j, :o_av].reshape(N_AV, p_pad)[:, :p] = av_k[:, ia]
            buf[j, o_av:o_bv].reshape(N_AV, p_pad)[:, :p] = bv_k[:, ib]
            buf[j, o_bv:o_pm].reshape(N_PM, p_pad)[:, :p] = pm_k[:, ia, ib]
            buf[j, o_pm:o_sc] = sc_k
            buf[j, o_sc:o_sc + p] = ia
            buf[j, o_ia:o_ia + p] = ib
            buf[j, o_ib:o_cf] = coeffs
        # pad event rows: unit speeds so the in-jit load/speed divide
        # cannot produce 0/0 NaNs (results are discarded, but
        # jax_debug_nans would trip on them; mirrors _pack's guard)
        buf[len(lf):, o_pm + SC.speed_a] = 1.0
        buf[len(lf):, o_pm + SC.speed_b] = 1.0
        fn = _get_fn(("pairs", e_pad, p_pad))
        with _bitwise_x64():
            terms = np.asarray(fn(buf))             # (E, 10, P)
        for j, k in enumerate(live):
            p = pairs_list[k].shape[0]
            results[k] = scorer_ops.combine_terms(
                terms[j, :, :p], feats[k][3], params)
        return results

    a_max = max(f[0].shape[1] for f in lf)
    b_max = max(f[1].shape[1] for f in lf)
    if backend == "numpy":
        if len(lf) == 1:
            av, bv, pm = (f[None] for f in lf[0][:3])
            sc = lf[0][3][None]
        else:
            av, bv, pm, sc = _pack(lf, a_max, b_max, len(lf), np.float64)
        out = ref.score_tiles(av, bv, pm, sc)
    elif backend == "pallas":
        # bucket the interpret path too: score_tiles_fwd is jitted, so
        # shape-stable launches avoid per-event retracing just like "jit"
        a_pad, b_pad = bucket_lanes(a_max), bucket_lanes(b_max)
        av, bv, pm, sc = _pack(lf, a_pad, b_pad, bucket_events(len(lf)),
                               np.float64)
        out = _pallas_score(av, bv, pm, sc, interpret=interpret)
    elif backend == "pallas_compiled":
        a_pad, b_pad = _f32_pads(a_max, b_max)
        av, bv, pm, sc = _pack(lf, a_pad, b_pad, bucket_events(len(lf)),
                               np.float32)
        out = _pallas_compiled_score(av, bv, pm, sc)
    else:
        raise ValueError(f"unknown ccm_scorer backend: {backend!r}")

    if out.dtype != np.float64:
        out = np.asarray(out, np.float64)       # f32 path: lossless upcast
    if len(live) == 1:
        # solo event: combine only the gathered shortlist lanes
        p = pairs_list[live[0]]
        outp = out[0][:, p[:, 0], p[:, 1]]              # (N_OUT, P)
        results[live[0]] = scorer_ops.combine_work_pairs(
            outp, feats[live[0]][3], params)
        return results
    # batched flush: ONE full-tile combine for all events amortizes the
    # numpy op dispatch (gather-then-combine per event would multiply it
    # by E); combine-then-gather is bitwise-identical per pair
    w_a, w_b, feas = scorer_ops.combine_work(out, sc, params)
    for j, k in enumerate(live):
        p = pairs_list[k]
        ia, ib = p[:, 0], p[:, 1]
        results[k] = (w_a[j, ia, ib], w_b[j, ia, ib], feas[j, ia, ib])
    return results
