"""Compiled shape-bucketed scorer runtime (kernels/ccm_scorer/jit.py).

Four contracts:
  * bucket grid — lane/event/pair rounding (powers of two, 128-lane cap);
  * padding invariance — bucketed/padded f64 jit scoring is BITWISE-equal
    to the unpadded numpy backend for arbitrary candidate counts,
    including the empty-candidate and single-task edges (property test
    when hypothesis is installed, seeded sweep otherwise);
  * recompile-count guard — a 500-event trajectory triggers at most one
    XLA trace per distinct shape bucket, so shape churn cannot silently
    reintroduce per-event tracing;
  * f32 parity tiers — the pallas_compiled path must reproduce the
    numpy backend's ASSIGNMENTS on well-separated instances and its ulp
    divergence on adversarial tiles is measured and bounded.
"""
import numpy as np
import pytest

from repro.core import CCMParams, CCMState, ccm_lb, random_phase
from repro.core.clusters import build_clusters
from repro.core.engine import ExchangeEvent, PhaseEngine
from repro.core.problem import Phase, initial_assignment
from repro.kernels.ccm_scorer import N_AV, N_PM, N_SC, SC, jit, ops, ref

PARAMS = CCMParams(alpha=1.0, beta=1e-9, gamma=1e-11, delta=1e-9,
                   memory_constraint=True)


# ------------------------------------------------------------ bucket grid
def test_bucket_lanes_grid():
    assert [jit.bucket_lanes(n) for n in (1, 7, 8, 9, 16, 17, 100)] == \
        [8, 8, 8, 16, 16, 32, 128]
    # at the 128-lane boundary buckets stop doubling and grow in lanes
    assert jit.bucket_lanes(128) == 128
    assert jit.bucket_lanes(129) == 256
    assert jit.bucket_lanes(513) == 640


def test_bucket_events_and_pairs_grid():
    assert [jit.bucket_events(e) for e in (1, 2, 3, 5, 8)] == [1, 2, 4, 8, 8]
    assert jit.bucket_pairs(1) == 32     # floor = the default shortlist cap
    assert jit.bucket_pairs(32) == 32
    assert jit.bucket_pairs(33) == 64


# ----------------------------------------------------- padding invariance
def _random_tiles(rng, e_n, a_n, b_n):
    av = rng.uniform(-2, 2, (e_n, N_AV, a_n))
    bv = rng.uniform(-2, 2, (e_n, N_AV, b_n))
    pm = rng.uniform(-2, 2, (e_n, N_PM, a_n, b_n))
    sc = rng.uniform(0.1, 3.0, (e_n, N_SC))
    sc[:, SC.na] = rng.integers(0, a_n, e_n)
    sc[:, SC.nb] = rng.integers(0, b_n, e_n)
    return av, bv, pm, sc


def _assert_padding_invariant(e_n, a_n, b_n, seed):
    rng = np.random.default_rng(seed)
    av, bv, pm, sc = _random_tiles(rng, e_n, a_n, b_n)
    want = ref.score_tiles(av, bv, pm, sc)
    got = ops.ccm_score_tiles(av, bv, pm, sc, backend="jit")
    np.testing.assert_array_equal(got, want)


def test_padding_invariance_seeded_sweep():
    """Bucketed/padded jit == unpadded numpy, bit for bit, across the edge
    shapes: A/B of 1 (empty-candidate tiles), non-bucket sizes, and sizes
    straddling bucket boundaries."""
    for seed, (e_n, a_n, b_n) in enumerate(
            [(1, 1, 1), (1, 2, 9), (2, 13, 13), (3, 8, 16), (1, 17, 5),
             (2, 33, 3)]):
        _assert_padding_invariant(e_n, a_n, b_n, seed)


try:
    from hypothesis import given, settings, strategies as st
except ImportError:                                   # pragma: no cover
    pass
else:
    @settings(max_examples=30, deadline=None)
    @given(e_n=st.integers(1, 4), a_n=st.integers(1, 40),
           b_n=st.integers(1, 40), seed=st.integers(0, 10_000))
    def test_padding_invariance_property(e_n, a_n, b_n, seed):
        _assert_padding_invariant(e_n, a_n, b_n, seed)


def test_engine_jit_backend_bitwise_and_edges():
    """Engine-level parity incl. the empty-candidate and single-task edges:
    jit scores == numpy scores bitwise on full events and the empty event
    returns empty outputs."""
    phase = random_phase(5, num_ranks=8, num_tasks=120, num_blocks=14,
                         num_comms=260, mem_cap=4e8)
    state = CCMState.build(phase, initial_assignment(phase, "home"), PARAMS)
    clusters = build_clusters(state)
    empty = np.zeros(0, np.int64)
    events = []
    for r_a, r_b in ((0, 1), (2, 3), (4, 5)):
        cand_a = [empty] + clusters[r_a][:6]
        cand_b = [empty] + clusters[r_b][:6]
        pairs = [(ia, ib) for ia in range(len(cand_a))
                 for ib in range(len(cand_b)) if ia or ib]
        events.append(ExchangeEvent(r_a, r_b, cand_a, cand_b, pairs))
    events.append(ExchangeEvent(6, 7, [empty], [empty], []))  # na = nb = 0
    res_np = PhaseEngine(state, backend="numpy") \
        .batch_exchange_eval_multi(events)
    res_jit = PhaseEngine(state, backend="jit") \
        .batch_exchange_eval_multi(events)
    for (wa, wb, fe), (wa2, wb2, fe2) in zip(res_np, res_jit):
        np.testing.assert_array_equal(wa, wa2)
        np.testing.assert_array_equal(wb, wb2)
        np.testing.assert_array_equal(fe, fe2)
    assert res_jit[-1][0].shape == (0,)


def test_single_task_phase_jit():
    phase = Phase(
        task_load=np.array([2.0]), task_mem=np.array([8.0]),
        task_overhead=np.array([1.0]), task_block=np.array([0]),
        block_size=np.array([16.0]), block_home=np.array([0]),
        comm_src=np.array([0]), comm_dst=np.array([0]),
        comm_vol=np.array([3.0]),
        rank_mem_base=np.zeros(2), rank_mem_cap=np.full(2, 1e9))
    state = CCMState.build(phase, np.array([0]), PARAMS)
    clusters = build_clusters(state)
    empty = np.zeros(0, np.int64)
    ev = [ExchangeEvent(0, 1, [empty] + clusters[0], [empty], [(1, 0)])]
    res = {be: PhaseEngine(state, backend=be).batch_exchange_eval_multi(ev)
           for be in ("numpy", "jit")}
    np.testing.assert_array_equal(res["numpy"][0][0], res["jit"][0][0])
    np.testing.assert_array_equal(res["numpy"][0][1], res["jit"][0][1])
    assert res["jit"][0][2][0]


def test_gather_then_combine_is_combine_then_gather():
    """combine_work_pairs on gathered planes == combine_work on the full
    tile followed by the gather (the hot path's correctness hinge)."""
    rng = np.random.default_rng(3)
    av, bv, pm, sc = _random_tiles(rng, 2, 9, 7)
    out = ref.score_tiles(av, bv, pm, sc)
    w_a, w_b, feas = ops.combine_work(out, sc, PARAMS)
    for e in range(2):
        ia = rng.integers(0, 9, 11)
        ib = rng.integers(0, 7, 11)
        wa2, wb2, fe2 = ops.combine_work_pairs(out[e][:, ia, ib], sc[e],
                                               PARAMS)
        np.testing.assert_array_equal(wa2, w_a[e, ia, ib])
        np.testing.assert_array_equal(wb2, w_b[e, ia, ib])
        np.testing.assert_array_equal(fe2, feas[e, ia, ib])


# ------------------------------------------------- recompile-count guard
def test_recompile_count_bounded_over_trajectory():
    """Scoring a 500-event trajectory with churning candidate counts and
    shortlist sizes must trigger at most one XLA trace per distinct shape
    bucket (the bucket cache growth), not one per event."""
    phase = random_phase(9, num_ranks=10, num_tasks=160, num_blocks=18,
                         num_comms=340, mem_cap=1e12)
    state = CCMState.build(phase, initial_assignment(phase, "home"), PARAMS)
    clusters = build_clusters(state)
    engine = PhaseEngine(state, backend="jit")
    empty = np.zeros(0, np.int64)
    rng = np.random.default_rng(0)
    traces0 = jit.trace_count()
    buckets0 = jit.bucket_cache_size()
    for i in range(500):
        r_a, r_b = rng.choice(10, size=2, replace=False)
        n_a = int(rng.integers(0, min(6, len(clusters[r_a])) + 1))
        n_b = int(rng.integers(0, min(6, len(clusters[r_b])) + 1))
        cand_a = [empty] + clusters[r_a][:n_a]
        cand_b = [empty] + clusters[r_b][:n_b]
        pairs = [(ia, ib) for ia in range(n_a + 1)
                 for ib in range(n_b + 1) if ia or ib]
        if pairs:
            pairs = pairs[:int(rng.integers(1, len(pairs) + 1))]
        engine.batch_exchange_eval(r_a, r_b, cand_a, cand_b, pairs)
    new_traces = jit.trace_count() - traces0
    new_buckets = jit.bucket_cache_size() - buckets0
    assert new_traces <= max(new_buckets, 1), \
        (f"{new_traces} traces for {new_buckets} new buckets — per-event "
         "retracing has crept back in")
    # the pair-gathered layout is lane-free: candidate-count churn at one
    # event per call must stay within a handful of (E, P) buckets
    assert jit.bucket_cache_size() - buckets0 <= 4


# ------------------------------------------------------- f32 parity tiers
def test_pallas_compiled_assignment_identity_well_separated():
    """The f32 compiled path's parity bar: on well-separated instances
    (continuous loads/volumes, gaps far above f32 noise) the end-to-end
    CCM-LB assignment must be IDENTICAL to the numpy backend's.  Runs via
    the interpret fallback on hosts without a Pallas compile target —
    same f32 dtype, same 128-lane layout."""
    for seed in (11, 23):
        phase = random_phase(seed, num_ranks=6, num_tasks=90, num_blocks=12,
                             num_comms=200, mem_cap=5e8)
        params = CCMParams(delta=1e-9)
        a0 = initial_assignment(phase)
        want = ccm_lb(phase, a0, params, n_iter=2, seed=1, backend="numpy")
        got = ccm_lb(phase, a0, params, n_iter=2, seed=1,
                     backend="pallas_compiled")
        np.testing.assert_array_equal(got.assignment, want.assignment,
                                      err_msg=f"seed {seed}")
        assert got.transfers == want.transfers


def _ulps_f32(a, b):
    """Units-in-last-place distance between two f32 arrays (finite lanes)."""
    ai = np.frombuffer(np.float32(a).tobytes(), np.int32).astype(np.int64)
    bi = np.frombuffer(np.float32(b).tobytes(), np.int32).astype(np.int64)
    ai = np.where(ai < 0, np.int64(-2**31) - ai, ai)
    bi = np.where(bi < 0, np.int64(-2**31) - bi, bi)
    return np.abs(ai - bi)


def test_pallas_compiled_ulp_budget_adversarial():
    """Adversarial tiles (large dynamic range, cancellation-prone sums):
    record the max ulp divergence of the f32 path vs the f64 reference
    rounded to f32.  The budget is generous — the point is a tracked
    number, not bitwise equality (that tier belongs to f64)."""
    rng = np.random.default_rng(7)
    e_n, a_n, b_n = 2, 12, 12
    av = rng.uniform(-1e5, 1e5, (e_n, N_AV, a_n))
    bv = rng.uniform(-1e5, 1e5, (e_n, N_AV, b_n))
    pm = rng.uniform(-1e4, 1e4, (e_n, N_PM, a_n, b_n))
    sc = rng.uniform(1.0, 1e6, (e_n, N_SC))
    sc[:, SC.na] = a_n - 1
    sc[:, SC.nb] = b_n - 1
    want64 = ref.score_tiles(av, bv, pm, sc)
    got32 = ops.ccm_score_tiles(av, bv, pm, sc, backend="pallas_compiled")
    finite = np.isfinite(want64) & np.isfinite(got32)
    ulps = _ulps_f32(np.float32(want64[finite]), np.float32(got32[finite]))
    max_ulp = int(ulps.max()) if ulps.size else 0
    print(f"pallas_compiled adversarial max ulp divergence: {max_ulp}")
    # f32 accumulation over ~20-term sums with 10-decade dynamic range:
    # a few hundred ulps is expected, runaway divergence is not
    assert max_ulp < 4096, max_ulp
    # infinities (masked tail) must agree exactly
    np.testing.assert_array_equal(np.isinf(want64), np.isinf(got32))


def test_pallas_compiled_fallback_reporting():
    """On the CPU backend the compiled path interprets the f32 kernel and
    says so."""
    import jax
    assert jax.default_backend() == "cpu"
    av, bv, pm, sc = _random_tiles(np.random.default_rng(0), 1, 4, 4)
    ops.ccm_score_tiles(av, bv, pm, sc, backend="pallas_compiled")
    assert jit.pallas_compiled_fallback()


@pytest.mark.parametrize("backend", ["jit", "pallas"])
def test_f64_backends_refuse_off_cpu(monkeypatch, backend):
    """The f64 tier's bar is bit-for-bit numpy, which only XLA:CPU meets;
    elsewhere (the TPU emulates f64) the backends refuse to run."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    av, bv, pm, sc = _random_tiles(np.random.default_rng(2), 1, 4, 4)
    with pytest.raises(NotImplementedError, match="bitwise"):
        ops.ccm_score_tiles(av, bv, pm, sc, backend=backend)
    phase = random_phase(0, num_ranks=4, num_tasks=24, num_blocks=6,
                         num_comms=24, mem_cap=1e12)
    with pytest.raises(NotImplementedError, match="bitwise"):
        ccm_lb(phase, initial_assignment(phase), CCMParams(), n_iter=1,
               backend=backend)


def test_pallas_compiled_raises_off_cpu(monkeypatch):
    """On any backend but the CPU a kernel that cannot be compiled raises:
    it is never interpreted in silence.  The platform is steered here; the
    CPU then refuses ``interpret=False``, as a broken lowering would."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    av, bv, pm, sc = _random_tiles(np.random.default_rng(1), 1, 4, 4)
    with pytest.raises(Exception, match="(?i)interpret|cpu|lower"):
        ops.ccm_score_tiles(av, bv, pm, sc, backend="pallas_compiled")
