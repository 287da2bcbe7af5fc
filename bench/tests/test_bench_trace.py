"""The trace reduction: interval arithmetic by hand, op names, and busy time
and kernel time on a small trace recorded on one v5e chip by
``record_trace.py`` (a scorer kernel launch and a matmul, inside the
harness's traced window)."""
from pathlib import Path

import pytest

import benchutil  # noqa: F401  (puts the repository on the path)
from bench import kernels, trace

SMALL = Path(__file__).resolve().parent / "data" / "small_trace.xplane.pb"

# read from the recorded trace by hand: ten op events, none overlapping,
# of 5 + 331 + 3 + 2 + 2 + 295 + 921 (the kernel) + 13 + 3217 + 15107 ns
EXPECTED = {"busy_s": 19896e-9, "kernel_s": 921e-9, "kernel_launches": 1}


def test_union_of_overlapping_intervals():
    iv = [(0, 10), (5, 20), (30, 40), (35, 36), (50, 60)]
    assert trace.union_seconds(iv, 0, 100) == pytest.approx(40e-9)
    # clipped to the window [8, 55)
    assert trace.union_seconds(iv, 8, 55) == pytest.approx(27e-9)


def test_gaps_are_the_complement_in_the_window():
    iv = [(10, 20), (15, 30), (40, 50)]
    assert trace.gaps(iv, 0, 60) == [(0, 10), (30, 40), (50, 60)]
    assert trace.gaps(iv, 12, 45) == [(30, 40)]
    assert trace.gaps([], 0, 5) == [(0, 5)]


@pytest.mark.parametrize("name, collective", [
    ("%all-reduce.3 = f32[1024]{0} all-reduce(f32[1024]{0} %x), "
     "replica_groups={{0,1,2,3}}", True),
    ("%all-reduce-start.1 = f32[8]{0} all-reduce-start(f32[8]{0} %y)", True),
    ("%collective-permute.2 = bf16[4,8]{1,0} collective-permute(%z)", True),
    ("%fusion.7 = bf16[3,4096]{1,0} fusion(bf16[3,4096]{1,0} %p), "
     "kind=kLoop", False),
    ("%all-reduce-like-fusion = f32[8]{0} fusion(%q)", False),
])
def test_collectives_are_told_by_their_opcode(name, collective):
    assert kernels.is_collective(name, {}) is collective


@pytest.fixture(scope="module")
def small():
    return trace.reduce(SMALL)


def test_small_trace_devices_and_window(small):
    assert small.device_ids == [0]
    assert 0.1 < small.window_s < 5
    for busy in small.busy_per_device:
        assert 0 < busy < small.window_s


def test_small_trace_busy_is_the_union_of_op_intervals(small):
    lo, hi = small.window
    for d, busy in zip(small.device_ids, small.busy_per_device):
        # by hand: sweep the sorted clipped intervals
        iv = sorted((max(s, lo), min(e, hi)) for _, s, e, _ in small.ops[d]
                    if min(e, hi) > max(s, lo))
        total, end = 0.0, lo
        for s, e in iv:
            if e > end:
                total += e - max(s, end)
                end = e
        assert busy == pytest.approx(total * 1e-9, rel=1e-12)


def _is_scorer(name, stats):
    # the recorded kernel, the CCM scorer's, by the names its launch gives
    text = " ".join([name] + [str(v) for v in stats.values()])
    return "_scorer_kernel" in text or "score_tiles_fwd" in text


def test_small_trace_kernel_and_collective_time(small):
    kern = small.op_seconds(_is_scorer)
    assert kern[0] == pytest.approx(EXPECTED["kernel_s"], rel=1e-9)
    assert small.op_count(_is_scorer, 0) == EXPECTED["kernel_launches"]
    assert small.busy_s == pytest.approx(EXPECTED["busy_s"], rel=1e-9)
    # one chip: the matmul's sum needs no exchange
    assert small.op_seconds(kernels.is_collective) == [0.0]


def test_small_trace_breakdown_is_bounded(small):
    b = small.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    idle = sum(v for _, v in b["idle_gaps"])
    assert idle == pytest.approx(small.window_s - small.busy_per_device[0],
                                 rel=1e-6)
