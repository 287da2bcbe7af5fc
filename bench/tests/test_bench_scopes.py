"""Device time by name scope: the trace decoder on the small chip trace, the
matching of scopes against ``tf_op`` names, the split of a step's device
time, and the four readers that report it."""
from pathlib import Path

import pytest

import benchutil  # noqa: F401  (puts the repository on the path)
from bench import harness, scopes, trace

SMALL = Path(__file__).resolve().parent / "data" / "small_trace.xplane.pb"
READERS = {"attn.device_ms_per_step": "attn",
           "moe.device_ms_per_step": "moe",
           "moe.experts_ms_per_step": "moe/experts",
           "optim.device_ms_per_step": "optimizer"}


@pytest.fixture(scope="module")
def small_ops():
    return scopes.decode(SMALL)


@pytest.fixture(scope="module")
def small():
    return trace.reduce(SMALL)


def _by_name(ops, prefix):
    found = [op for op in ops if op.name.startswith(prefix)]
    assert len(found) == 1, prefix
    return found[0]


def test_decoder_reads_the_op_metadata(small_ops):
    assert list(small_ops) == [0]
    ops = small_ops[0]
    assert len(ops) == 10
    matmul = _by_name(ops, "%convolution_reduce_fusion ")
    assert matmul.tf_op == "jit(<lambda>)/dot_general:"
    assert matmul.source.endswith("bench/tests/record_trace.py:37")
    kernel = _by_name(ops, "%score_tiles_fwd.1 ")
    assert kernel.tf_op == "jit(score_tiles_fwd)/pallas_call:"
    assert kernel.source.endswith("ccm_scorer/kernel.py:151")
    assert kernel.end_ns - kernel.start_ns == 921


def test_decoder_busy_time_agrees_with_the_reduction(small_ops, small):
    ivs = [(op.start_ns, op.end_ns) for op in small_ops[0]]
    busy = trace.union_seconds(ivs, *small.window)
    assert busy == pytest.approx(19896e-9, rel=1e-12)
    assert busy == small.busy_s
    # the same op intervals as ProfileData's events, one for one
    assert sorted(ivs) == sorted((s, e) for _, s, e, _ in small.ops[0])


def test_decoder_keeps_to_the_devices_asked_for():
    assert scopes.decode(SMALL, device_ids=[3]) == {}


@pytest.mark.parametrize("tf_op, inside, outside", [
    ("jit(train_step)/jvp()/while/body/closed_call/moe/experts/dot_general:",
     ["moe", "moe/experts"], ["experts/moe", "moe/router", "attn"]),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "moe/experts/jit(silu)/mul", ["moe", "moe/experts"], ["moe/combine"]),
    ("jit(train_step)/transpose(jvp())/while/body/closed_call/checkpoint/"
     "rematted_computation/attn/bqhgd,bkhd->bhgqk/dot_general:",
     ["attn"], ["moe"]),
    ("jit(train_step)/transpose(jvp(head))/bsd,dv->bsv/dot_general:",
     ["head"], ["embed"]),
    ("jit(train_step)/jvp(embed)/jit(_take)/gather", ["embed"], ["head"]),
    ("jit(train_step)/attn/le;jit(train_step)/jvp()/broadcast_in_dim",
     ["attn"], []),
    ("jit(train_step)/optimizer/sub", ["optimizer"], ["moe"]),
    ("jit(train_step)/moe_x/experts_y/dot_general:", [],
     ["moe", "moe/experts"]),
    ("jit(train_step)/while/body/closed_call/mul", [], list(scopes.SCOPES)),
    # a scope's name as the op's primitive is no scope
    ("jit(train_step)/moe", [], ["moe"]),
])
def test_scopes_match_whole_segments(tf_op, inside, outside):
    path = scopes.name_path(tf_op)
    for s in inside:
        assert scopes.in_scope(path, s), s
    for s in outside:
        assert not scopes.in_scope(path, s), s


@pytest.mark.parametrize("tf_op, top", [
    ("jit(s)/transpose(jvp())/checkpoint/moe/mlp/dot_general", "moe"),
    ("jit(s)/jvp()/while/body/closed_call/attn/exp", "attn"),
    ("jit(s)/transpose(jvp(head))/mul", "head"),
    ("jit(s)/jvp()/while/body/closed_call/rsqrt", None),
    ("av:", None),
])
def test_each_op_belongs_to_its_outermost_scope(tf_op, top):
    assert scopes.top_scope(scopes.name_path(tf_op)) == top


def _op(s, e, tf_op, source=""):
    return scopes.Op(s, e, tf_op, source, f"%op.{s}")


def test_split_takes_unions_and_averages_over_devices_and_steps():
    fwd = "jit(s)/jvp()/moe/experts/dot_general:"
    ops = {
        # device 0: a container op over two of its own, so the union counts
        # 0-100 once; an unscoped op; the optimizer partly out of the window
        0: [_op(0, 100, "jit(s)/moe/while:"), _op(10, 40, fwd),
            _op(50, 90, "jit(s)/moe/combine/add"),
            _op(100, 120, "jit(s)/rsqrt", "transformer.py:346"),
            _op(120, 200, "jit(s)/optimizer/sub")],
        1: [_op(0, 60, fwd), _op(60, 80, "jit(s)/rsqrt", "transformer.py:346")],
    }
    sp = scopes.split(ops, (0, 160), steps=2)
    ns_to_ms = 1e-6
    # per device (100 + 60) / 2, then per step / 2
    assert sp.scopes["moe"] == pytest.approx(40 * ns_to_ms)
    assert sp.scopes["moe/experts"] == pytest.approx((30 + 60) / 4 * ns_to_ms)
    assert sp.scopes["moe/combine"] == pytest.approx(40 / 4 * ns_to_ms)
    assert sp.scopes["optimizer"] == pytest.approx(40 / 4 * ns_to_ms)
    assert sp.scopes["attn"] is None and sp.scopes["moe/router"] is None
    assert sp.unscoped == pytest.approx(40 / 4 * ns_to_ms)
    assert sp.unscoped_sources == [("transformer.py:346",
                                    pytest.approx(40 / 4 * ns_to_ms))]
    assert sp.busy == pytest.approx((160 + 80) / 4 * ns_to_ms)
    assert sum(sp.top.values()) + sp.unscoped == pytest.approx(sp.busy)


class _Run:
    def __init__(self, trace_path, steps):
        self.counters = {"traced_steps": steps}
        self.trace_path = trace_path
        self.root = Path(benchutil.ROOT)
        self.lines = []

    def log(self, msg):
        self.lines.append(msg)


@pytest.mark.parametrize("metric", sorted(READERS))
def test_readers_give_nothing_without_a_trace(metric):
    reader = harness.metric_reader(metric)
    assert reader.read(_Run(None, 5), None, {}) is None


@pytest.mark.parametrize("metric", sorted(READERS))
def test_readers_give_nothing_where_no_op_carries_the_scope(metric, small):
    # the recorded trace's ops carry no scope of the program: a stale
    # scope reads null, not 0
    run = _Run(SMALL, 1)
    assert harness.metric_reader(metric).read(run, small, {}) is None
    assert any("unscoped" in line for line in run.lines)


def test_the_trace_is_decoded_once_a_run(small, monkeypatch):
    run = _Run(SMALL, 1)
    calls = []
    decode = scopes.decode
    monkeypatch.setattr(scopes, "decode",
                        lambda *a, **k: calls.append(1) or decode(*a, **k))
    for metric in READERS:
        harness.metric_reader(metric).read(run, small, {})
    assert calls == [1]
    sp = run.counters[scopes._CACHE_KEY]
    assert sp.busy == pytest.approx(19896e-6)
    assert sp.unscoped == pytest.approx(sp.busy)
