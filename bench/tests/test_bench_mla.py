"""The latent-attention MoE cell and the expert-parallel cell at the smoke
size on the CPU: their control flow and checks (the plain float32
references agree with the program, and the control and the planted faults
are caught), the work counts their MFU and roofline share divide by, and
their readers on the small recorded trace."""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

from benchutil import PEAKS_KIND, ROOT
from bench import flops_mla, harness, trace

DATA = Path(__file__).resolve().parent / "data"
SMALL = DATA / "small_trace.xplane.pb"
MLA_CELL = "train.moonlight-5L.s8k"

TINY = {"hidden_size": 4, "num_attention_heads": 2, "kv_lora_rank": 2,
        "qk_nope_head_dim": 2, "qk_rope_head_dim": 2, "v_head_dim": 2,
        "intermediate_size": 3, "moe_intermediate_size": 2,
        "n_routed_experts": 2, "published": {"n_routed_experts": 4},
        "num_experts_per_tok": 2, "n_shared_experts": 1,
        "num_hidden_layers": 2, "first_k_dense_replace": 1, "vocab_size": 5}


def test_mla_flops_hand_count():
    # per token and layer: q 4x2x4 + kv_a 4x4 + kv_b 2x2x4 + o 2x2x4 = 80
    # MACs, causal core at seq 3: 2 heads x (4 + 2) x (3+1)/2 = 24; two
    # layers 208.  Dense MLP 3 x 4x3 = 36; the MoE layer: router 4x4 = 16,
    # shared 3 x 4x2 = 24, two of four experts held at top-2: 2 x 2/4 x
    # 3 x 4x2 = 24.  Head 4x5 = 20.  328 MACs, x2 FLOPs, x3 = 1968.
    assert flops_mla.train_flops_per_token(TINY, 3) == pytest.approx(1968)
    # the core alone over 6 tokens: 3 x 2 x 2 layers x 6 x 24
    assert flops_mla.causal_core_train_flops(TINY, 3, 6) == \
        pytest.approx(1728)


def test_moonlight_cut_flops_per_token():
    cfg = json.loads((ROOT / "bench/configs/moonlight-16b-a3b-5L.json")
                     .read_text())
    # 380.5M forward MACs a token at seq 8192, of which the causal core
    # 104.9M (5 layers x 16 heads x 320 x 8193/2)
    assert flops_mla.train_flops_per_token(cfg, 8192) == \
        pytest.approx(6 * 380_514_816, rel=1e-9)
    assert 5 * flops_mla.causal_core_macs_per_token(cfg, 8192) == \
        pytest.approx(104_870_400)


class _Run:
    def __init__(self, trace_path, steps, **counters):
        self.counters = {"traced_steps": steps, **counters}
        self.trace_path = trace_path
        self.root = Path(ROOT)
        self.e2e = {}
        self.devices = [None]
        self.config = json.loads((DATA / "moonlight-smoke.json").read_text())
        self.traffic = {"seq_len": 64, "global_batch": 2}

    def log(self, msg):
        pass


NEW_READERS = ("attn.core_roofline_pct", "moe.shared_ms_per_step",
               "ep.collective_ms_per_step", "ep.replan_ms",
               "train.mla_mfu_pct")


@pytest.mark.parametrize("metric", NEW_READERS)
def test_new_readers_give_nothing_without_a_trace(metric):
    assert harness.metric_reader(metric).read(_Run(None, 5), None, {}) is None


@pytest.mark.parametrize("metric", NEW_READERS)
def test_new_readers_give_nothing_where_nothing_is_there(metric):
    # the recorded trace (one chip) carries no program scope and no
    # collective, and the run made no replan: null, never 0
    small = trace.reduce(SMALL)
    assert harness.metric_reader(metric).read(_Run(SMALL, 1), small,
                                              {"flops_bf16": 1}) is None


def test_replan_reader_takes_the_median():
    run = _Run(None, 0, replan_s=[0.2, 0.01, 0.03])
    assert harness.metric_reader("ep.replan_ms").read(run, None, {}) == \
        pytest.approx(30.0)


# ------------------------------------------------ the MLA cell, smoke size
def _run_mla(fault=None, control=False, trace_on=False):
    import jax
    cell = harness.resolve(MLA_CELL)
    cell.config = json.loads((DATA / "moonlight-smoke.json").read_text())
    cell.traffic = dict(cell.traffic, seq_len=64, global_batch=2,
                        distinct_batches=8, trace_from_step=1, trace_steps=2)
    run = harness.Run(cell=cell, seed=2 ** 31 + 7, seconds=0.5,
                      trace=trace_on, devices=jax.devices()[:1], fault=fault,
                      control=control)
    run.counters["peaks"] = harness.peaks_for(PEAKS_KIND)
    out = harness.execute(run, time.perf_counter())
    return out, run


@pytest.fixture(scope="module")
def mla_traced():
    return _run_mla(trace_on=True)


def test_mla_cell_runs_and_agrees_with_the_reference(mla_traced):
    out, run = mla_traced
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    # the window counts what the held experts take of the routed tokens,
    # and the check says how many of their parameters it compared
    assert 0 < run.counters["held_share"] <= 1
    assert run.counters["held_counted"] > 0


def test_mla_cell_traced_reports_its_mfu(mla_traced):
    out, _ = mla_traced
    assert out["metrics"]["train.mla_mfu_pct"]["value"] > 0
    assert "train.mfu_pct" not in out["metrics"]


@pytest.mark.parametrize("fault, control, number", [
    (None, True, "grad_median_gap"),        # the reference in float8
    ("half", False, "grad_gap"),            # half the batch out of the mean
    ("unchanged", False, "update_median_gap"),   # a step that moves nothing
])
def test_mla_control_and_faults_are_caught(fault, control, number):
    out, _ = _run_mla(fault=fault, control=control)
    assert not out["correct"]
    c = out["checks"][number]
    assert c["value"] > c["limit"]


# ---------------------------------- the expert-parallel cell, 4 CPU devices
EP_RUN = r"""
import json, sys, time
sys.path[:0] = [sys.argv[1], sys.argv[1] + "/src", sys.argv[1] + "/bench/tests"]
import jax
import benchutil
from bench import harness
cell = harness.resolve("train-ep4.qwen3moe-1L.s4k.replan50")
cell.config = json.loads(benchutil.SMOKE_MODEL.read_text())
cell.traffic = dict(cell.traffic, seq_len=64, global_batch=4,
                    distinct_batches=8, trace_from_step=1, trace_steps=2,
                    replan_every=5, hbm_budget_bytes=16e9)
fault = None if sys.argv[2] == "none" else sys.argv[2]
run = harness.Run(cell=cell, seed=2 ** 31 + 7, seconds=1.0, trace=False,
                  devices=jax.devices()[:4], fault=fault)
run.counters["peaks"] = harness.peaks_for(benchutil.PEAKS_KIND)
out = harness.execute(run, time.perf_counter())
print(json.dumps({"correct": out["correct"], "checks": out["checks"],
                  "replans": len(run.counters["replan_s"]),
                  "moved": run.counters.get("setup_moved"),
                  "compiles": run.counters["compiles_in_window"]}))
"""


def _run_ep(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "-c", EP_RUN, str(ROOT), fault],
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_ep_cell_replans_in_the_window_and_agrees():
    out = _run_ep("none")
    assert out["correct"], out["checks"]
    assert out["replans"] >= 1
    assert out["compiles"] == 0          # the permutation compiled in set-up
    # the compared steps span a replan that moved experts across devices
    assert min(out["moved"]) > 0


@pytest.mark.parametrize("fault, number", [
    ("nopsum", "grad_median_gap"),      # the experts' cross-device sum left out
    # a set-up replan that moves the experts but not their router columns:
    # the steps after it train the wrong experts
    ("norouter", "update_median_gap"),
])
def test_ep_cell_catches_a_planted_fault(fault, number):
    out = _run_ep(fault)
    assert not out["correct"]
    c = out["checks"][number]
    assert c["value"] > c["limit"]


def test_split_without_a_loop_op_adds_up_to_busy():
    """A scan the compiler keeps is a ``while`` op whose event spans its
    body's ops; without it the scopes and the unscoped rest add up to the
    busy time again (``bench/scope_time.py``)."""
    from types import SimpleNamespace

    from bench import scope_time, scopes
    body = "jit(train_step)/jvp()/while/body/closed_call/"
    ops = {0: [
        scopes.Op(0, 100, "jit(train_step)/jvp()/while", "",
                  "%while.3 = (s32[]) while(%t), body=%b"),
        scopes.Op(0, 40, body + "attn/core/dot_general:", "",
                  "%fusion.1 = bf16[8] fusion(%a)"),
        scopes.Op(40, 90, body + "moe/experts/dot_general:", "",
                  "%fusion.2 = bf16[8] fusion(%b)"),
        scopes.Op(90, 100, body + "mul", "", "%fusion.3 = bf16[8] fusion(%c)"),
    ]}
    lines = []
    run = SimpleNamespace(counters={"traced_steps": 1}, log=lines.append)
    scope_time._log_split_without_control_flow(
        run, SimpleNamespace(window=(0, 100)), ops)
    assert "without 1 control-flow op events" in lines[0]
    assert lines[0].endswith("unscoped 0.0000 = 0.0001 ms; busy 0.0001 ms")
    # with it, the split counts the loop's span again as unscoped
    sp = scopes.split(ops, (0, 100), 1)
    assert sum(sp.top.values()) + sp.unscoped > sp.busy
