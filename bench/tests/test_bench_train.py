"""The training driver's control flow at the smoke size, and its check:
the plain float32 reference agrees with the program's train step, and the
faults a one-chip training cell can have are caught."""
import pytest

from benchutil import run_small

CELL = "train.qwen3moe-1L.s4k"


@pytest.fixture(scope="module")
def traced():
    return run_small(CELL, trace=True)


def test_train_cell_runs_and_agrees_with_the_reference(traced):
    out = traced
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0


def test_train_cell_traced_reports_mfu(traced):
    assert traced["metrics"]["train.mfu_pct"]["value"] > 0


def test_train_cell_traced_reports_the_median_step(traced):
    ms = traced["metrics"]["train.step_ms_median"]["value"]
    # the smoke step takes milliseconds; a window of 0.5 s holds it
    assert 0 < ms < 500


def test_train_control_is_not_correct():
    # the reference computed in float8 in the program's place
    out = run_small(CELL, control=True)
    assert not out["correct"]
    c = out["checks"]["grad_median_gap"]
    assert c["value"] > c["limit"]


@pytest.mark.parametrize("fault, number", [
    ("unchanged", "update_median_gap"),   # a step that returns its state unchanged
    ("half", "grad_gap"),          # half the batch left out of the mean
])
def test_train_faults_are_caught(fault, number):
    out = run_small(CELL, fault=fault)
    assert not out["correct"]
    c = out["checks"][number]
    assert c["value"] > c["limit"]
