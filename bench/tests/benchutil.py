"""Helpers for the benchmark's CPU tests: run a cell through the harness at
a size a test can hold, with the harness's look for a chip skipped."""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

SMOKE_MODEL = Path(__file__).resolve().parent / "data" / "qwen3-moe-smoke.json"
PEAKS_KIND = "TPU v5 lite"


def small_cell(name: str):
    """The cell as ``BENCHMARK.json`` defines it, shrunk to the smoke-size
    model at seq 64 and batch 2; the smoke model carries the limits set
    from its own readings."""
    from bench import harness
    cell = harness.resolve(name)
    cell.config = json.loads(SMOKE_MODEL.read_text())
    cell.traffic = dict(cell.traffic, seq_len=64, global_batch=2,
                        distinct_batches=8, trace_from_step=1, trace_steps=2)
    return cell


def run_small(name: str, *, seed: int = 2 ** 31 + 7, seconds: float = 0.5,
              fault=None, control=False, trace=False):
    """Run a shrunk cell through ``harness.execute`` on the CPU devices;
    returns the result object."""
    import jax

    from bench import harness
    cell = small_cell(name)
    devices = jax.devices()[:cell.chips]
    run = harness.Run(cell=cell, seed=seed, seconds=seconds, trace=trace,
                      devices=devices, fault=fault, control=control)
    run.counters["peaks"] = harness.peaks_for(PEAKS_KIND)
    return harness.execute(run, time.perf_counter())
