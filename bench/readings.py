"""Read a cell's compared numbers on many seeds in one process, to set and
prove the limits of ``correct`` (the program's readings, the control's, a
fault's).  Set-up is paid once for the compiles; each seed still makes its
own inputs and weights, runs its own window and its own check.

  python3 bench/readings.py --workload <name> --seeds 1,2,3 --seconds 2 \
      [--control] [--fault <name>]

One JSON line a seed on standard output: the seed, ``correct`` and the
numbers compared with their limits.  Needs the chips, like ``run.py``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from bench import harness
    try:
        cell = harness.resolve(args.workload)
        harness.configure_jax()
        devices = harness.chip_devices(cell.chips)
        peaks = harness.peaks_for(devices[0].device_kind)
    except harness.BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 1
    for seed in (int(s) for s in args.seeds.split(",")):
        run = harness.Run(cell=cell, seed=seed, seconds=args.seconds,
                          trace=False, devices=devices, control=args.control,
                          fault=args.fault)
        run.counters["peaks"] = peaks
        out = harness.execute(run, time.perf_counter())
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "control": args.control, "fault": args.fault,
                          "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
