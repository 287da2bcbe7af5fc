"""Run one benchmark cell once, on the chips of this machine.

  python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the root of
the checkout.  Set-up, the measured window and the correctness check run in
this one process; the last line of standard output is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, with
``--trace 1``, ``breakdown``).  With ``--trace 0`` the metrics are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics.

The run exits non-zero, printing no result, when JAX finds no TPU or fewer
chips than the cell asks for, or when the system under test (``src/repro``)
is not in the checkout.  ``bench/readings.py`` reads the numbers compared
on many seeds, of the program, its control or a planted fault, to set the
limits of ``correct``.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if str(ROOT) not in sys.path:
        sys.path.insert(0, str(ROOT))
    from bench import harness
    return harness.main(args)


if __name__ == "__main__":
    sys.exit(main())
