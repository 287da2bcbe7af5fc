"""Record the small chip trace that ``test_bench_trace.py`` reduces.

  python3 bench/tests/record_trace.py <out.xplane.pb>

On every chip of the machine (four on a 2x2 host): one launch of the CCM
scorer's Pallas kernel at a 13 x 13 candidate tile, then a sharded matmul
whose result is summed across the chips (an all-reduce), inside the harness's
traced window, as the harness traces it.  Needs the chips; the trace it
writes is a few hundred KB at most.
"""
from __future__ import annotations

import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from bench import harness, trace
    from repro.kernels.ccm_scorer import jit as scorer_jit
    from repro.kernels.ccm_scorer.layout import N_AV, N_PM, N_SC, SC

    devices = jax.devices()
    mesh = jax.make_mesh((len(devices),), ("x",), devices=devices)
    x = jax.device_put(jnp.ones((1024 * len(devices), 1024), jnp.bfloat16),
                       NamedSharding(mesh, P("x", None)))
    w = jnp.ones((1024, 1024), jnp.bfloat16)
    f = jax.jit(lambda a, b: jnp.sum(a @ b, axis=0),
                out_shardings=NamedSharding(mesh, P()))
    sc = np.zeros((1, N_SC))
    sc[:, SC.speed_a] = sc[:, SC.speed_b] = 1.0
    sc[:, SC.na] = sc[:, SC.nb] = 12.0
    tiles = (np.ones((1, N_AV, 16)), np.ones((1, N_AV, 128)),
             np.ones((1, N_PM, 16, 128)), sc)
    scorer_jit.score_tiles_f32(*tiles)           # compile outside the trace
    f(x, w).block_until_ready()

    cell = harness.Cell("record", len(devices), "", {}, "", {}, None, [], [])
    run = harness.Run(cell=cell, seed=0, seconds=0.0, trace=True,
                      devices=devices)
    with run.profiled():
        # the device's clock in the trace sits up to a few milliseconds off
        # the host's: idle host time on both sides keeps every device
        # event inside the traced window
        time.sleep(0.05)
        scorer_jit.score_tiles_f32(*tiles)
        f(x, w).block_until_ready()
        time.sleep(0.05)
    Path(out).parent.mkdir(parents=True, exist_ok=True)
    shutil.copy(trace.find_xplane(run.trace_path), out)
    shutil.rmtree(run.trace_path, ignore_errors=True)
    print(f"wrote {out} ({Path(out).stat().st_size} bytes)")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
