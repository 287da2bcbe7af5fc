"""Device milliseconds a traced train step spends in cross-chip
collectives (``bench/kernels.is_collective``: all-reduce, all-gather,
reduce-scatter, all-to-all, collective-permute) on the busiest chip:
expert parallelism's psum of the expert outputs and what sharding adds
around it.  ``None`` with no trace or no collective op."""
from bench import kernels


def read(run, trace, peaks):
    steps = run.counters.get("traced_steps", 0)
    if trace is None or not trace.device_ids or not steps:
        return None
    if not any(trace.op_count(kernels.is_collective, d)
               for d in trace.device_ids):
        return None
    return max(trace.op_seconds(kernels.is_collective)) * 1e3 / steps
