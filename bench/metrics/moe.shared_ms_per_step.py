"""Device milliseconds a traced train step spends in the MoE layers'
shared experts: the ops under the ``moe/shared`` name scope
(``models/moe.py``), forward, recomputed and backward.  ``None`` where no
op carries the scope (a model without shared experts)."""
from bench import scope_time


def read(run, trace, peaks):
    return scope_time.ms_per_step(run, trace, "moe/shared")
