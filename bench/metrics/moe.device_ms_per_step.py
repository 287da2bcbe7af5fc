"""Device milliseconds a traced train step spends in the MoE layer: the
union of the intervals of the ops under the ``moe`` name scope and its
sub-scopes (router, dispatch, experts, combine, stats; ``models/moe.py``),
forward, recomputed and backward (``bench/scopes.py``).  ``None`` where no
op carries the scope."""
from bench import scopes


def read(run, trace, peaks):
    return scopes.ms_per_step(run, trace, "moe")
