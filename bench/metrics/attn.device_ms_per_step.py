"""Device milliseconds a traced train step spends in attention: the union
of the intervals of the ops under the ``attn`` name scope
(``models/attention.py``), forward, recomputed and backward (``bench/
scopes.py``).  ``None`` where no op carries the scope."""
from bench import scopes


def read(run, trace, peaks):
    return scopes.ms_per_step(run, trace, "attn")
