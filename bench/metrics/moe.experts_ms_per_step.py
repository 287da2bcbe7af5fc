"""Device milliseconds a traced train step spends in the experts' own work,
their three GEMMs and the activation: the ops under the ``moe/experts``
name scope (``models/moe.py``).  ``moe.device_ms_per_step`` less this is
the layer's routing, dispatch and combine (``bench/scopes.py``).  ``None``
where no op carries the scope."""
from bench import scopes


def read(run, trace, peaks):
    return scopes.ms_per_step(run, trace, "moe/experts")
