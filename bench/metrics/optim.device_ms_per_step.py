"""Device milliseconds a traced train step spends in the optimizer: the
ops under the ``optimizer`` name scope (``optim/adamw.py``: the gradients'
global norm, the moments and the update; ``bench/scopes.py``).  ``None``
where no op carries the scope."""
from bench import scopes


def read(run, trace, peaks):
    return scopes.ms_per_step(run, trace, "optimizer")
