import os
import sys
from pathlib import Path

# smoke tests and benches see the host's one CPU device; a test that needs a
# mesh of several runs a subprocess with
# XLA_FLAGS=--xla_force_host_platform_device_count=N.
sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import jax  # noqa: E402

jax.config.update("jax_enable_x64", False)

# hypothesis is a dev-only dependency (requirements-dev.txt); property tests
# importorskip it themselves — without the guard a missing install would kill
# the whole suite at collection time.
try:
    from hypothesis import HealthCheck, settings  # noqa: E402
except ModuleNotFoundError:
    pass
else:
    # deterministic property tests (CI reproducibility)
    settings.register_profile(
        "ci", derandomize=True, deadline=None,
        suppress_health_check=[HealthCheck.too_slow])
    settings.load_profile("ci")
