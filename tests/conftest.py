# NOTE: no XLA_FLAGS here on purpose — smoke tests and benches must see the
# single real CPU device. Multi-device mesh behaviour is tested via
# subprocesses (test_mesh_multidevice.py / test_distributed.py) that set
# --xla_force_host_platform_device_count themselves.
import os
from datetime import timedelta

import jax
import numpy as np
import pytest

# The persistent compilation cache pays off only across processes, and the
# cold-start tests (test_distributed.py) relaunch subprocesses to see it.
# In-process, the test workers would only race on partly written entries
# of one cache directory.
jax.config.update("jax_enable_compilation_cache", False)

try:
    from hypothesis import settings as _hyp_settings
except ImportError:  # hypothesis is the optional 'test' extra
    pass
else:
    # Property suites inherit these unless a test's @settings overrides the
    # field: "ci" is derandomized (stable example schedules — a failure on
    # one machine reproduces on every machine) with an explicit per-example
    # deadline generous enough for a first-example JAX trace; "dev" keeps
    # fresh randomness for local exploration. Select with the
    # HYPOTHESIS_PROFILE env var (default: ci).
    _hyp_settings.register_profile(
        "ci", derandomize=True, deadline=timedelta(seconds=15),
        print_blob=True)
    _hyp_settings.register_profile(
        "dev", derandomize=False, deadline=timedelta(seconds=15))
    _hyp_settings.load_profile(os.environ.get("HYPOTHESIS_PROFILE", "ci"))


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(0)
