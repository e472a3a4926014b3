import os
import sys

# Tests run on the single real CPU device by default — the N-device trick is
# for the OPT-IN sharded lane
# (`make test-sharded` sets REPRO_SHARDED_LANE=1 together with
# XLA_FLAGS=--xla_force_host_platform_device_count=8 so the ring ppermute
# path runs with nshards > 1; see tests/test_sharded_engine.py). Keep any
# accidental XLA_FLAGS leakage honest outside that lane:
if not os.environ.get("REPRO_SHARDED_LANE"):
    assert "xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", "")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def key():
    return jax.random.PRNGKey(0)
