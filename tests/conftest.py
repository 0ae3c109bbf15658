"""Test configuration: run the suite on a virtual 8-device CPU mesh so that
multi-chip sharding paths are exercised without TPU hardware (the driver
separately dry-runs the multi-chip path via __graft_entry__.dryrun_multichip).

The backend is configured through jax.config here, before any test touches
a device, so the suite needs no environment variable."""

import os
import sys

import jax
import pytest

# repo root on sys.path: the editable install has vanished between sessions
# before (transient env resets); the suite must not depend on it
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Force CPU: the suite needs f64/c128 (unsupported on TPU) and a virtual
# multi-device mesh. Set SIRIUS_TPU_TEST_PLATFORM to override.
jax.config.update("jax_platforms", os.environ.get("SIRIUS_TPU_TEST_PLATFORM", "cpu"))
jax.config.update("jax_num_cpu_devices", 8)
jax.config.update("jax_enable_x64", True)

def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: heavy decks / long SCF runs (deselect with -m 'not slow')")
    config.addinivalue_line(
        "markers", "faults: fault-injection tests for the SCF recovery ladder")


@pytest.fixture(autouse=True)
def _clear_faults():
    """Fault plans must never leak between tests (utils/faults.py keeps
    module-level state)."""
    from sirius_tpu.utils import faults

    faults.clear()
    yield
    faults.clear()


REFERENCE_ROOT = "/root/reference"


def reference_available() -> bool:
    return os.path.isdir(os.path.join(REFERENCE_ROOT, "verification"))


requires_reference = pytest.mark.skipif(
    not reference_available(),
    reason="reference verification data not mounted at /root/reference",
)
