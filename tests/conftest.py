import os
import sys

# Tests see ONE device (multi-device tests set their own XLA_FLAGS in a
# subprocess). Keep CPU compile fast.
os.environ.setdefault("JAX_PLATFORMS", "cpu")

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import pytest  # noqa: E402

jax.config.update("jax_enable_x64", False)


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "tier1: fast in-process tests (the default tier; every test "
        "without an explicit multihost marker)",
    )
    config.addinivalue_line(
        "markers",
        "multihost: subprocess tests driving an "
        "--xla_force_host_platform_device_count fake-device mesh (the "
        "slower distributed tier; `pytest -m multihost`)",
    )


def pytest_collection_modifyitems(config, items):
    # Every test is in exactly one tier: multihost where marked (module
    # pytestmark or per-test), tier1 otherwise — so
    # `-m "not multihost"` + `-m multihost` partition the suite.
    for item in items:
        if item.get_closest_marker("multihost") is None:
            item.add_marker(pytest.mark.tier1)
