"""Test harness: an 8-device virtual CPU mesh (SURVEY.md §4 — multi-node
behavior is validated in-process, the reference's loopback-test shape;
here the 'loopback' is xla_force_host_platform_device_count). The suite
asks for the CPU platform whatever the machine has; both settings must be
in the environment before JAX starts a backend.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import pytest  # noqa: E402


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: long multi-process orchestrations (tier-1 runs -m 'not slow')",
    )


@pytest.fixture
def tuned_flags():
    """Snapshot/restore any process-global flag a test retunes — shared
    by every test file that tweaks flags (rpcz, telemetry, auto_cl...),
    so one implementation owns the restore discipline."""
    from incubator_brpc_tpu.utils.flags import (
        flag_registry,
        set_flag_unchecked,
    )

    touched = {}

    def tune(name, value):
        if name not in touched:
            touched[name] = flag_registry.get(name)
        set_flag_unchecked(name, value)

    yield tune
    for name, value in touched.items():
        set_flag_unchecked(name, value)
