"""Test harness: force an 8-device virtual CPU mesh before JAX initializes.

Mirrors SURVEY.md's test strategy: multi-chip sharding is validated on a
virtual host-platform mesh (the driver separately dry-runs the real
multi-chip path via __graft_entry__.dryrun_multichip).

Tests run on the CPU: JAX_PLATFORMS is forced here (also for any
subprocess a test spawns), and the platform is pinned through jax.config
before first backend use.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
# subprocesses a test spawns get the same 8 virtual devices
_FLAG = "--xla_force_host_platform_device_count=8"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " " + _FLAG).strip()

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import numpy as np
import pytest


def pytest_configure(config):
    # tier-1 CI deselects these (`-m 'not slow'`); the deeper sweeps
    # (e.g. the SENDS=3 pod model run) still run on demand
    config.addinivalue_line(
        "markers", "slow: deeper sweeps excluded from the tier-1 run")


@pytest.fixture
def rng():
    return np.random.default_rng(0xDF170)
