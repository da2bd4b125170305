"""Test configuration: force an 8-device virtual CPU mesh so every sharding
path (TP/DP/SP/EP) is exercised without TPU hardware, mirroring the
reference's everything-runs-on-CPU-CI test strategy (SURVEY §4).

The tests run on the CPU platform whatever the machine holds: the env var
and jax.config both say so before any backend is initialized. Only
tests/test_chip_compile.py describes a chip (inside a fixture), and
nothing here runs on one — chip_smoke.py does that.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")

import numpy as np
import pytest


@pytest.fixture(scope="session")
def rng():
    return np.random.default_rng(42)
