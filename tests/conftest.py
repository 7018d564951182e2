"""Test configuration: force an 8-device virtual CPU mesh before any XLA
client is created.

This is the analogue of the reference's Spark ``local[N]`` / threaded
ParallelWrapper test strategy (SURVEY.md §4): multi-device semantics are
validated on one host by faking 8 XLA CPU devices.  Also enables x64 so the
gradient-check suite can run central differences in double precision, like
the reference's double-precision gradient checks.

Tests run on the CPU wherever they run: ``JAX_PLATFORMS=cpu`` and the
``jax.config.update`` below are set here, at conftest import time, before
any test imports compute code and creates a backend client.  The chip is
checked by ``chip_smoke.py``, not by this suite.
"""

import os
import sys

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running test (multi-process cluster, "
        "large serde round-trips)")
