"""Test harness: force an 8-device virtual CPU platform BEFORE jax import.

Mirrors the reference's test strategy (SURVEY §4): deterministic virtual time
via ManualClock, and distributed-checker tests without hardware via
``--xla_force_host_platform_device_count=8`` (the analog of the reference's
single-JVM cluster-checker tests).
"""

import os

# The suite never takes a chip: it pins the CPU platform through
# `jax.config.update` (which outranks whatever JAX_PLATFORMS says), so a
# test run beside a process that holds the TPU cannot fail or hang on it.
# XLA_FLAGS works here because the CPU client isn't created until first use.
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

from sentinel_tpu.core.clock import ManualClock, set_global_clock  # noqa: E402


@pytest.fixture
def clock():
    """Virtual clock installed globally for the test (AbstractTimeBasedTest)."""
    c = ManualClock(start_ms=10_000_000)
    prev = set_global_clock(c)
    yield c
    set_global_clock(prev)
