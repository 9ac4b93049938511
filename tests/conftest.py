"""Shared fixtures.  NOTE: no XLA_FLAGS here — smoke tests and benches must
see the 1 real device; multi-device tests spawn subprocesses that set
--xla_force_host_platform_device_count themselves."""

import os

# The launchers turn JAX's persistent compilation cache on
# (repro/launch/compile_cache.py); the tests, and the launcher subprocesses
# they start, never write it.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)
