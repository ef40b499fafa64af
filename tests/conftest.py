"""Test fixtures. Tests run on the CPU and see 1 device (dryrun forces 512
in its own process); Pallas kernels run in interpret mode there.  The
chip is reached through ``chip_smoke.py``, and ``tests/test_tpu_compile.py``
compiles the kernels for a described TPU v5e without one."""
import os

# keep XLA single-threaded enough to not oversubscribe CI boxes
os.environ.setdefault("XLA_FLAGS", "--xla_cpu_multi_thread_eigen=false")
# servers started by tests (they inherit this) keep no compile cache in
# the checkout
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

import jax
import pytest


@pytest.fixture(scope="session")
def rng():
    return jax.random.key(0)


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running integration test")
