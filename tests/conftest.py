"""Test env: 8 virtual CPU devices so multi-chip sharding tests run without
TPU hardware (SURVEY §4 implication: CPU-backend XLA simulation of a mesh)."""
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=8")

import numpy as np
import pytest

# One compile cache for the whole session — this process and the children
# the tests start — placed by the framework's own resolver
# (JAX_COMPILATION_CACHE_DIR if set, else <checkout>/.jax_cache).  Tests
# rebuild the same small programs over and over; an identical HLO is then
# loaded instead of recompiled, which takes about a seventh off the suite's
# wall time even from an empty directory.
from paddle_tpu.fluid import compile_cache as _compile_cache

os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                      _compile_cache.enable_jax_cache())
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'` (ROADMAP.md): `slow` marks suites kept
    # out of the 870s budget — multi-process/subprocess launchers and the
    # shard_map-compile-heavy parallel sweeps.  They still run in the
    # nightly `pytest tests/` tier and standalone.
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 budgeted run")


@pytest.fixture(autouse=True)
def fresh_programs():
    """Each test gets fresh default programs + scope (fluid global state)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework, core
    prev_main = framework._main_program
    prev_startup = framework._startup_program
    prev_scope = core._global_scope
    framework._main_program = framework.Program()
    framework._startup_program = framework.Program()
    core._global_scope = core.Scope()
    framework.reset_unique_name()
    yield
    framework._main_program = prev_main
    framework._startup_program = prev_startup
    core._global_scope = prev_scope


@pytest.fixture
def rng():
    return np.random.RandomState(42)


# opt-in hang watchdog: HANG_DEBUG=1 dumps every thread's traceback and
# exits if any single test runs >300s (how the VarBase sequence-protocol
# hang was caught)
import faulthandler as _fh
import os as _os
if _os.environ.get("HANG_DEBUG"):
    _fh.dump_traceback_later(300, exit=True)
