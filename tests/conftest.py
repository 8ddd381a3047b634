"""Test configuration.

Tests run on the CPU, on a virtual 8-device mesh, so multi-chip
sharding paths (parallel_eda_tpu.parallel) are exercised without TPU
hardware.  The chip itself is checked by ``python chip_smoke.py``
through the builders' chip tool, never from here: force the platform
through the environment (child processes inherit it) and the config
(before any jax computation runs in this process).
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

# NO persistent compile cache for the CPU suite, child processes
# included (JAX reads this variable itself): every entry point turns
# the cache on by the one rule (router.enable_persistent_compile_cache)
# and XLA:CPU cache loads can SEGFAULT on machine-feature mismatch
# ("+prefer-no-gather not supported") when a cache dir is reused across
# hosts.  Tests of the cache rule itself re-enable it in a child.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402  (import does not initialize backends)

jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import gc  # noqa: E402

import pytest  # noqa: E402

# XLA:CPU maps memory for every executable it compiles, and a process
# may hold vm.max_map_count mappings (65,530 here).  An xdist worker
# that compiles its way through enough test files crosses that, and the
# next compile dies inside LLVM (SIGSEGV or SIGABRT in
# backend_compile_and_load; the hung run that follows is cut at its
# time limit).  Seen three runs in three once the suite grew to ~790
# tests: the workers of a healthy run already stand at 55-60 thousand.
# So between modules, past a fifth of the limit, the compiled programs
# are let go (jax.clear_caches() unmaps them); the next module compiles
# what it needs, as it would in a worker of its own.  A module that
# maps most of the limit by itself (tests/test_kernel_pack.py: 57
# thousand) calls release_compiled_programs between its own tests.
_MAPS_RELEASE_AT = 12000


def _n_maps() -> int:
    try:
        with open("/proc/self/maps") as fh:
            return sum(1 for _ in fh)
    except OSError:
        return 0


def release_compiled_programs(at: int = _MAPS_RELEASE_AT) -> None:
    if _n_maps() > at:
        jax.clear_caches()
        gc.collect()


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_programs():
    yield
    release_compiled_programs()
