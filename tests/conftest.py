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
