"""Test harness config: force an 8-device CPU platform before JAX initialises.

This is the standard JAX trick for testing distributed code without a cluster
(SURVEY.md §4): ``xla_force_host_platform_device_count=8`` gives 8 virtual CPU
devices, so ``shard_map`` tree merges run exactly the collective program they
would run on an 8-chip TPU slice.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# Keep CPU feature-parity with TPU numerics tests deterministic.
os.environ.setdefault("JAX_ENABLE_X64", "0")
# The persistent compile cache stays off for tests — in this process and in
# every subprocess a test starts (cli.configure_compile_cache only places
# the directory; this switch decides whether anything is read or written).
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "0"

# Pin the platform in the config too, so a test run never reaches for an
# accelerator even where one is attached.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="module")
def _clear_jax_caches_per_module():
    """Cap cumulative executable/tracing state across the suite.

    Most tests jit fresh lambdas/closures, each a permanent entry in the
    global jit cache; by ~370 tests the accumulated executables crashed
    the process (deterministic SIGSEGV mid-suite at test_pallas_decode,
    observed 2026-07-31 — passes in any smaller combination). Cross-file
    cache sharing is negligible, so dropping caches at module teardown
    bounds the growth at the cost of a few intra-file recompiles.
    """
    yield
    jax.clear_caches()
