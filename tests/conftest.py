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


def instruments_left_on():
    """What a test module left of the process-wide instruments
    (``obs.FLIGHT``, ``obs.TRACER``) that a later module in the same
    process would inherit: a worker runs several files one after another."""
    from tree_attention_tpu import obs

    left = []
    if obs.FLIGHT.enabled:
        left.append("the flight recorder armed")
    snap = obs.FLIGHT.snapshot()
    if snap["records"] or "programs" in snap:
        left.append("the flight recorder's ring or program tables uncleared")
    if obs.TRACER.active:
        left.append("the span tracer active")
    return left


def instruments_off():
    """The state :func:`instruments_left_on` calls clean."""
    from tree_attention_tpu import obs

    obs.FLIGHT.disarm()
    obs.FLIGHT.clear()
    obs.TRACER.close()


@pytest.fixture(autouse=True, scope="module")
def _instruments_off_at_module_end(request):
    """A module that leaves an instrument on fails here, by its own name,
    and not in whichever file the worker runs next; the state is put right
    first, so one leak fails once."""
    yield
    left = instruments_left_on()
    instruments_off()
    assert not left, f"{request.module.__name__} left " + ", ".join(left)
