"""Test configuration.

Multi-device TPU-style tests run on a virtual 8-device CPU mesh (the
reference's `_fake_gpus` trick generalized: reference
rllib/algorithms/algorithm_config.py:66 places fake GPU towers on CPU; here
XLA emulates N host devices).  Must be set before jax import anywhere in the
test process.
"""

import os

# Tests run on the CPU whatever the environment points JAX at: the chip
# is exercised by chip_smoke.py through the chip tool, not by pytest.
os.environ["JAX_PLATFORMS"] = "cpu"
prev = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in prev:
    os.environ["XLA_FLAGS"] = (
        prev + " --xla_force_host_platform_device_count=8").strip()

# The perf observatory's AOT cost harvest (device_stats.instrument)
# adds one extra XLA compile per engine program; across the dozens of
# engine configs this suite builds that would eat real minutes of the
# tier-1 870s budget.  Default it off for tests — the observatory test
# opts back in explicitly for the programs it asserts on.
os.environ.setdefault("RAYTPU_DEVICE_STATS_COST", "0")

import pytest  # noqa: E402


@pytest.fixture
def ray_start_regular():
    """Start a fresh single-node cluster for the test (reference analog:
    python/ray/tests/conftest.py:245 ray_start_regular)."""
    import ray_tpu

    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024)
    yield
    ray_tpu.shutdown()


@pytest.fixture(scope="module")
def ray_start_shared():
    """Module-shared cluster (reference analog: ray_start_regular_shared)."""
    import ray_tpu

    ray_tpu.init(num_cpus=4, object_store_memory=256 * 1024 * 1024)
    yield
    ray_tpu.shutdown()


@pytest.fixture(autouse=True)
def _tracing_isolation():
    """Reset util.tracing after every test: the fallback span list and
    the enabled flag are process globals, so without this a test that
    calls enable_tracing() leaks spans (and the enabled bit) into every
    later test in the same process."""
    yield
    from ray_tpu.util import tracing

    tracing.reset_tracing()


@pytest.fixture
def per_call_us():
    """``per_call_us(fn)``: microseconds one call of `fn` costs, measured
    in isolation -- ``timeit``, the minimum over repeats, so a loaded
    box (six xdist workers on shared cores) slows the test down but
    does not decide it.  The overhead guards hold a recorder's hot call
    to an absolute budget with this, where they used to time two whole
    decode loops against each other.  ``per_call_us.calls`` is how often
    one measurement calls `fn`."""
    import timeit

    number, repeat = 2000, 7

    def measure(fn) -> float:
        return min(timeit.repeat(fn, number=number, repeat=repeat)) \
            / number * 1e6

    measure.calls = number * repeat
    return measure
