"""Test fixtures.

Mirrors the reference's conftest design (reference:
python/ray/tests/conftest.py:532 ray_start_regular, :479 _ray_start):
fixtures boot/teardown runtimes per test; JAX tests run on a virtual
8-device CPU mesh (the reference's fake-multi-node trick applied to chips —
SURVEY.md §4 item (d)).
"""

import os

# The suite runs on a virtual 8-device CPU mesh, chip or no chip: the env
# var holds every process the tests spawn to the CPU, the jax.config update
# below holds this one even if a plugin imported jax before this file. No
# test process writes a persistent compile cache.
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# helper modules that hold test functions for several collected files: their
# asserts say what they compared, as a test file's do
pytest.register_assert_rewrite("_block_cases", "_tpu_compile")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from the tier-1 "
        "`-m 'not slow'` sweep")
    config.addinivalue_line(
        "markers", "pallas_interpret: Pallas TPU kernel tests that run "
        "in interpret mode on the tier-1 CPU sweep (JAX_PLATFORMS=cpu) "
        "— same kernel logic, emulated lowering")
    config.addinivalue_line(
        "markers", "chaos: fault-injection lifecycle tests driven via "
        "ray_tpu.util.fault_injector (RTPU_FAULT_INJECT hook points)")


@pytest.fixture
def fault_injector():
    """Armed-and-disarmed FaultInjector access: yields the module, then
    resets the point table and env var in teardown so chaos specs never
    leak into the next test."""
    from ray_tpu.util import fault_injector as fi
    yield fi
    fi.reset()
    os.environ.pop(fi.ENV_VAR, None)


@pytest.fixture
def pallas_interpret():
    """Interpret flag for Pallas kernel tests: True off-TPU (tier-1 runs
    the kernels via the Pallas interpreter on CPU), False on real TPU
    where the compiled kernel itself should be exercised."""
    return jax.default_backend() != "tpu"


@pytest.fixture
def rtpu_local():
    import ray_tpu
    ray_tpu.init(local_mode=True, num_cpus=4)
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture
def rtpu_cluster():
    import ray_tpu
    ray_tpu.init(num_cpus=2, _system_config={
        "object_store_memory_bytes": 256 * 1024 * 1024,
        "worker_pool_max": 4,
    })
    yield ray_tpu
    ray_tpu.shutdown()


@pytest.fixture(scope="session")
def cpu_mesh_devices():
    import jax
    devices = jax.devices("cpu")
    assert len(devices) >= 8, "conftest must provide 8 virtual devices"
    return devices


# The engine makes a launch it holds back behind a free slot when the
# program in flight is DUE, by its own clocks (llm/engine.py: step). On the
# CPU, where a tiny program runs a millisecond or two, whether a step finds
# its flight landed, due or still to wait for is the machine's timing: the
# tokens are the same either way, the order of launches and bookings is
# not. The suite reads that order (spans, counters, hand-overs), so here no
# program is ever clocked: every engine does what it does before it has
# seen a (kind, shape) once, which is what it did before the late decision.
# The tests OF the late decision ask for the real estimate (`late_decision`)
# and script the clocks and the device (tests/test_llm_ahead.py).
from ray_tpu.llm.engine import InferenceEngine  # noqa: E402

_EXPECTED_END_NS = InferenceEngine._expected_end_ns
InferenceEngine._expected_end_ns = lambda self, flight: None


@pytest.fixture
def late_decision(monkeypatch):
    """The engine as it runs outside the tests: programs are clocked and a
    held launch is made when the flight is due."""
    monkeypatch.setattr(InferenceEngine, "_expected_end_ns",
                        _EXPECTED_END_NS)
