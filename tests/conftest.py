"""Test config: force an 8-device virtual CPU mesh before jax initializes.

Mirrors the reference's strategy (SURVEY.md §4): distributed correctness is
asserted as numerical equivalence to the serial model, on one host. XLA's
host-platform device-count flag gives 8 fake devices for mesh/collective tests.
"""
import os

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()  # noqa: PTA007 -- session-lifetime: device count must precede backend creation

# The tests are a CPU program: JAX_PLATFORMS=cpu selects the backend, and
# its 8 virtual devices are what every mesh in the suite is built from.
os.environ.setdefault("JAX_PLATFORMS", "cpu")  # noqa: PTA007 -- session-lifetime: the backend must be chosen before it is created

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from paddle_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

# Persistent compile cache: repeat suite runs skip XLA compilation entirely.
enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.5)  # noqa: PTA007 -- session-lifetime cache config


def pytest_configure(config):
    # tier-1 runs `-m 'not slow'`: register the marker so filtered tests
    # (multi-device overlap sweeps, benches) don't warn as unknown
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 CPU run (-m 'not slow')")


@pytest.fixture(autouse=True)
def _seed_everything():
    import paddle_tpu as paddle
    paddle.set_device("cpu")
    paddle.seed(2024)
    np.random.seed(2024)
    yield


@pytest.fixture(scope="session", autouse=True)
def _telemetry_no_host_sync():
    """Observability acceptance guard: a telemetry-enabled TrainStep must not
    leak host syncs (device->host transfers / tracer leaks) into the jitted
    hot path. The first call compiles OUTSIDE the guard (compiles legally
    fetch cost analysis); steady-state steps run under jax.checking_leaks +
    a disallow transfer guard and fail the session loudly if telemetry ever
    grows a block_until_ready or implicit host fetch."""
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    from paddle_tpu import observability as obs
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.optimizer import AdamW

    paddle.set_device("cpu")
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(8, 8), nn.GELU(), nn.Linear(8, 4))
    opt = AdamW(learning_rate=1e-2, parameters=model.parameters())
    step = TrainStep(model, lambda o, l: paddle.mean((o - l) ** 2), opt,
                     telemetry=True)
    x = paddle.to_tensor(np.zeros((4, 8), np.float32))
    y = paddle.to_tensor(np.zeros((4, 4), np.float32))
    step(x, labels=y)  # compile step: trace + cost analysis happen here
    try:
        with jax.checking_leaks(), \
                jax.transfer_guard_device_to_host("disallow"):
            step(x, labels=y)
            step(x, labels=y)
    except Exception as e:  # pragma: no cover - the failure being guarded
        pytest.fail(
            f"telemetry leaked a host sync into the jitted step: {e!r}")
    finally:
        if step.telemetry is not None:
            step.telemetry.close()
        obs.set_active(None)
        obs.reset_counters()
    yield
