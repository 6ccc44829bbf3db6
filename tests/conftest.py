"""Test harness configuration.

Two jobs:

1. Force JAX onto a *virtual 8-device CPU mesh* so every sharding/collective
   path is exercised without TPU hardware (tests/test_parallel.py runs the
   (dp, tp, sp) matrix on it; ``chip_smoke.py`` is the proof on the chip).
   Must happen before jax import.
2. Provide asyncio test support without pytest-asyncio: ``async def`` test
   functions are run via asyncio.run().

Reference test strategy being mirrored: SURVEY.md section 4 (duck-typed fakes,
fake engine servers on localhost, no accelerator required).
"""

import asyncio
import inspect
import os
import sys

# Tests run on the CPU, on eight virtual devices, whatever the machine
# holds; chip_smoke.py and bench/run.py are the entries that need the chip.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

try:
    import jax
except ImportError:  # router-only environment: engine tests will skip
    jax = None

if jax is not None:
    if jax.devices()[0].platform != "cpu":
        raise RuntimeError(
            "tests must run on the virtual CPU mesh; got "
            f"{jax.devices()[0].platform!r} (TPU float32 matmuls break "
            "HF-parity tolerances)"
        )

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


def pytest_pyfunc_call(pyfuncitem):
    """Run coroutine tests with asyncio.run (stand-in for pytest-asyncio)."""
    fn = pyfuncitem.obj
    if inspect.iscoroutinefunction(fn):
        kwargs = {
            name: pyfuncitem.funcargs[name]
            for name in pyfuncitem._fixtureinfo.argnames
        }
        asyncio.run(fn(**kwargs))
        return True
    return None


@pytest.fixture()
def registry():
    """Fresh service registry per test (reference resets SingletonMeta._instances,
    src/tests/test_singleton.py:14-60)."""
    from production_stack_tpu.utils.registry import ServiceRegistry

    return ServiceRegistry()


@pytest.fixture(autouse=True, scope="session")
def plans_from_host_state():
    """A served engine ends a decode window as soon as its step thread's pass
    is covered, by its own two clocks (scheduler.WindowPace).  Under test a
    plan is a function of host state alone, so that streams and records are
    those of the run before: the pace never has its samples.  For the whole
    session, so that a module's fixture that drives an engine is held to it
    too.  A test of the pace gives it ``MIN_SAMPLES`` back through its own
    ``monkeypatch`` (tests/test_window_plan.py)."""
    if jax is None:
        yield
        return
    from production_stack_tpu.engine.core.scheduler import WindowPace

    patch = pytest.MonkeyPatch()
    patch.setattr(WindowPace, "MIN_SAMPLES", float("inf"))
    yield
    patch.undo()
