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
import contextlib
import faulthandler
import inspect
import os
import signal
import sys
import tempfile

# Tests run on the CPU, on eight virtual devices, whatever the machine
# holds; chip_smoke.py and bench/run.py are the entries that need the chip.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# One persistent compile cache for every process of a run.  A fresh
# LLMEngine builds fresh jax.jit closures, so the jit's in-memory cache never
# hits from one test to the next though the HLO is the same text, and each
# xdist worker would compile it again on its own.  JAX_COMPILATION_CACHE_DIR
# is obeyed where set (utils/compile_cache.py's rule); otherwise one fixed
# directory outside the checkout (the driver copies the tree, and .jax_cache
# holds the chip's entries), named for the user and not for a pid or a time,
# so that workers, children and the next run share it (where the caller gives
# a TMPDIR of its own, gettempdir() follows it, and so does the cache: runs
# under different TMPDIRs share nothing).  To start cold, delete the directory.
_COMPILE_CACHE = {
    "jax_compilation_cache_dir": os.environ.get("JAX_COMPILATION_CACHE_DIR")
    or os.path.join(
        tempfile.gettempdir(), f"production-stack-tpu-jax-cache-{os.getuid()}"
    ),
    # A cap, so that the directory cannot grow without bound; with one set,
    # JAX also holds a file lock around every read and write, so six workers
    # never see each other's half-written entries.  The price sets the two
    # numbers: under a cap every write lists the whole directory under that
    # lock (55-71 us an entry), and the directory's steady state is not
    # empty but full: a tree that changes leaves its old programs behind
    # until the cap evicts them.  Full of stale entries a write costs 106 ms
    # at 64 MiB, 135 ms at 80 and 272 ms at 128 (a hit 0.2-0.3 ms at any
    # size), and a cold suite writes some 1,500 (57 MB).  So: one suite and
    # less than half again.  When a cold suite's entries come near the cap,
    # raise it (or the threshold below): past it a warm run evicts what it
    # is about to read.
    "jax_compilation_cache_max_size": 80 * 1024**2,
    # JAX's default (1 s) keeps a 2-layer model's CPU programs out: most
    # compile in less.  Of 4,543 programs that 445 tests compiled, the 540
    # that took 0.15 s or more were 89 % of the seconds spent compiling a
    # program again (0.1 s: 854 and 91 %; 0.2 s: 428 and 80 %).
    "jax_persistent_cache_min_compile_time_secs": 0.15,
}
if "JAX_COMPILATION_CACHE_DIR" in os.environ:
    # The caller's directory is the caller's to bound: a cap of ours would
    # evict whatever else it holds.
    del _COMPILE_CACHE["jax_compilation_cache_max_size"]
# Through the environment for the children tests start, and before the import
# for this process.  A directory that cannot be written costs one warning and
# the tests compile as they did without it (jax/_src/compiler.py: _cache_read,
# _cache_write).
for _name, _value in _COMPILE_CACHE.items():
    os.environ[_name.upper()] = str(_value)

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
    for _name, _value in _COMPILE_CACHE.items():
        jax.config.update(_name, _value)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402

if jax is not None:
    from production_stack_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()  # the hit and miss counts of /debug/compiles


# Several times the slowest honest test (62 s under six busy workers at PR 63:
# test_spec_model_drafter.py::test_greedy_parity_matrix_pure_decode), and a
# fifth of the run's own limit.
TEST_TIME_LIMIT_S = 300.0


# Where the stacks of a hang go: the process's own stderr, which pytest's
# capture has moved away from fd 2 by the time a test runs.  A test that hangs
# below Python never ends, so what it wrote into the capture is never shown.
_stacks_to = sys.stderr


def pytest_configure(config):
    global _stacks_to
    capture = config.pluginmanager.getplugin("capturemanager")
    if capture is not None:
        with capture.global_and_fixture_disabled():
            _stacks_to = os.fdopen(os.dup(2), "w")


@contextlib.contextmanager
def time_limit(seconds, what, stacks_to=None):
    """Fail ``what`` by name, with every thread's stack on stderr, once it has
    run for ``seconds``: a test that hangs then costs its limit and not the
    whole run's, which ends as an anonymous exit 124.  Main thread only (an
    alarm); the failure is pytest's own BaseException, so that no ``except
    Exception`` between the hang and the test swallows it.

    The alarm's handler runs only once the main thread is back in Python
    bytecode.  A hang inside XLA or on a native lock never gets there, so
    faulthandler's own watchdog thread is armed a second behind the alarm: it
    cannot fail the test, but it prints every stack, the test's frames among
    them, to ``stacks_to`` (the process's stderr), and the run's 124 then
    has a name."""

    def expired(signum, frame):
        faulthandler.dump_traceback(file=sys.stderr)
        pytest.fail(f"{what} ran past its time limit of {seconds:g} s")

    stacks_to = stacks_to or _stacks_to
    old_handler = signal.signal(signal.SIGALRM, expired)
    old_timer = signal.setitimer(signal.ITIMER_REAL, seconds)
    faulthandler.dump_traceback_later(seconds + 1.0, file=stacks_to)
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, *old_timer)
        signal.signal(signal.SIGALRM, old_handler)
        # One watchdog a process: an enclosing limit's is armed again.
        faulthandler.cancel_dump_traceback_later()
        if old_timer[0] > 0:
            faulthandler.dump_traceback_later(old_timer[0] + 1.0, file=_stacks_to)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_protocol(item):
    """Fixtures boot engines too, so the limit spans a test's set-up, call
    and tear-down; the alarm lands inside whichever is running and is
    reported as that phase's failure."""
    with time_limit(TEST_TIME_LIMIT_S, item.nodeid):
        return (yield)


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
