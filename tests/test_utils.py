"""Utils: URL validation, static parsing, registry semantics.

Reference counterparts: src/vllm_router/utils.py:42-95,
src/tests/test_singleton.py:14-60.
"""

import pytest

from production_stack_tpu.utils.net import (
    parse_static_aliases,
    parse_static_models,
    parse_static_urls,
    validate_url,
)
from production_stack_tpu.utils.registry import ServiceRegistry


@pytest.mark.parametrize(
    "url,ok",
    [
        ("http://localhost:8000", True),
        ("https://engine-0.ns.svc.cluster.local:8000", True),
        ("http://10.0.0.1:8000/v1", True),
        ("ftp://host", False),
        ("localhost:8000", False),
        ("", False),
        ("http://", False),
    ],
)
def test_validate_url(url, ok):
    assert validate_url(url) is ok


def test_parse_static_urls():
    assert parse_static_urls("http://a:1, http://b:2") == ["http://a:1", "http://b:2"]
    with pytest.raises(ValueError):
        parse_static_urls("http://a:1,not-a-url")


def test_parse_static_models():
    assert parse_static_models("m1, m2,m3") == ["m1", "m2", "m3"]
    assert parse_static_models("") == []


def test_parse_static_aliases():
    assert parse_static_aliases("gpt-4:llama-3-8b") == {"gpt-4": "llama-3-8b"}
    with pytest.raises(ValueError):
        parse_static_aliases("no-colon")


def test_registry_require_raises():
    reg = ServiceRegistry()
    with pytest.raises(KeyError):
        reg.require("router")


def test_registry_replace_atomic_and_closes_old():
    reg = ServiceRegistry()
    closed = []
    reg.set("svc", "old")
    out = reg.replace("svc", lambda: "new", close_old=closed.append)
    assert out == "new"
    assert reg.get("svc") == "new"
    assert closed == ["old"]


def test_registry_reset():
    reg = ServiceRegistry()
    reg.set("a", 1)
    reg.reset()
    assert not reg.contains("a")


# -- the harness itself (tests/conftest.py) ----------------------------------


def test_a_test_past_its_time_limit_fails_by_name_with_its_stack(capfd):
    import signal
    import threading

    from conftest import time_limit

    outer = signal.getitimer(signal.ITIMER_REAL)[0]
    with pytest.raises(pytest.fail.Exception, match="the sleeper ran past"):
        with time_limit(0.05, "the sleeper"):
            threading.Event().wait(30)
    assert "test_a_test_past_its_time_limit" in capfd.readouterr().err
    # The limit this test itself runs under is armed again, less what passed.
    assert 0 < signal.getitimer(signal.ITIMER_REAL)[0] <= outer


def test_a_hang_below_python_still_prints_its_stack(tmp_path):
    """The main thread in a native call that no signal interrupts (the alarm
    goes to another thread, and its Python handler waits for bytecode): for
    as long as the call lasts the test cannot be failed, but the watchdog
    thread has said where it hangs."""
    import signal
    import threading
    import time

    from conftest import time_limit

    done = threading.Event()
    takes_the_alarm = threading.Thread(target=done.wait)
    takes_the_alarm.start()
    signal.pthread_sigmask(signal.SIG_BLOCK, {signal.SIGALRM})
    try:
        with open(tmp_path / "stacks", "w+") as stacks:
            with pytest.raises(pytest.fail.Exception, match="the sleeper"):
                with time_limit(0.05, "the sleeper", stacks_to=stacks):
                    time.sleep(1.3)
            stacks.seek(0)
            assert "test_a_hang_below_python_still_prints_its_stack" in stacks.read()
    finally:
        signal.pthread_sigmask(signal.SIG_UNBLOCK, {signal.SIGALRM})
        done.set()
        takes_the_alarm.join()


def test_a_second_closure_of_a_program_comes_from_the_persistent_cache():
    """Tier-1 compiles a program once a run: a fresh ``jax.jit`` closure, as
    every fresh ``LLMEngine`` builds, finds the executable that the closure
    before it (or another xdist worker, or the run before) left in the one
    directory, which is not in the checkout."""
    jax = pytest.importorskip("jax")
    import os

    import numpy as np

    from production_stack_tpu.utils.compile_cache import compile_cache_report

    def fresh():
        return jax.jit(lambda x: jax.numpy.tanh(x) * 3.0 + 1.0)

    x = np.arange(8, dtype=np.float32)
    # Whatever threshold tier-1 keeps: this program compiles in no time.
    threshold = "jax_persistent_cache_min_compile_time_secs"
    kept = getattr(jax.config, threshold)
    jax.config.update(threshold, 0.0)
    try:
        first = np.asarray(fresh()(x))  # a miss on an empty directory
        before = compile_cache_report()
        assert np.array_equal(np.asarray(fresh()(x)), first)
        after = compile_cache_report()
    finally:
        jax.config.update(threshold, kept)
    assert (after["hits"], after["misses"]) == (
        before["hits"] + 1, before["misses"])

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert after["dir"] == os.environ["JAX_COMPILATION_CACHE_DIR"]
    assert not os.path.realpath(after["dir"]).startswith(repo + os.sep)
