"""JAX's persistent compilation cache: one directory for every process of a run.

A cold 32-layer step program takes a quarter of a minute to a minute and a
half to compile, and every entry point that reaches the chip (the engine
server, ``chip_smoke.py`` and the children they start) compiles
the same programs.  They all call :func:`enable_compile_cache` first thing, so
a second process, or a second run on the same machine, loads what the first
one compiled.

Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no code
sets another directory.  Where it is not, the cache lives in ``.jax_cache`` at
the root of the checkout: a fixed path, because the path is part of what a
cache entry is looked up by, so a directory made from a temporary name, a pid
or the time never hits.  The tests come through here as well:
``tests/conftest.py`` names, through that variable, one directory outside the
checkout for the pytest workers and the children they start, with thresholds
low enough for a small model's CPU programs, and calls
:func:`enable_compile_cache` for the counts.
"""

from __future__ import annotations

import logging
import os
from typing import Dict

logger = logging.getLogger(__name__)

REPO_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache",
)

_EVENTS = {
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "misses",
}
# Process-wide, like the cache itself: filled by JAX's monitoring events
# once enable_compile_cache() has run, served by GET /debug/compiles.
_state: Dict = {"dir": None, "hits": 0, "misses": 0}


def _count(event: str, **_kwargs) -> None:
    key = _EVENTS.get(event)
    if key is not None:
        _state[key] += 1


def enable_compile_cache() -> str:
    """Switch the persistent cache on for this process; returns its directory."""
    import jax
    from jax import monitoring

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = REPO_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    if _state["dir"] is None:
        monitoring.register_event_listener(_count)
    _state["dir"] = path
    logger.info("Persistent compile cache: %s", path)
    return path


def compile_cache_report() -> Dict:
    """{"dir", "hits", "misses"} of this process (dir None: cache not enabled)."""
    return dict(_state)
