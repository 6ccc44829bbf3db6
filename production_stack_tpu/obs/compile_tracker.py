"""XLA compile-event tracking for the engine's jit entry points.

The windowed engine's compiled-shape inventory is a product space
(|prefill buckets| x |decode batch buckets| x O(log K) scan variants x
spec/mixed variants); first requests routinely pay multi-second compiles
that would otherwise surface only as unexplained TTFT outliers.  The
tracker wraps each ``jax.jit`` callable in a thin proxy that watches the
executable cache size across calls: a growing cache means THIS call
traced+compiled a new input shape, and the call's wall time is (almost
entirely) that compile.  Events are keyed by a compact
``name[shape-signature]`` executable key and exported as
``tpu:compile_seconds_total{executable}`` + the ``tpu:compiled_shapes``
gauge; the engine drains pending events after each dispatch to tag the
owning windows/requests ``compile=true``.

On a TPU each event also carries ``kernels``: how many Pallas kernels
(``tpu_custom_call``) the lowered text of the program just compiled holds —
the evidence that a step took the kernel path and not the XLA gather/dense
path (``chip_smoke.py`` asserts on it through GET /debug/compiles).

jax-free at import (duck-typed ``_cache_size`` / shape probing; jax is
imported only after a wrapped jit callable has compiled), so the module
imports in the bare router/CI venv; when a wrapped callable lacks
``_cache_size`` the proxy degrades to pass-through.

Thread-safety: wrapped callables fire on the engine step thread; the HTTP
server reads snapshots from the event loop — every mutation of the shared
maps holds ``_lock``.  Disabled, ``wrap`` returns the callable unchanged,
so the fast path keeps bare jit functions (byte-identical dispatch).
"""

from __future__ import annotations

import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional

logger = logging.getLogger(__name__)

_SIG_MAX_CHARS = 96  # keep executable label cardinality readable


def _sig_part(x: Any, depth: int = 0) -> str:
    """Compact shape token for one argument: arrays render as
    ``dtype[d0,d1]``, weight pytrees collapse to ``params``, small tuples
    recurse one level, scalars render literally."""
    shape = getattr(x, "shape", None)
    if shape is not None:
        try:
            dims = ",".join(str(int(d)) for d in shape)
        except TypeError:
            dims = "?"
        dtype = getattr(x, "dtype", "")
        return f"{dtype}[{dims}]"
    if isinstance(x, dict):
        return "params"
    if isinstance(x, (list, tuple)):
        if depth >= 1 or len(x) > 4:
            return f"tree{len(x)}"
        return "(" + ",".join(_sig_part(v, depth + 1) for v in x) + ")"
    if isinstance(x, (bool, int, float)) or x is None:
        return repr(x)
    return type(x).__name__


def arg_signature(args: tuple, kwargs: dict) -> str:
    parts = [_sig_part(a) for a in args]
    parts.extend(f"{k}={_sig_part(v)}" for k, v in sorted(kwargs.items()))
    sig = ",".join(parts)
    if len(sig) > _SIG_MAX_CHARS:
        sig = sig[: _SIG_MAX_CHARS - 1] + "~"
    return sig


def count_kernels(fn: Callable, args: tuple, kwargs: dict) -> Optional[int]:
    """Pallas kernels in the program ``fn`` compiles for these arguments:
    occurrences of ``tpu_custom_call`` in its lowered text.  Lowers from
    shapes, so arguments the call has donated (and deleted) are fine.
    None where the callable cannot be lowered again — an observability
    sink never fails the step that fed it."""
    import jax

    def spec(x):
        if isinstance(x, jax.Array):
            # Only a committed array pins its devices: an uncommitted
            # scalar beside mesh-sharded params must stay free to follow.
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype,
                sharding=x.sharding if x.committed else None,
            )
        return x

    try:
        shaped_args, shaped_kwargs = jax.tree_util.tree_map(
            spec, (args, kwargs)
        )
        text = fn.lower(*shaped_args, **shaped_kwargs).as_text()
    except Exception:
        logger.exception("count_kernels: cannot lower %r again", fn)
        return None
    return text.count("tpu_custom_call")


def _on_tpu() -> bool:
    try:
        import jax
    except ImportError:  # bare router venv: a duck-typed callable compiled
        return False
    return jax.default_backend() == "tpu"


class _TrackedJit:
    """Pass-through proxy for one jit callable; detects compiles via the
    executable-cache-size delta around each call."""

    __slots__ = ("_tracker", "_name", "_fn")

    def __init__(self, tracker: "CompileTracker", name: str, fn: Callable):
        self._tracker = tracker
        self._name = name
        self._fn = fn

    # stackcheck: allow=SC201 reason=compile wall-time measurement is an observability sink; no plan state reads it (obs layer is plan-inert by contract)
    def __call__(self, *args, **kwargs):
        fn = self._fn
        on_launch = self._tracker.on_launch
        if on_launch is not None:
            on_launch(self._name)
        try:
            before = fn._cache_size()
        except Exception:
            return fn(*args, **kwargs)
        t0 = time.time()
        out = fn(*args, **kwargs)
        try:
            grew = fn._cache_size() > before
        except Exception:
            grew = False
        if grew:
            seconds = time.time() - t0
            # The kernels are dispatched on the TPU only: elsewhere the
            # second trace would buy a certain zero.
            kernels = count_kernels(fn, args, kwargs) if _on_tpu() else None
            self._tracker.record(
                self._name, arg_signature(args, kwargs), seconds,
                kernels=kernels,
            )
        return out

    def __getattr__(self, item):
        # lower()/clear_cache()/_cache_size() etc. reach the real jit fn.
        return getattr(self._fn, item)


class CompileTracker:
    """Per-engine compile-event store + the wrap() instrumentation hook."""

    def __init__(self, enabled: bool = True):
        self.enabled = bool(enabled)
        self._lock = threading.Lock()
        # executable key -> [count, seconds, kernels]
        self._by_executable: Dict[str, list] = {}
        # events since the engine last drained (tag owning windows/spans)
        self._events: List[Dict] = []
        # Compile events since boot (step thread writes; a phase span
        # compares it across its own extent to learn that it compiled).
        self.events_total = 0
        # Called with a wrapped callable's name just before each call
        # (EngineObs stamps the launch onto the open flight record).
        self.on_launch: Optional[Callable[[str], None]] = None

    def wrap(self, name: str, fn: Optional[Callable]) -> Optional[Callable]:
        """Instrument one jit entry point.  Identity when disabled or fn
        is None, so the gated-off engine keeps bare callables."""
        if not self.enabled or fn is None:
            return fn
        return _TrackedJit(self, name, fn)

    def record(
        self, name: str, signature: str, seconds: float,
        kernels: Optional[int] = None,
    ) -> None:
        key = f"{name}[{signature}]"
        with self._lock:
            ent = self._by_executable.setdefault(key, [0, 0.0, None])
            ent[0] += 1
            ent[1] += float(seconds)
            if kernels is not None:
                ent[2] = kernels
            self._events.append({"executable": key, "seconds": float(seconds)})
            self.events_total += 1

    def drain_events(self) -> List[Dict]:
        """Events recorded since the last drain (engine step thread calls
        this after each dispatch to taint the owning window/request)."""
        if not self.enabled:
            return []
        with self._lock:
            if not self._events:
                return []
            events, self._events = self._events, []
        return events

    # -- exposition --------------------------------------------------------

    def compiled_shapes(self) -> int:
        with self._lock:
            return len(self._by_executable)

    def compile_seconds(self) -> float:
        with self._lock:
            return sum(ent[1] for ent in self._by_executable.values())

    def seconds_by_executable(self) -> Dict[str, float]:
        """{executable key: cumulative seconds} — the
        tpu:compile_seconds_total{executable} label set."""
        with self._lock:
            return {k: ent[1] for k, ent in self._by_executable.items()}

    def snapshot(self) -> List[Dict]:
        """Per-executable compile events, most expensive first."""
        with self._lock:
            rows = [
                {"executable": k, "count": ent[0],
                 "seconds": round(ent[1], 6), "kernels": ent[2]}
                for k, ent in self._by_executable.items()
            ]
        rows.sort(key=lambda r: -r["seconds"])
        return rows
