"""Engine-side observability hub: request tracer + latency/step histograms.

One ``EngineObs`` lives on each ``LLMEngine`` (and on the fake engine's
state, so the CI contract matches the real engine).  The engine core calls
the lifecycle hooks from its step thread; the API server starts traces
(with the router-propagated trace id) and attaches the detokenize span.

Everything is gated on ``enabled`` (config ``obs.tracing``): disabled, every
hook returns before touching any state — no histogram observes, no trace
allocations, no per-step bookkeeping — restoring the pre-tracing fast path.
"""

from __future__ import annotations

import contextlib
import threading
import time
from collections import deque
from typing import Callable, Deque, Dict, List, Optional

from production_stack_tpu.obs.compile_tracker import CompileTracker
from production_stack_tpu.obs.flight_recorder import (
    FlightRecorder,
    WindowRecord,
)
from production_stack_tpu.obs.histogram import (
    Histogram,
    render_histogram,
)
from production_stack_tpu.obs.trace import Tracer

# Engine step phases (host-side attribution of ONE engine step; every
# observation is per-step so the families are unit-comparable).  Keys map
# to ``tpu:step_<phase>_seconds`` histogram families (vocabulary.py):
#   schedule - scheduler planning (schedule / schedule_provisional)
#   dispatch - host work launching device execution (array build + H2D)
#   collect  - blocking device compute + sample readback
#   sample   - host sampling post-process (append, finish checks, guided)
#   mixed    - one fused decode+prefill-chunk step, wall time end to end
#              (array build + blocking device compute + both segments'
#              sampling); its _count is the number of mixed steps, so
#              rate(mixed_count)/rate(all step counts) is the fraction of
#              steps where a prompt chunked alongside live decodes.
# schedule covers every step; dispatch/collect/sample are the PIPELINED
# decode split (the steady-state hot path).  Synchronous steps (prefill,
# host-state fallbacks) are cut into the finer PHASES spans below but stay
# out of these families, whose sums the dashboard and bench.py read per
# pipelined step.  Mixed steps are synchronous by design and get their own
# family instead.
STEP_PHASES = ("schedule", "dispatch", "collect", "sample", "mixed")

# What the step thread is doing, as spans (EngineObs.phase): the closed set
# a flight record's ``phases`` and the loose ring of GET /debug/windows
# hold, each also a ``pstpu.<phase>`` TraceAnnotation on the profiler's
# clock.  Finer than STEP_PHASES and covering every dispatch path, so that
# device idle can be laid against them:
#   schedule - scheduler planning, incl. landing completed prefetches
#   build    - host arrays and H2D for the dispatch (incl. the small
#              programs that unpack or advance device-resident state)
#   launch   - the jitted step call returning (enqueue, not compute)
#   collect  - the blocking device read-back
#   sample   - host post-processing of the read-back (append, finish
#              checks, guided decoding, a chunk's first token)
#   emit     - fan-out of the step's outputs to the event loop
#   wait     - nothing to do: asleep on the wake-up event or the 1 ms
#              transfer back-off
#   compile  - any of the above inside which a jit call traced and
#              compiled (the span keeps its place, its name says why it
#              was long)
# ``dispatch`` and ``mixed`` of STEP_PHASES are ENCLOSING spans: they feed
# their histogram family (and the profiler) and hold build/launch/... spans
# inside, so they never land on a record themselves.
PHASES = ("schedule", "build", "launch", "collect", "sample", "emit",
          "wait", "compile")
_ENCLOSING = frozenset(STEP_PHASES) - frozenset(PHASES)

# Request-level engine histograms -> ``tpu:*_seconds`` families; one
# observation per request, EXCEPT itl which observes every token gap (its
# _count is ~tokens, not requests).  detokenize_time is the request's
# TOTAL host detokenize cost (accumulated across its tokens in the API
# server) — a request-level quantity, which is why it lives here and not
# in the per-step families above.
REQUEST_HISTS = ("ttft", "itl", "e2e_latency", "queue_time", "prefill_time",
                 "decode_time", "detokenize_time")

# Async KV transfer-plane phases -> ``tpu:*_seconds`` families
# (vocabulary.TPU_KV_HISTOGRAMS).  Observed from the plane's BACKGROUND
# threads (prefetch fetchers, offload stager writer), never the step
# thread — that is the point: these families measure the store/DMA
# latency the plane keeps OFF the step loop.
#   remote_kv_fetch - one store round-trip (MGET chain fetch/restore GET)
#   offload_stage   - one staged preemption snapshot, gather dispatch ->
#                     host copy landed
KV_PHASES = ("remote_kv_fetch", "offload_stage")

# The span set a joined router+engine timeline is scored against
# (/debug/requests/{id}: phase_sum_s vs total_s).  engine.detokenize is
# accumulated host time interleaved WITH engine.decode (marked
# accumulated=True on the span): it can push phase_sum slightly above
# total for detokenize-heavy outputs, bounded by the detokenize fraction.
# The other five partition the wall clock.
PHASE_SPAN_NAMES = (
    "router.queue",
    "router.backend_connect",
    "engine.queue",
    "engine.prefill",
    "engine.decode",
    "engine.detokenize",
)


class _PhaseSpan:
    """One open ``EngineObs.phase``; see there."""

    __slots__ = ("_obs", "_name", "_rec", "_family", "_ann", "_t0",
                 "_compiles0", "_outer_rec", "_top")

    def __init__(self, obs: "EngineObs", name: str,
                 rec: Optional[WindowRecord], family: bool):
        self._obs = obs
        self._name = name
        self._rec = rec
        self._family = family
        self._ann = None

    # stackcheck: allow=SC201 reason=phase-span timestamps are observability sinks; no plan state reads them (obs layer is plan-inert by contract)
    def __enter__(self) -> "_PhaseSpan":
        obs = self._obs
        self._outer_rec = obs._open_rec
        if self._rec is None:
            self._rec = self._outer_rec
        else:
            obs._open_rec = self._rec
        if self._name in _ENCLOSING:
            self._top = False
        else:
            self._top = obs._depth == 0
            obs._depth += 1
        self._compiles0 = obs.compile_tracker.events_total
        if obs._annotation is not None:
            kwargs = ({} if self._rec is None
                      else {"window_id": self._rec.window_id})
            self._ann = obs._annotation("pstpu." + self._name, **kwargs)
            self._ann.__enter__()
        self._t0 = time.time_ns()
        return self

    # stackcheck: allow=SC201 reason=phase-span timestamps are observability sinks; no plan state reads them (obs layer is plan-inert by contract)
    def __exit__(self, *exc) -> bool:
        t1 = time.time_ns()
        obs = self._obs
        if self._ann is not None:
            self._ann.__exit__(*exc)
        obs._open_rec = self._outer_rec
        if self._name not in _ENCLOSING:
            obs._depth -= 1
        if self._family:
            obs.step_hists[self._name].observe((t1 - self._t0) / 1e9)
        if self._top:
            name = self._name
            if obs.compile_tracker.events_total != self._compiles0:
                name = "compile"
            obs._keep_phase(self._rec, name, self._t0, t1)
        return False


_NULL_PHASE = contextlib.nullcontext()


class EngineObs:
    def __init__(
        self,
        enabled: bool = True,
        ring_size: int = 256,
        ring_bytes: int = 0,
        window_ring_size: int = 1024,
        annotation: Optional[Callable] = None,
    ):
        self.enabled = bool(enabled)
        self.tracer = Tracer(
            "engine", enabled=self.enabled, ring_size=ring_size,
            ring_bytes=ring_bytes,
        )
        # Window flight recorder: one record per engine dispatch
        # (GET /debug/windows, joined into /debug/requests/{id}).
        self.recorder = FlightRecorder(
            enabled=self.enabled, ring_size=window_ring_size,
        )
        # XLA compile-event tracker: the engine wraps its jit entry
        # points through this when tracing is on (GET /debug/compiles,
        # tpu:compile_seconds_total{executable}).
        self.compile_tracker = CompileTracker(enabled=self.enabled)
        # Histograms are created eagerly (fixed, small set) so /metrics
        # always renders every family — dashboards and the router scraper
        # see stable names from the first scrape.
        self.step_hists: Dict[str, Histogram] = {
            phase: Histogram() for phase in STEP_PHASES
        }
        self.request_hists: Dict[str, Histogram] = {
            name: Histogram() for name in REQUEST_HISTS
        }
        self.kv_hists: Dict[str, Histogram] = {
            name: Histogram() for name in KV_PHASES
        }
        # Phase spans (step thread only, like the recorder's write path).
        # ``annotation``: jax.profiler.TraceAnnotation where the owner has
        # JAX (the engine core passes it; the fake engine has none).
        self._annotation = annotation if self.enabled else None
        self._depth = 0            # open non-enclosing phases
        self._open_rec: Optional[WindowRecord] = None
        # Spans that belong to no dispatch (schedule, emit, wait): a ring
        # covering about as much history as the window ring does (at most
        # a schedule, an emit and a wait per dispatch), served as
        # "phases" by GET /debug/windows.  The step thread appends, the
        # event loop snapshots: both under ``_phase_lock``.
        self._loose: Deque[list] = deque(maxlen=4 * max(1, window_ring_size))
        self._phase_lock = threading.Lock()
        # Last profiler session (/start_profile, /stop_profile): unix ns
        # taken before and after start_trace / stop_trace.
        self._profile: Dict[str, List[int]] = {}
        if self.enabled:
            self.compile_tracker.on_launch = self._on_launch

    # -- step phases (engine step thread) ----------------------------------

    def phase(self, name: str, rec: Optional[WindowRecord] = None,
              family: Optional[bool] = None):
        """Span of the step thread's work, as a context manager with three
        sinks: a ``pstpu.<name>`` TraceAnnotation (with the record's
        ``window_id``) on the profiler's clock; ``[name, start_ns,
        end_ns]`` (``time.time_ns()``) appended to ``rec.phases``, or with
        no record to the loose ring of GET /debug/windows; and one
        observation of ``tpu:step_<name>_seconds`` where ``name`` is a
        STEP_PHASES family (``family=False``: not this time — the
        synchronous paths' collect/sample splits stay out of the families
        that time the pipelined path).

        ``name`` is one of PHASES, or ``dispatch``/``mixed``, which
        enclose other spans and reach only the profiler and their family.
        A phase opened inside another (a first token sampled inside a
        window's ``sample``) inherits its record, and reaches the profiler
        and its family but not the record: a record's ``phases`` stay
        ordered and disjoint.  With tracing off: one shared null context,
        no state touched."""
        if not self.enabled:
            return _NULL_PHASE
        if family is None:
            family = name in self.step_hists
        return _PhaseSpan(self, name, rec, family)

    def _keep_phase(self, rec: Optional[WindowRecord], name: str,
                    t0: int, t1: int) -> None:
        if rec is not None:
            rec.phases.append([name, t0, t1])
            if name == "collect":
                rec.collected_ns = t1
            return
        with self._phase_lock:
            last = self._loose[-1] if self._loose else None
            if name == "wait" and last is not None and last[0] == "wait":
                # An idle engine wakes every 10 ms to look for work: one
                # span for the whole sleep, not a ring full of them.
                last[2] = t1
            else:
                self._loose.append([name, t0, t1])

    # stackcheck: allow=SC201 reason=launch stamps are observability sinks; no plan state reads them (obs layer is plan-inert by contract)
    def _on_launch(self, program: str) -> None:
        """A tracked jit callable is about to be called (compile tracker
        hook, step thread): stamp it onto the record whose phase is
        open."""
        rec = self._open_rec
        if rec is None:
            return
        rec.programs.append(program)
        rec.program_ns.append(time.time_ns())

    def note_profile(self, edge: str, before_ns: int, after_ns: int) -> None:
        """``edge`` = "start" | "stop": unix ns around the profiler call."""
        if edge == "start":
            self._profile = {}
        self._profile[edge + "_unix_ns"] = [before_ns, after_ns]

    # -- KV transfer plane (prefetch/stager background threads) ------------

    def kv_phase(self, phase: str, seconds: float) -> None:
        if not self.enabled:
            return
        self.kv_hists[phase].observe(seconds)

    # -- request lifecycle (engine step thread) ----------------------------

    # stackcheck: allow=SC201 reason=observability timeline math; the whole obs layer is plan-inert by contract (tracing=False removes it entirely and greedy parity is asserted in tests)
    def on_first_scheduled(self, seq, now: Optional[float] = None) -> None:
        """First prefill chunk launched: the queue-wait span ends here."""
        if not self.enabled:
            return
        now = now if now is not None else time.time()
        self.request_hists["queue_time"].observe(now - seq.arrival_time)
        self.tracer.add_span(seq.seq_id, "engine.queue", seq.arrival_time, now)

    def on_first_token(self, seq, now: float) -> None:
        if not self.enabled:
            return
        self.request_hists["ttft"].observe(now - seq.arrival_time)
        sched = seq.first_scheduled_time
        if sched is not None:
            self.request_hists["prefill_time"].observe(now - sched)
            self.tracer.add_span(seq.seq_id, "engine.prefill", sched, now)

    def on_token_gap(self, seq, gap: float) -> None:
        if not self.enabled:
            return
        self.request_hists["itl"].observe(gap)

    # stackcheck: allow=SC201 reason=observability timeline math; the whole obs layer is plan-inert by contract (tracing=False removes it entirely and greedy parity is asserted in tests)
    def on_finish(self, seq, now: Optional[float] = None) -> None:
        """Single finish hook (called from _finish_seq_now): e2e + decode
        histograms, the decode span, and trace completion."""
        if not self.enabled:
            return
        now = now if now is not None else time.time()
        self.request_hists["e2e_latency"].observe(now - seq.arrival_time)
        first = seq.first_token_time
        if first is not None:
            self.request_hists["decode_time"].observe(now - first)
            self.tracer.add_span(seq.seq_id, "engine.decode", first, now)
        self.tracer.finish(
            seq.seq_id,
            end=now,
            finish_reason=(
                seq.finish_reason.value if seq.finish_reason else None
            ),
            num_prompt_tokens=seq.num_prompt_tokens,
            num_output_tokens=seq.num_generated,
        )

    def on_abort(self, request_id: str) -> None:
        if not self.enabled:
            return
        self.tracer.finish(request_id, aborted=True)

    # -- compile taint (engine step thread writes, server reads) -----------

    def on_compile(self, seq_ids, events, rec=None) -> None:
        """Attribute drained compile events: mark the owning window
        record compile-tainted and tag every co-scheduled request's trace
        ``compile=true`` so compile-tainted TTFT samples are separable
        from steady-state ones."""
        if not self.enabled or not events:
            return
        total = sum(e.get("seconds", 0.0) for e in events)
        self.recorder.note_compile(rec, total)
        for sid in seq_ids:
            self.tracer.set_attrs(sid, compile=True)

    def compile_tainted(self, request_id: str) -> bool:
        """Did an XLA compile fire inside this request's dispatches?  The
        API server stamps the answer into the first response chunk so the
        router can keep a compile-excluded TTFT window."""
        if not self.enabled:
            return False
        return bool(self.tracer.get_attr(request_id, "compile", False))

    # -- server-side hooks -------------------------------------------------

    def start_request(
        self, request_id: str, trace_id: Optional[str], **attrs
    ) -> None:
        if not self.enabled:
            return
        self.tracer.start(request_id, trace_id=trace_id, attrs=attrs)

    def record_detokenize(self, request_id: str, seconds: float) -> None:
        """Accumulated host detokenize time for one request, reported by
        the API server after the stream ends.  The span is anchored at the
        trace end (the work was interleaved with decode; ``accumulated``
        marks it as a duration, not a wall-clock interval)."""
        if not self.enabled:
            return
        self.request_hists["detokenize_time"].observe(seconds)
        trace = self.tracer.get(request_id)
        if trace is not None:
            anchor = trace.end if trace.end is not None else time.time()
            self.tracer.add_span(
                request_id, "engine.detokenize", anchor, anchor + seconds,
                accumulated=True,
            )

    # -- exposition --------------------------------------------------------

    def render_metrics(self) -> str:
        """Histogram families appended to the engine's /metrics body.
        Rendered even at zero observations so names are scrape-stable."""
        from production_stack_tpu.router.stats import vocabulary as vocab

        parts = []
        for name, hist in self.request_hists.items():
            parts.append(render_histogram(vocab.TPU_REQUEST_HISTOGRAMS[name], hist))
        for phase, hist in self.step_hists.items():
            parts.append(render_histogram(vocab.TPU_STEP_HISTOGRAMS[phase], hist))
        for phase, hist in self.kv_hists.items():
            parts.append(render_histogram(vocab.TPU_KV_HISTOGRAMS[phase], hist))
        return "".join(parts)

    def debug_payload(self) -> Dict:
        return {
            "enabled": self.enabled,
            # Lock-held snapshots: the step thread mutates these traces.
            "requests": self.tracer.snapshots(),
            "dropped": self.tracer.dropped,
        }

    def request_payload(self, request_id: str) -> Optional[Dict]:
        """One request's timeline with its window flight records joined in
        (/debug/requests/{id}): which windows it rode, what else shared
        them, which one stalled.  None when the trace is unknown."""
        snap = self.tracer.snapshot(request_id)
        if snap is None:
            return None
        snap["windows"] = self.recorder.for_request(request_id)
        return snap

    def windows_payload(self, seq: Optional[str] = None) -> Dict:
        """GET /debug/windows (+?seq= filter): the flight-recorder ring,
        newest first."""
        payload = {
            "enabled": self.enabled,
            "windows": self.recorder.snapshot(seq=seq),
            "recorded": self.recorder.windows_recorded,
            "dropped": self.recorder.dropped,
            "profile": dict(self._profile),
        }
        if seq is None:
            # The step thread's spans that belong to no dispatch, oldest
            # first (one request's view leaves them out).
            with self._phase_lock:
                payload["phases"] = [list(p) for p in self._loose]
        return payload
