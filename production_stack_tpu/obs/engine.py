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
import logging
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

logger = logging.getLogger(__name__)

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
# out of these families, whose sums the dashboard reads per
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
# _count is ~tokens, not requests): a token's gap is its share of the
# stretch that produced it, (this record's close - the close that gave the
# row its tokens before) / the tokens the row took at this close, so that a
# K-step window reads as K gaps of a step's length and not as K - 1 of
# nothing and one of the window's.  detokenize_time is the request's
# TOTAL host detokenize cost (accumulated across its tokens in the API
# server) — a request-level quantity, which is why it lives here and not
# in the per-step families above.
#
# A request's time to first token, hop by hop (one stamp each, time.time()):
#   upstream (router's x-request-start) -> received (handler entry)
#   -> submitted (AsyncEngine.generate's append) -> admitted (add_request
#   on the step thread) -> first scheduled -> first token -> first_written
#   (the stream's first write returned)
# request_upstream / request_admit / request_pending / queue_time /
# prefill_time / first_token_write are the six gaps, in that order; ttft
# and e2e_latency start at ``received``, so that ttft = admit + pending +
# queue + prefill.
#
# Who waited for whom on the device, on the flight recorder's clock
# (EngineObs._on_record_close).  request_prefill_behind: the part of
# prefill_time in which the request's first prefill program, launched
# behind the program in flight, waited for the device; observed with ttft's
# parts.  request_decode_behind: the part of decode_time a request of two
# tokens or more stood still behind other prompts' prefills, from the record
# that gave its first token to the one that gave its last, observed where
# that one closes; its _sum over decode_time's is the share of the decoders'
# time that went to other requests' prompts.
REQUEST_HISTS = ("ttft", "itl", "e2e_latency", "queue_time", "prefill_time",
                 "decode_time", "detokenize_time", "request_upstream",
                 "request_admit", "request_pending", "first_token_write",
                 "request_prefill_behind", "request_decode_behind")

# A step-thread phase longer than this is a stall: one WARNING line and
# tpu:step_stall_total{phase}.  Every stream the engine serves stands still
# for as long, and tpu:last_step_age_seconds shows it only to a scrape that
# lands inside it.
STALL_S = 1.0

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
# The others partition the wall clock: the engine's follow each other from
# its hand-over list on without hole or overlap (engine.first_write is
# there for a streamed request; engine.decode starts where it ends).
# engine.upstream and engine.admit are left out: the router's own two spans
# cover the same stretch (a stream's response headers, which end
# router.backend_connect, leave the engine at engine.admit's end).
PHASE_SPAN_NAMES = (
    "router.queue",
    "router.backend_connect",
    "engine.pending",
    "engine.queue",
    "engine.prefill",
    "engine.first_write",
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
            if t1 - self._t0 > STALL_S * 1e9:
                obs._on_stall(name, self._rec, (t1 - self._t0) / 1e9)
        return False


_NULL_PHASE = contextlib.nullcontext()


class _Decoder:
    """One request between the record that gave its first token and the one
    that gave its last: what EngineObs._on_record_close keeps of it."""

    __slots__ = ("seq", "opened_at", "last_close", "tokens", "own_s",
                 "own_prefill_s", "own_prefills", "prefill_s0", "prefills0")

    def __init__(self, seq):
        self.seq = seq
        self.opened_at: Optional[float] = None  # None: its first record is open
        self.last_close = 0.0   # the close that last gave it tokens
        self.tokens = 0         # seq.num_generated as of that close
        self.own_s = 0.0        # attributed_s of the records it rode
        self.own_prefill_s = 0.0   # of those, the ``prefill`` records
        self.own_prefills = 0      # (its own prompt, computed anew)
        self.prefill_s0 = 0.0   # the recorder's prefill totals at the opening
        self.prefills0 = 0


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
        # Phases that lasted over STALL_S, by phase (step thread writes).
        self.step_stalls: Dict[str, int] = dict.fromkeys(PHASES, 0)
        # Who waits for whom (_on_record_close).  The step thread writes;
        # on_abort, which the event loop calls too, only pops, and no entry
        # is put back after a read, so a pop in between leaves nothing
        # behind.  A request's first prefill record: [None] from its first
        # schedule to that record's close, then [the record's ``behind_s``]
        # until its first token takes it.  The requests that are decoding,
        # and those of them whose first / last token was taken inside the
        # record that is being collected now.
        self._first_prefill: Dict[str, List[Optional[float]]] = {}
        self._decoders: Dict[str, _Decoder] = {}
        self._opening: List[_Decoder] = []
        self._closing: List[_Decoder] = []
        if self.enabled:
            self.compile_tracker.on_launch = self._on_launch
            self.recorder.on_close = self._on_record_close

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

    def _on_stall(self, phase: str, rec: Optional[WindowRecord],
                  seconds: float) -> None:
        self.step_stalls[phase] += 1
        logger.warning(
            "step thread stalled: phase=%s seconds=%.3f window_id=%s "
            "kind=%s k=%s rows=%s",
            phase, seconds,
            *((rec.window_id, rec.kind, rec.k, rec.rows)
              if rec is not None else (None,) * 4),
        )

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

    @staticmethod
    def _hops(seq):
        """(arrival, submitted, admitted) of a sequence; where add_request
        was called directly the three are one instant."""
        arrival, admitted = seq.arrival_time, seq.admitted_time
        if admitted is None:
            return arrival, arrival, arrival
        submitted = seq.submitted_time
        return arrival, admitted if submitted is None else submitted, admitted

    # stackcheck: allow=SC201 reason=observability timeline math; the whole obs layer is plan-inert by contract (tracing=False removes it entirely and greedy parity is asserted in tests)
    def on_first_scheduled(self, seq, now: Optional[float] = None) -> None:
        """First prefill chunk launched: the queue-wait span ends here,
        and the two before it (handler -> hand-over list -> this thread)
        are known by now."""
        if not self.enabled:
            return
        now = now if now is not None else time.time()
        arrival, submitted, admitted = self._hops(seq)
        self._first_prefill[seq.seq_id] = [None]
        spans = [("engine.queue", admitted, now)]
        if seq.admitted_time is not None:
            spans = [("engine.admit", arrival, submitted),
                     ("engine.pending", submitted, admitted)] + spans
        self.tracer.with_trace(
            seq.seq_id, lambda t: [t.add_span(*span) for span in spans])

    def on_first_token(self, seq, now: float) -> None:
        """The time to first token and its four parts, observed together:
        over any set of requests ttft = admit + pending + queue + prefill
        exactly (a request that never gets here is in none of them); and of
        the prefill part, what its first program waited behind the one in
        flight.  Called inside the collect of the record that carries the
        token: the request starts decoding where that record closes."""
        if not self.enabled:
            return
        behind = self._first_prefill.pop(seq.seq_id, (0.0,))[0]
        if behind is None:
            # A prompt of one chunk: its first prefill record is the one
            # being collected, and what it waited is known before it closes.
            rec = self._open_rec
            behind = 0.0
            if rec is not None and seq.seq_id in rec.seq_ids[rec.rows:]:
                behind = rec.behind_s = self.recorder.behind_of(rec)
        decoder = self._decoders[seq.seq_id] = _Decoder(seq)
        self._opening.append(decoder)
        hists = self.request_hists
        hists["ttft"].observe(now - seq.arrival_time)
        sched = seq.first_scheduled_time
        if sched is not None:
            arrival, submitted, admitted = self._hops(seq)
            if seq.admitted_time is not None:
                hists["request_admit"].observe(submitted - arrival)
                hists["request_pending"].observe(admitted - submitted)
            hists["queue_time"].observe(sched - admitted)
            hists["prefill_time"].observe(now - sched)
            hists["request_prefill_behind"].observe(behind)
            self.tracer.add_span(seq.seq_id, "engine.prefill", sched, now,
                                 behind_s=round(behind, 6))

    def on_first_written(self, request_id: str, now: float) -> None:
        """The stream's first write has returned (event loop): the first
        token's way from the step thread to the socket, engine.prefill's
        end -> ``now``.  engine.decode starts where this span ends, also
        where the request finished before the write returned."""
        if not self.enabled:
            return

        def mark(trace):
            ends = [s.end for s in trace.spans if s.name == "engine.prefill"]
            if not ends:
                return None
            first = ends[0]
            trace.add_span("engine.first_write", first, max(first, now))
            for s in trace.spans:
                if s.name == "engine.decode":
                    s.start = min(max(s.start, now), s.end)
            return max(0.0, now - first)

        seconds = self.tracer.with_trace(request_id, mark)
        if seconds is not None:
            self.request_hists["first_token_write"].observe(seconds)

    def _on_record_close(self, rec: WindowRecord) -> None:
        """Every flight record as it closes (FlightRecorder.on_close, step
        thread): the one account of what each request waited for on the
        device.  ``attributed_s`` telescopes, so between two closes the
        records' sum *is* the wall time, less the stretches with nothing in
        flight; a decoder's span, from the close that gave its first token
        to the close that gave its last, divides exactly into the records it
        rode (``own_s``), the ``prefill`` records of other prompts
        (``prefill_s``, ``prefills``) and the remainder (``rest_s``:
        windows it did not ride, time nothing was in flight).  One addition
        a row, no work a token; tpu:itl_seconds is fed here too, a row's
        stretch shared among the tokens it brought."""
        now, took = rec.collected_at, rec.attributed_s
        decoders = self._decoders
        if decoders:
            itl = self.request_hists["itl"]
            for seq_id in rec.seq_ids:
                d = decoders.get(seq_id)
                if d is None or d.opened_at is None:
                    continue
                d.own_s += took
                if rec.kind == "prefill":
                    d.own_prefill_s += took
                    d.own_prefills += 1
                n = d.seq.num_generated - d.tokens
                if n > 0:
                    itl.observe((now - d.last_close) / n, n)
                    d.tokens += n
                    d.last_close = now
        if self._first_prefill and rec.chunk_prompts:
            for seq_id in rec.seq_ids[rec.rows:]:
                first = self._first_prefill.get(seq_id)
                if first is not None and first[0] is None:
                    if rec.behind_s is None:
                        rec.behind_s = self.recorder.behind_of(rec)
                    first[0] = rec.behind_s
        if not (self._opening or self._closing):
            return
        prefill_s, prefills = self.recorder.prefill_s, self.recorder.prefills
        for d in self._opening:
            # The record that carried the first token, the request's own
            # prefill, lies before the opening and is in no part.
            d.opened_at = d.last_close = now
            d.tokens = d.seq.num_generated
            d.prefill_s0, d.prefills0 = prefill_s, prefills
        self._opening.clear()
        hists = self.request_hists
        for d in self._closing:
            seq_id, tokens = d.seq.seq_id, d.seq.num_generated
            if decoders.pop(seq_id, None) is not d or tokens < 2:
                continue  # aborted since, or no gap between tokens
            span_s = now - d.opened_at
            behind_s = prefill_s - d.prefill_s0 - d.own_prefill_s
            behind_n = prefills - d.prefills0 - d.own_prefills
            rest_s = span_s - d.own_s - behind_s
            hists["request_decode_behind"].observe(max(0.0, behind_s))
            rec.finished.append([
                seq_id, tokens, round(span_s, 9), round(d.own_s, 9),
                round(behind_s, 9), behind_n, round(rest_s, 9)])
            self._tag_decode_span(
                seq_id, own_s=round(d.own_s, 6), prefill_s=round(behind_s, 6),
                prefills=behind_n, rest_s=round(rest_s, 6))
        self._closing.clear()

    def _tag_decode_span(self, request_id: str, **attrs) -> None:
        """Attributes onto a (just finished) request's engine.decode span."""
        def tag(trace):
            for span in trace.spans:
                if span.name == "engine.decode":
                    span.attrs.update(attrs)

        self.tracer.with_trace(request_id, tag)

    # stackcheck: allow=SC201 reason=observability timeline math; the whole obs layer is plan-inert by contract (tracing=False removes it entirely and greedy parity is asserted in tests)
    def on_finish(self, seq, now: Optional[float] = None) -> None:
        """Single finish hook (called from _finish_seq_now): e2e + decode
        histograms, the decode span, and trace completion."""
        if not self.enabled:
            return
        now = now if now is not None else time.time()
        self._first_prefill.pop(seq.seq_id, None)
        decoder = self._decoders.get(seq.seq_id)
        if decoder is not None:
            # Its parts are known where the record that carries its last
            # token closes (_on_record_close); this runs inside its collect.
            self._closing.append(decoder)
        self.request_hists["e2e_latency"].observe(now - seq.arrival_time)
        first = seq.first_token_time
        if first is not None:
            self.request_hists["decode_time"].observe(now - first)

            def decode(trace):
                # After the first write where there was one: the spans
                # tile the timeline; the family above keeps its meaning.
                start = max([first] + [s.end for s in trace.spans
                                       if s.name == "engine.first_write"])
                trace.add_span("engine.decode", min(start, now), now)

            self.tracer.with_trace(seq.seq_id, decode)
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
        self._first_prefill.pop(request_id, None)
        self._decoders.pop(request_id, None)
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
        self, request_id: str, trace_id: Optional[str],
        received: Optional[float] = None,
        upstream_start: Optional[float] = None,
        parent_span_id: Optional[str] = None, **attrs
    ) -> None:
        """Open the trace at ``received`` (the handler's entry; now where
        the caller took no stamp).  ``upstream_start``: when the router in
        front took the request (a sane x-request-start header), observed
        as engine.upstream / tpu:request_upstream_seconds.
        ``parent_span_id``: the sender's span (traceparent's parent-id),
        kept as an attribute: the span that caused this root."""
        if not self.enabled:
            return
        if parent_span_id is not None:
            attrs["parent_span_id"] = parent_span_id
        self.tracer.start(
            request_id, trace_id=trace_id, attrs=attrs, start=received)
        if upstream_start is not None and received is not None:
            self.request_hists["request_upstream"].observe(
                received - upstream_start)
            self.tracer.add_span(
                request_id, "engine.upstream", upstream_start, received)

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
        parts.append(vocab.render_labeled_counter(
            vocab.TPU_STEP_STALL, "phase", self.step_stalls))
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
