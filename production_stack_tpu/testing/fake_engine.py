"""Fake TPU serving engine: SSE token streaming + TPU-vocabulary /metrics.

Reference counterpart: src/tests/perftest/fake-openai-server.py:50-171 — the
stand-in backend that makes the whole stack testable without accelerators
(SURVEY.md section 4 takeaway).  Ours emits the ``tpu:`` metric vocabulary
our scraper/dashboard/HPA key off, simulates a configurable TTFT and
tokens/s, and tracks running-request gauges so load-aware routing is
exercisable in CI.

Usable three ways: as an importable aiohttp app factory (unit tests), as a
CLI (perf tests / CI workflows), and inside the helm chart's clusterless CI
values as a stand-in engine image command.
"""

from __future__ import annotations

import argparse
import asyncio
import hashlib
import json
import random
import time
import uuid

from aiohttp import web

from production_stack_tpu.obs.engine import EngineObs
from production_stack_tpu.obs.histogram import Histogram, render_histogram
from production_stack_tpu.obs.trace import (
    parse_request_start,
    parse_traceparent_ids,
)
from production_stack_tpu.router.stats import vocabulary as vocab


class FakeSliceGroup:
    """Simulated multi-host slice group behind ONE fake leader endpoint
    (docs/robustness.md "Slice lifecycle contract", jax-free).

    Mirrors the real contract exactly enough for the router/fleet plane
    to be chaos-tested in tier-1: followers "ack" continuously while
    alive; :meth:`kill_member` freezes a member's acks, so after
    ``member_timeout_s`` the leader's /health fails (the slice is ONE
    endpoint whose health is the conjunction of its members) and the
    data plane starts refusing connections (the leader fatal-exits in
    production).  :meth:`restart` models the parallel k8s group restart:
    a STRICTLY larger epoch, members revived, drains cleared.  A
    follower's POST /drain relays to the leader — the leader drains the
    whole group.
    """

    def __init__(
        self,
        num_members: int = 4,
        member_timeout_s: float = 1.0,
        clock=time.monotonic,
    ):
        from production_stack_tpu.engine.parallel.distributed import new_epoch

        self._new_epoch = new_epoch
        self.num_members = int(num_members)
        self.member_timeout_s = float(member_timeout_s)
        self._clock = clock
        self.epoch = new_epoch()
        self._last_ack = {
            pid: clock() for pid in range(1, self.num_members)
        }
        self._killed: set = set()
        self._problem: str | None = None
        self.member_failures: dict = {}  # reason -> count
        self.drain_relays = 0
        self.drain_relayed = False
        self.restarts = 0

    def member_ack_ages(self) -> dict:
        """Live members ack continuously (age ~0); killed members' ages
        grow in real time — the tpu:lockstep_member_last_ack_seconds
        truth the leader exports."""
        now = self._clock()
        for pid in self._last_ack:
            if pid not in self._killed:
                self._last_ack[pid] = now
        return {pid: max(0.0, now - t) for pid, t in self._last_ack.items()}

    def kill_member(self, pid: int) -> None:
        if pid not in self._last_ack:
            raise ValueError(f"no such member ordinal {pid}")
        self._killed.add(pid)

    def problem(self) -> str | None:
        """Non-None once any member has been silent past the timeout
        (first detection counts one member_silent failure, like the real
        GroupLivenessMonitor)."""
        if self._problem is None:
            for pid, age in self.member_ack_ages().items():
                if age > self.member_timeout_s:
                    self._problem = (
                        f"slice member {pid} silent for {age:.1f}s "
                        f"(member timeout {self.member_timeout_s:.1f}s)"
                    )
                    self.member_failures["member_silent"] = (
                        self.member_failures.get("member_silent", 0) + 1
                    )
                    break
        return self._problem

    def relay_drain(self, pid: int) -> None:
        self.drain_relays += 1
        self.drain_relayed = True

    def restart(self) -> None:
        """The parallel group restart k8s performs after a failure: every
        member comes back into ONE fresh incarnation whose epoch is
        strictly larger — a restarted member can never replay into it."""
        self.epoch = self._new_epoch()
        assert self.epoch > 0
        self._killed.clear()
        self._problem = None
        self.drain_relayed = False
        now = self._clock()
        for pid in self._last_ack:
            self._last_ack[pid] = now
        self.restarts += 1


class FakeEngineState:
    def __init__(
        self,
        model: str = "fake/llama-3-8b",
        tokens_per_sec: float = 500.0,
        ttft: float = 0.02,
        max_tokens_default: int = 100,
        seed: int = 0,
        capacity: int | None = None,
        max_queued: int = 0,
        admission_control: bool = True,
        disagg_role: str | None = None,
        shared_store: set | None = None,
        prefetch_outcome: str | None = None,
        prefix_chunk_chars: int = 64,
        prefill_chars_per_sec: float | None = None,
        prefill_scales_with_load: bool = False,
        remote_store_import: bool = False,
        store_import_chars_per_sec: float | None = None,
        slice_group: FakeSliceGroup | None = None,
        simulate_compiles: bool = False,
        tracing: bool = True,
        max_queued_encode_texts: int = 256,
    ):
        self.model = model
        self.tokens_per_sec = tokens_per_sec
        self.ttft = ttft
        self.max_tokens_default = max_tokens_default
        self.num_running = 0
        self.num_waiting = 0
        self.total_requests = 0
        self.total_model_probes = 0  # GETs of /v1/models (discovery probes)
        self.total_prompt_tokens = 0
        self.total_generated_tokens = 0  # bumped per emitted token
        self.total_finished = 0  # bumped at completion (real-engine semantics)
        # -- prefix-cache simulation (chunk-chain granularity) -------------
        # ``note_prompt`` walks the prompt's chained chunk digests
        # (fake_prefix_chain) against the set this engine has "cached":
        # the matched leading run counts as hit tokens, the rest as cold
        # prefill — the same token-weighted accounting the real engine's
        # BlockPool keeps, so fleet KV hit rates measured against fakes
        # respond to routing affinity the way real engines do.
        self.prefix_chunk_chars = int(prefix_chunk_chars)
        self.prefix_hit_tokens = 0
        self.prefix_query_tokens = 0
        # Prefill cost model: with ``prefill_chars_per_sec`` set, TTFT
        # grows with the UNCACHED prompt tail (cold prefill); with
        # ``prefill_scales_with_load`` + capacity, it additionally
        # stretches with oversubscription (prefill queueing).  Both
        # default off, preserving the constant-TTFT legacy fake exactly.
        self.prefill_chars_per_sec = prefill_chars_per_sec
        self.prefill_scales_with_load = bool(prefill_scales_with_load)
        # Remote-store warming (the PR-4 plane, simulated): computed
        # chunks are exported to ``shared_store`` and store-resident
        # chunks import instead of recomputing (a cache hit at a cheaper
        # per-char cost) — how a popularity-grown replica warms a hot
        # prefix without paying the full prefill.
        self.remote_store_import = bool(remote_store_import)
        self.store_import_chars_per_sec = store_import_chars_per_sec
        self._rng = random.Random(seed)
        self._seen_chunks: set = set()
        # Same obs contract as the real engine (EngineObs): tracing tests
        # and the bench trace_report run against this in CI.  tracing=False
        # mirrors obs.tracing=off — the recorder/tracker zero-state gate.
        self.obs = EngineObs(enabled=tracing)
        # Simulated XLA compiles: a cold prompt-size bucket records one
        # compile event (first request of each pow2 size pays it, repeats
        # don't — the real cache-growth semantics), taints the request's
        # trace/window, and stamps '"compile": true' into the first
        # response chunk exactly like the real server, so the router's
        # compile-excluded TTFT path and /debug/compiles are CI-testable
        # without jax.
        self.simulate_compiles = bool(simulate_compiles)
        # Headers of the most recent completion request (trace-propagation
        # assertions in tests).
        self.last_headers: dict = {}
        # -- overload / lifecycle model (docs/robustness.md) ---------------
        # ``capacity`` models max_num_seqs: with it set, per-token
        # intervals scale with in-flight/capacity (a deterministic
        # oversubscription-degrades-ITL model — the signal the
        # shed-vs-no-shed tier-1 test measures without a TPU), and
        # bounded admission 429s once in-flight exceeds
        # capacity + max_queued.  capacity=None keeps the legacy
        # constant-rate fake exactly.
        self.capacity = capacity
        self.max_queued = max_queued
        self.admission_control = admission_control
        # What a completion's service times (TTFT, token intervals) are
        # slept on.  A test that measures them gives one that moves its
        # own clock, so that a busy machine's sleeps are not in the gaps.
        self.sleep = asyncio.sleep
        self.admission_rejected = 0  # tpu:admission_rejected_total
        self.deadline_expired = 0  # tpu:deadline_expired_total
        # Deterministic fault-injection surface (FakeEngineState.inject):
        # kind -> params.  Counted kinds decrement per use; count=-1 means
        # "until cleared".
        self.injections: dict = {}
        # Request ids whose handler was torn down mid-stream (client/router
        # disconnect or cancellation) — the abort-propagation assertions.
        self.aborted_requests: list = []
        self.draining = False
        # Completion-handler entries BEFORE any injection fires: counts
        # every connection the router actually made (the breaker tests'
        # "an open backend receives no traffic" assertion).
        self.data_plane_hits = 0
        # -- disaggregated prefill/decode emulation (--disagg-role) --------
        # Same contract as the real engine (docs/engine.md): a prefill
        # prime (x-disagg-phase: prefill) returns a handoff token and
        # records the chain export; a handoff-tagged generation
        # (x-disagg-handoff) simulates the prefetch — a hit skips the
        # TTFT sleep (the prompt was imported, decode runs no prompt
        # tokens) and stamps X-Disagg-Prefix.  ``shared_store`` is the
        # simulated shared KV store: pass ONE set to every fake in a
        # fleet so prefill-pool exports are visible to decode-pool fakes.
        if disagg_role not in (None, "prefill", "decode", "both", "encode"):
            raise ValueError(f"unknown disagg_role {disagg_role!r}")
        self.disagg_role = disagg_role
        self.shared_store = shared_store if shared_store is not None else set()
        # Force the decode-phase outcome ("hit"/"miss") regardless of the
        # store — the prefetch-miss fallback tests key on this.
        self.prefetch_outcome = prefetch_outcome
        self.exports: list = []  # recorded prime exports (chains)
        self.disagg_prefill_primes = 0
        self.disagg_handoff_hits = 0
        self.disagg_handoff_misses = 0
        # -- encode lane emulation (embeddings / rerank / score) -----------
        # Same contract as the real engine's batched encode lane
        # (engine/server/encode_batcher.py): each request lands as ONE
        # batch, deterministic unit vectors keyed by text alone (so any
        # two fakes — or two scrapes of one fake — agree bit-for-bit,
        # the semantic-cache parity property), admission 429s once
        # queued texts would exceed ``max_queued_encode_texts``, and the
        # tpu:encode_* metric families render live values.
        self.max_queued_encode_texts = int(max_queued_encode_texts)
        self.encode_texts_total = 0
        self.encode_in_flight = 0  # tpu:encode_queue_depth mirror
        self.encode_batch_size_hist = Histogram(
            bounds=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0)
        )
        self.encode_seconds_hist = Histogram(
            bounds=(0.005, 0.02, 0.05, 0.1, 0.25, 1.0, 4.0)
        )
        # -- multi-host slice-group emulation (FakeSliceGroup) -------------
        # This state becomes the LEADER (ordinal 0) of a simulated slice:
        # /health conjoins member liveness, a failed group refuses data-
        # plane connections (the fatal-exited leader as the router sees
        # it), and build_fake_follower_app() serves the follower
        # ordinals' probe/drain surface against the same group object.
        self.slice_group = slice_group

    def inject(self, kind: str, **params) -> None:
        """Arm a fault: ``refuse`` (close the connection pre-response;
        count=N or -1), ``error_5xx`` (status=503, count=N),
        ``reject_429`` (retry_after=1, count=N), ``stall_stream``
        (after_tokens=K: emit K chunks then hang until torn down),
        ``slow_admission`` (delay_s before the first byte)."""
        if kind not in (
            "refuse", "error_5xx", "reject_429", "stall_stream",
            "slow_admission",
        ):
            raise ValueError(f"unknown injection kind {kind!r}")
        params.setdefault("count", -1)
        self.injections[kind] = dict(params)

    def clear_injection(self, kind: str) -> None:
        self.injections.pop(kind, None)

    def _take_injection(self, kind: str):
        """Params if the fault is armed (consuming one count), else None."""
        inj = self.injections.get(kind)
        if inj is None or inj["count"] == 0:
            return None
        if inj["count"] > 0:
            inj["count"] -= 1
        return inj

    @property
    def in_flight(self) -> int:
        return self.num_running + self.num_waiting

    def token_interval(self) -> float:
        """Current per-token interval: degrades linearly once in-flight
        work oversubscribes capacity (the deterministic ITL model the
        overload tests measure)."""
        base = 1.0 / self.tokens_per_sec
        if self.capacity:
            return base * max(1.0, self.in_flight / self.capacity)
        return base

    def note_prompt(self, prompt_text: str) -> tuple:
        """Chunk-chain prefix-cache simulation.

        Walks the prompt's chained chunk digests against this engine's
        cached set: the matched leading run is a local hit; with
        ``remote_store_import``, a contiguous store-resident extension
        imports (counted as hit — the real prefetch plane lands imports
        in the prefix cache before schedule, so ``match_prefix`` serves
        them); the rest is cold prefill.  Returns
        ``(uncached_chars, imported_chars)`` for the TTFT cost model.
        """
        cc = self.prefix_chunk_chars
        chain = fake_prefix_chain(prompt_text, cc)
        matched = 0
        for digest in chain:
            if digest not in self._seen_chunks:
                break
            matched += 1
        imported = 0
        if self.remote_store_import:
            for digest in chain[matched:]:
                if digest not in self.shared_store:
                    break
                imported += 1
        total_chars = max(len(prompt_text), 1)
        hit_chars = min((matched + imported) * cc, total_chars)
        self.prefix_query_tokens += max(1, total_chars // 4)
        self.prefix_hit_tokens += hit_chars // 4
        self._seen_chunks.update(chain)
        if self.remote_store_import:
            self.shared_store.update(chain)  # px-export of computed chunks
        uncached_chars = max(0, total_chars - hit_chars)
        imported_chars = min(imported * cc, total_chars)
        return uncached_chars, imported_chars

    def prefill_seconds(self, uncached_chars: int, imported_chars: int) -> float:
        """TTFT beyond the base: cold-prefill the uncached tail, import
        the store-warmed span (cheaper), stretch with oversubscription
        when the load model is on.  0.0 with the cost model off."""
        if not self.prefill_chars_per_sec:
            return 0.0
        import_rate = (
            self.store_import_chars_per_sec or 4.0 * self.prefill_chars_per_sec
        )
        cost = (
            uncached_chars / self.prefill_chars_per_sec
            + imported_chars / import_rate
        )
        if self.prefill_scales_with_load and self.capacity:
            cost *= max(1.0, (self.in_flight + 1) / self.capacity)
        return cost

    @property
    def prefix_hit_rate(self) -> float:
        if not self.prefix_query_tokens:
            return 0.0
        return self.prefix_hit_tokens / self.prefix_query_tokens

    @property
    def prefix_cached_chunks(self) -> int:
        """Resident content chunks — the tpu:prefix_cache_blocks mirror."""
        return len(self._seen_chunks)

    @property
    def kv_usage(self) -> float:
        return min(1.0, self.num_running * 0.05)


def _sse(data: dict) -> bytes:
    return f"data: {json.dumps(data)}\n\n".encode()


def fake_prefix_chain(prompt_text: str, chunk_chars: int = 64) -> list:
    """Deterministic stand-in for the engine's prefix hash chain: one
    chained blake2b digest per ``chunk_chars`` of prompt text.  Prefill
    and decode fakes derive the SAME chain from the same prompt — the
    content-keyed-store property the real handoff relies on."""
    chain = []
    h = hashlib.blake2b(digest_size=8)
    for start in range(0, max(len(prompt_text), 1), chunk_chars):
        h.update(prompt_text[start : start + chunk_chars].encode("utf-8"))
        chain.append(h.hexdigest())
    return chain


def fake_embedding(text: str, dim: int = 32) -> list:
    """Deterministic unit vector for ``text`` — a function of the text
    ALONE (no per-engine seed), so every fake in a fleet returns the
    identical embedding for the same input.  That's the property the
    router's semantic cache tests lean on: a cached answer must be
    byte-identical to a fresh one regardless of which backend served it."""
    digest = hashlib.blake2b(text.encode("utf-8"), digest_size=32).digest()
    raw = [((b / 255.0) * 2.0 - 1.0) for b in digest[:dim]]
    norm = sum(v * v for v in raw) ** 0.5 or 1.0
    return [round(v / norm, 8) for v in raw]


def _word(rng: random.Random) -> str:
    return rng.choice(
        ["alpha", "bravo", "charlie", "delta", "echo", "foxtrot", "tensor", "tpu"]
    )


def build_fake_engine_app(state: FakeEngineState | None = None) -> web.Application:
    state = state or FakeEngineState()
    app = web.Application()
    app["state"] = state

    async def models(_request: web.Request) -> web.Response:
        state.total_model_probes += 1
        return web.json_response(
            {
                "object": "list",
                "data": [
                    {
                        "id": state.model,
                        "object": "model",
                        "created": int(time.time()),
                        "owned_by": "fake-tpu-engine",
                    }
                ],
            }
        )

    async def health(_request: web.Request) -> web.Response:
        if state.slice_group is not None:
            problem = state.slice_group.problem()
            if problem is not None:
                # The slice is ONE endpoint whose health is the
                # conjunction of its members (the real leader's
                # /health conjoins GroupLivenessMonitor.problem()).
                return web.json_response(
                    {"status": "unhealthy", "problem": problem,
                     "epoch": state.slice_group.epoch},
                    status=503,
                )
        return web.json_response({"status": "ok", "last_step_age_s": 0.0})

    async def ready(_request: web.Request) -> web.Response:
        if state.draining:
            return web.json_response(
                {"status": "draining", "in_flight_streams": state.num_running},
                status=503,
            )
        return web.json_response({"status": "ready"})

    async def drain_endpoint(_request: web.Request) -> web.Response:
        state.draining = True
        return web.json_response(
            {"draining": True, "in_flight_streams": state.num_running}
        )

    async def metrics(_request: web.Request) -> web.Response:
        # Same serializer + same names as the real engine server
        # (engine/server/api_server.py) so the observability contract is
        # identical against fake and real engines.
        text = _render_metrics_pairs(state)
        return web.Response(text=text)

    def _render_metrics_pairs(state: FakeEngineState) -> str:
        # With a capacity model, "waiting" is the oversubscription beyond
        # capacity (queue-depth gauge the overload tests assert on), and
        # "running" the rest: disjoint, as the real engine's scheduler
        # reports them.  The router adds the two into one load
        # (routing/base.py: effective_load) and takes the larger of that
        # and its own count of the same requests: counted in both gauges,
        # a stale scrape would outweigh the count and steer every request
        # onto the other replica, past its bound.
        waiting = (
            max(0, state.num_running - state.capacity)
            if state.capacity else state.num_waiting
        )
        running = state.num_running - (waiting if state.capacity else 0)
        return vocab.render_prometheus([
            (vocab.TPU_NUM_REQUESTS_RUNNING, running),
            (vocab.TPU_NUM_REQUESTS_WAITING, waiting),
            (vocab.TPU_HBM_KV_USAGE_PERC, state.kv_usage),
            (vocab.TPU_PREFIX_CACHE_HIT_RATE, state.prefix_hit_rate),
            # Prefix-cache truth (live values from the chunk-chain sim):
            # the router's fleet popularity view scrapes these, so the
            # whole reconcile/fleet-hit-rate path runs in CI on fakes.
            (vocab.TPU_PREFIX_CACHE_HIT_TOKENS, state.prefix_hit_tokens),
            (vocab.TPU_PREFIX_CACHE_QUERY_TOKENS, state.prefix_query_tokens),
            (vocab.TPU_PREFIX_CACHE_BLOCKS, state.prefix_cached_chunks),
            (vocab.TPU_HOST_KV_USAGE_PERC, 0.0),
            (vocab.TPU_DUTY_CYCLE, min(1.0, state.num_running * 0.1)),
            (vocab.TPU_TOTAL_PROMPT_TOKENS, state.total_prompt_tokens),
            (vocab.TPU_TOTAL_GENERATED_TOKENS, state.total_generated_tokens),
            (vocab.TPU_TOTAL_FINISHED_REQUESTS, state.total_finished),
            (vocab.TPU_NUM_PREEMPTIONS, 0),
            # Pipeline-health + capability gauges: the fake engine has no
            # device (zero host gap) and no adapters, but the families
            # must exist for the scrape contract (metric_registry.py —
            # stackcheck SC303 pins this mirror).
            (vocab.TPU_DECODE_HOST_GAP_MS, 0.0),
            (vocab.TPU_LOADED_LORAS, 0),
            # Cross-engine prefix sharing + speculative decoding counters
            # (no store and no drafter here; contract parity only).
            (vocab.TPU_REMOTE_PREFIX_BLOCKS_FETCHED, 0),
            (vocab.TPU_REMOTE_PREFIX_BLOCKS_EXPORTED, 0),
            # Disaggregated serving emulation (--disagg-role): primes
            # served and simulated handoff prefetch outcomes — live
            # values, so router CI can assert the whole two-phase flow
            # through /metrics alone.
            (vocab.TPU_DISAGG_PREFILL_PRIMES, state.disagg_prefill_primes),
            (vocab.TPU_DISAGG_HANDOFF_HITS, state.disagg_handoff_hits),
            (vocab.TPU_DISAGG_HANDOFF_MISSES, state.disagg_handoff_misses),
            (vocab.TPU_SPEC_TOKENS_DRAFTED, 0),
            (vocab.TPU_SPEC_TOKENS_ACCEPTED, 0),
            # Draft-model speculation: no device, so no draft forwards
            # ever run — zero, but the family must exist (SC303).
            (vocab.TPU_SPEC_DRAFT_FRACTION_SECONDS, 0.0),
            # The fake engine serves every prompt instantly, so no mixed
            # chunking ever happens (windowed or not) — but the counters
            # must exist so the scrape contract matches the real engine.
            (vocab.TPU_PREFILL_CHUNK_TOKENS, 0),
            (vocab.TPU_MIXED_WINDOW_CHUNK_TOKENS, 0),
            # Overlapped window dispatch: no device, so no transfers ever
            # overlap a window — zero, but the family must exist
            # (tpu:mixed_window_prompts_per_window renders below).
            (vocab.TPU_WINDOW_TRANSFER_OVERLAP_SECONDS, 0.0),
            # Async KV transfer plane: the fake engine has no remote
            # store, but the families must exist for the scrape contract
            # (obs.render_metrics below adds the matching
            # tpu:remote_kv_fetch/offload_stage histograms).
            (vocab.TPU_KV_PREFETCH_HIT, 0),
            (vocab.TPU_KV_PREFETCH_WASTE, 0),
            (vocab.TPU_KV_PREFETCH_INFLIGHT, 0),
            # Overload protection + watchdog families (scrape contract
            # parity with the real engine; the fake engine's "step loop"
            # is the event loop, so its age is always fresh).
            (vocab.TPU_ADMISSION_REJECTED, state.admission_rejected),
            (vocab.TPU_DEADLINE_EXPIRED, state.deadline_expired),
            (vocab.TPU_QUEUED_PROMPT_TOKENS, 0),
            (vocab.TPU_LAST_STEP_AGE, 0.0),
            # K-step decode windows: the fake engine has no device, so
            # nothing falls back and nothing is wasted — but both
            # families must exist for the scrape contract
            # (TPU_MULTISTEP_FALLBACK renders its labeled header below).
            (vocab.TPU_MULTISTEP_WASTED_TOKENS, 0),
            # The fake routes nothing: the families, at zero (SC303).
            (vocab.TPU_MOE_EXPERTS_TOUCHED, 0),
            # The fake has one residual stream: the families, at zero.
            (vocab.TPU_MHC_CLAMPED, 0),
            (vocab.TPU_MHC_ENTRIES, 0),
            (vocab.TPU_MHC_SINKHORN_ERR, 0.0),
            # The fake has no state-space layer: the families, at zero.
            (vocab.TPU_SSM_STATE_ABSMAX, 0.0),
            (vocab.TPU_SSM_DT_MAX, 0.0),
            # The fake samples nothing on a device: the families, at zero.
            (vocab.TPU_SAMPLE_DISPATCH, 0),
            (vocab.TPU_SAMPLE_SORTED_DISPATCH, 0),
            # The fake keeps no block pool and hashes no chain: at zero.
            (vocab.TPU_PREFIX_CHAIN_BLOCKS, 0),
            (vocab.TPU_PREFIX_CHAIN_STEP_BLOCKS, 0),
            # The fake builds no dispatch and stages no transfer: at zero.
            (vocab.TPU_STEP_BUILD_TRANSFERS, 0),
            (vocab.TPU_STEP_UNCHAINED_DISPATCH, 0),
            # The fake keeps no recurrent state: the families, at zero.
            (vocab.TPU_STATE_SLOTS_IN_USE, 0),
            (vocab.TPU_STATE_SNAPSHOTS_TAKEN, 0),
            (vocab.TPU_STATE_RESUMES, 0),
            (vocab.TPU_STATE_RESUME_MISS, 0),
            (vocab.TPU_STATE_RECOMPUTED_TOKENS, 0),
            # Batched encode lane (embed/rerank/score): live values from
            # the fake lane below — texts encoded and the queue-depth
            # gauge — so router encode-lane CI asserts batching through
            # /metrics alone (SC303; the batch-size/latency histograms
            # render below).
            (vocab.TPU_ENCODE_TEXTS, state.encode_texts_total),
            (vocab.TPU_ENCODE_QUEUE_DEPTH, state.encode_in_flight),
        ]) + render_histogram(
            vocab.TPU_ENCODE_BATCH_SIZE, state.encode_batch_size_hist,
        ) + render_histogram(
            vocab.TPU_ENCODE_SECONDS, state.encode_seconds_hist,
        ) + vocab.render_labeled_counter(
            vocab.TPU_MULTISTEP_FALLBACK, "reason",
            dict.fromkeys(vocab.TPU_MULTISTEP_FALLBACK_REASONS, 0),
        ) + vocab.render_labeled_counter(
            # The fake dispatches nothing, behind or not: at zero (SC303).
            vocab.TPU_STEP_DISPATCH_BEHIND, "kind",
            dict.fromkeys(vocab.TPU_STEP_DISPATCH_BEHIND_KINDS, 0),
        ) + vocab.render_labeled_counter(
            vocab.TPU_STEP_DISPATCH_BEHIND_DECLINED, "reason",
            dict.fromkeys(vocab.TPU_STEP_DISPATCH_BEHIND_DECLINE_REASONS, 0),
        ) + vocab.render_labeled_counter(
            # No prefill kernel in the fake: the family, at zero (SC303).
            vocab.TPU_PREFILL_ATTN_TILES, "state",
            dict.fromkeys(vocab.TPU_PREFILL_ATTN_TILE_STATES, 0),
        ) + vocab.render_labeled_counter(
            vocab.TPU_MOE_ASSIGNMENTS, "where",
            dict.fromkeys(vocab.TPU_MOE_ASSIGNMENT_WHERE, 0),
        ) + vocab.render_labeled_counter2(
            # Fused speculative windows: no device, so no drafts — but
            # the family (all outcome x drafter cells) must exist for
            # the scrape contract (SC303).
            vocab.TPU_SPEC_WINDOW_TOKENS, ("outcome", "drafter"),
            {
                (o, d): 0
                for o in vocab.TPU_SPEC_WINDOW_OUTCOMES
                for d in vocab.TPU_SPEC_WINDOW_DRAFTERS
            },
        ) + vocab.render_labeled_counter2(
            # Quantized KV tiering plane: no KV tiers in the fake, but
            # both families must exist for the scrape contract (SC303).
            vocab.TPU_KV_WIRE_BYTES, ("tier", "format"),
            {
                (t, f): 0
                for t in vocab.TPU_KV_WIRE_TIERS
                for f in vocab.TPU_KV_WIRE_FORMATS
            },
        ) + vocab.render_labeled_counter(
            vocab.TPU_KV_SNAPSHOT_FORMAT, "version",
            dict.fromkeys(vocab.TPU_KV_SNAPSHOT_VERSIONS, 0),
        ) + render_histogram(
            # Packed multi-prompt windows: the fake engine never packs
            # (no device scan), so the histogram is empty — but the
            # family must exist for the scrape contract (SC303).
            vocab.TPU_MIXED_WINDOW_PROMPTS,
            Histogram(bounds=(1.0, 2.0, 4.0, 8.0, 16.0, 32.0)),
        ) + render_histogram(
            # The fake plans no window: the family, empty (SC303).
            vocab.TPU_DECODE_WINDOW_STEPS,
            Histogram(bounds=(1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 16.0)),
        ) + vocab.render_prometheus([
            # Slice-group lifecycle: live values in slice mode so the
            # whole group-liveness contract (epoch steps on restart,
            # relays count) is scrapeable against fakes in CI; zeros —
            # but stable families — single-host (SC303).
            (vocab.TPU_LOCKSTEP_GROUP_EPOCH,
             state.slice_group.epoch if state.slice_group else 0),
            (vocab.TPU_SLICE_DRAIN_RELAYS,
             state.slice_group.drain_relays if state.slice_group else 0),
        ]) + vocab.render_labeled_gauge(
            vocab.TPU_LOCKSTEP_MEMBER_LAST_ACK, "member",
            {} if state.slice_group is None else {
                str(pid): age
                for pid, age in state.slice_group.member_ack_ages().items()
            },
        ) + vocab.render_labeled_counter(
            vocab.TPU_LOCKSTEP_MEMBER_FAILURES, "reason",
            {
                **dict.fromkeys(vocab.TPU_LOCKSTEP_FAILURE_REASONS, 0),
                **(
                    state.slice_group.member_failures
                    if state.slice_group else {}
                ),
            },
        ) + vocab.render_labeled_counter(
            # XLA compile events per executable key: live values when
            # simulate_compiles is on, empty header otherwise — family
            # present either way for the scrape contract (SC303).
            vocab.TPU_COMPILE_SECONDS, "executable",
            state.obs.compile_tracker.seconds_by_executable(),
        ) + vocab.render_prometheus([
            (vocab.TPU_COMPILED_SHAPES,
             state.obs.compile_tracker.compiled_shapes()),
            (vocab.TPU_OBS_TRACE_DROPPED, state.obs.tracer.dropped),
        ]) + state.obs.render_metrics()

    async def debug_requests(_request: web.Request) -> web.Response:
        return web.json_response(state.obs.debug_payload())

    async def debug_request(request: web.Request) -> web.Response:
        snap = state.obs.request_payload(request.match_info["request_id"])
        if snap is None:
            return web.json_response(
                {"error": {"message": "unknown request id"}}, status=404
            )
        return web.json_response(snap)

    async def debug_windows(request: web.Request) -> web.Response:
        return web.json_response(
            state.obs.windows_payload(seq=request.query.get("seq") or None)
        )

    async def debug_compiles(_request: web.Request) -> web.Response:
        # Mirror of the real engine's compiles_payload(), jax-free: the
        # fake has no config-derived shape inventory, so coverage reports
        # the observed families as fully covered (contract tests assert
        # the payload SHAPE; the coverage math is engine-side logic).
        tracker = state.obs.compile_tracker
        coverage = {}
        for key in tracker.seconds_by_executable():
            fam = key.split("[", 1)[0]
            ent = coverage.setdefault(fam, {"compiled": 0, "expected": 0})
            ent["compiled"] += 1
            ent["expected"] += 1
        return web.json_response({
            "enabled": state.obs.enabled,
            "compiled_shapes": tracker.compiled_shapes(),
            "compile_seconds": round(tracker.compile_seconds(), 6),
            "executables": tracker.snapshot(),
            "coverage": coverage,
        })

    async def chat_completions(request: web.Request) -> web.StreamResponse:
        return await _completion_common(request, chat=True)

    async def completions(request: web.Request) -> web.StreamResponse:
        return await _completion_common(request, chat=False)

    def _finish_trace(
        request_id: str, t_recv: float, t_first: float, t_end: float
    ) -> None:
        """Simulated request timeline, partitioned exactly like the real
        engine's span set: zero queue wait, prefill = TTFT sleep, decode =
        token emission, zero detokenize."""
        obs = state.obs
        if not obs.enabled:
            return
        obs.request_hists["queue_time"].observe(0.0)
        obs.request_hists["ttft"].observe(t_first - t_recv)
        obs.request_hists["prefill_time"].observe(t_first - t_recv)
        obs.request_hists["decode_time"].observe(t_end - t_first)
        obs.request_hists["e2e_latency"].observe(t_end - t_recv)
        obs.tracer.add_span(request_id, "engine.prefill", t_recv, t_first)
        obs.tracer.add_span(request_id, "engine.decode", t_first, t_end)
        obs.tracer.add_span(
            request_id, "engine.detokenize", t_end, t_end, accumulated=True
        )
        obs.tracer.finish(request_id, end=t_end)

    async def _completion_common(request: web.Request, chat: bool) -> web.StreamResponse:
        # -- fault injection + overload surface (docs/robustness.md) ------
        state.data_plane_hits += 1
        if state.draining:
            resp = web.json_response(
                {"error": {"message": "server is draining for shutdown",
                           "type": "shutting_down", "code": 503}},
                status=503,
            )
            resp.force_close()
            return resp
        inj = state._take_injection("refuse")
        if inj is not None:
            # Connect-stage failure as the router sees it: the transport
            # dies before any response byte (ServerDisconnectedError).
            if request.transport is not None:
                request.transport.close()
            raise ConnectionResetError("injected connection refusal")
        if (
            state.slice_group is not None
            and state.slice_group.problem() is not None
        ):
            # A failed slice's leader fatal-exits within the member
            # timeout: the router sees connection refusals (breaker
            # opens, retry budget fails the request over to healthy
            # backends) — never a clean 5xx from a half-dead group.
            if request.transport is not None:
                request.transport.close()
            raise ConnectionResetError("slice group failed (leader exited)")
        inj = state._take_injection("error_5xx")
        if inj is not None:
            return web.json_response(
                {"error": {"message": "injected backend failure",
                           "type": "internal_error"}},
                status=int(inj.get("status", 503)),
            )
        inj = state._take_injection("slow_admission")
        if inj is not None:
            await asyncio.sleep(float(inj.get("delay_s", 0.2)))
        body = await request.json()
        state.last_headers = dict(request.headers)
        stream = bool(body.get("stream", False))
        max_tokens = int(
            body.get("max_tokens")
            or body.get("max_completion_tokens")
            or state.max_tokens_default
        )
        # Deadline contract parity with the real engine server: an
        # already-expired propagated deadline is shed with a 504.
        deadline_hdr = request.headers.get("x-request-deadline")
        if deadline_hdr is not None:
            try:
                deadline = float(deadline_hdr)
            except (TypeError, ValueError):
                deadline = None
            if deadline is not None and time.time() >= deadline:
                state.deadline_expired += 1
                return web.json_response(
                    {"error": {"message": "request deadline already "
                               "expired at admission",
                               "type": "deadline_expired", "code": 504}},
                    status=504,
                )
        inj = state._take_injection("reject_429")
        retry_after = int(inj.get("retry_after", 1)) if inj is not None else None
        if retry_after is None and (
            state.admission_control
            and state.capacity
            and state.in_flight >= state.capacity + state.max_queued
        ):
            retry_after = max(1, state.in_flight // state.capacity)
        if retry_after is not None:
            state.admission_rejected += 1
            return web.json_response(
                {
                    "error": {
                        "message": "engine overloaded: "
                                   f"{state.in_flight} requests in flight",
                        "type": "overloaded",
                        "code": 429,
                        "detail": {
                            "queued_requests": max(
                                0,
                                state.in_flight - (state.capacity or 0),
                            ),
                            "max_queued_requests": state.max_queued,
                            "kv_usage_perc": state.kv_usage,
                        },
                    }
                },
                status=429,
                headers={"Retry-After": str(retry_after)},
            )
        stall_after = None
        inj = state._take_injection("stall_stream")
        if inj is not None:
            stall_after = int(inj.get("after_tokens", 1))
        if chat:
            prompt_text = json.dumps(body.get("messages", ""))
        else:
            prompt_text = str(body.get("prompt", ""))
        uncached_chars, imported_chars = state.note_prompt(prompt_text)
        # Honor the router-assigned request id + trace context (the real
        # engine does the same), so router and engine timelines join.
        request_id = (
            request.headers.get("x-request-id")
            or f"cmpl-{uuid.uuid4().hex[:16]}"
        )

        # -- disagg prefill prime (x-disagg-phase) -------------------------
        # Same contract as the real engine server: run the (simulated)
        # prefill, record the eager export, return the handoff token
        # with zero completion tokens.
        if request.headers.get("x-disagg-phase") == "prefill":
            state.total_requests += 1
            state.num_running += 1
            try:
                await asyncio.sleep(state.ttft)  # the prefill cost
                chain = fake_prefix_chain(prompt_text)
                exported = state.disagg_role in ("prefill", "both")
                if exported:
                    state.shared_store.update(chain)
                    state.exports.append(chain)
                state.disagg_prefill_primes += 1
                prompt_tokens = max(1, len(prompt_text) // 4)
                state.total_prompt_tokens += prompt_tokens
                return web.json_response(
                    {
                        "id": request_id,
                        "object": "disagg.prefill",
                        "created": int(time.time()),
                        "model": body.get("model", state.model),
                        "disagg": {"handoff": {
                            "chain": chain,
                            "chain_len": len(chain),
                            "chain_tail": chain[-1],
                            "prompt_tokens": prompt_tokens,
                            "block_size": 16,
                            "px": "px:fake:",
                            "exported": exported,
                        }},
                        "usage": {
                            "prompt_tokens": prompt_tokens,
                            "completion_tokens": 0,
                            "total_tokens": prompt_tokens,
                        },
                    },
                    headers={"X-Request-Id": request_id},
                )
            finally:
                state.num_running -= 1

        # -- disagg decode-phase handoff (x-disagg-handoff) ----------------
        # A hit means the prefix chain "imported": decode starts with no
        # prefill work, so the TTFT sleep is skipped.  Any other outcome
        # keeps the full TTFT (the in-place recompute fallback).
        disagg_outcome = None
        ttft_s = state.ttft + state.prefill_seconds(
            uncached_chars, imported_chars
        )
        handoff_hdr = request.headers.get("x-disagg-handoff")
        if handoff_hdr:
            try:
                handoff = json.loads(handoff_hdr)
            except json.JSONDecodeError:
                handoff = None
            if state.prefetch_outcome is not None:
                disagg_outcome = state.prefetch_outcome
            elif state.disagg_role not in ("decode", "both"):
                disagg_outcome = "disabled"
            elif (
                isinstance(handoff, dict)
                and handoff.get("exported")
                and handoff.get("chain_tail") in state.shared_store
            ):
                disagg_outcome = "hit"
            else:
                disagg_outcome = "miss"
            if disagg_outcome == "hit":
                state.disagg_handoff_hits += 1
                ttft_s = 0.0
            else:
                state.disagg_handoff_misses += 1
        t_recv = time.time()
        # As the real engine: the router's trace id and span, and the hop
        # from the router (x-request-start) where it says when it began.
        trace_id, parent_span_id = parse_traceparent_ids(
            request.headers.get("traceparent"))
        state.obs.start_request(
            request_id, trace_id, received=t_recv,
            upstream_start=parse_request_start(
                request.headers.get("x-request-start"), t_recv),
            parent_span_id=parent_span_id,
            model=body.get("model", state.model), stream=stream,
        )
        state.obs.tracer.add_span(request_id, "engine.queue", t_recv, t_recv)
        created = int(t_recv)
        state.total_requests += 1
        state.num_running += 1
        state.total_prompt_tokens += max(1, len(prompt_text) // 4)
        # One simulated flight record per request: the whole decode rides
        # one "window" (k = token budget, one row), so /debug/windows and
        # the /debug/requests/{id} join are contract-testable without a
        # device.
        rec = state.obs.recorder.on_dispatch(
            "decode", k=max_tokens, rows=1, seq_ids=(request_id,),
        )
        if state.simulate_compiles and uncached_chars and state.obs.enabled:
            sig = f"chars{1 << max(0, uncached_chars - 1).bit_length()}"
            if (
                f"prefill_fn[{sig}]"
                not in state.obs.compile_tracker.seconds_by_executable()
            ):
                state.obs.compile_tracker.record("prefill_fn", sig, ttft_s)
                state.obs.on_compile(
                    (request_id,),
                    state.obs.compile_tracker.drain_events(),
                    rec,
                )
        try:
            object_name = "chat.completion.chunk" if chat else "text_completion"
            if stream:
                stream_headers = {
                    "Content-Type": "text/event-stream",
                    "Cache-Control": "no-cache",
                    "X-Request-Id": request_id,
                }
                if disagg_outcome is not None:
                    stream_headers["X-Disagg-Prefix"] = disagg_outcome
                response = web.StreamResponse(headers=stream_headers)
                # Prepare BEFORE the TTFT sleep, like the real engine
                # server: the router's backend_connect span must end at
                # connect, not absorb prefill time.
                await response.prepare(request)
                await state.sleep(ttft_s)
                t_first = time.time()
                t_last = t_first
                for i in range(max_tokens):
                    token = _word(state._rng) + " "
                    if chat:
                        delta = {"content": token}
                        if i == 0:
                            delta["role"] = "assistant"
                        choice = {"index": 0, "delta": delta, "finish_reason": None}
                    else:
                        choice = {"index": 0, "text": token, "finish_reason": None}
                    chunk = {
                        "id": request_id,
                        "object": object_name,
                        "created": created,
                        "model": body.get("model", state.model),
                        "choices": [choice],
                    }
                    if i == 0 and state.obs.compile_tainted(request_id):
                        # Same first-chunk marker the real server stamps.
                        chunk["compile"] = True
                    await response.write(_sse(chunk))
                    state.total_generated_tokens += 1
                    if stall_after is not None and i + 1 >= stall_after:
                        # Injected stall: the stream hangs byte-less until
                        # the peer (router sock_read timeout, client
                        # disconnect) tears it down — the CancelledError
                        # lands in the abort tracking below.
                        await asyncio.Event().wait()
                    await state.sleep(state.token_interval())
                    now = time.time()
                    if state.obs.enabled and i > 0:
                        state.obs.request_hists["itl"].observe(now - t_last)
                    t_last = now
                state.total_finished += 1
                state.obs.recorder.on_collect(
                    rec, tokens_emitted=max_tokens,
                    tokens_delivered=max_tokens,
                )
                _finish_trace(request_id, t_recv, t_first, time.time())
                final_choice = (
                    {"index": 0, "delta": {}, "finish_reason": "length"}
                    if chat
                    else {"index": 0, "text": "", "finish_reason": "length"}
                )
                await response.write(
                    _sse(
                        {
                            "id": request_id,
                            "object": object_name,
                            "created": created,
                            "model": body.get("model", state.model),
                            "choices": [final_choice],
                            "usage": {
                                "prompt_tokens": len(prompt_text) // 4,
                                "completion_tokens": max_tokens,
                                "total_tokens": len(prompt_text) // 4 + max_tokens,
                            },
                        }
                    )
                )
                await response.write(b"data: [DONE]\n\n")
                await response.write_eof()
                return response
            await state.sleep(ttft_s)
            t_first = time.time()
            interval = state.token_interval()
            await state.sleep(max_tokens * interval)
            text = " ".join(_word(state._rng) for _ in range(max_tokens))
            state.total_generated_tokens += max_tokens
            state.total_finished += 1
            state.obs.recorder.on_collect(
                rec, tokens_emitted=max_tokens, tokens_delivered=max_tokens,
            )
            if state.obs.enabled:
                # Same obs contract as the real engine: ITL is observed
                # per token gap regardless of stream mode.
                for _ in range(max(0, max_tokens - 1)):
                    state.obs.request_hists["itl"].observe(interval)
            _finish_trace(request_id, t_recv, t_first, time.time())
            if chat:
                choice = {
                    "index": 0,
                    "message": {"role": "assistant", "content": text},
                    "finish_reason": "length",
                }
                object_name = "chat.completion"
            else:
                choice = {"index": 0, "text": text, "finish_reason": "length"}
                object_name = "text_completion"
            resp_headers = {"X-Request-Id": request_id}
            if disagg_outcome is not None:
                resp_headers["X-Disagg-Prefix"] = disagg_outcome
            final_body = {
                "id": request_id,
                "object": object_name,
                "created": created,
                "model": body.get("model", state.model),
                "choices": [choice],
                "usage": {
                    "prompt_tokens": len(prompt_text) // 4,
                    "completion_tokens": max_tokens,
                    "total_tokens": len(prompt_text) // 4 + max_tokens,
                },
            }
            if state.obs.compile_tainted(request_id):
                # Same body marker the real server stamps non-streaming.
                final_body["compile"] = True
            return web.json_response(final_body, headers=resp_headers)
        except (asyncio.CancelledError, ConnectionResetError):
            # The peer tore the stream down (client disconnect, router
            # idle-read timeout, proxy teardown): record the abort so
            # propagation tests can assert the engine-side release
            # happened, then re-raise — cancellation must not be eaten.
            state.aborted_requests.append(request_id)
            if rec is not None and rec.collected_at is None:
                # Publish the flight record exactly once even on abort —
                # an uncollected record would leak from /debug/windows.
                state.obs.recorder.on_collect(rec)
            if state.obs.enabled:
                state.obs.on_abort(request_id)
            raise
        finally:
            state.num_running -= 1

    def _encode_gate(request: web.Request, texts: list):
        """PR-5-shaped overload protection for the fake encode lane:
        expired propagated deadline -> 504, queued texts past the cap ->
        structured 429 + Retry-After (same body shape as the real
        engine's encode admission).  Returns an error response or None."""
        deadline_hdr = request.headers.get("x-request-deadline")
        if deadline_hdr is not None:
            try:
                deadline = float(deadline_hdr)
            except (TypeError, ValueError):
                deadline = None
            if deadline is not None and time.time() >= deadline:
                state.deadline_expired += 1
                return web.json_response(
                    {"error": {"message": "request deadline already "
                               "expired at admission",
                               "type": "deadline_expired", "code": 504}},
                    status=504,
                )
        if (
            state.admission_control
            and state.encode_in_flight + len(texts)
            > state.max_queued_encode_texts
        ):
            state.admission_rejected += 1
            retry_after = max(1, state.encode_in_flight // 32)
            return web.json_response(
                {
                    "error": {
                        "message": (
                            "engine overloaded: "
                            f"{state.encode_in_flight} texts already "
                            "queued on the encode lane; retry after "
                            f"{retry_after}s"
                        ),
                        "type": "overloaded",
                        "code": 429,
                        "detail": {
                            "queued_requests": state.encode_in_flight,
                            "max_queued_requests":
                                state.max_queued_encode_texts,
                            "retry_after_s": retry_after,
                        },
                    }
                },
                status=429,
                headers={"Retry-After": str(retry_after)},
            )
        return None

    async def _encode_batch(texts: list) -> list:
        """One request = ONE simulated encode batch, like the real step
        thread's window-boundary drain: the whole list lands as a single
        forward, observed once in the batch-size histogram."""
        state.encode_in_flight += len(texts)
        t0 = time.time()
        try:
            await asyncio.sleep(state.ttft)
            return [fake_embedding(t) for t in texts]
        finally:
            state.encode_in_flight -= len(texts)
            state.encode_texts_total += len(texts)
            state.encode_batch_size_hist.observe(float(len(texts)))
            state.encode_seconds_hist.observe(time.time() - t0)

    async def embeddings(request: web.Request) -> web.Response:
        state.data_plane_hits += 1
        body = await request.json()
        state.last_headers = dict(request.headers)
        raw_input = body.get("input")
        inputs = [raw_input] if isinstance(raw_input, str) else raw_input
        if not isinstance(inputs, list) or not all(
            isinstance(x, str) for x in inputs
        ) or not inputs:
            return web.json_response(
                {"error": {"message": "'input' must be a string or list of "
                           "strings", "type": "invalid_request_error"}},
                status=400,
            )
        err = _encode_gate(request, inputs)
        if err is not None:
            return err
        state.total_requests += 1
        vectors = await _encode_batch(inputs)
        total_tokens = sum(max(1, len(t) // 4) for t in inputs)
        state.total_prompt_tokens += total_tokens
        return web.json_response({
            "object": "list",
            "data": [
                {"object": "embedding", "index": i, "embedding": vec}
                for i, vec in enumerate(vectors)
            ],
            "model": body.get("model", state.model),
            "usage": {"prompt_tokens": total_tokens,
                      "total_tokens": total_tokens},
        })

    async def rerank(request: web.Request) -> web.Response:
        state.data_plane_hits += 1
        body = await request.json()
        state.last_headers = dict(request.headers)
        query, documents = body.get("query"), body.get("documents")
        if not isinstance(query, str) or not isinstance(documents, list):
            return web.json_response(
                {"error": {"message": "'query' must be a string and "
                           "'documents' a list of strings",
                           "type": "invalid_request_error"}},
                status=400,
            )
        err = _encode_gate(request, [query] + documents)
        if err is not None:
            return err
        state.total_requests += 1
        vectors = await _encode_batch([query] + documents)
        qvec, dvecs = vectors[0], vectors[1:]
        results = [
            {"index": i, "document": {"text": documents[i]},
             "relevance_score": sum(a * b for a, b in zip(qvec, dvec))}
            for i, dvec in enumerate(dvecs)
        ]
        results.sort(key=lambda r: r["relevance_score"], reverse=True)
        top_n = body.get("top_n")
        if top_n is not None:
            results = results[:top_n]
        total_tokens = sum(
            max(1, len(t) // 4) for t in [query] + documents
        )
        return web.json_response({
            # Deterministic id (hash of the inputs, not a uuid) so a
            # cached rerank answer is byte-identical to a fresh one.
            "id": "rerank-" + hashlib.blake2b(
                json.dumps([query, documents], sort_keys=True).encode(),
                digest_size=8,
            ).hexdigest(),
            "model": body.get("model", state.model),
            "usage": {"prompt_tokens": total_tokens,
                      "total_tokens": total_tokens},
            "results": results,
        })

    async def score(request: web.Request) -> web.Response:
        state.data_plane_hits += 1
        body = await request.json()
        state.last_headers = dict(request.headers)

        def as_list(v):
            if isinstance(v, str):
                return [v]
            return v if isinstance(v, list) else None

        t1, t2 = as_list(body.get("text_1")), as_list(body.get("text_2"))
        if t1 is None or t2 is None or not t1 or not t2:
            return web.json_response(
                {"error": {"message": "'text_1' and 'text_2' must be "
                           "non-empty strings or lists of strings",
                           "type": "invalid_request_error"}},
                status=400,
            )
        if len(t1) == 1:
            t1 = t1 * len(t2)
        distinct = list(dict.fromkeys(t1 + t2))
        err = _encode_gate(request, distinct)
        if err is not None:
            return err
        state.total_requests += 1
        vectors = await _encode_batch(distinct)
        by_text = dict(zip(distinct, vectors))
        data = [
            {"object": "score", "index": i,
             "score": sum(x * y for x, y in zip(by_text[a], by_text[b]))}
            for i, (a, b) in enumerate(zip(t1, t2))
        ]
        total_tokens = sum(
            max(1, len(a) // 4) + max(1, len(b) // 4)
            for a, b in zip(t1, t2)
        )
        return web.json_response({
            "object": "list",
            "data": data,
            "model": body.get("model", state.model),
            "usage": {"prompt_tokens": total_tokens,
                      "total_tokens": total_tokens},
        })

    app.router.add_get("/v1/models", models)
    app.router.add_get("/health", health)
    app.router.add_get("/ready", ready)
    app.router.add_post("/drain", drain_endpoint)
    app.router.add_get("/metrics", metrics)
    app.router.add_get("/debug/requests", debug_requests)
    app.router.add_get("/debug/requests/{request_id}", debug_request)
    app.router.add_get("/debug/windows", debug_windows)
    app.router.add_get("/debug/compiles", debug_compiles)
    app.router.add_post("/v1/chat/completions", chat_completions)
    app.router.add_post("/v1/completions", completions)
    app.router.add_post("/v1/embeddings", embeddings)
    app.router.add_post("/v1/rerank", rerank)
    app.router.add_post("/rerank", rerank)
    app.router.add_post("/v1/score", score)
    app.router.add_post("/score", score)
    return app


def build_fake_follower_app(
    leader_state: FakeEngineState, ordinal: int
) -> web.Application:
    """Probe/drain surface of one follower ordinal in a fake slice group
    (the real follower serves exactly /health + /ready + POST /drain —
    api_server._run_follower).  POST /drain RELAYS to the leader: the
    whole slice drains through the leader's data plane, and the follower
    keeps "stepping" (stays healthy) until the group exits together."""
    group = leader_state.slice_group
    if group is None:
        raise ValueError("leader state has no slice_group")
    app = web.Application()

    async def health(_request: web.Request) -> web.Response:
        problem = group.problem()
        if problem is not None:
            return web.json_response(
                {"status": "unhealthy", "role": "follower",
                 "problem": problem},
                status=503,
            )
        return web.json_response(
            {"status": "ok", "role": "follower", "process_id": ordinal}
        )

    async def ready(_request: web.Request) -> web.Response:
        if group.drain_relayed:
            return web.json_response(
                {"status": "draining", "role": "follower"}, status=503
            )
        return web.json_response({"status": "ready", "role": "follower"})

    async def drain_endpoint(_request: web.Request) -> web.Response:
        group.relay_drain(ordinal)
        # The LEADER drains the group: it stops admitting and finishes
        # the in-flight streams; members exit together afterwards.
        leader_state.draining = True
        return web.json_response({
            "draining": True, "role": "follower", "relayed": True,
        })

    app.router.add_get("/health", health)
    app.router.add_get("/ready", ready)
    app.router.add_post("/drain", drain_endpoint)
    return app


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description="Fake TPU serving engine")
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=9000)
    parser.add_argument("--model", default="fake/llama-3-8b")
    parser.add_argument("--tokens-per-sec", type=float, default=500.0)
    parser.add_argument("--ttft", type=float, default=0.02)
    parser.add_argument(
        "--capacity", type=int, default=None,
        help="model max_num_seqs: per-token intervals degrade once "
        "in-flight exceeds this, the waiting gauge rises, and bounded "
        "admission 429s past capacity+max-queued (live-drive stand-in "
        "for a saturating engine; None keeps the constant-rate fake)",
    )
    parser.add_argument("--max-queued", type=int, default=0)
    parser.add_argument(
        "--disagg-role",
        default=None,
        choices=["prefill", "decode", "both", "encode"],
        help="emulate a disagg role pool member: prefill serves prime "
        "calls and records exports; decode honors handoff tokens with a "
        "simulated prefetch hit (TTFT skipped) or miss; encode marks a "
        "dedicated embed/rerank/score pool member",
    )
    args = parser.parse_args(argv)
    state = FakeEngineState(
        model=args.model, tokens_per_sec=args.tokens_per_sec, ttft=args.ttft,
        capacity=args.capacity, max_queued=args.max_queued,
        disagg_role=args.disagg_role,
    )
    web.run_app(
        build_fake_engine_app(state), host=args.host, port=args.port, access_log=None
    )


if __name__ == "__main__":
    main()
