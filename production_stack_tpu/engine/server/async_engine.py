"""Async bridge between the aiohttp server and the LLMEngine.

The engine step loop runs in one dedicated thread (device execution releases
the GIL, so the event loop keeps serving HTTP while XLA runs).  Requests and
per-token outputs cross the thread boundary via a lock-guarded submission
list and ``loop.call_soon_threadsafe`` hand-offs into per-request asyncio
queues — one queue per request, one engine, no polling of shared state from
the event loop.

The loop drives the engine's dispatch/collect pipeline directly: each
iteration tops up the device pipeline (with pipeline_decode on, decode
step N+1 is enqueued before step N's tokens are read back), then collects
and fans out step N — so detokenization and SSE emission overlap device
compute of the next step instead of serializing against it.  The lockstep
publish sits at the same dispatch boundary: followers replay the event
batch and run the identical dispatch/collect discipline (engine.step()),
keeping every replica's jitted launch sequence byte-identical.
"""

from __future__ import annotations

import asyncio
import dataclasses
import logging
import threading
import time
import uuid
from typing import AsyncIterator, Dict, List, Optional

from production_stack_tpu.engine.config import EngineConfig
from production_stack_tpu.engine.core.engine import LLMEngine
from production_stack_tpu.engine.core.sequence import FinishReason, SamplingParams

logger = logging.getLogger(__name__)


class DeadlineExceeded(Exception):
    """Raised into a request's event stream when its client deadline
    expired while the sequence was still waiting/preempted (the step
    loop's deadline sweep aborted it before it could occupy a batch
    slot).  The API server maps this to a structured 504."""


@dataclasses.dataclass
class AdmissionRejection:
    """Why bounded admission refused a request (serialized into the 429
    body so clients and the router see queue/KV pressure, not a bare
    status code)."""

    queued_requests: int
    queued_tokens: int
    max_queued_requests: int
    max_queued_tokens: int
    kv_usage_perc: float
    retry_after_s: int


@dataclasses.dataclass
class TokenEvent:
    token_id: int
    finished: bool
    finish_reason: Optional[FinishReason]
    num_prompt_tokens: int
    num_output_tokens: int
    logprob: Optional[float] = None
    top_logprobs: Optional[list] = None  # [(token_id, logprob), ...]
    # First event of an echo+logprobs request: per-prompt-position entries.
    prompt_logprobs: Optional[list] = None


class AsyncEngine:
    def __init__(self, config: EngineConfig, lockstep=None):
        # lockstep: parallel.distributed.LockstepChannel when this is the
        # leader of a multi-host slice group — every event batch is
        # broadcast to follower processes right before stepping, keeping
        # all replicas' jitted launches identical (SPMD requirement).
        self.engine = LLMEngine(config)
        self._lockstep = lockstep
        if lockstep is not None:
            # The followers replay this engine's plans from its events
            # alone: no plan may read a clock (scheduler.WindowPace).
            self.engine.plan_from_clocks = False
        # Group liveness (docs/robustness.md "Slice lifecycle contract"):
        # a real lockstep channel with a control-plane side channel gets
        # a member-liveness monitor — the slice's health becomes the
        # conjunction of its members' through /health.  Recording stubs
        # in tests carry no denv and stay monitor-free.
        from production_stack_tpu.engine.parallel.distributed import (
            GroupLivenessMonitor,
        )

        self._slice_monitor: Optional[GroupLivenessMonitor] = None
        denv = getattr(lockstep, "denv", None)
        if (
            denv is not None
            and denv.num_processes > 1
            and getattr(lockstep, "ack_store", None) is not None
            and getattr(lockstep, "member_timeout_s", 0) > 0
        ):
            self._slice_monitor = GroupLivenessMonitor(lockstep)
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._queues: Dict[str, asyncio.Queue] = {}
        # (request_id, prompt_ids, sampling_params, adapter, received,
        # submitted): the last two are obs stamps, None with tracing off.
        self._pending: List = []
        self._aborts: List[str] = []
        self._lock = threading.Lock()
        self._shutdown = threading.Event()
        self._wakeup = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # Prompt tokens submitted but not yet drained into the engine by
        # the step thread (guarded by _lock); bounded admission counts
        # these beside the scheduler's waiting queue so a burst between
        # step-loop iterations cannot slip past the caps.
        self._pending_tokens = 0
        # True once any request carried a deadline: keeps the per-step
        # deadline sweep off the hot path for deadline-free serving.
        self._any_deadlines = False
        # Watchdog: wall clock of the step loop's most recent iteration
        # start.  A hung device dispatch (or a wedged collective) stops
        # the stamp advancing, and /health turns that into a liveness
        # failure instead of serving a green probe (tpu:last_step_age_seconds).
        self._last_step_ts: Optional[float] = None
        # Batched encode lane (encode_batcher.py): the event loop queues
        # embed/rerank/score token lists and THIS object's step thread
        # drains them as [B, T]-bucketed encode batches at window
        # boundaries.  Disabled under multi-host lockstep (a leader-only
        # encode forward would desync the SPMD followers' jitted launch
        # sequence) and for models without a batched encode path — both
        # fall back to the legacy serial embed.
        self.encode_batcher = None
        if (
            config.scheduler.encode_lane_enabled
            and (denv is None or denv.num_processes <= 1)
            and hasattr(self.engine.model, "encode_batch")
        ):
            from production_stack_tpu.engine.server.encode_batcher import (
                EncodeBatcher,
            )

            self.encode_batcher = EncodeBatcher(self.engine)

    # -- lifecycle ---------------------------------------------------------

    async def start(self) -> None:
        self._loop = asyncio.get_running_loop()
        self._thread = threading.Thread(
            target=self._run_loop, name="engine-step-loop", daemon=True
        )
        self._thread.start()
        if self._slice_monitor is not None:
            self._slice_monitor.start()

    async def close(self) -> None:
        self._shutdown.set()
        self._wakeup.set()
        if self._slice_monitor is not None:
            # Before the step-thread join: a member dying mid-close must
            # not fatal_exit a process already shutting down cleanly.
            await asyncio.to_thread(self._slice_monitor.stop)
        if self._thread is not None:
            await asyncio.to_thread(self._thread.join, 30)
        if self.encode_batcher is not None:
            # The step thread is gone; queued embeds can never run.
            self.encode_batcher.fail_all(
                RuntimeError("engine shutting down")
            )
        # Release the engine's own workers AFTER the step thread is gone
        # (it is their producer): prefetch fetchers, offload stager
        # writer, prefix exporter, the remote-KV deleter (whose queued
        # DELs a drain must flush or the store leaks one snapshot per
        # in-flight discard), and the kvserver sockets.
        await asyncio.to_thread(self.engine.close)

    # -- request API (event-loop side) ------------------------------------

    async def generate(
        self,
        prompt: Optional[str] = None,
        prompt_token_ids: Optional[List[int]] = None,
        sampling_params: Optional[SamplingParams] = None,
        request_id: Optional[str] = None,
        adapter: Optional[str] = None,
        received: Optional[float] = None,
    ) -> AsyncIterator[TokenEvent]:
        """``received``: the handler's stamp of the request's arrival
        (tracing on), carried to ``Sequence.arrival_time`` so that the
        engine's latency families start where the request arrived and not
        where the step thread first met it."""
        request_id = request_id or f"req-{uuid.uuid4().hex[:12]}"
        queue: asyncio.Queue = asyncio.Queue()
        self._queues[request_id] = queue
        if prompt_token_ids is None:
            prompt_token_ids = self.engine.tokenizer.encode(prompt or "")
        params = sampling_params or SamplingParams()
        if params.deadline is not None:
            self._any_deadlines = True
        # The prompt's prefix chain, hashed here while the pass in flight
        # keeps the device busy, so the step thread plans without hashing.
        chain = self.engine.prompt_prefix_chain(prompt_token_ids, adapter)
        submitted = time.time() if received is not None else None
        with self._lock:
            self._pending.append(
                (request_id, prompt_token_ids, params, adapter, received,
                 submitted, chain)
            )
            self._pending_tokens += len(prompt_token_ids)
        self._wakeup.set()
        finished = False
        try:
            while True:
                event = await queue.get()
                if isinstance(event, Exception):
                    raise event
                yield event
                if event.finished:
                    finished = True
                    return
        finally:
            self._queues.pop(request_id, None)
            if not finished:
                # Consumer stopped early (client disconnect, pump cancel,
                # error on a sibling choice): abort in-engine so the
                # scheduler doesn't keep decoding for nobody.  Inline sync
                # append — `await` in an async-generator finally runs
                # during aclose and must not block.
                with self._lock:
                    self._aborts.append(request_id)
                self._wakeup.set()

    async def abort(self, request_id: str) -> None:
        with self._lock:
            self._aborts.append(request_id)
        self._wakeup.set()

    async def embed_batch(
        self,
        batch_token_ids: List[List[int]],
        deadline: Optional[float] = None,
    ) -> List:
        """Embed a list of tokenized inputs.  With the encode lane on
        (the default) every text is queued on the EncodeBatcher and the
        STEP THREAD runs the [B, T]-bucketed batch at a window boundary
        — the device is never touched from this coroutine's thread.
        With the lane off (--no-encode-lane / multi-host lockstep) each
        text runs the legacy serial encode off-thread, preserving the
        pre-lane behavior exactly.  Raises ValueError on empty or
        over-long inputs either way."""
        max_len = self.engine.encode_max_len()
        for ids in batch_token_ids:
            if not ids:
                raise ValueError("input produced no tokens")
            if len(ids) > max_len:
                raise ValueError(
                    f"input is {len(ids)} tokens; the embedding path "
                    f"supports up to {max_len}"
                )
        if self.encode_batcher is None:
            return [
                await asyncio.to_thread(self.engine.embed, ids)
                for ids in batch_token_ids
            ]
        futures = self.encode_batcher.submit(
            batch_token_ids, asyncio.get_running_loop(), deadline
        )
        self._wakeup.set()
        return list(await asyncio.gather(*futures))

    def stats(self) -> Dict[str, float]:
        return self.engine.stats()

    # -- overload protection / lifecycle reads -----------------------------

    def check_admission(
        self, n_requests: int, n_tokens: int
    ) -> Optional[AdmissionRejection]:
        """Bounded admission (docs/robustness.md): None = admit; otherwise
        the structured rejection the server turns into a 429.

        Queue depth = scheduler waiting/preempted + submissions the step
        thread has not drained yet.  The read is advisory (concurrent
        handlers may interleave between check and submit), but the
        overshoot is bounded by the handful of requests parsing bodies at
        once — the queue cannot grow without bound either way."""
        cfg = self.engine.config.scheduler
        if not cfg.admission_enabled:
            return None
        with self._lock:
            pending_n = len(self._pending)
            pending_tok = self._pending_tokens
        queued_requests = self.engine.scheduler.num_waiting + pending_n
        queued_tokens = (
            self.engine.scheduler.queued_prompt_tokens + pending_tok
        )
        if (
            queued_requests + n_requests <= cfg.queued_requests_cap
            and queued_tokens + n_tokens <= cfg.queued_tokens_cap
        ):
            return None
        # Crude service-rate estimate: each batch generation drains up to
        # max_num_seqs queued requests; tell the client to come back after
        # roughly that many "turns".
        retry_after = max(
            1, -(-queued_requests // max(1, cfg.max_num_seqs))
        )
        return AdmissionRejection(
            queued_requests=queued_requests,
            queued_tokens=queued_tokens,
            max_queued_requests=cfg.queued_requests_cap,
            max_queued_tokens=cfg.queued_tokens_cap,
            kv_usage_perc=float(self.engine.block_pool.usage),
            retry_after_s=min(retry_after, 60),
        )

    def check_encode_admission(
        self, n_texts: int, n_tokens: int
    ) -> Optional[AdmissionRejection]:
        """Bounded admission for the encode lane: the queue the batcher
        carries is bounded in texts (queued_encode_texts_cap) and tokens
        (the shared queued_tokens_cap), so an embed burst sheds with a
        structured 429 at the edge instead of queueing unboundedly.
        With the lane off, encode requests count against the generation
        caps (one text = one request) — they compete for the same
        device either way."""
        cfg = self.engine.config.scheduler
        if not cfg.admission_enabled:
            return None
        if self.encode_batcher is None:
            return self.check_admission(n_texts, n_tokens)
        depth, queued_tokens = self.encode_batcher.snapshot()
        if (
            depth + n_texts <= cfg.queued_encode_texts_cap
            and queued_tokens + n_tokens <= cfg.queued_tokens_cap
        ):
            return None
        # Service-rate estimate, encode flavor: each window boundary
        # drains up to one full encode batch bucket.
        retry_after = max(
            1, -(-depth // max(1, cfg.encode_batch_buckets[-1]))
        )
        return AdmissionRejection(
            queued_requests=depth,
            queued_tokens=queued_tokens,
            max_queued_requests=cfg.queued_encode_texts_cap,
            max_queued_tokens=cfg.queued_tokens_cap,
            kv_usage_perc=float(self.engine.block_pool.usage),
            retry_after_s=min(retry_after, 60),
        )

    @property
    def last_step_age_s(self) -> float:
        """Seconds since the step loop last started an iteration (0.0
        before the loop boots).  Exported as tpu:last_step_age_seconds;
        /health fails liveness past scheduler.step_watchdog_s."""
        ts = self._last_step_ts
        if ts is None:
            return 0.0
        return max(0.0, time.time() - ts)

    # -- slice-group liveness reads (docs/robustness.md) --------------------

    @property
    def slice_monitor(self):
        return self._slice_monitor

    def slice_problem(self) -> Optional[str]:
        """Non-None when the slice group lost a member (the leader's
        /health conjoins this with the step watchdog, so the WHOLE slice
        fails liveness within --slice-member-timeout-s of the member
        going silent — the router's breaker routes around it in
        seconds).  None on single-host engines."""
        if self._slice_monitor is None:
            return None
        return self._slice_monitor.problem()

    @property
    def slice_epoch(self) -> int:
        """The group epoch (leader boot nonce; 0 single-host) —
        tpu:lockstep_group_epoch."""
        if self._lockstep is None:
            return 0
        return getattr(self._lockstep, "epoch", 0)

    @property
    def step_thread_healthy(self) -> bool:
        """False only when the step thread died unexpectedly (crashed out
        of its loop without a shutdown request)."""
        if self._thread is None or self._shutdown.is_set():
            return True  # not started yet / clean shutdown in progress
        return self._thread.is_alive()

    # -- engine thread -----------------------------------------------------

    # stackcheck: root=step-thread
    # stackcheck: thread=engine-step-loop
    def _run_loop(self) -> None:
        logger.info("engine step loop started")
        last_publish = time.time()
        while not self._shutdown.is_set():
            self._last_step_ts = time.time()
            with self._lock:
                pending, self._pending = self._pending, []
                aborts, self._aborts = self._aborts, []
                self._pending_tokens -= sum(len(p[1]) for p in pending)
            # Deadline sweep (each scheduler pass): expired waiting/
            # preempted sequences fold into this iteration's abort batch —
            # published under lockstep like any client abort, so followers
            # replay the leader's wall-clock decision instead of making
            # their own.  The consumer sees DeadlineExceeded, not silence.
            expired: List[str] = []
            if self._any_deadlines and self.engine.has_unfinished():
                expired = [
                    rid
                    for rid in self.engine.scan_expired_deadlines(
                        self._last_step_ts
                    )
                    if rid not in aborts
                ]
                for rid in expired:
                    aborts.append(rid)
            if self._lockstep is not None and (
                pending or aborts or self.engine.has_unfinished()
                # Idle heartbeat: followers detect a dead leader by event
                # staleness (their /health fails, k8s restarts the group
                # member); without it an idle group is indistinguishable
                # from a dead one.
                or time.time() - last_publish
                > self._lockstep.heartbeat_seconds
            ):
                from production_stack_tpu.engine.parallel.distributed import (
                    StepEvents,
                )

                self._lockstep.publish(StepEvents(
                    # The wire format carries no stamps: a follower's
                    # observations are on its own clock.
                    requests=[p[:4] for p in pending],
                    aborts=list(aborts),
                ))
                last_publish = time.time()
            for request_id in aborts:
                self.engine.abort_request(request_id)
            # Told only once the abort is applied: a client that sees the
            # 504 finds the sequence gone from the queue.
            for request_id in expired:
                self.engine.deadline_expired += 1
                self._emit(
                    request_id,
                    DeadlineExceeded(
                        f"request {request_id} missed its deadline while "
                        "queued; shed before occupying a batch slot"
                    ),
                )
            for (request_id, token_ids, params, adapter, received,
                 submitted, chain) in pending:
                try:
                    self.engine.add_request(
                        request_id,
                        prompt_token_ids=token_ids,
                        sampling_params=params,
                        adapter=adapter,
                        arrival_time=received,
                        submitted_time=submitted,
                        prefix_chain=chain,
                    )
                except Exception as e:
                    self._emit(request_id, e)
            if not self.engine.has_unfinished():
                # Device idle: encode batches are the only work there is
                # — drain the queue completely before sleeping.
                if (
                    self.encode_batcher is not None
                    and self.encode_batcher.run_pending(max_batches=0)
                ):
                    continue
                with self.engine.obs.phase("wait"):
                    self._wakeup.wait(timeout=0.01)
                self._wakeup.clear()
                continue
            try:
                # Keep the device fed before fanning out results: with
                # pipeline_decode on, dispatch() enqueues decode N+1
                # (chained on N's in-flight sample) and collect() then
                # reads N back — the _emit loop below runs while N+1 is
                # computing.
                self.engine.dispatch()
                outputs = self.engine.collect()
            except Exception:
                if self._lockstep is not None:
                    # Fatal under lockstep: followers have already
                    # launched this iteration's collectives (or will
                    # hang waiting for them).  Retrying against a
                    # desynced SPMD group wedges it in collectives;
                    # exiting lets k8s restart the slice group together.
                    # The shutdown publish is best-effort — if the
                    # collective transport still works, followers exit
                    # cleanly instead of waiting out the staleness
                    # window.
                    logger.exception(
                        "engine step failed under lockstep; exiting so "
                        "the slice group restarts together"
                    )
                    from production_stack_tpu.engine.parallel.distributed import (
                        StepEvents,
                        fatal_exit,
                    )

                    try:
                        self._lockstep.publish(StepEvents(shutdown=True))
                    except Exception:
                        logger.exception("shutdown publish failed")
                    fatal_exit(1)
                    return  # unreachable except under monkeypatched exit
                logger.exception("engine step failed")
                # stackcheck: allow=SC101 reason=error backoff after a failed step; the device produced nothing to wait for and hammering a failing dispatch would spin the log
                time.sleep(0.1)
                continue
            if outputs:
                with self.engine.obs.phase("emit"):
                    self._emit_outputs(outputs)
            # Window boundary: at most ONE encode batch per iteration
            # while generation is live — an embed burst adds one
            # prefill-chunk-shaped pass between decode windows, never
            # preempts a window mid-scan, and generation ITL stays
            # bounded.  (The batcher is None under lockstep, so
            # followers never see a forward they didn't replay.)
            if self.encode_batcher is not None:
                self.encode_batcher.run_pending(max_batches=1)
        if self._lockstep is not None:
            from production_stack_tpu.engine.parallel.distributed import (
                StepEvents,
            )

            self._lockstep.publish(StepEvents(shutdown=True))
        logger.info("engine step loop exited")

    def _emit_outputs(self, outputs) -> None:
        for out in outputs:
            # Drop events for requests whose client vanished.
            if out.seq_id in self._queues:
                self._emit(
                    out.seq_id,
                    TokenEvent(
                        token_id=out.new_token_id,
                        finished=out.finished,
                        finish_reason=out.finish_reason,
                        num_prompt_tokens=out.num_prompt_tokens,
                        num_output_tokens=out.num_output_tokens,
                        logprob=out.logprob,
                        top_logprobs=out.top_logprobs,
                        prompt_logprobs=out.prompt_logprobs,
                    ),
                )

    def _emit(self, request_id: str, event) -> None:
        queue = self._queues.get(request_id)
        if queue is None or self._loop is None:
            return
        self._loop.call_soon_threadsafe(queue.put_nowait, event)
