"""OpenAI-compatible API server for the TPU engine.

Speaks exactly the contract the router (and the reference's router) expects
from a serving engine: /v1/chat/completions, /v1/completions (SSE streaming
and non-streaming), /v1/models, /health, and Prometheus /metrics in the
``tpu:`` vocabulary (production_stack_tpu/router/stats/vocabulary.py).
This is the process the helm chart runs per engine pod — the TPU analogue of
``vllm serve`` (reference deployment-vllm-multi.yaml:57-64).
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import logging
import os
import time
import uuid
from typing import Optional

import numpy as np
from aiohttp import web

from production_stack_tpu.engine.config import config_from_preset
from production_stack_tpu.engine.core.sequence import FinishReason, SamplingParams
from production_stack_tpu.engine.server.async_engine import (
    AsyncEngine,
    DeadlineExceeded,
)
from production_stack_tpu.obs.histogram import render_histogram
from production_stack_tpu.obs.trace import (
    parse_request_start,
    parse_traceparent_ids,
)
from production_stack_tpu.router.stats import vocabulary as vocab
from production_stack_tpu.utils.drain import DrainController
from production_stack_tpu.utils.log import init_logger
from production_stack_tpu.utils.net import parse_deadline

logger = logging.getLogger(__name__)


def _sampling_from_body(body: dict, chat: bool) -> SamplingParams:
    stop = body.get("stop")
    if isinstance(stop, str):
        stop = [stop]
    # logprobs: chat uses bool `logprobs` + int `top_logprobs`; the legacy
    # completions API uses int-or-null `logprobs` as the top-k count.
    if chat:
        want_logprobs = bool(body.get("logprobs", False))
        top_logprobs = int(body.get("top_logprobs") or 0)
    else:
        raw = body.get("logprobs")
        want_logprobs = raw is not None and raw is not False
        top_logprobs = int(raw or 0) if not isinstance(raw, bool) else 0
    logit_bias = body.get("logit_bias") or None
    if logit_bias is not None:
        if not isinstance(logit_bias, dict):
            raise ValueError("'logit_bias' must be a map of token id -> bias")
        try:
            logit_bias = {int(k): float(v) for k, v in logit_bias.items()}
        except (TypeError, ValueError):
            raise ValueError(
                "'logit_bias' keys must be token ids and values numbers"
            ) from None
    stop_token_ids = body.get("stop_token_ids") or None
    if stop_token_ids is not None:
        if not isinstance(stop_token_ids, list):
            raise ValueError("'stop_token_ids' must be a list of token ids")
        try:
            stop_token_ids = [int(t) for t in stop_token_ids]
        except (TypeError, ValueError):
            raise ValueError("'stop_token_ids' entries must be token ids") from None
    min_p = float(body.get("min_p") or 0.0)
    if not 0.0 <= min_p <= 1.0:
        raise ValueError(f"'min_p' must be in [0, 1], got {min_p}")
    response_format = None
    rf = body.get("response_format")
    if rf is not None:
        rf_type = rf.get("type") if isinstance(rf, dict) else rf
        if rf_type == "json_object":
            response_format = "json_object"
        elif rf_type == "json_schema":
            # OpenAI structured outputs: {"type": "json_schema",
            # "json_schema": {"name":..., "schema": {...}, "strict":...}}.
            spec = rf.get("json_schema") if isinstance(rf, dict) else None
            if not isinstance(spec, dict):
                raise ValueError(
                    "response_format json_schema requires a 'json_schema' "
                    "object"
                )
            schema = spec.get("schema")
            if not isinstance(schema, dict):
                raise ValueError(
                    "response_format json_schema requires "
                    "json_schema.schema (an object)"
                )
            # Compile HERE so unsupported schemas 400 before any stream
            # starts (SchemaCompileError is a ValueError); the cache makes
            # the per-sequence guides reuse this compilation.
            from production_stack_tpu.engine.guided_schema import (
                compile_schema_cached,
            )

            compile_schema_cached(schema)
            response_format = {"type": "json_schema", "schema": schema}
        elif rf_type in ("text", None):
            response_format = None
        else:
            raise ValueError(
                f"Unsupported response_format type {rf_type!r} "
                "(supported: text, json_object, json_schema)"
            )
    raw_max = body.get("max_tokens")
    if raw_max is None:
        raw_max = body.get("max_completion_tokens")
    # Explicit 0 is meaningful (echo+logprobs scoring wants NO generated
    # tokens); only absence falls back to the default.
    max_tokens = 128 if raw_max is None else int(raw_max)
    if max_tokens < 0:
        raise ValueError(f"'max_tokens' must be >= 0, got {max_tokens}")
    rep = body.get("repetition_penalty")
    if rep is not None and not (isinstance(rep, (int, float)) and rep > 0):
        raise ValueError(
            f"'repetition_penalty' must be a positive number, got {rep}"
        )
    min_tokens = int(body.get("min_tokens") or 0)
    if min_tokens < 0 or min_tokens > max_tokens:
        raise ValueError(
            f"'min_tokens' must be in [0, max_tokens], got {min_tokens}"
        )
    try:
        priority = int(body.get("priority") or 0)
    except (TypeError, ValueError):
        raise ValueError("'priority' must be an integer") from None
    return SamplingParams(
        max_tokens=max_tokens,
        temperature=float(body.get("temperature") or 0.0),
        top_p=float(body.get("top_p") or 1.0),
        top_k=int(body.get("top_k") or 0),
        min_p=min_p,
        stop=stop,
        stop_token_ids=stop_token_ids,
        logit_bias=logit_bias,
        echo=bool(body.get("echo")) and not chat,
        # Guided decoding forces EOS when the JSON completes, so
        # ignore_eos would loop forever; response_format wins.
        ignore_eos=bool(body.get("ignore_eos", False)) and response_format is None,
        response_format=response_format,
        seed=body.get("seed"),
        logprobs=want_logprobs,
        top_logprobs=max(0, min(top_logprobs, 20)),
        presence_penalty=float(body.get("presence_penalty") or 0.0),
        frequency_penalty=float(body.get("frequency_penalty") or 0.0),
        repetition_penalty=float(body.get("repetition_penalty") or 1.0),
        min_tokens=min_tokens,
        priority=priority,
    )


class StopChecker:
    """Incremental detokenization with stop-string truncation."""

    def __init__(self, tokenizer, stop: Optional[list]):
        self.tokenizer = tokenizer
        self.stop = stop or []
        self.token_ids: list = []
        self.emitted_text = ""

    def push(self, token_id: int):
        """Returns (delta_text, stopped).  Negative ids are no-text
        sentinels (a stop_token_ids match ends generation without
        contributing text)."""
        if token_id >= 0:
            self.token_ids.append(token_id)
        text = self.tokenizer.decode(self.token_ids)
        for s in self.stop:
            idx = text.find(s)
            if idx != -1:
                delta = text[len(self.emitted_text) : idx]
                self.emitted_text = text[:idx]
                return delta, True
        # Hold back a partial-stop-suffix so we never emit half a stop string.
        hold = 0
        for s in self.stop:
            for k in range(1, len(s)):
                if text.endswith(s[:k]):
                    hold = max(hold, k)
        safe = text[: len(text) - hold] if hold else text
        delta = safe[len(self.emitted_text) :]
        if delta:
            self.emitted_text = safe
        return delta, False

    def flush(self) -> str:
        """Remaining held-back text when generation ends WITHOUT a stop
        match (e.g. max_tokens with output ending in a partial stop
        prefix); without this the tail characters are silently dropped."""
        text = self.tokenizer.decode(self.token_ids)
        delta = text[len(self.emitted_text):]
        self.emitted_text = text
        return delta

    def aligned_token_count(self) -> int:
        """Largest k such that the first k tokens detokenize within the
        emitted (post-stop-trim) text — i.e. how many tokens' logprobs
        entries align with the returned content.  Tokens consumed by a
        multi-token stop string fall outside."""
        emitted = len(self.emitted_text)
        for k in range(len(self.token_ids), -1, -1):
            if len(self.tokenizer.decode(self.token_ids[:k])) <= emitted:
                return k
        return 0


def _is_engine_data_plane(request: web.Request) -> bool:
    """Mutating model-serving work a draining engine must refuse (the
    same contract as the router's drain middleware): completions,
    embeddings/rerank/score, tokenize/detokenize, LoRA admin.  GET
    control-plane surfaces (/health, /ready, /metrics, /debug...) and
    POST /drain itself stay served throughout."""
    if request.method not in ("POST", "DELETE") or request.path == "/drain":
        return False
    return (
        request.path.startswith("/v1/")
        or request.path in ("/rerank", "/score", "/tokenize", "/detokenize")
        or request.path.startswith("/admin/")
    )


def build_engine_app(
    engine: AsyncEngine, served_model: str, drain_grace_s: float = 30.0
) -> web.Application:
    # Graceful lifecycle: /drain (helm preStop) and SIGTERM (main) both
    # converge here.  busy = any stream still attached to the engine OR
    # sequences still decoding.  exit_cb stays None under tests; main()
    # installs a SIGINT-to-self so the process exits 0 after the drain.
    drain = DrainController(
        grace_s=drain_grace_s,
        busy_fn=lambda: bool(engine._queues) or engine.engine.has_unfinished(),
    )

    @web.middleware
    async def drain_gate(request: web.Request, handler):
        """503 + Connection: close for ALL data-plane work during a drain
        — one gate instead of per-handler checks, so new endpoints cannot
        forget it, and the connection is never reused for a pod about to
        exit."""
        if drain.draining and _is_engine_data_plane(request):
            resp = web.json_response(
                {"error": {"message": "server is draining for shutdown",
                           "type": "shutting_down", "code": 503}},
                status=503,
            )
            resp.force_close()
            return resp
        return await handler(request)

    app = web.Application(middlewares=[drain_gate])
    app["engine"] = engine
    app["drain"] = drain

    def _watchdog_problem() -> Optional[str]:
        if not engine.step_thread_healthy:
            return "engine step thread died"
        # Slice-group liveness conjunction (docs/robustness.md "Slice
        # lifecycle contract"): the leader IS the slice's one discovery
        # endpoint, so a silent member fails the WHOLE slice's health
        # here — within --slice-member-timeout-s, well before the step
        # watchdog would notice the wedged collective.
        slice_problem = engine.slice_problem()
        if slice_problem is not None:
            return slice_problem
        wd = engine.engine.config.scheduler.step_watchdog_s
        age = engine.last_step_age_s
        if wd and age > wd:
            return (
                f"step loop stalled: last iteration started {age:.1f}s ago "
                f"(watchdog {wd:.0f}s)"
            )
        return None

    async def models(_req: web.Request) -> web.Response:
        def card(model_id: str) -> dict:
            return {
                "id": model_id,
                "object": "model",
                "created": int(time.time()),
                "owned_by": "production-stack-tpu",
            }

        # Loaded LoRA adapters are addressable as "<base>:<adapter>".
        data = [card(served_model)] + [
            card(f"{served_model}:{name}")
            for name in engine.engine.loaded_adapters()
        ]
        return web.json_response({"object": "list", "data": data})

    async def health(_req: web.Request) -> web.Response:
        """Liveness: fails when the step loop is hung or dead (watchdog),
        NOT during a drain — kubelet killing a draining pod would drop
        the very streams the drain exists to finish."""
        problem = _watchdog_problem()
        if problem is not None:
            return web.json_response(
                {"status": "unhealthy", "problem": problem,
                 "last_step_age_s": engine.last_step_age_s},
                status=503,
            )
        return web.json_response(
            {"status": "ok", "last_step_age_s": engine.last_step_age_s}
        )

    async def ready(_req: web.Request) -> web.Response:
        """Readiness: additionally fails while draining, so k8s pulls the
        pod from its Service (and the router's discovery drops it) while
        in-flight streams finish."""
        if drain.draining:
            return web.json_response(
                {"status": "draining", "in_flight_streams": len(engine._queues)},
                status=503,
            )
        problem = _watchdog_problem()
        if problem is not None:
            return web.json_response(
                {"status": "unhealthy", "problem": problem}, status=503
            )
        return web.json_response({"status": "ready"})

    async def drain_endpoint(_req: web.Request) -> web.Response:
        """POST /drain: flip readiness, stop admission, let in-flight
        streams finish within the grace, then exit (helm preStop hook;
        SIGTERM lands on the same controller)."""
        drain.begin()
        return web.json_response({
            "draining": True,
            "in_flight_streams": len(engine._queues),
            "unfinished_sequences": engine.engine.has_unfinished(),
            "grace_s": drain.grace_s,
        })

    async def metrics(_req: web.Request) -> web.Response:
        s = engine.stats()
        monitor = engine.slice_monitor
        pairs = [
            (vocab.TPU_NUM_REQUESTS_RUNNING, s["num_requests_running"]),
            (vocab.TPU_NUM_REQUESTS_WAITING, s["num_requests_waiting"]),
            (vocab.TPU_HBM_KV_USAGE_PERC, s["hbm_kv_usage_perc"]),
            (vocab.TPU_PREFIX_CACHE_HIT_RATE, s["prefix_cache_hit_rate"]),
            # Prefix-cache truth for the router's fleet popularity view:
            # hit/query token counters + resident content-blocks gauge.
            (vocab.TPU_PREFIX_CACHE_HIT_TOKENS, s["prefix_cache_hit_tokens"]),
            (vocab.TPU_PREFIX_CACHE_QUERY_TOKENS,
             s["prefix_cache_query_tokens"]),
            (vocab.TPU_PREFIX_CACHE_BLOCKS, s["prefix_cache_blocks"]),
            (vocab.TPU_HOST_KV_USAGE_PERC, s["host_kv_usage_perc"]),
            (vocab.TPU_DUTY_CYCLE, s["duty_cycle"]),
            (vocab.TPU_DECODE_HOST_GAP_MS, s["decode_host_gap_ms"]),
            (vocab.TPU_LOADED_LORAS, s["loaded_loras"]),
            (vocab.TPU_TOTAL_PROMPT_TOKENS, s["total_prompt_tokens"]),
            (vocab.TPU_TOTAL_GENERATED_TOKENS, s["total_generated_tokens"]),
            (vocab.TPU_TOTAL_FINISHED_REQUESTS, s["total_finished"]),
            (vocab.TPU_NUM_PREEMPTIONS, s["num_preemptions"]),
            (vocab.TPU_REMOTE_PREFIX_BLOCKS_FETCHED,
             s["remote_prefix_blocks_fetched"]),
            (vocab.TPU_REMOTE_PREFIX_BLOCKS_EXPORTED,
             s["remote_prefix_blocks_exported"]),
            # Disaggregated serving: prime completions served and
            # decode-phase handoff prefetch outcomes (docs/engine.md).
            (vocab.TPU_DISAGG_PREFILL_PRIMES, s["disagg_prefill_primes"]),
            (vocab.TPU_DISAGG_HANDOFF_HITS, s["disagg_handoff_hits"]),
            (vocab.TPU_DISAGG_HANDOFF_MISSES, s["disagg_handoff_misses"]),
            (vocab.TPU_KV_PREFETCH_HIT, s["kv_prefetch_hit"]),
            (vocab.TPU_KV_PREFETCH_WASTE, s["kv_prefetch_waste"]),
            (vocab.TPU_KV_PREFETCH_INFLIGHT, s["kv_prefetch_inflight"]),
            (vocab.TPU_SPEC_TOKENS_DRAFTED, s["spec_tokens_drafted"]),
            (vocab.TPU_SPEC_TOKENS_ACCEPTED, s["spec_tokens_accepted"]),
            (vocab.TPU_PREFILL_CHUNK_TOKENS, s["prefill_chunk_tokens"]),
            (vocab.TPU_MIXED_WINDOW_CHUNK_TOKENS,
             s["mixed_window_chunk_tokens"]),
            # Overlapped window dispatch: transfer seconds issued while
            # the device was busy with an in-flight window (H2D chunk
            # staging for chained windows + D2H offload gathers).
            (vocab.TPU_WINDOW_TRANSFER_OVERLAP_SECONDS,
             s["window_transfer_overlap_seconds"]),
            # Overload protection + step-loop watchdog (docs/robustness.md).
            (vocab.TPU_ADMISSION_REJECTED, s["admission_rejected_total"]),
            (vocab.TPU_DEADLINE_EXPIRED, s["deadline_expired_total"]),
            (vocab.TPU_QUEUED_PROMPT_TOKENS, s["queued_prompt_tokens"]),
            (vocab.TPU_LAST_STEP_AGE, engine.last_step_age_s),
            # K-step decode windows: emitted-but-undeliverable tokens
            # (the labeled fallback family renders below).
            (vocab.TPU_MULTISTEP_WASTED_TOKENS, s["multistep_wasted_tokens"]),
            # Routed experts held by share: held experts with a row, over
            # routed layers and decode steps (the labeled family, pairs
            # by where they fell, renders below).
            (vocab.TPU_MOE_EXPERTS_TOUCHED, s["moe_experts_touched"]),
            (vocab.TPU_MOE_ZERO_ASSIGNED, s["moe_zero_assigned"]),
            # Several residual streams' mixing matrices: clamped entries
            # of entries seen, and the worst row sum's distance from 1.
            (vocab.TPU_MHC_CLAMPED, s["mhc_clamped"]),
            (vocab.TPU_MHC_ENTRIES, s["mhc_entries"]),
            (vocab.TPU_MHC_SINKHORN_ERR, s["mhc_sinkhorn_err"]),
            # Selective state-space layers: the largest |h| left in a slot
            # and the largest step size since boot.
            (vocab.TPU_SSM_STATE_ABSMAX, s["ssm_state_absmax"]),
            (vocab.TPU_SSM_DT_MAX, s["ssm_dt_max"]),
            # Delta-rule layers under a decay a head: the largest |S| left in
            # a slot and the largest beta since boot.
            (vocab.TPU_GDN_STATE_ABSMAX, s["gdn_state_absmax"]),
            (vocab.TPU_GDN_BETA_MAX, s["gdn_beta_max"]),
            # Programs that sample, and those that sort the vocabulary for
            # it: both from boot, so that their ratio reads 0, not nothing.
            (vocab.TPU_SAMPLE_DISPATCH, s["sample_dispatches"]),
            (vocab.TPU_SAMPLE_SORTED_DISPATCH,
             s["sample_sorted_dispatches"]),
            # Blocks of prefix chains hashed, and the part the step thread
            # hashed: from boot, so that their ratio reads 0, not nothing.
            (vocab.TPU_PREFIX_CHAIN_BLOCKS, s["prefix_chain_blocks"]),
            (vocab.TPU_PREFIX_CHAIN_STEP_BLOCKS,
             s["prefix_chain_step_blocks"]),
            # Dispatches built from host state (prefills, rebuilt windows)
            # and the host -> device transfers their builds started: from
            # boot, so that their ratio reads over any window.
            (vocab.TPU_STEP_BUILD_TRANSFERS, s["step_build_transfers"]),
            (vocab.TPU_STEP_UNCHAINED_DISPATCH,
             s["step_unchained_dispatches"]),
            # The state pool of a model with recurrent state (zero without).
            (vocab.TPU_STATE_SLOTS_IN_USE, s["state_slots_in_use"]),
            (vocab.TPU_STATE_SNAPSHOTS_TAKEN, s["state_snapshots_taken"]),
            (vocab.TPU_STATE_RESUMES, s["state_resumes"]),
            (vocab.TPU_STATE_RESUME_MISS, s["state_resume_misses"]),
            (vocab.TPU_STATE_RECOMPUTED_TOKENS,
             s["state_recomputed_tokens"]),
            # Where one DMA of the paged decode walk carries a group of
            # pages: groups held and groups that were one region of the
            # pool (zero where a page is a descriptor of its own).
            (vocab.TPU_PAGED_DECODE_GROUPS,
             s["paged_decode_groups"]["total"]),
            (vocab.TPU_PAGED_DECODE_GROUPS_COALESCED,
             s["paged_decode_groups"]["coalesced"]),
            # Slice-group lifecycle (0 on single-host engines): the group
            # epoch steps on every group restart, and drain relays count
            # follower-initiated slice-wide drains (docs/robustness.md).
            (vocab.TPU_LOCKSTEP_GROUP_EPOCH, engine.slice_epoch),
            (vocab.TPU_SLICE_DRAIN_RELAYS,
             monitor.drain_relays if monitor is not None else 0),
        ]
        # Latency histogram families (TTFT/ITL/e2e + step phases) ride the
        # same exposition; rendered even at zero observations so the
        # router scraper and dashboards see stable names.
        text = (
            vocab.render_prometheus(pairs)
            + vocab.render_labeled_counter(
                vocab.TPU_MULTISTEP_FALLBACK, "reason",
                {
                    **dict.fromkeys(vocab.TPU_MULTISTEP_FALLBACK_REASONS, 0),
                    **s["multistep_fallback"],
                },
            )
            + vocab.render_labeled_counter(
                vocab.TPU_PREFILL_ATTN_TILES, "state",
                s["prefill_attn_tiles"],
            )
            + vocab.render_labeled_counter(
                vocab.TPU_ATTN_POSITIONS, "kind", s["attn_positions"],
            )
            # Unchained dispatches launched behind a program in flight, and
            # the admissions that waited for a read-back, by why: every
            # series from boot, so that a share reads 0, not nothing.
            + vocab.render_labeled_counter(
                vocab.TPU_STEP_DISPATCH_BEHIND, "kind",
                s["step_dispatch_behind"],
            )
            + vocab.render_labeled_counter(
                vocab.TPU_STEP_DISPATCH_BEHIND_DECLINED, "reason",
                {
                    **dict.fromkeys(
                        vocab.TPU_STEP_DISPATCH_BEHIND_DECLINE_REASONS, 0),
                    **s["step_dispatch_behind_declined"],
                },
            )
            + vocab.render_labeled_counter(
                vocab.TPU_MOE_ASSIGNMENTS, "where", s["moe_assignments"],
            )
            # Fused speculative windows: outcome x drafter (one engine
            # runs at most one proposal source, so the live counts land
            # on the configured drafter's series; all six cells pre-seed
            # at zero so dashboards see a stable label set from boot),
            # plus the draft-forward time the model drafter spent.
            + vocab.render_labeled_counter2(
                vocab.TPU_SPEC_WINDOW_TOKENS, ("outcome", "drafter"),
                {
                    **{
                        (o, d): 0
                        for o in vocab.TPU_SPEC_WINDOW_OUTCOMES
                        for d in vocab.TPU_SPEC_WINDOW_DRAFTERS
                    },
                    **{
                        (o, s["spec_drafter"]): v
                        for o, v in s["spec_window_tokens"].items()
                        if s["spec_drafter"]
                    },
                },
            )
            + vocab.render_prometheus([
                (vocab.TPU_SPEC_DRAFT_FRACTION_SECONDS,
                 s["spec_draft_fraction_seconds"]),
            ])
            # Quantized KV tiering plane: bytes per tier boundary by
            # wire format, and snapshot serde versions on the kvserver
            # wire (pre-seeded with the closed label sets so scrapers
            # see stable series from boot).
            + vocab.render_labeled_counter2(
                vocab.TPU_KV_WIRE_BYTES, ("tier", "format"),
                {
                    **{
                        (t, f): 0
                        for t in vocab.TPU_KV_WIRE_TIERS
                        for f in vocab.TPU_KV_WIRE_FORMATS
                    },
                    **s["kv_wire_bytes"],
                },
            )
            + vocab.render_labeled_counter(
                vocab.TPU_KV_SNAPSHOT_FORMAT, "version",
                {
                    **dict.fromkeys(vocab.TPU_KV_SNAPSHOT_VERSIONS, 0),
                    **s["kv_snapshot_format"],
                },
            )
            # Slice-group member liveness (empty member set single-host;
            # the TYPE headers still render so the scrape contract is
            # stable across single- and multi-host engines).
            + vocab.render_labeled_gauge(
                vocab.TPU_LOCKSTEP_MEMBER_LAST_ACK, "member",
                {} if monitor is None else {
                    str(pid): age
                    for pid, age in monitor.member_ack_ages().items()
                },
            )
            + vocab.render_labeled_counter(
                vocab.TPU_LOCKSTEP_MEMBER_FAILURES, "reason",
                {
                    **dict.fromkeys(vocab.TPU_LOCKSTEP_FAILURE_REASONS, 0),
                    **({} if monitor is None else monitor.member_failures),
                },
            )
            # Packed multi-prompt windows: how many distinct prompts'
            # chunks rode each mixed K-step window (mass above bucket 1
            # is queue depth converted into device utilization).
            + render_histogram(
                vocab.TPU_MIXED_WINDOW_PROMPTS,
                engine.engine.mixed_window_prompts_hist,
            )
            # How long the decode windows were planned (scheduler._plan_window).
            + render_histogram(
                vocab.TPU_DECODE_WINDOW_STEPS,
                engine.engine.window_steps_hist,
            )
            # Encode lane: batched embed/rerank/score texts, the queue
            # the batcher is carrying, and per-batch size/latency
            # (docs/engine.md "The encode lane").
            + vocab.render_prometheus([
                (vocab.TPU_ENCODE_TEXTS, s["encode_texts_total"]),
                (vocab.TPU_ENCODE_QUEUE_DEPTH, s["encode_queue_depth"]),
            ])
            + render_histogram(
                vocab.TPU_ENCODE_BATCH_SIZE,
                engine.engine.encode_batch_size_hist,
            )
            + render_histogram(
                vocab.TPU_ENCODE_SECONDS,
                engine.engine.encode_seconds_hist,
            )
            # XLA compile events per executable shape key + the
            # distinct-shape gauge, and trace-ring byte-bound evictions
            # (obs/compile_tracker.py, obs/trace.py).
            + vocab.render_labeled_counter(
                vocab.TPU_COMPILE_SECONDS, "executable",
                s["compile_seconds"],
            )
            + vocab.render_prometheus([
                (vocab.TPU_COMPILED_SHAPES, s["compiled_shapes"]),
                (vocab.TPU_OBS_TRACE_DROPPED, s["obs_trace_dropped"]),
            ])
            + engine.engine.obs.render_metrics()
        )
        return web.Response(text=text)

    # -- request tracing debug surface (obs/) ------------------------------

    async def debug_requests(_req: web.Request) -> web.Response:
        """Ring buffer of completed request timelines, newest first."""
        return web.json_response(engine.engine.obs.debug_payload())

    async def debug_request(request: web.Request) -> web.Response:
        snap = engine.engine.obs.request_payload(
            request.match_info["request_id"]
        )
        if snap is None:
            return web.json_response(
                {"error": {"message": "unknown request id (expired from the "
                           "trace ring, or tracing is off)"}},
                status=404,
            )
        return web.json_response(snap)

    async def debug_windows(request: web.Request) -> web.Response:
        """Window flight-recorder ring, newest first (?seq= filters to
        windows one sequence rode)."""
        return web.json_response(
            engine.engine.obs.windows_payload(
                seq=request.query.get("seq") or None
            )
        )

    async def debug_compiles(_req: web.Request) -> web.Response:
        """XLA compile events per executable + warmup coverage report."""
        return web.json_response(engine.engine.compiles_payload())

    async def chat_completions(request: web.Request) -> web.StreamResponse:
        return await _serve_completion(request, chat=True)

    async def completions(request: web.Request) -> web.StreamResponse:
        return await _serve_completion(request, chat=False)

    async def _serve_completion(request: web.Request, chat: bool) -> web.StreamResponse:
        obs = engine.engine.obs
        # Where the request arrives, before its body is read: the start of
        # its trace and of tpu:ttft_seconds / tpu:e2e_latency_seconds
        # (Sequence.arrival_time).  None with tracing off: no stamp taken.
        received = time.time() if obs.enabled else None
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response(
                {"error": {"message": "invalid JSON", "type": "invalid_request_error"}},
                status=400,
            )
        tokenizer = engine.engine.tokenizer
        tools = body.get("tools") if chat else None
        tool_choice = body.get("tool_choice", "auto") if chat else "auto"
        forced_tool = None
        if chat and tool_choice not in ("auto", "none") and not tools:
            # OpenAI: tool_choice is only allowed when tools are given.
            return web.json_response(
                {"error": {"message": "'tool_choice' requires a non-empty "
                           "'tools' array", "type": "invalid_request_error"}},
                status=400,
            )
        if chat and tools:
            if not isinstance(tools, list) or not all(
                isinstance(t, dict) and t.get("type") == "function"
                and isinstance(t.get("function"), dict)
                and t["function"].get("name")
                for t in tools
            ):
                return web.json_response(
                    {"error": {"message": "'tools' must be a list of "
                               "{type: function, function: {name, ...}}",
                               "type": "invalid_request_error"}},
                    status=400,
                )
            if isinstance(tool_choice, dict):
                wanted = (tool_choice.get("function") or {}).get("name")
                match = [t for t in tools
                         if t["function"]["name"] == wanted]
                if not match:
                    return web.json_response(
                        {"error": {"message": f"tool_choice function "
                                   f"{wanted!r} not in tools",
                                   "type": "invalid_request_error"}},
                        status=400,
                    )
                forced_tool = match[0]
            elif tool_choice == "required":
                if len(tools) > 1:
                    # Model-driven tool selection needs per-family output
                    # parsers (out of scope); with several tools the
                    # caller must force one explicitly rather than get
                    # tools[0] silently.
                    return web.json_response(
                        {"error": {"message": "tool_choice 'required' with "
                                   "multiple tools is not supported; force "
                                   "one with {type: function, function: "
                                   "{name: ...}}",
                                   "type": "invalid_request_error"}},
                        status=400,
                    )
                forced_tool = tools[0]
            elif tool_choice not in ("auto", "none"):
                return web.json_response(
                    {"error": {"message": f"Unsupported tool_choice "
                               f"{tool_choice!r} (auto | none | required | "
                               "{type: function, ...})",
                               "type": "invalid_request_error"}},
                    status=400,
                )
        if chat:
            messages = list(body.get("messages") or [])
            if forced_tool is not None:
                # Steer content quality; the JSON guarantee comes from the
                # guided decoder below.  The instruction rides the LAST
                # USER turn — an appended system message would be rejected
                # by strict templates (gemma; role-alternation checks).
                steer = (
                    f"\n\n(Call the function "
                    f"{forced_tool['function']['name']} by replying with "
                    "ONLY its JSON arguments object.)"
                )
                if messages and messages[-1].get("role") == "user" and \
                        isinstance(messages[-1].get("content"), str):
                    messages[-1] = dict(
                        messages[-1],
                        content=messages[-1]["content"] + steer,
                    )
                else:
                    messages.append({"role": "user", "content": steer.strip()})
            prompt = tokenizer.apply_chat_template(
                messages,
                # 'none' means the model must not call tools: don't prompt
                # it with them.
                tools=tools if tool_choice != "none" else None,
            )
        else:
            prompt = body.get("prompt") or ""
            if isinstance(prompt, list):
                prompt = "\n".join(str(p) for p in prompt)
        try:
            params = _sampling_from_body(body, chat)
        except (TypeError, ValueError) as e:
            return web.json_response(
                {"error": {"message": str(e),
                           "type": "invalid_request_error"}},
                status=400,
            )
        stream = bool(body.get("stream", False))
        stream_options = body.get("stream_options")
        if stream_options is not None:
            # OpenAI: stream_options is only valid with stream=true.
            if not isinstance(stream_options, dict):
                return web.json_response(
                    {"error": {"message": "'stream_options' must be an "
                               "object", "type": "invalid_request_error"}},
                    status=400,
                )
            if not stream:
                return web.json_response(
                    {"error": {"message": "'stream_options' is only "
                               "allowed when 'stream' is true",
                               "type": "invalid_request_error"}},
                    status=400,
                )
        include_usage = bool((stream_options or {}).get("include_usage"))
        if params.echo and stream:
            return web.json_response(
                {"error": {"message": "'echo' is not supported with "
                           "streaming", "type": "invalid_request_error"}},
                status=400,
            )
        if forced_tool is not None:
            if stream:
                return web.json_response(
                    {"error": {"message": "forced tool_choice is not "
                               "supported with streaming",
                               "type": "invalid_request_error"}},
                    status=400,
                )
            # The arguments object is produced under the JSON guarantee —
            # and when the tool's parameters schema compiles under the
            # guided_schema subset, under THAT schema (strict tool calls:
            # correct keys/types by construction, not just valid JSON).
            params.response_format = "json_object"
            tool_schema = (forced_tool.get("function") or {}).get(
                "parameters"
            )
            if isinstance(tool_schema, dict):
                from production_stack_tpu.engine.guided_schema import (
                    SchemaCompileError,
                    compile_schema_cached,
                )

                try:
                    compile_schema_cached(tool_schema)
                    params.response_format = {
                        "type": "json_schema", "schema": tool_schema,
                    }
                except SchemaCompileError:
                    pass  # outside the subset: generic JSON guarantee
            params.ignore_eos = False
        request_id = request.headers.get("x-request-id") or f"cmpl-{uuid.uuid4().hex[:16]}"
        created = int(time.time())
        model_name = body.get("model", served_model)
        # "<base>:<adapter>" selects a loaded LoRA adapter; validate BEFORE
        # any stream starts so unknown adapters 400 cleanly.  Only active
        # on LoRA-enabled engines: otherwise ':' stays an opaque character
        # in the model id (e.g. ollama-style names) as before.
        adapter = None
        if ":" in model_name and engine.engine.lora_registry is not None:
            _, adapter = model_name.split(":", 1)
            try:
                engine.engine.lora_registry.slot_of(adapter)
            except ValueError as e:
                return web.json_response(
                    {"error": {"message": str(e),
                               "type": "invalid_request_error", "code": 404}},
                    status=400,
                )
        object_name = "chat.completion.chunk" if chat else "text_completion"
        prompt_token_ids = tokenizer.encode(prompt)

        # Reject over-long prompts BEFORE the stream starts: once the SSE
        # response is prepared, a scheduler-side ValueError can only
        # truncate the chunked body (clients see ClientPayloadError, not a
        # clean 400).
        max_len = engine.engine.config.scheduler.max_model_len
        if len(prompt_token_ids) >= max_len:
            return web.json_response(
                {
                    "error": {
                        "message": (
                            f"This model's maximum context length is "
                            f"{max_len} tokens, but the prompt is "
                            f"{len(prompt_token_ids)} tokens long"
                        ),
                        "type": "invalid_request_error",
                        "code": "context_length_exceeded",
                    }
                },
                status=400,
            )

        # n > 1: fan out one engine request per choice (OpenAI `n`).  Each
        # choice gets a distinct seed when one was supplied; without one
        # the engine's per-slot seeding already diversifies sampled runs.
        n_choices = body.get("n", 1)
        if n_choices is None:
            n_choices = 1
        if not isinstance(n_choices, int) or isinstance(n_choices, bool):
            # int() would silently truncate 2.9 and accept True.
            return web.json_response(
                {"error": {"message": f"n must be an integer, got "
                           f"{body.get('n')!r}",
                           "type": "invalid_request_error"}},
                status=400,
            )
        if not 1 <= n_choices <= 16:
            return web.json_response(
                {"error": {"message": f"n must be in [1, 16], got {n_choices}",
                           "type": "invalid_request_error"}},
                status=400,
            )

        # -- overload protection (docs/robustness.md) ----------------------
        now = time.time()
        try:
            deadline = parse_deadline(request.headers, body, now)
        except ValueError as e:
            return web.json_response(
                {"error": {"message": str(e),
                           "type": "invalid_request_error"}},
                status=400,
            )
        # Bounded admission: reject early and cheaply at the edge with a
        # structured 429 instead of queueing unboundedly and timing out
        # expensively in the middle.
        rejection = engine.check_admission(
            n_choices, n_choices * len(prompt_token_ids)
        )
        if rejection is not None:
            engine.engine.admission_rejected += 1
            return web.json_response(
                {
                    "error": {
                        "message": (
                            "engine overloaded: "
                            f"{rejection.queued_requests} requests "
                            f"({rejection.queued_tokens} prompt tokens) "
                            "already queued; retry after "
                            f"{rejection.retry_after_s}s"
                        ),
                        "type": "overloaded",
                        "code": 429,
                        "detail": dataclasses.asdict(rejection),
                    }
                },
                status=429,
                headers={"Retry-After": str(rejection.retry_after_s)},
            )

        def _shed_deadline(why: str, type_: str) -> web.Response:
            # Event-loop-side counter: the step thread owns
            # deadline_expired; sharing one attribute across threads
            # would lose increments (non-atomic +=).
            engine.engine.deadline_expired_admission += 1
            return web.json_response(
                {"error": {"message": why, "type": type_, "code": 504}},
                status=504,
            )

        if deadline is not None:
            params.deadline = deadline
            if now >= deadline:
                return _shed_deadline(
                    "request deadline already expired at admission",
                    "deadline_expired",
                )
            # "Would miss the deadline before first token -> shed now":
            # conservative wait estimate from the observed median TTFT
            # scaled by queue depth in batch units.  Only meaningful once
            # the histogram has real observations; tracing-off engines
            # skip the estimate and rely on the queued-expiry sweep.
            ttft_hist = engine.engine.obs.request_hists["ttft"]
            if ttft_hist.count >= 8:
                sched_cfg = engine.engine.config.scheduler
                est_wait = ttft_hist.quantile(0.5) * (
                    1.0
                    + engine.engine.scheduler.num_waiting
                    / max(1, sched_cfg.max_num_seqs)
                )
                if now + est_wait > deadline:
                    return _shed_deadline(
                        f"deadline unmeetable: estimated {est_wait:.2f}s to "
                        "first token exceeds the remaining budget",
                        "deadline_unmeetable",
                    )

        # -- disaggregated prefill phase (docs/engine.md) ------------------
        # The router's disagg policy primes a prefill-pool engine with
        # this marker: run the prefill (admission control and deadlines
        # above already applied), EAGERLY flush the prefix-chain export
        # so the shared store holds it before we answer — the decode
        # side's prefetch must never race the export writer — and return
        # a handoff token instead of generating.
        if request.headers.get("x-disagg-phase") == "prefill":
            prime_params = dataclasses.replace(
                params, max_tokens=0, logprobs=False, top_logprobs=0,
                echo=False,
            )
            gen = engine.generate(
                prompt_token_ids=prompt_token_ids,
                sampling_params=prime_params,
                request_id=request_id,
                adapter=adapter,
                received=received,
            )
            try:
                async for _event in gen:
                    pass
            except DeadlineExceeded as e:
                engine.engine.deadline_expired_admission += 1
                return web.json_response(
                    {"error": {"message": str(e), "type": "deadline_expired",
                               "code": 504}},
                    status=504,
                )
            # Eager (not off-step) export: the gather ran on the step
            # thread at final prefill; this blocks (off the event loop)
            # until the px-export writer has MPUT the chain.
            await asyncio.to_thread(
                engine.engine.flush_prefix_exports, 10.0
            )
            handoff = await asyncio.to_thread(
                engine.engine.handoff_token,
                prompt_token_ids,
                engine.engine.cache_ns_of(adapter),
            )
            engine.engine.disagg_prefill_primes += 1
            return web.json_response(
                {
                    "id": request_id,
                    "object": "disagg.prefill",
                    "created": created,
                    "model": model_name,
                    "disagg": {"handoff": handoff},
                    "usage": {
                        "prompt_tokens": len(prompt_token_ids),
                        "completion_tokens": 0,
                        "total_tokens": len(prompt_token_ids),
                    },
                },
                headers={"X-Request-Id": request_id},
            )

        # -- disaggregated decode phase -------------------------------------
        # A handoff-tagged generation waits (bounded, off the event loop
        # and off the step thread) for the prefetched chain to land in
        # the prefix cache, so its first schedule() serves the whole
        # prompt from cache.  Any other outcome admits normally — the
        # engine recomputes the prefill locally (in-place fused
        # fallback), never fails the request.
        disagg_prefix_outcome: Optional[str] = None
        handoff_hdr = request.headers.get("x-disagg-handoff")
        if handoff_hdr:
            try:
                handoff = json.loads(handoff_hdr)
            except json.JSONDecodeError:
                handoff = None
            disagg_prefix_outcome = "disabled"
            if isinstance(handoff, dict):
                wait_s = engine.engine.config.cache.disagg_handoff_wait_s
                if deadline is not None:
                    # Leave headroom for the generation itself.
                    wait_s = min(
                        wait_s, max(0.0, deadline - time.time() - 0.05)
                    )
                disagg_prefix_outcome = await asyncio.to_thread(
                    engine.engine.wait_handoff_prefix,
                    prompt_token_ids,
                    engine.engine.cache_ns_of(adapter),
                    handoff,
                    wait_s,
                )
            if disagg_prefix_outcome == "hit":
                engine.engine.disagg_handoff_hits += 1
            else:
                engine.engine.disagg_handoff_misses += 1

        if obs.enabled:
            # Start the trace only AFTER every validation 400 above: a
            # rejected request must not leave a permanently-active trace
            # (the bounded active map would evict legitimate in-flight
            # timelines under a stream of rejects); it opens AT
            # ``received`` all the same.  The router-propagated W3C
            # context joins this timeline to the router's: its trace id,
            # and the router's span as this root's parent.  With n>1
            # the trace follows the PRIMARY choice (choice 0 shares the
            # request id); sibling choices' engine lifecycles are not
            # traced — their token counts still land in the histograms.
            trace_id, parent_span_id = parse_traceparent_ids(
                request.headers.get("traceparent"))
            obs.start_request(
                request_id, trace_id, received=received,
                upstream_start=parse_request_start(
                    request.headers.get("x-request-start"), received),
                parent_span_id=parent_span_id,
                model=model_name, path=request.path, stream=stream,
                n=n_choices,
            )

        def choice_params(i: int) -> SamplingParams:
            if params.seed is None or i == 0:
                return params if i == 0 else dataclasses.replace(params)
            return dataclasses.replace(params, seed=params.seed + i)

        choice_ids = [
            request_id if i == 0 else f"{request_id}-c{i}"
            for i in range(n_choices)
        ]
        gens = [
            engine.generate(
                prompt_token_ids=prompt_token_ids,
                sampling_params=choice_params(i),
                request_id=choice_ids[i],
                adapter=adapter,
                received=received,
            )
            for i in range(n_choices)
        ]
        checkers = [
            StopChecker(tokenizer, params.stop) for _ in range(n_choices)
        ]
        # Accumulated host detokenize time across all choices, reported to
        # the obs layer when the request ends (the per-step phase the
        # engine core cannot see — it happens here in the server).  With
        # tracing off the untimed push keeps the pre-tracing hot path:
        # zero perf_counter calls per token.
        detok_s = [0.0]
        if obs.enabled:
            def timed_push(checker: StopChecker, token_id: int):
                t0 = time.perf_counter()
                out = checker.push(token_id)
                detok_s[0] += time.perf_counter() - t0
                return out
        else:
            def timed_push(checker: StopChecker, token_id: int):
                return checker.push(token_id)

        # Running character offset per choice for the legacy completions
        # logprobs text_offset array (consumed by e.g. lm-evaluation-harness).
        stream_offsets = [0] * n_choices

        def _logprob_entry(event) -> dict:
            """One token's OpenAI chat-style logprobs entry."""
            return {
                "token": (
                    tokenizer.decode([event.token_id])
                    if event.token_id >= 0 else ""
                ),
                "logprob": event.logprob,
                "top_logprobs": [
                    {"token": tokenizer.decode([tid]), "logprob": lp}
                    for tid, lp in (event.top_logprobs or [])
                ],
            }

        def chunk_payload(delta_text: str, finish_reason, first: bool,
                          event=None, index: int = 0):
            if chat:
                delta = {}
                if first:
                    delta["role"] = "assistant"
                if delta_text:
                    delta["content"] = delta_text
                choice = {"index": index, "delta": delta,
                          "finish_reason": finish_reason}
                if params.logprobs and event is not None:
                    choice["logprobs"] = {"content": [_logprob_entry(event)]}
            else:
                choice = {"index": index, "text": delta_text,
                          "finish_reason": finish_reason}
                if params.logprobs and event is not None:
                    tok_text = (
                        tokenizer.decode([event.token_id])
                        if event.token_id >= 0 else ""
                    )
                    choice["logprobs"] = {
                        "tokens": [tok_text],
                        "token_logprobs": [event.logprob],
                        "top_logprobs": [
                            {
                                tokenizer.decode([tid]): lp
                                for tid, lp in (event.top_logprobs or [])
                            }
                        ],
                        "text_offset": [stream_offsets[index]],
                    }
                    stream_offsets[index] += len(tok_text)
            return {
                "id": request_id,
                "object": object_name,
                "created": created,
                "model": model_name,
                "choices": [choice],
            }

        if stream:
            stream_headers = {
                "Content-Type": "text/event-stream",
                "Cache-Control": "no-cache",
                "X-Request-Id": request_id,
            }
            if disagg_prefix_outcome is not None:
                stream_headers["X-Disagg-Prefix"] = disagg_prefix_outcome
            response = web.StreamResponse(headers=stream_headers)
            await response.prepare(request)

            # Merge the n per-choice event streams through one queue so
            # chunks interleave as tokens arrive (each chunk carries its
            # choice index).
            queue: asyncio.Queue = asyncio.Queue()

            async def pump(i: int, g):
                try:
                    async for ev in g:
                        await queue.put((i, ev, None))
                    await queue.put((i, None, None))
                except Exception as e:  # surfaced on the write loop
                    await queue.put((i, None, e))

            pumps = [
                asyncio.create_task(pump(i, g)) for i, g in enumerate(gens)
            ]
            first = [True] * n_choices
            live = [True] * n_choices
            retired = [False] * n_choices  # manually removed from `remaining`
            total_out = 0
            shed_on_deadline = False
            # The compile taint rides the FIRST data chunk (headers are
            # already on the wire at prepare(), before TTFT is known):
            # the router proxy sniffs it to keep a compile-excluded TTFT
            # window without parsing every chunk.
            compile_stamped = False
            # The stream's first write has not returned yet (obs only).
            first_write_due = obs.enabled
            try:
                remaining = n_choices
                while remaining:
                    i, event, error = await queue.get()
                    if error is not None:
                        if isinstance(error, DeadlineExceeded):
                            # Expired while queued: the stream is already
                            # prepared, so surface a structured SSE error
                            # event (no [DONE] — the stream did not
                            # complete) instead of a truncated body.
                            shed_on_deadline = True
                            await response.write(
                                f"data: {json.dumps({'error': {'message': str(error), 'type': 'deadline_expired', 'code': 504}})}\n\n".encode()
                            )
                            break
                        raise error
                    if event is None:
                        # A choice retired on a stop match was already
                        # deducted; its pump may still deliver a stale
                        # sentinel (it can enqueue finished+sentinel before
                        # the writer handles the stop token) — counting it
                        # again would end the stream under live siblings.
                        if not retired[i]:
                            remaining -= 1
                        continue
                    if not live[i]:
                        continue  # post-stop events of an aborting choice
                    checker = checkers[i]
                    delta, stopped = timed_push(checker, event.token_id)
                    if event.finished and not stopped:
                        # Flush any partial-stop-suffix holdback so the
                        # client gets the full tail.
                        delta += checker.flush()
                    if delta or first[i] or params.logprobs:
                        # A stop-triggering token is trimmed from the text,
                        # so it must not contribute a logprobs entry either
                        # (OpenAI: logprobs.content aligns with content).
                        payload = chunk_payload(
                            delta, None, first[i],
                            # The -1 sentinel (stop_token_ids) is equally
                            # absent from content, so no entry for it.
                            event=(
                                None if stopped or event.token_id < 0
                                else event
                            ),
                            index=i,
                        )
                        if not compile_stamped:
                            compile_stamped = True
                            if obs.enabled and obs.compile_tainted(
                                request_id
                            ):
                                payload["compile"] = True
                        await response.write(
                            f"data: {json.dumps(payload)}\n\n".encode()
                        )
                        if first_write_due:
                            first_write_due = False
                            obs.on_first_written(request_id, time.time())
                        first[i] = False
                    if stopped or event.finished:
                        if stopped or event.finish_reason == FinishReason.STOP:
                            reason = "stop"
                        elif event.finish_reason == FinishReason.GUIDED_INVALID:
                            reason = "guided_invalid"
                        else:
                            reason = "length"
                        if stopped and not event.finished:
                            # Abort emits no further events, so this pump
                            # will never send its sentinel: retire the
                            # choice here (cancelling the pump runs the
                            # generator's finally, which aborts in-engine).
                            pumps[i].cancel()
                            retired[i] = True
                            remaining -= 1
                        live[i] = False
                        total_out += event.num_output_tokens
                        final = chunk_payload("", reason, first[i], index=i)
                        await response.write(
                            f"data: {json.dumps(final)}\n\n".encode()
                        )
                if include_usage and not shed_on_deadline:
                    # OpenAI stream_options.include_usage: one extra
                    # final chunk with empty choices carrying the usage
                    # (and no usage anywhere otherwise).
                    usage_chunk = {
                        "id": request_id,
                        "object": object_name,
                        "created": created,
                        "model": model_name,
                        "choices": [],
                        "usage": {
                            "prompt_tokens": len(prompt_token_ids),
                            "completion_tokens": total_out,
                            "total_tokens": len(prompt_token_ids) + total_out,
                        },
                    }
                    await response.write(
                        f"data: {json.dumps(usage_chunk)}\n\n".encode()
                    )
                if not shed_on_deadline:
                    await response.write(b"data: [DONE]\n\n")
                await response.write_eof()
            except ConnectionResetError:
                pass  # cleanup below aborts every live choice
            finally:
                # Cancelling a pump closes its generator, whose finally
                # aborts the engine request if it hasn't finished — so a
                # disconnect or a mid-stream error on one choice never
                # leaves sibling choices decoding for nobody.
                for task in pumps:
                    task.cancel()
                if obs.enabled:
                    obs.record_detokenize(request_id, detok_s[0])
            return response

        # Non-streaming: drain all choices CONCURRENTLY (async generators
        # are lazy — a sequential for-loop would only submit choice i+1's
        # engine request after choice i finished, serializing what the
        # engine would otherwise batch).
        async def drain(i: int, gen):
            checker = checkers[i]
            text_parts = []
            logprob_entries = []
            prompt_lp = None
            finish_reason = "length"
            out_tokens = 0
            async for event in gen:
                if event.prompt_logprobs is not None:
                    prompt_lp = event.prompt_logprobs
                delta, stopped = timed_push(checker, event.token_id)
                text_parts.append(delta)
                if params.logprobs and event.token_id >= 0:
                    # The stop_token_ids sentinel contributes no text, so
                    # it must not contribute a logprobs entry either.
                    logprob_entries.append(event)
                if stopped:
                    finish_reason = "stop"
                    out_tokens = event.num_output_tokens
                    if not event.finished:
                        await engine.abort(choice_ids[i])
                    break
                if event.finished:
                    text_parts.append(checker.flush())
                    out_tokens = event.num_output_tokens
                    if event.finish_reason == FinishReason.STOP:
                        finish_reason = "stop"
                    elif event.finish_reason == FinishReason.GUIDED_INVALID:
                        finish_reason = "guided_invalid"
                    else:
                        finish_reason = "length"
                    break
            return ("".join(text_parts), logprob_entries, finish_reason,
                    out_tokens, prompt_lp)

        drain_tasks = [
            asyncio.create_task(drain(i, g)) for i, g in enumerate(gens)
        ]
        try:
            drained = await asyncio.gather(*drain_tasks)
        except DeadlineExceeded as e:
            # One choice expired while queued (the engine already released
            # its state).  The deadline is a WHOLE-REQUEST contract: a
            # non-streaming response must carry all n choices together,
            # and past the deadline nobody is waiting for it — so cancel
            # the sibling drains too (each cancellation closes its
            # generator, whose finally aborts the choice in-engine, even
            # ones already running) and shed with a clean 504.  The
            # engine-side "running sequences are exempt" rule is about
            # the SWEEP not killing independent streaming requests;
            # sibling choices of a dead request are not independent.
            for t in drain_tasks:
                t.cancel()
            if obs.enabled:
                obs.on_abort(request_id)
            return web.json_response(
                {"error": {"message": str(e), "type": "deadline_expired",
                           "code": 504}},
                status=504,
            )
        if obs.enabled:
            obs.record_detokenize(request_id, detok_s[0])
        choices = []
        total_out = 0
        for i, (text, logprob_entries, finish_reason, out_tokens,
                prompt_lp) in enumerate(drained):
            checker = checkers[i]
            total_out += out_tokens
            if params.logprobs:
                # Align with the post-stop-trim content: tokens consumed by
                # a (possibly multi-token) stop string contribute no
                # entries.  (Streaming can't retract already-sent entries;
                # this exact alignment is the non-streaming guarantee.)
                logprob_entries = logprob_entries[
                    : checker.aligned_token_count()
                ]
            if chat:
                tool_args_ok = False
                if forced_tool is not None:
                    try:
                        json.loads(text)
                        tool_args_ok = True
                    except (json.JSONDecodeError, TypeError):
                        # Budget too small for the guided close: surface
                        # the truncation (finish_reason from drain, plain
                        # content) instead of claiming a tool call with
                        # unparseable arguments.
                        tool_args_ok = False
                if forced_tool is not None and tool_args_ok:
                    # OpenAI tool-calling shape: arguments carry the
                    # guided-JSON output verbatim.
                    message = {
                        "role": "assistant",
                        "content": None,
                        "tool_calls": [{
                            "id": f"call_{uuid.uuid4().hex[:20]}",
                            "type": "function",
                            "function": {
                                "name": forced_tool["function"]["name"],
                                "arguments": text,
                            },
                        }],
                    }
                    finish_reason = "tool_calls"
                else:
                    message = {"role": "assistant", "content": text}
                choice = {
                    "index": i,
                    "message": message,
                    "finish_reason": finish_reason,
                }
                if params.logprobs:
                    choice["logprobs"] = {
                        "content": [_logprob_entry(e) for e in logprob_entries]
                    }
            else:
                out_text = (prompt + text) if params.echo else text
                choice = {"index": i, "text": out_text,
                          "finish_reason": finish_reason}
                if params.logprobs:
                    token_texts = [
                        tokenizer.decode([e.token_id]) if e.token_id >= 0 else ""
                        for e in logprob_entries
                    ]
                    token_lps = [e.logprob for e in logprob_entries]
                    tops = [
                        {
                            tokenizer.decode([tid]): lp
                            for tid, lp in (e.top_logprobs or [])
                        }
                        for e in logprob_entries
                    ]
                    if params.echo and prompt_lp is not None:
                        # Prepend the prompt's per-position entries (echo
                        # + logprobs: the lm-eval loglikelihood surface;
                        # position 0 has null logprob per OpenAI).
                        p_texts = [
                            tokenizer.decode([tid])
                            for tid in prompt_token_ids[: len(prompt_lp)]
                        ]
                        p_lps = [entry[0] for entry in prompt_lp]
                        p_tops = [
                            {
                                tokenizer.decode([tid]): lp
                                for tid, lp in (entry[1] or [])
                            } if entry[1] is not None else None
                            for entry in prompt_lp
                        ]
                        token_texts = p_texts + token_texts
                        token_lps = p_lps + token_lps
                        tops = p_tops + tops
                    offsets, pos = [], 0
                    for t in token_texts:
                        offsets.append(pos)
                        pos += len(t)
                    choice["logprobs"] = {
                        "tokens": token_texts,
                        "token_logprobs": token_lps,
                        "top_logprobs": tops,
                        "text_offset": offsets,
                    }
            choices.append(choice)
        obj = "chat.completion" if chat else "text_completion"
        n_out = total_out
        final_headers = {"X-Request-Id": request_id}
        if disagg_prefix_outcome is not None:
            final_headers["X-Disagg-Prefix"] = disagg_prefix_outcome
        final_body = {
            "id": request_id,
            "object": obj,
            "created": created,
            "model": model_name,
            "choices": choices,
            "usage": {
                "prompt_tokens": len(prompt_token_ids),
                "completion_tokens": n_out,
                "total_tokens": len(prompt_token_ids) + n_out,
            },
        }
        if obs.enabled and obs.compile_tainted(request_id):
            # An XLA compile fired inside this request's dispatches: its
            # latency is cold-start, not steady state.  The router's
            # stats monitor reads this to keep a compile-excluded TTFT
            # window (same marker the streaming path puts in the first
            # SSE chunk).
            final_body["compile"] = True
        return web.json_response(final_body, headers=final_headers)

    async def embeddings(request: web.Request) -> web.Response:
        """OpenAI /v1/embeddings: normalized mean-pooled final hidden
        states (llama.encode).  The engine the router proxies this path to
        must actually serve it."""
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response(
                {"error": {"message": "invalid JSON",
                           "type": "invalid_request_error"}},
                status=400,
            )
        raw_input = body.get("input")
        if isinstance(raw_input, str):
            inputs = [raw_input]
        elif isinstance(raw_input, list) and all(
            isinstance(x, str) for x in raw_input
        ):
            inputs = raw_input
        else:
            return web.json_response(
                {"error": {"message": "'input' must be a string or list of "
                           "strings", "type": "invalid_request_error"}},
                status=400,
            )
        if not 1 <= len(inputs) <= 128:
            # Each item is a full device forward; an unbounded list would
            # let one request starve completions traffic.
            return web.json_response(
                {"error": {"message": f"'input' must contain 1-128 items, "
                           f"got {len(inputs)}",
                           "type": "invalid_request_error"}},
                status=400,
            )
        err, token_lists, deadline = _encode_admission(request, body, inputs)
        if err is not None:
            return err
        try:
            vectors, token_counts = await _embed_texts(
                inputs, token_lists=token_lists, deadline=deadline
            )
            total_tokens = sum(token_counts)
        except DeadlineExceeded as e:
            return web.json_response(
                {"error": {"message": str(e), "type": "deadline_expired",
                           "code": 504}},
                status=504,
            )
        except ValueError as e:
            # Over-long input, or a model without an encode path.
            return web.json_response(
                {"error": {"message": str(e),
                           "type": "invalid_request_error"}},
                status=400,
            )
        data = [
            {
                "object": "embedding",
                "index": i,
                "embedding": [float(v) for v in vector],
            }
            for i, vector in enumerate(vectors)
        ]
        return web.json_response({
            "object": "list",
            "data": data,
            "model": body.get("model", served_model),
            "usage": {"prompt_tokens": total_tokens,
                      "total_tokens": total_tokens},
        })

    def _encode_admission(request, body, texts):
        """Shared PR-5 overload protection for the encode surface
        (embeddings / rerank / score), applied BEFORE any device work is
        queued: deadline parse (400 on malformed), bounded admission
        (structured 429 + Retry-After against the encode-queue caps),
        expired-deadline shed (504).  Returns (error_response,
        token_lists, deadline); the token lists are reused by the embed
        call so each text tokenizes once."""
        tokenizer = engine.engine.tokenizer
        token_lists = [tokenizer.encode(text) for text in texts]
        now = time.time()
        try:
            deadline = parse_deadline(request.headers, body, now)
        except ValueError as e:
            return (
                web.json_response(
                    {"error": {"message": str(e),
                               "type": "invalid_request_error"}},
                    status=400,
                ),
                None, None,
            )
        rejection = engine.check_encode_admission(
            len(token_lists), sum(len(ids) for ids in token_lists)
        )
        if rejection is not None:
            engine.engine.admission_rejected += 1
            return (
                web.json_response(
                    {
                        "error": {
                            "message": (
                                "engine overloaded: "
                                f"{rejection.queued_requests} texts "
                                f"({rejection.queued_tokens} prompt tokens) "
                                "already queued on the encode lane; retry "
                                f"after {rejection.retry_after_s}s"
                            ),
                            "type": "overloaded",
                            "code": 429,
                            "detail": dataclasses.asdict(rejection),
                        }
                    },
                    status=429,
                    headers={"Retry-After": str(rejection.retry_after_s)},
                ),
                None, None,
            )
        if deadline is not None and now >= deadline:
            # Event-loop-side counter (the step thread owns
            # deadline_expired), same split as the completions path.
            engine.engine.deadline_expired_admission += 1
            return (
                web.json_response(
                    {"error": {"message": (
                        "request deadline already expired at admission"
                    ), "type": "deadline_expired", "code": 504}},
                    status=504,
                ),
                None, None,
            )
        return None, token_lists, deadline

    async def _embed_texts(texts, token_lists=None, deadline=None):
        """Embed a list of strings via the batched encode lane: texts
        queue on the EncodeBatcher and the STEP THREAD runs them as
        [B, T]-bucketed encode batches at window boundaries
        (engine/server/encode_batcher.py) — this coroutine never touches
        the device.  --no-encode-lane restores the legacy serial
        per-text path.  Returns (unit vectors, per-text token counts).

        Raises ValueError for over-long inputs or models without an
        encode path — callers map that to a 400 — and DeadlineExceeded
        when a queued text's deadline expired before dispatch (504).
        """
        tokenizer = engine.engine.tokenizer
        if token_lists is None:
            token_lists = [tokenizer.encode(text) for text in texts]
        vectors = await engine.embed_batch(token_lists, deadline=deadline)
        return vectors, [len(ids) for ids in token_lists]

    def _dot(a, b) -> float:
        return float(np.dot(a, b))

    async def rerank(request: web.Request) -> web.Response:
        """Jina/Cohere-style rerank (the contract the reference router
        proxies at /v1/rerank and /rerank): cosine relevance of each
        document to the query via the encode path, sorted descending."""
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response(
                {"error": {"message": "invalid JSON",
                           "type": "invalid_request_error"}},
                status=400,
            )
        query = body.get("query")
        documents = body.get("documents")
        if not isinstance(query, str) or not isinstance(documents, list) or not all(
            isinstance(d, str) for d in documents
        ):
            return web.json_response(
                {"error": {"message": "'query' must be a string and "
                           "'documents' a list of strings",
                           "type": "invalid_request_error"}},
                status=400,
            )
        if not 1 <= len(documents) <= 128:
            return web.json_response(
                {"error": {"message": f"'documents' must contain 1-128 items, "
                           f"got {len(documents)}",
                           "type": "invalid_request_error"}},
                status=400,
            )
        top_n = body.get("top_n")
        if top_n is not None and (
            not isinstance(top_n, int) or isinstance(top_n, bool) or top_n < 1
        ):
            # Validate BEFORE the device forwards below, like every other
            # parameter on this endpoint.
            return web.json_response(
                {"error": {"message": "'top_n' must be a positive integer",
                           "type": "invalid_request_error"}},
                status=400,
            )
        texts = [query] + documents
        err, token_lists, deadline = _encode_admission(request, body, texts)
        if err is not None:
            return err
        try:
            vectors, token_counts = await _embed_texts(
                texts, token_lists=token_lists, deadline=deadline
            )
            total_tokens = sum(token_counts)
        except DeadlineExceeded as e:
            return web.json_response(
                {"error": {"message": str(e), "type": "deadline_expired",
                           "code": 504}},
                status=504,
            )
        except ValueError as e:
            return web.json_response(
                {"error": {"message": str(e), "type": "invalid_request_error"}},
                status=400,
            )
        qvec, dvecs = vectors[0], vectors[1:]
        results = [
            {"index": i, "document": {"text": documents[i]},
             "relevance_score": _dot(qvec, dvec)}
            for i, dvec in enumerate(dvecs)
        ]
        results.sort(key=lambda r: r["relevance_score"], reverse=True)
        if top_n is not None:
            results = results[:top_n]
        if not body.get("return_documents", True):
            for r in results:
                r.pop("document")
        return web.json_response({
            "id": f"rerank-{uuid.uuid4().hex[:16]}",
            "model": body.get("model", served_model),
            "usage": {"prompt_tokens": total_tokens,
                      "total_tokens": total_tokens},
            "results": results,
        })

    async def score(request: web.Request) -> web.Response:
        """vLLM-style /score: similarity of text_1 x text_2 pairs.  A single
        text_1 broadcasts over the text_2 list; equal-length lists pair
        elementwise."""
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response(
                {"error": {"message": "invalid JSON",
                           "type": "invalid_request_error"}},
                status=400,
            )

        def as_list(v):
            if isinstance(v, str):
                return [v]
            if isinstance(v, list) and all(isinstance(x, str) for x in v):
                return v
            return None

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
        if len(t1) != len(t2):
            return web.json_response(
                {"error": {"message": f"'text_1' ({len(t1)}) and 'text_2' "
                           f"({len(t2)}) must broadcast (1-to-N or equal "
                           "length)", "type": "invalid_request_error"}},
                status=400,
            )
        if len(t2) > 128:
            return web.json_response(
                {"error": {"message": f"at most 128 pairs, got {len(t2)}",
                           "type": "invalid_request_error"}},
                status=400,
            )
        # Embed each distinct text once: a broadcast text_1 would
        # otherwise re-run the device forward per pair.
        distinct = list(dict.fromkeys(t1 + t2))
        err, token_lists, deadline = _encode_admission(request, body, distinct)
        if err is not None:
            return err
        try:
            vectors, token_counts = await _embed_texts(
                distinct, token_lists=token_lists, deadline=deadline
            )
        except DeadlineExceeded as e:
            return web.json_response(
                {"error": {"message": str(e), "type": "deadline_expired",
                           "code": 504}},
                status=504,
            )
        except ValueError as e:
            return web.json_response(
                {"error": {"message": str(e), "type": "invalid_request_error"}},
                status=400,
            )
        by_text = dict(zip(distinct, vectors))
        tokens_by_text = dict(zip(distinct, token_counts))
        # Usage reflects the logical pairs (per-pair accounting), even
        # though broadcast texts are embedded once.
        total_tokens = sum(
            tokens_by_text[a] + tokens_by_text[b] for a, b in zip(t1, t2)
        )
        data = [
            {"object": "score", "index": i,
             "score": _dot(by_text[a], by_text[b])}
            for i, (a, b) in enumerate(zip(t1, t2))
        ]
        return web.json_response({
            "id": f"score-{uuid.uuid4().hex[:16]}",
            "object": "list",
            "model": body.get("model", served_model),
            "usage": {"prompt_tokens": total_tokens,
                      "total_tokens": total_tokens},
            "data": data,
        })

    # -- multi-LoRA admin (proposals/lora-tpu-support.md control plane) ----

    async def lora_list(_req: web.Request) -> web.Response:
        return web.json_response({"adapters": engine.engine.loaded_adapters()})

    async def lora_load(request: web.Request) -> web.Response:
        try:
            body = await request.json()
            name = body["name"]
            path = body["path"]
        except (json.JSONDecodeError, KeyError):
            return web.json_response(
                {"error": {"message": "need JSON body with 'name' and 'path'"}},
                status=400,
            )
        try:
            # Off-loop: file I/O + hundreds of host->device transfers would
            # otherwise stall every in-flight SSE stream.  Catch broadly:
            # a corrupt file raises safetensors' own error type.
            slot = await asyncio.to_thread(
                engine.engine.load_lora_from_path,
                name, path, float(body.get("alpha", 16.0)),
            )
        except Exception as e:
            return web.json_response(
                {"error": {"message": f"{type(e).__name__}: {e}"}}, status=400
            )
        return web.json_response({"name": name, "slot": slot})

    async def lora_unload(request: web.Request) -> web.Response:
        try:
            engine.engine.unload_lora(request.match_info["name"])
        except ValueError as e:
            return web.json_response({"error": {"message": str(e)}}, status=400)
        return web.json_response({"ok": True})

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
    app.router.add_get("/admin/lora", lora_list)
    app.router.add_post("/admin/lora", lora_load)
    app.router.add_delete("/admin/lora/{name}", lora_unload)

    # vLLM's /tokenize + /detokenize: clients budget long-context
    # requests against max_model_len without shipping the tokenizer.
    async def tokenize(request: web.Request) -> web.Response:
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response(
                {"error": {"message": "invalid JSON",
                           "type": "invalid_request_error"}},
                status=400,
            )
        tokenizer = engine.engine.tokenizer
        prompt = body.get("prompt")
        messages = body.get("messages")
        if isinstance(messages, list):
            try:
                prompt = tokenizer.apply_chat_template(messages)
            except Exception as e:
                return web.json_response(
                    {"error": {"message": f"chat template failed: {e}",
                               "type": "invalid_request_error"}},
                    status=400,
                )
        if not isinstance(prompt, str):
            return web.json_response(
                {"error": {"message": "'prompt' (string) or 'messages' "
                           "(list) is required",
                           "type": "invalid_request_error"}},
                status=400,
            )
        ids = tokenizer.encode(prompt)
        return web.json_response({
            "tokens": ids,
            "count": len(ids),
            "max_model_len": engine.engine.config.scheduler.max_model_len,
        })

    async def detokenize(request: web.Request) -> web.Response:
        try:
            body = await request.json()
        except json.JSONDecodeError:
            return web.json_response(
                {"error": {"message": "invalid JSON",
                           "type": "invalid_request_error"}},
                status=400,
            )
        tokens = body.get("tokens")
        if not isinstance(tokens, list) or not all(
            isinstance(t, int) for t in tokens
        ):
            return web.json_response(
                {"error": {"message": "'tokens' must be a list of ids",
                           "type": "invalid_request_error"}},
                status=400,
            )
        return web.json_response(
            {"prompt": engine.engine.tokenizer.decode(tokens)}
        )

    app.router.add_post("/tokenize", tokenize)
    app.router.add_post("/detokenize", detokenize)

    # On-demand device profiling (vLLM's /start_profile and /stop_profile,
    # TPU-native: jax.profiler traces, viewable in TensorBoard/XProf or
    # Perfetto).  Serving continues while the trace records AND while it is
    # written, so a production TTFT spike can be captured in situ: the
    # Python tracer is off (the host tracer keeps the step loop's pstpu.*
    # spans), and stop_trace runs on a worker thread.
    profile_state = {"dir": None}

    async def start_profile(request: web.Request) -> web.Response:
        if profile_state["dir"] is not None:
            return web.json_response(
                {"error": {"message": "profiling already running "
                           f"(writing {profile_state['dir']})"}},
                status=409,
            )
        import jax

        try:
            body = await request.json()
        except Exception:
            body = {}
        trace_dir = body.get("trace_dir") or os.environ.get(
            "PSTPU_PROFILE_DIR", "/tmp/pstpu_profile"
        )
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        # The trace has a clock of its own.  Bracket the session's start on
        # the host's, and make the first host event of the session say
        # what time it was: the flight records' unix ns (GET
        # /debug/windows) are laid against the trace from these.
        before_ns = time.time_ns()
        try:
            jax.profiler.start_trace(trace_dir, profiler_options=options)
        except Exception as e:
            return web.json_response(
                {"error": {"message": f"start_trace failed: {e}"}},
                status=500,
            )
        after_ns = time.time_ns()
        with jax.profiler.TraceAnnotation(
            "pstpu.anchor", unix_ns=time.time_ns()
        ):
            pass
        engine.engine.obs.note_profile("start", before_ns, after_ns)
        profile_state["dir"] = trace_dir
        logger.info("profiling started -> %s", trace_dir)
        return web.json_response({"ok": True, "trace_dir": trace_dir})

    async def stop_profile(_req: web.Request) -> web.Response:
        if profile_state["dir"] is None:
            return web.json_response(
                {"error": {"message": "profiling is not running"}},
                status=409,
            )
        import jax

        trace_dir, profile_state["dir"] = profile_state["dir"], None
        before_ns = time.time_ns()
        try:
            # Writing the trace takes seconds: off the event loop, so that
            # no stream stalls behind it.
            await asyncio.to_thread(jax.profiler.stop_trace)
        except Exception as e:
            return web.json_response(
                {"error": {"message": f"stop_trace failed: {e}"}},
                status=500,
            )
        after_ns = time.time_ns()
        engine.engine.obs.note_profile("stop", before_ns, after_ns)
        stop_s = (after_ns - before_ns) / 1e9
        logger.info("profiling stopped in %.3f s; trace in %s",
                    stop_s, trace_dir)
        return web.json_response(
            {"ok": True, "trace_dir": trace_dir, "stop_s": stop_s}
        )

    app.router.add_post("/start_profile", start_profile)
    app.router.add_post("/stop_profile", stop_profile)

    async def lifecycle(app):
        await engine.start()
        # Follower->leader drain relay (slice-wide drain): a follower's
        # SIGTERM/preStop never leaves the collectives — it relays to
        # the leader, and the LEADER runs the one drain the whole group
        # follows (in-flight streams finish, then the step loop's
        # shutdown publish releases every member to exit 0 in order).
        # The relay fires on the monitor thread; begin() needs the loop.
        if engine.slice_monitor is not None:
            loop = asyncio.get_running_loop()
            engine.slice_monitor.on_drain_relay = (
                lambda: loop.call_soon_threadsafe(drain.begin)
            )
        yield
        await engine.close()

    app.cleanup_ctx.append(lifecycle)
    return app


def _parse_buckets(args):
    """Validate --prefill-buckets at parse time: each bucket must be a
    positive multiple of --block-size (the prefill plan sizes new_block_ids
    as bucket//block_size), returned ascending (the scheduler chunks long
    prompts at prefill_buckets[-1])."""
    try:
        buckets = sorted(int(b) for b in args.prefill_buckets.split(","))
    except ValueError:
        raise SystemExit(f"--prefill-buckets must be integers: {args.prefill_buckets!r}")
    for b in buckets:
        if b <= 0 or b % args.block_size:
            raise SystemExit(
                f"--prefill-buckets entries must be positive multiples of "
                f"--block-size={args.block_size}; got {b}"
            )
    return tuple(buckets)


# stackcheck: thread=health-serve
def _serve_health(health_loop, health_app, host, port) -> None:
    """Follower health-probe server thread: own loop + AppRunner (not
    web.run_app) so _run_follower can stop this thread and join it on
    the way out — a bare run_app daemon thread would die with the
    process holding a half-written probe response."""
    asyncio.set_event_loop(health_loop)
    runner = web.AppRunner(
        health_app, handle_signals=False, access_log=None
    )
    try:
        # The drain path's stop() can land while we are still inside
        # a startup run_until_complete (follower_loop failing fast,
        # e.g. unreachable leader): that raises "Event loop stopped
        # before Future completed" — fall through to cleanup anyway
        # so the listener socket is always released.
        health_loop.run_until_complete(runner.setup())
        site = web.TCPSite(runner, host, port)
        health_loop.run_until_complete(site.start())
        health_loop.run_forever()
    except RuntimeError:
        pass
    finally:
        try:
            if not health_loop.is_closed():
                health_loop.run_until_complete(runner.cleanup())
        except RuntimeError:
            pass
        finally:
            if not health_loop.is_closed():
                health_loop.close()


# stackcheck: thread=slice-guard
def _slice_guard(channel, stop_event) -> None:
    """Follower-side group-fail watcher: the leader's monitor writes a
    group-fail marker on the control-plane side channel when a member
    dies, and THIS thread is how a live follower sees it — the main
    thread is blocked inside a collective the dead member will never
    join, so only an off-collective poll can release it.  fatal_exit
    (never sys.exit): the wedged collective would hang atexit teardown."""
    from production_stack_tpu.engine.parallel import distributed

    while not stop_event.wait(0.5):
        reason = channel.group_failed()
        if reason is not None:
            logger.error(
                "slice group marked failed (%s); exiting for a parallel "
                "group restart", reason,
            )
            distributed.fatal_exit(1)
            return  # unreachable except under monkeypatched exit


def _run_follower(config, denv, args) -> None:
    """Follower process of a multi-host slice group: tiny probe app for
    k8s (the StatefulSet has one pod template, so every ordinal must
    answer probes AND the preStop /drain hook) + the lockstep step loop.

    Drain contract (docs/robustness.md "Slice lifecycle contract"):
    SIGTERM or POST /drain on a follower RELAYS the drain intent to the
    leader through the control-plane side channel — the follower keeps
    stepping (it never unilaterally leaves the collectives, which would
    kill every in-flight stream on the slice) until the leader finishes
    the in-flight streams and announces shutdown, releasing the whole
    group to exit 0 in order."""
    import signal
    import threading

    from production_stack_tpu.engine.core.engine import LLMEngine
    from production_stack_tpu.engine.parallel import distributed

    health_app = web.Application()
    engine = LLMEngine(config)
    engine.plan_from_clocks = False  # as the leader's (AsyncEngine)
    channel = distributed.LockstepChannel(
        denv, member_timeout_s=args.slice_member_timeout_s
    )

    async def health(_req: web.Request) -> web.Response:
        if channel.stale():
            # Leader heartbeats while idle; prolonged silence means it is
            # gone, and an SPMD group cannot heal a lost member in place:
            # fail liveness so k8s restarts this pod into a fresh group.
            return web.json_response(
                {"status": "unhealthy", "role": "follower",
                 "problem": "no leader event within the staleness window"},
                status=503,
            )
        return web.json_response(
            {"status": "ok", "role": "follower",
             "process_id": denv.process_id}
        )

    async def ready(_req: web.Request) -> web.Response:
        """Follower readiness: 503 once a drain was relayed (the pod is
        on its way out; the client Service only selects ordinal 0, but
        operators and preStop ordering read this) or when the leader
        went stale."""
        if channel.drain_relayed:
            return web.json_response(
                {"status": "draining", "role": "follower"}, status=503
            )
        if channel.stale():
            return web.json_response(
                {"status": "unhealthy", "role": "follower"}, status=503
            )
        return web.json_response({"status": "ready", "role": "follower"})

    def _relay_drain(source: str) -> bool:
        relayed = channel.relay_drain()
        if relayed:
            logger.info(
                "follower %d: %s -> drain relayed to the leader; stepping "
                "until the group shutdown", denv.process_id, source,
            )
        else:
            logger.warning(
                "follower %d: %s but no control-plane side channel; "
                "relying on the leader's own drain/staleness path",
                denv.process_id, source,
            )
        return relayed

    async def drain_endpoint(_req: web.Request) -> web.Response:
        """POST /drain (helm preStop — one pod template, every ordinal
        gets the hook): relay to the leader, never exit unilaterally."""
        relayed = _relay_drain("POST /drain")
        return web.json_response({
            "draining": True, "role": "follower", "relayed": relayed,
        })

    health_app.router.add_get("/health", health)
    health_app.router.add_get("/ready", ready)
    health_app.router.add_post("/drain", drain_endpoint)

    # SIGTERM (kubelet pod termination) converges on the same relay.
    # signal.signal works here: _run_follower runs on the main thread.
    try:
        signal.signal(
            signal.SIGTERM, lambda _sig, _frm: _relay_drain("SIGTERM")
        )
    except (ValueError, OSError):  # non-main thread (tests) / platform
        pass

    health_loop = asyncio.new_event_loop()

    health_thread = threading.Thread(
        target=_serve_health,
        args=(health_loop, health_app, args.host, args.port),
        name="health-serve", daemon=True,
    )
    health_thread.start()
    guard_stop = threading.Event()
    guard_thread = threading.Thread(
        target=_slice_guard, args=(channel, guard_stop),
        name="slice-guard", daemon=True,
    )
    guard_thread.start()
    logger.info(
        "tpu-engine follower %d/%d ready (leader owns the HTTP surface)",
        denv.process_id, denv.num_processes,
    )
    try:
        distributed.follower_loop(engine, channel)
    finally:
        # Drain path: stop the probe server and join it, then release
        # the engine's worker threads (deleter queue included) so a
        # follower restart never strands queued remote work.  The loop
        # may already be closed (_serve_health died on a bind error);
        # engine.close() must run regardless.
        guard_stop.set()
        guard_thread.join(5)
        try:
            health_loop.call_soon_threadsafe(health_loop.stop)
        except RuntimeError:
            pass
        health_thread.join(10)
        engine.close()


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description="TPU serving engine (OpenAI API)")
    parser.add_argument("--host", default="0.0.0.0")
    parser.add_argument("--port", type=int, default=8000)
    parser.add_argument("--model", default="tiny-llama", help="model preset name")
    parser.add_argument("--served-model-name", default=None)
    parser.add_argument("--weights-path", default=None)
    parser.add_argument("--tokenizer", default=None)
    parser.add_argument(
        "--chat-template",
        default=None,
        help="path to a Jinja chat-template file overriding the "
        "tokenizer's (the chart mounts modelSpec.chatTemplate here; "
        "reference deployment-vllm-multi.yaml:260-270)",
    )
    parser.add_argument("--max-num-seqs", type=int, default=8)
    parser.add_argument("--max-model-len", type=int, default=2048)
    parser.add_argument("--block-size", type=int, default=16)
    parser.add_argument("--num-blocks", type=int, default=None)
    parser.add_argument(
        "--prefill-buckets",
        default=None,
        help="comma-separated token slots of the prefill programs (a prompt "
        "runs as the cheapest run of them: full chunks, then a padded one)",
    )
    parser.add_argument(
        "--speculative-ngram",
        type=int,
        default=0,
        help="n-gram (prompt-lookup) speculative decoding: draft K tokens "
        "from the sequence's own history and verify them alongside the "
        "committed token in one forward.  With the K-step decode window "
        "active (the default) the drafter runs INSIDE the window scan — "
        "drafts proposed on-device, acceptance folded into the carried "
        "state, a rejected draft costs a scan iteration, never a host "
        "round-trip.  Greedy-only; refused with --no-multi-step-window "
        "(speculation runs inside the window)",
    )
    parser.add_argument(
        "--speculative-model",
        default=None,
        help="draft-MODEL speculative decoding: a model preset name "
        "(e.g. a 2-layer llama sharing the target's tokenizer/vocab — "
        "a vocab mismatch refuses to boot) loaded as a second tiny "
        "model on the same mesh.  It proposes --speculative-draft-len "
        "tokens per scan iteration INSIDE the K-step window, "
        "autoregressively from its own small device-resident KV cache "
        "(dedicated draft pool; target KV capacity untouched), and the "
        "target verifies draft+1 rows in the same wide forward the "
        "n-gram drafter uses.  Mutually exclusive with "
        "--speculative-ngram; requires the window machinery.  Unlike "
        "n-gram lookup, acceptance holds up on "
        "non-templated text",
    )
    parser.add_argument(
        "--speculative-draft-len",
        type=int,
        default=4,
        help="draft tokens the model drafter proposes per scan "
        "iteration (the D in the W = D+1 verify-row fan-out; only "
        "meaningful with --speculative-model)",
    )
    parser.add_argument(
        "--speculative-draft-pool-blocks",
        type=int,
        default=None,
        help="device blocks reserved for the draft model's dedicated KV "
        "pool (default: auto-sized for max_num_seqs rows).  Exhaustion "
        "never stalls — a window that cannot allocate draft blocks "
        "declines to a plain window, counted under "
        "tpu:multistep_fallback_total{reason=draft_pool}",
    )
    parser.add_argument(
        "--no-speculative-model",
        action="store_true",
        help="force the model drafter OFF even if --speculative-model "
        "is set (deploy-template escape hatch; restores ngram-only / "
        "non-speculative behavior exactly)",
    )
    parser.add_argument(
        "--no-multi-step-window",
        action="store_true",
        help="disable K-step device-resident decode windows (the default "
        "decode fast path: K decode+sample iterations per device "
        "dispatch with on-device penalties, the min_tokens EOS floor "
        "and per-row stop masking) and restore single-token stepping "
        "exactly — A/B baseline / debugging.  Refused with either "
        "drafter (--speculative-ngram, --speculative-model)",
    )
    parser.add_argument(
        "--decode-window",
        type=int,
        default=8,
        help="the most iterations one pure-decode dispatch runs (the "
        "K-step decode fast path).  The scheduler plans each window no "
        "longer than the first row's last token and than the step "
        "thread's own pass needs to hide behind it; the device "
        "stop-mask keeps stop conditions from wasting a window's tail",
    )
    parser.add_argument(
        "--no-pipeline-decode",
        action="store_true",
        help="disable the async lookahead decode pipeline (dispatch "
        "decode step or K-step window N+1 while N's tokens are in "
        "flight; greedy streams are identical, decode_host_gap_ms shows "
        "the recovered host serialization)",
    )
    parser.add_argument(
        "--no-mixed-batch",
        action="store_true",
        help="disable fused mixed prefill+decode steps (arriving prompts "
        "then stall all decoders for a full prefill bucket per step — "
        "the pre-mixed alternating scheduler).  Auto-disabled by dp/sp "
        "meshes",
    )
    parser.add_argument(
        "--no-mixed-window",
        action="store_true",
        help="disable mixed K-step windows (a waiting prompt's prefill "
        "chunks riding the device-resident decode scan) and restore the "
        "K=1 mixed scheduling exactly: a waiting head forces "
        "single-token steps, counted under tpu:multistep_fallback_total"
        '{reason="waiting_head"} — A/B baseline / debugging',
    )
    parser.add_argument(
        "--max-num-batched-tokens",
        type=int,
        default=None,
        help="token budget per fused mixed step (decode tokens count "
        "first, the prefill chunk gets the remainder; a mixed K-step "
        "window applies it per scan iteration, so the window total is "
        "K x the budget); default admits the largest chunk bucket "
        "beside a full decode batch",
    )
    parser.add_argument("--host-offload-gb", type=float, default=0.0)
    parser.add_argument("--remote-kv-url", default=None)
    parser.add_argument(
        "--disagg-role",
        default=None,
        choices=["prefill", "decode", "both", "encode"],
        help="cross-engine prefix sharing through the remote KV store: "
        "'prefill' exports prompt KV blocks after prefill, 'decode' "
        "imports matching blocks instead of recomputing, 'both' shares "
        "symmetrically (requires --remote-kv-url); 'encode' marks a "
        "dedicated embed/rerank/score pool member (no KV handoff, no "
        "--remote-kv-url needed) — the router's encode lane prefers it",
    )
    parser.add_argument(
        "--no-remote-prefetch",
        action="store_true",
        help="disable the asynchronous batched KV transfer plane "
        "(admission-time remote-prefix prefetch, off-step offload "
        "staging, async restore page-in) and restore the legacy "
        "synchronous in-schedule transfers — A/B baseline / debugging",
    )
    parser.add_argument(
        "--prefetch-threads", type=int, default=2,
        help="background fetcher threads for the KV prefetch plane",
    )
    parser.add_argument(
        "--disagg-handoff-wait-s", type=float, default=2.0,
        help="decode-phase handoff: bounded wait for the prefetched "
        "prefix chain to land in the cache before admitting anyway "
        "(caps the TTFT tax of a slow store; a store miss exits early; "
        "0 disables the wait)",
    )
    parser.add_argument("--no-prefix-caching", action="store_true")
    parser.add_argument(
        "--kv-cache-dtype",
        default=None,
        choices=["auto", "int8"],
        help="KV cache precision (vLLM --kv-cache-dtype analogue): int8 "
        "stores cached K/V as int8 with per-(token, head) scales — KV HBM "
        "bytes roughly halve, so the pool holds ~2x the tokens",
    )
    parser.add_argument(
        "--kv-wire-format",
        default=None,
        choices=["auto", "fp32", "int8"],
        help="offload/remote wire representation for quantized KV caches: "
        "auto (default) serializes an int8 cache's native (data, scale) "
        "tuples — ~4x resident tokens per host-DRAM byte, kvserver serde "
        "v2 with a probe-once dense-v1 fallback against legacy stores; "
        "fp32 pins the legacy dense wire (rollout escape hatch / A/B "
        "baseline); int8 is auto plus strictness (requires an int8 "
        "cache; a non-v2 store logs a loud downgrade warning)",
    )
    parser.add_argument("--dtype", default=None, help="override preset dtype")
    parser.add_argument(
        "--quantization",
        default=None,
        choices=["int8"],
        help="weight-only quantization of the projection matmuls "
        "(halves decode's HBM weight traffic)",
    )
    # Mesh axes (TPU-first: the reference chart only passes
    # --tensor-parallel-size through to vLLM, deployment-vllm-multi.yaml:84-87;
    # here dp/tp/sp are first-class — config.ParallelConfig).
    parser.add_argument("--data-parallel", type=int, default=1)
    parser.add_argument("--tensor-parallel", type=int, default=1)
    parser.add_argument("--sequence-parallel", type=int, default=1)
    parser.add_argument(
        "--sequence-parallel-mode", choices=["ring", "ulysses"], default="ring"
    )
    # Multi-LoRA slots (engine/lora.py); adapters load via POST /admin/lora.
    parser.add_argument("--max-loras", type=int, default=0)
    parser.add_argument("--max-lora-rank", type=int, default=16)
    # Overload protection + graceful lifecycle (docs/robustness.md).
    parser.add_argument(
        "--no-admission-control",
        action="store_true",
        help="disable bounded admission (the waiting queue then grows "
        "without bound, exactly the legacy behavior; overload times out "
        "in the middle instead of being shed with a 429 at the edge)",
    )
    parser.add_argument(
        "--max-queued-requests", type=int, default=None,
        help="waiting-queue request bound for bounded admission "
        "(default: 4 x --max-num-seqs)",
    )
    parser.add_argument(
        "--max-queued-tokens", type=int, default=None,
        help="waiting-queue prompt-token bound for bounded admission "
        "(default: 2 x --max-num-seqs x --max-model-len)",
    )
    parser.add_argument(
        "--no-encode-lane",
        action="store_true",
        help="disable the batched encode lane (embed/rerank/score then "
        "run the legacy serial per-text encode off the step thread, and "
        "encode admission falls back to the generation caps) — A/B "
        "baseline / debugging",
    )
    parser.add_argument(
        "--encode-batch-buckets", default=None,
        help="comma-separated B-axis bucket grid for encode batches "
        "(default 1,2,4,8); the T axis pads to the prefill buckets",
    )
    parser.add_argument(
        "--max-queued-encode-texts", type=int, default=None,
        help="encode-queue text bound for bounded admission "
        "(default: 32 x the largest encode batch bucket)",
    )
    parser.add_argument(
        "--step-watchdog-s", type=float, default=300.0,
        help="fail /health liveness when the engine step loop has not "
        "iterated in this many seconds (hung device dispatch); 0 disables",
    )
    parser.add_argument(
        "--slice-member-timeout-s", type=float, default=10.0,
        help="multi-host slice groups: fail the leader's /health (and "
        "fatal-exit the whole group into a parallel restart) when a "
        "member's lockstep acks stop advancing for this long — well "
        "under --step-watchdog-s, so a dead follower fails the slice in "
        "seconds instead of wedging collectives until the watchdog; "
        "0 disables group liveness (staleness-window behavior only)",
    )
    parser.add_argument(
        "--drain-grace-s", type=float, default=30.0,
        help="on SIGTERM or POST /drain: stop admitting (503 + "
        "Connection: close), flip /ready to 503, let in-flight streams "
        "finish up to this many seconds, then exit 0",
    )
    parser.add_argument(
        "--no-tracing",
        action="store_true",
        help="disable request tracing + step-phase histograms "
        "(obs.tracing=off: restores the untraced hot path; /debug/requests "
        "returns an empty ring and /metrics drops the histogram families' "
        "samples growth)",
    )
    parser.add_argument(
        "--trace-ring-size", type=int, default=256,
        help="completed request timelines kept for GET /debug/requests",
    )
    parser.add_argument(
        "--trace-ring-bytes", type=int, default=8 * 1024 * 1024,
        help="byte bound on the completed-trace ring (JSON-encoded size; "
        "evictions past it count in tpu:obs_trace_dropped_total; 0 = "
        "count bound only)",
    )
    parser.add_argument(
        "--window-ring-size", type=int, default=1024,
        help="window flight records kept for GET /debug/windows",
    )
    parser.add_argument("--log-level", default="info")
    args = parser.parse_args(argv)

    init_logger("production_stack_tpu", args.log_level)
    from production_stack_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    config = config_from_preset(
        args.model,
        **{
            "weights_path": args.weights_path,
            "tokenizer": args.tokenizer,
            "scheduler.max_num_seqs": args.max_num_seqs,
            "scheduler.max_model_len": args.max_model_len,
            **(
                {"scheduler.prefill_buckets": _parse_buckets(args)}
                if args.prefill_buckets
                else {}
            ),
            "scheduler.speculative_ngram": args.speculative_ngram,
            **(
                {
                    "scheduler.speculative_model": args.speculative_model,
                    "scheduler.speculative_draft_len":
                        args.speculative_draft_len,
                    **(
                        {
                            "scheduler.speculative_draft_pool_blocks":
                                args.speculative_draft_pool_blocks,
                        }
                        if args.speculative_draft_pool_blocks is not None
                        else {}
                    ),
                }
                if args.speculative_model is not None
                and not args.no_speculative_model
                else {}
            ),
            **(
                {"scheduler.multi_step_window": False}
                if args.no_multi_step_window else {}
            ),
            "scheduler.decode_window": args.decode_window,
            **(
                {"scheduler.pipeline_decode": False}
                if args.no_pipeline_decode else {}
            ),
            **(
                {"scheduler.mixed_batch": False}
                if args.no_mixed_batch else {}
            ),
            **(
                {"scheduler.mixed_window": False}
                if args.no_mixed_window else {}
            ),
            **(
                {"scheduler.max_num_batched_tokens": args.max_num_batched_tokens}
                if args.max_num_batched_tokens is not None else {}
            ),
            "cache.block_size": args.block_size,
            "cache.num_blocks": args.num_blocks,
            "cache.host_offload_gb": args.host_offload_gb,
            "cache.remote_kv_url": args.remote_kv_url,
            "cache.disagg_role": args.disagg_role,
            **(
                {"cache.remote_prefetch": False}
                if args.no_remote_prefetch else {}
            ),
            "cache.prefetch_threads": args.prefetch_threads,
            "cache.disagg_handoff_wait_s": args.disagg_handoff_wait_s,
            "cache.enable_prefix_caching": not args.no_prefix_caching,
            **(
                {"cache.kv_cache_dtype": args.kv_cache_dtype}
                if args.kv_cache_dtype else {}
            ),
            **(
                {"cache.kv_wire_format": args.kv_wire_format}
                if args.kv_wire_format else {}
            ),
            **({"model.dtype": args.dtype} if args.dtype else {}),
            **(
                {"model.quantization": args.quantization}
                if args.quantization else {}
            ),
            "parallel.data_parallel": args.data_parallel,
            "parallel.tensor_parallel": args.tensor_parallel,
            "parallel.sequence_parallel": args.sequence_parallel,
            "parallel.sequence_parallel_mode": args.sequence_parallel_mode,
            "lora.max_loras": args.max_loras,
            "lora.max_rank": args.max_lora_rank,
            **(
                {"scheduler.admission_control": False}
                if args.no_admission_control else {}
            ),
            **(
                {"scheduler.max_queued_requests": args.max_queued_requests}
                if args.max_queued_requests is not None else {}
            ),
            **(
                {"scheduler.max_queued_tokens": args.max_queued_tokens}
                if args.max_queued_tokens is not None else {}
            ),
            **(
                {"scheduler.encode_lane": False}
                if args.no_encode_lane else {}
            ),
            **(
                {"scheduler.encode_batch_buckets": tuple(
                    int(b) for b in args.encode_batch_buckets.split(",")
                )}
                if args.encode_batch_buckets else {}
            ),
            **(
                {"scheduler.max_queued_encode_texts":
                    args.max_queued_encode_texts}
                if args.max_queued_encode_texts is not None else {}
            ),
            "scheduler.step_watchdog_s": args.step_watchdog_s,
            "obs.tracing": not args.no_tracing,
            "obs.trace_ring_size": args.trace_ring_size,
            "obs.trace_ring_bytes": args.trace_ring_bytes,
            "obs.window_ring_size": args.window_ring_size,
        },
    )
    # Multi-host slice bootstrap (chart StatefulSet mode / GKE TPU pod
    # env): initialize jax.distributed so the mesh spans every worker's
    # chips.  Follower processes build the same engine, serve only
    # /health, and step in lockstep with the leader's event broadcasts.
    from production_stack_tpu.engine.parallel import distributed

    denv = distributed.maybe_initialize()
    if denv is not None and config.cache.remote_prefetch is None:
        # Async KV transfers are thread-timing-dependent (stager slot
        # busy-ness, restore page-in readiness); inside a lockstep
        # multi-host group a per-replica difference in offload/restore
        # outcomes desyncs the step plans.  Auto mode therefore resolves
        # to the deterministic synchronous path here; an EXPLICIT
        # remote_prefetch=True is honored (operator's call).
        logger.info(
            "multi-host lockstep group: disabling async KV transfer "
            "plane (cache.remote_prefetch auto -> False)"
        )
        config.cache.remote_prefetch = False
    if denv is not None and config.scheduler.encode_lane is None:
        # A leader-only encode forward would desync the SPMD followers'
        # jitted launch sequence (encode batches are not part of the
        # lockstep event broadcast).  Auto resolves to off here; an
        # EXPLICIT encode_lane=True is still cleared by the AsyncEngine
        # guard, which is the one that owns device dispatch.
        logger.info(
            "multi-host lockstep group: disabling the batched encode "
            "lane (scheduler.encode_lane auto -> False)"
        )
        config.scheduler.encode_lane = False
    if denv is not None and args.data_parallel > 1:
        # dp shards the decode batch; across PROCESSES the leader could
        # not read the non-addressable logit/token shards (and dp over
        # DCN wastes the slice's ICI anyway).  Replica-level dp belongs
        # to the chart (replicaCount = more slice groups); within a
        # multi-host group use tp/sp.
        raise SystemExit(
            "--data-parallel > 1 is not supported inside a multi-host "
            "slice group; scale replicas with the chart's replicaCount "
            "and use --tensor-parallel/--sequence-parallel across hosts"
        )
    if denv is not None and not denv.is_leader:
        _run_follower(config, denv, args)
        return
    lockstep = (
        distributed.LockstepChannel(
            denv, member_timeout_s=args.slice_member_timeout_s
        )
        if denv is not None else None
    )

    engine = AsyncEngine(config, lockstep=lockstep)
    if args.chat_template:
        with open(args.chat_template, "r", encoding="utf-8") as f:
            engine.engine.tokenizer.chat_template = f.read()
        try:
            # Fail at boot, not per-request: render a probe conversation so
            # template typos (undefined vars, syntax errors) surface now.
            engine.engine.tokenizer.apply_chat_template(
                [{"role": "system", "content": "probe"},
                 {"role": "user", "content": "probe"}]
            )
        except Exception as e:
            raise SystemExit(
                f"--chat-template {args.chat_template} failed to render: "
                f"{type(e).__name__}: {e}"
            )
        logger.info("Chat template override: %s", args.chat_template)
    served = args.served_model_name or args.model
    app = build_engine_app(engine, served, drain_grace_s=args.drain_grace_s)

    # Graceful SIGTERM (k8s pod termination): replace aiohttp's
    # raise-GracefulExit handler with a drain — readiness flips, admission
    # stops, in-flight streams finish within --drain-grace-s, and the
    # drain's exit_cb re-enters aiohttp's graceful-exit path via SIGINT so
    # cleanup_ctx (engine.close) still runs and the process exits 0.
    # app.on_startup runs AFTER AppRunner.setup registered aiohttp's
    # handlers, so add_signal_handler here wins.
    import signal

    async def _install_sigterm(app_: web.Application) -> None:
        drain = app_["drain"]
        drain.exit_cb = lambda: os.kill(os.getpid(), signal.SIGINT)
        loop = asyncio.get_running_loop()
        try:
            loop.add_signal_handler(
                signal.SIGTERM,
                lambda: (
                    logger.info("SIGTERM: beginning graceful drain"),
                    drain.begin(),
                ),
            )
        except (NotImplementedError, RuntimeError):  # non-main thread / win
            pass

    app.on_startup.append(_install_sigterm)
    logger.info("Starting tpu-engine (%s) on %s:%d", served, args.host, args.port)
    web.run_app(app, host=args.host, port=args.port, access_log=None)


if __name__ == "__main__":
    main()
