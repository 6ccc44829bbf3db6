"""The router data path: parse -> rewrite -> route -> stream-proxy.

Reference counterpart: src/vllm_router/services/request_service/request.py
(route_general_request :120-196, process_request :44-117).  This is the
hottest path in the control plane; the proxy adds exactly one backend stream
and no buffering of the streamed body (SURVEY.md section 7, "Streaming proxy
fidelity").

Differences from the reference:

* pure-asyncio aiohttp instead of FastAPI+httpx (FastAPI is not a given on
  TPU images; one event loop, no thread hand-offs on the data path).
* stats hooks additionally record router-side queueing delay and per-chunk
  inter-token latency (reference monitors for these were never fed).
* failed/aborted requests are reported to the stats monitor instead of
  leaking in-flight counts.
"""

from __future__ import annotations

import asyncio
import json
import logging
import time
import uuid
from typing import Any, Dict, Optional

import aiohttp
from aiohttp import web

from production_stack_tpu.obs.trace import (
    make_request_start,
    make_traceparent,
    new_span_id,
    parse_traceparent,
)
from production_stack_tpu.router.capacity import (
    CAPACITY_MODEL,
    FLEET_ADMISSION,
    request_priority,
)
from production_stack_tpu.router.routing import ROUTING_SERVICE
from production_stack_tpu.router.service_discovery import DISCOVERY_SERVICE
from production_stack_tpu.utils.net import parse_deadline

logger = logging.getLogger(__name__)

# The read-side idle timeout (ClientSession sock_read tripping between
# response reads).  ONLY this timeout is exempt from circuit-breaker
# failure counting and connect-stage failover: the backend accepted the
# connection and is (possibly slowly) computing.  Connect-stage timeouts
# (aiohttp ConnectionTimeoutError, also a ServerTimeoutError subclass)
# must keep counting — a black-holed host that drops SYNs without an RST
# would otherwise never open its breaker.  getattr: SocketTimeoutError
# appeared in aiohttp 3.10; older versions collapse both into
# ServerTimeoutError, where we prefer the breaker-counting side.
_READ_IDLE_TIMEOUT_EXC = getattr(aiohttp, "SocketTimeoutError", ())

CLIENT_SESSION = "client_session"
REQUEST_STATS_MONITOR = "request_stats_monitor"
ENGINE_STATS_SCRAPER = "engine_stats_scraper"
REQUEST_REWRITER = "request_rewriter"
ROUTER_TRACER = "router_tracer"
# Per-backend circuit breaker (router/circuit_breaker.py); absent/None =
# breaker disabled, reproducing the pre-breaker proxy path exactly.
CIRCUIT_BREAKER = "circuit_breaker"
# Per-request connect-stage retry budget (int): at most 1 + budget
# backends are tried, so failover cannot amplify an overload across the
# whole fleet.  Absent = unbounded (legacy behavior, and what bare-registry
# unit tests get).
RETRY_BUDGET = "retry_budget"

# Encode-lane surface: requests to these paths run the engines' batched
# encode lane (embed/rerank/score), not the decode scan — they gate on
# the ENCODE pool's fleet headroom and route to encode-capable backends
# (docs/router.md "Encode lanes & semantic cache").
ENCODE_PATHS = ("/v1/embeddings", "/v1/rerank", "/rerank", "/v1/score", "/score")

# Headers that must not be forwarded either direction: hop-by-hop headers,
# plus encoding headers — aiohttp's client auto-decompresses the backend body
# and negotiates its own Accept-Encoding, so forwarding either would claim an
# encoding the relayed bytes no longer have.
_HOP_BY_HOP = {
    "host",
    "connection",
    "keep-alive",
    "proxy-authenticate",
    "proxy-authorization",
    "te",
    "trailers",
    "transfer-encoding",
    "upgrade",
    "content-length",
    "content-encoding",
    "accept-encoding",
    # Identity/trace headers the router owns and re-stamps explicitly on
    # both directions; forwarding the inbound casing too would emit the
    # header twice (dict keys are case-sensitive, the wire is not).
    "x-request-id",
    "traceparent",
    # Deadline header: normalized to absolute epoch seconds and re-stamped
    # explicitly (the inbound value may be the one we minted from a
    # `timeout` body field).
    "x-request-deadline",
    # When the router took the request (``t=<unix seconds>``, the nginx /
    # Heroku convention): re-stamped from in_router_time so the engine can
    # time the hop; a client's own value must not reach it.
    "x-request-start",
    # Disagg control plane: the router mints these itself (the prime
    # marker and the handoff token) — an external client must not be able
    # to smuggle either through the proxy.
    "x-disagg-phase",
    "x-disagg-handoff",
}




def _forward_headers(headers) -> Dict[str, str]:
    return {k: v for k, v in headers.items() if k.lower() not in _HOP_BY_HOP}


def _error_response(status: int, message: str, type_: str = "invalid_request_error") -> web.Response:
    return web.json_response(
        {"error": {"message": message, "type": type_, "code": status}}, status=status
    )


async def route_general_request(
    request: web.Request, endpoint_path: str, background: Optional[Any] = None
) -> web.StreamResponse:
    """Proxy one OpenAI-style POST to the chosen serving engine.

    ``background`` is an optional async callable ``(body_json, response_text)``
    invoked after a successful non-streaming-aware completion (used by the
    semantic cache, reference request.py:113-117).
    """
    registry = request.app["registry"]
    in_router_time = time.time()
    # The request-id middleware (app.py) honors/mints x-request-id and
    # echoes it on every response; fall back here for direct callers.
    request_id = (
        request.get("request_id")
        or request.headers.get("x-request-id")
        or str(uuid.uuid4())
    )
    tracer = registry.get(ROUTER_TRACER)
    if tracer is not None and not tracer.enabled:
        tracer = None

    body_bytes = await request.read()
    try:
        body_json: Optional[Dict[str, Any]] = json.loads(body_bytes) if body_bytes else None
    except json.JSONDecodeError:
        return _error_response(400, "Request body is not valid JSON")

    requested_model = (body_json or {}).get("model")
    if body_json is not None and requested_model is None and endpoint_path.startswith("/v1/"):
        return _error_response(400, "Request body must include a 'model' field")

    # Rewrite hook (reference request.py:149-160).
    rewriter = registry.get(REQUEST_REWRITER)
    if rewriter is not None and body_json is not None:
        rewritten = rewriter.rewrite_request(body_json, requested_model, endpoint_path)
        if rewritten is not body_json:
            body_json = rewritten
            body_bytes = json.dumps(body_json).encode("utf-8")
        requested_model = (body_json or {}).get("model", requested_model)

    trace = None
    if tracer is not None:
        # Honor an inbound W3C traceparent (the caller's trace id) or mint
        # one; either way the id is forwarded to the engine so both
        # components' timelines join under it.  Started only AFTER the
        # body read + validation: a client dying mid-upload (or a rejected
        # body) must never leak a permanently-active trace.  The trace
        # start timestamp is still the receive time.
        trace = tracer.start(
            request_id,
            trace_id=parse_traceparent(request.headers.get("traceparent")),
            attrs={"path": endpoint_path},
            start=in_router_time,
        )

    def _reject(resp: web.Response, why: str) -> web.Response:
        """Close the trace on pre-proxy rejections so the ring shows them."""
        if tracer is not None:
            tracer.finish(request_id, error=why, status=resp.status)
        return resp

    # Deadline propagation: shed requests whose deadline already expired
    # in the router's own queue — forwarding them would waste an engine
    # batch slot on an answer nobody is waiting for.
    try:
        deadline = parse_deadline(request.headers, body_json, in_router_time)
    except ValueError as e:
        return _reject(_error_response(400, str(e)), "bad_deadline")
    if deadline is not None and time.time() >= deadline:
        from production_stack_tpu.router.services import metrics_service as ms

        ms.deadline_expired_total.inc()
        return _reject(
            _error_response(
                504, "request deadline expired in the router queue",
                "deadline_expired",
            ),
            "deadline_expired",
        )

    discovery = registry.require(DISCOVERY_SERVICE)
    endpoints = [ep for ep in discovery.get_endpoint_info() if not ep.sleep]
    scraper = registry.get(ENGINE_STATS_SCRAPER)
    # Avoid engines whose last /metrics scrape failed — as long as at least
    # one reachable engine remains (otherwise optimistically try them all;
    # the scrape may lag an engine's recovery).
    if scraper is not None:
        unreachable = scraper.get_unreachable_urls()
        if unreachable:
            reachable = [ep for ep in endpoints if ep.url not in unreachable]
            if reachable:
                endpoints = reachable
    if requested_model is not None:
        endpoints = [
            ep
            for ep in endpoints
            if not ep.model_names or requested_model in ep.model_names
        ]
    if not endpoints:
        return _reject(
            _error_response(
                400,
                f"Model '{requested_model}' not served by any healthy engine",
                "model_not_found",
            ),
            "model_not_found",
        )

    # Circuit breaker: opened backends receive no traffic (a half-open
    # probe-ready backend passes the filter; the probe slot is consumed in
    # process_request when routing actually picks it).  Backpressured
    # engines (recent 429) lose routing weight while alternatives exist.
    breaker = registry.get(CIRCUIT_BREAKER)
    if breaker is not None:
        from production_stack_tpu.router.routing.base import (
            deprioritize_backpressured,
            filter_circuit_available,
        )

        available = filter_circuit_available(endpoints, breaker)
        if not available:
            return _reject(
                _error_response(
                    503,
                    f"All serving engines for model '{requested_model}' "
                    "have open circuit breakers",
                    "circuit_open",
                ),
                "circuit_open",
            )
        endpoints = deprioritize_backpressured(available, breaker)

    engine_stats = scraper.get_engine_stats() if scraper else {}
    monitor = registry.get(REQUEST_STATS_MONITOR)
    request_stats = monitor.get_request_stats(time.time()) if monitor else {}

    # Encode lane: embed/rerank/score requests prefer the dedicated
    # encode pool (role-less fused backends serve both; prefill/decode
    # members are reserved for generation) and gate on the ENCODE
    # pool's headroom below — an embed burst sheds against its own
    # knee instead of stretching generation ITL.
    lane = "encode" if endpoint_path in ENCODE_PATHS else "generate"
    if lane == "encode":
        from production_stack_tpu.router.routing.base import prefer_encode_pool

        endpoints = prefer_encode_pool(endpoints)

    # Fleet-level admission (router/capacity.py): when the online
    # capacity model estimates the admission pool's headroom exhausted,
    # shed HERE with a structured 429 + Retry-After — before a routing
    # decision, a backend connect, or an engine queue slot is spent.
    # Fleet sheds therefore strictly precede engine 429s in an overload
    # (docs/robustness.md "Fleet admission & autoscaling contract").
    admission = registry.get(FLEET_ADMISSION)
    if admission is not None:
        shed = admission.check(
            endpoints, engine_stats, request_stats,
            priority=request_priority(request.headers, body_json),
            monitor=monitor,
            lane=lane,
        )
        if shed is not None:
            from production_stack_tpu.router.services import (
                metrics_service as ms,
            )

            ms.fleet_admission_rejected_total.labels(reason=shed.reason).inc()
            resp = web.json_response(
                {
                    "error": {
                        "message": (
                            "fleet overloaded: estimated "
                            f"{shed.pool}-pool headroom exhausted "
                            f"({shed.headroom:.1f}/{shed.capacity:.1f} "
                            "slots free)"
                        ),
                        "type": "fleet_overloaded",
                        "code": 429,
                        "detail": {
                            "reason": shed.reason,
                            "pool": shed.pool,
                            "headroom_slots": round(shed.headroom, 2),
                            "capacity_slots": round(shed.capacity, 2),
                        },
                    }
                },
                status=429,
                headers={"Retry-After": str(max(1, int(shed.retry_after_s)))},
            )
            return _reject(resp, f"fleet_shed_{shed.reason}")

    router = registry.require(ROUTING_SERVICE)

    # Two-phase disaggregated prefill/decode (routing policy `disagg`):
    # prime a prefill-pool backend (which eagerly exports the prefix
    # chain), then route the generation to a decode-pool backend whose
    # admission-time prefetch imports it.  Every failure mode degrades to
    # the fused single-backend path below — never a 500
    # (docs/robustness.md "Disagg handoff failure semantics").
    server_url: Optional[str] = None
    extra_headers: Optional[Dict[str, str]] = None
    if (
        getattr(router, "two_phase", False)
        and body_json is not None
        and endpoint_path in ("/v1/chat/completions", "/v1/completions")
    ):
        from production_stack_tpu.router.services.request_service.disagg import (
            prefill_phase,
        )

        prime_fwd = _forward_headers(request.headers)
        if deadline is not None:
            prime_fwd["x-request-deadline"] = repr(float(deadline))
        if trace is not None:
            prime_fwd["traceparent"] = make_traceparent(trace.trace_id)
        outcome = await prefill_phase(
            request, registry,
            endpoints=endpoints,
            all_endpoints=[ep for ep in discovery.get_endpoint_info()
                           if not ep.sleep],
            engine_stats=engine_stats,
            request_stats=request_stats,
            body_bytes=body_bytes,
            forward_headers=prime_fwd,
            request_id=request_id,
            deadline=deadline,
            endpoint_path=endpoint_path,
            tracer=tracer,
        )
        if outcome.shed is not None:
            return outcome.shed
        endpoints = outcome.endpoints
        extra_headers = outcome.extra_headers or None
        server_url = outcome.server_url

    if server_url is None:
        try:
            server_url = router.route_request(
                endpoints, engine_stats, request_stats, request, body_json
            )
        except ValueError as e:
            return _reject(
                _error_response(503, str(e), "service_unavailable"),
                "routing_failed",
            )

    if tracer is not None and trace is not None:
        tracer.add_span(
            request_id, "router.route", in_router_time, time.time(),
            server=server_url,
        )
        tracer.set_attrs(request_id, model=requested_model, server=server_url)

    logger.debug(
        "Routing request %s (model=%s) to %s at %.6f, took %.3f ms",
        request_id,
        requested_model,
        server_url,
        in_router_time,
        (time.time() - in_router_time) * 1e3,
    )

    # Connect-stage failover list: if the routed backend dies between
    # scrapes, surviving replicas still serve the request (the reference
    # 502s here — SURVEY.md section 5; see test_router_e2e).  Once a byte
    # has streamed there is no failover (the client has partial state).
    fallback_urls = [ep.url for ep in endpoints if ep.url != server_url]

    return await process_request(
        request,
        body_bytes=body_bytes,
        body_json=body_json,
        server_url=server_url,
        endpoint_path=endpoint_path,
        request_id=request_id,
        in_router_time=in_router_time,
        background=background,
        fallback_urls=fallback_urls,
        deadline=deadline,
        extra_headers=extra_headers,
    )


async def process_request(
    request: web.Request,
    *,
    body_bytes: bytes,
    body_json: Optional[Dict[str, Any]],
    server_url: str,
    endpoint_path: str,
    request_id: str,
    in_router_time: float,
    background: Optional[Any] = None,
    fallback_urls: Optional[list] = None,
    deadline: Optional[float] = None,
    extra_headers: Optional[Dict[str, str]] = None,
) -> web.StreamResponse:
    """Open one backend stream and relay chunks, feeding the stats lifecycle
    (reference process_request, request.py:44-117).

    ``fallback_urls``: tried in order when the routed backend fails at the
    connect stage (before any response byte), capped by the per-request
    retry budget so failover cannot amplify an overload.  Mid-stream
    failures never fail over — the client already holds partial state."""
    registry = request.app["registry"]
    monitor = registry.get(REQUEST_STATS_MONITOR)
    session: aiohttp.ClientSession = registry.require(CLIENT_SESSION)
    breaker = registry.get(CIRCUIT_BREAKER)
    retry_budget = registry.get(RETRY_BUDGET)
    tracer = registry.get(ROUTER_TRACER)
    if tracer is not None and not tracer.enabled:
        tracer = None
    trace = tracer.get(request_id) if tracer is not None else None

    headers = _forward_headers(request.headers)
    headers["x-request-id"] = request_id
    headers["x-request-start"] = make_request_start(in_router_time)
    if extra_headers:
        # Router-minted control headers (the disagg handoff token) —
        # added after the hop-by-hop strip so clients cannot spoof them.
        headers.update(extra_headers)
    if deadline is not None:
        # Normalized absolute form, whatever the client sent (header or
        # `timeout` body field) — the engine enforces it at admission and
        # in its scheduler-pass sweep.
        headers["x-request-deadline"] = repr(float(deadline))
    if trace is not None:
        # Propagate the trace context so the engine's timeline joins this
        # one under the same trace id (/debug/requests/{id}); the span id
        # sent is kept here, and the engine keeps it as its root's
        # ``parent_span_id``.
        span_id = new_span_id()
        tracer.set_attrs(request_id, span_id=span_id)
        headers["traceparent"] = make_traceparent(trace.trace_id, span_id)
    elif request.headers.get("traceparent"):
        # Tracing off: stay a transparent proxy for the caller's context
        # (it was stripped from the generic forward set above).
        headers["traceparent"] = request.headers["traceparent"]

    candidates = [server_url] + list(fallback_urls or [])
    if retry_budget is not None:
        # Retry budget: the routed backend + at most `retry_budget`
        # failover attempts.  Under a fleet-wide brownout, unbounded
        # failover would replay every request against every backend —
        # multiplying the very load that caused the failures.
        candidates = candidates[: 1 + max(0, int(retry_budget))]
    collected: list = []
    want_store = background is not None
    # First connect attempt's start: router.queue must end HERE, not at
    # the successful attempt's connect start — otherwise a dead backend's
    # connect timeout would masquerade as router queueing.
    first_connect0: Optional[float] = None

    for attempt, url in enumerate(candidates):
        if deadline is not None and attempt > 0 and time.time() >= deadline:
            # Failover burned the remaining budget: shed instead of
            # handing a dead-on-arrival request to the next backend.
            from production_stack_tpu.router.services import (
                metrics_service as ms,
            )

            ms.deadline_expired_total.inc()
            if tracer is not None:
                tracer.finish(request_id, error="deadline_expired", server=url)
            return _error_response(
                504, "request deadline expired during connect-stage failover",
                "deadline_expired",
            )
        if breaker is not None and not breaker.on_attempt(url):
            # Open circuit (or a half-open probe already in flight):
            # skip without counting a failure.
            continue
        if monitor:
            monitor.on_new_request(url, request_id, in_router_time)
        first_chunk_seen = False
        t_first: Optional[float] = None
        t_connected: Optional[float] = None
        response: Optional[web.StreamResponse] = None
        t_connect0 = time.time()
        if first_connect0 is None:
            first_connect0 = t_connect0

        def _fail_spans() -> None:
            """Attach whatever phases completed before a failure — the
            slow/failed requests are exactly the ones the debug surface
            must explain, so their timelines can't be span-less."""
            if tracer is None:
                return
            tracer.add_span(
                request_id, "router.queue", in_router_time, first_connect0
            )
            if t_connected is not None:
                tracer.add_span(
                    request_id, "router.backend_connect", t_connect0,
                    t_connected, server=url,
                )
                if t_first is not None:
                    tracer.add_span(
                        request_id, "router.first_token", t_connected, t_first
                    )

        try:
            async with session.request(
                request.method,
                f"{url}{endpoint_path}",
                data=body_bytes if body_bytes else None,
                headers=headers,
            ) as backend:
                t_connected = time.time()
                if breaker is not None:
                    if backend.status == 429:
                        # Engine shedding: backpressure, never a breaker
                        # failure (routing weight drops instead).
                        try:
                            retry_after = float(
                                backend.headers.get("Retry-After", "")
                            )
                        except (TypeError, ValueError):
                            retry_after = None
                        breaker.on_backpressure(url, retry_after)
                        # The same event is a ZERO-HEADROOM observation
                        # for the fleet capacity model: the engine told
                        # us its bound, so fleet admission stops sending
                        # work its way for the advertised window.
                        capacity = registry.get(CAPACITY_MODEL)
                        if capacity is not None:
                            capacity.on_backpressure(url, retry_after)
                    elif backend.status >= 500:
                        breaker.on_failure(url)
                    else:
                        breaker.on_success(url)
                if monitor:
                    monitor.on_backend_connected(url, request_id, t_connected)
                if extra_headers and "x-disagg-handoff" in extra_headers:
                    # Decode-phase prefetch outcome: anything but a full
                    # chain import means the decode engine recomputed the
                    # prefill locally — the in-place fused fallback the
                    # two-phase contract degrades to (never a third
                    # backend, never a failure).
                    px_outcome = backend.headers.get("x-disagg-prefix")
                    if px_outcome is not None and px_outcome != "hit":
                        from production_stack_tpu.router.services import (
                            metrics_service as ms,
                        )

                        ms.disagg_fallback_total.labels(
                            reason="prefix_miss"
                        ).inc()
                resp_headers = _forward_headers(backend.headers)
                # Echo the request id on the proxied response too (the
                # engine may predate the header; the client must always
                # get it back, streaming included).
                resp_headers["x-request-id"] = request_id
                response = web.StreamResponse(
                    status=backend.status, headers=resp_headers
                )
                await response.prepare(request)
                async for chunk in backend.content.iter_any():
                    if not chunk:
                        continue
                    now = time.time()
                    if not first_chunk_seen:
                        t_first = now
                        first_chunk_seen = True
                        if monitor:
                            # Seeds the token clock + counts this chunk; no
                            # ITL sample (first chunk defines no interval).
                            # The engine stamps '"compile": true' into the
                            # first chunk (SSE or JSON body alike) when an
                            # XLA compile fired inside the request: a byte
                            # sniff — not a parse — keeps that cold-start
                            # sample out of the compile-excluded TTFT
                            # window on the proxy hot path.
                            tainted = (
                                b'"compile": true' in chunk
                                or b'"compile":true' in chunk
                            )
                            monitor.on_request_response(
                                url, request_id, now,
                                compile_tainted=tainted,
                            )
                    elif monitor:
                        monitor.on_token_chunk(url, request_id, now)
                    if want_store:
                        collected.append(chunk)
                    await response.write(chunk)
                await response.write_eof()
            t_end = time.time()
            if monitor:
                monitor.on_request_complete(url, request_id, t_end)
            if tracer is not None:
                # Routing decision -> backend connect -> first token ->
                # stream end (the span set the ISSUE names; router.queue +
                # router.backend_connect are the non-overlapping phases
                # the /debug join scores against engine spans).
                tracer.add_span(
                    request_id, "router.queue", in_router_time, first_connect0
                )
                if attempt > 0:
                    # Time burned on dead backends before this one; keeps
                    # the timeline honest without blaming router.queue.
                    tracer.add_span(
                        request_id, "router.failover", first_connect0,
                        t_connect0, attempts=attempt,
                    )
                tracer.add_span(
                    request_id, "router.backend_connect", t_connect0,
                    t_connected, server=url,
                )
                if t_first is not None:
                    tracer.add_span(
                        request_id, "router.first_token", t_connected, t_first
                    )
                    tracer.add_span(
                        request_id, "router.stream", t_first, t_end
                    )
                tracer.finish(
                    request_id, end=t_end, server=url,
                    status=response.status,
                )
        except asyncio.CancelledError:
            # Client disconnected (or server shutdown): release in-flight
            # stats, then propagate — cancellation must not be swallowed.
            if monitor:
                monitor.on_request_failed(url, request_id, time.time())
            if tracer is not None:
                _fail_spans()
                tracer.finish(request_id, error="client_disconnect", server=url)
            raise
        except (aiohttp.ClientError, ConnectionResetError) as e:
            if monitor:
                monitor.on_request_failed(url, request_id, time.time())
            idle_timeout = isinstance(e, _READ_IDLE_TIMEOUT_EXC)
            if breaker is not None and not idle_timeout:
                # sock_read idle timeouts are deliberately NOT breaker
                # failures: the backend accepted the connection — it may
                # just be slow (first XLA compile of a bucket can take
                # minutes with zero response bytes).  The per-stream
                # teardown is the remedy; opening the circuit would cut
                # ALL traffic to a healthy-but-compiling backend.
                # Connect-stage timeouts DO count (see _READ_IDLE_TIMEOUT_EXC).
                breaker.on_failure(url)
            if response is not None:
                # Mid-stream failure: the client already has a partial
                # body; terminate the stream (reference behavior, SURVEY.md
                # section 5 "no request retry/failover mid-stream").
                logger.warning("Backend %s failed mid-stream: %s", url, e)
                if tracer is not None:
                    _fail_spans()
                    tracer.finish(
                        request_id, error="mid_stream_failure", server=url
                    )
                raise
            if idle_timeout:
                # The backend accepted the request and is mid-compute
                # (headers not sent yet: a long non-streaming generation
                # past --stream-idle-timeout-s).  Failing over would
                # re-execute the WHOLE completion on another engine while
                # the first keeps decoding until the disconnect-abort
                # lands — duplicated generation load, not recovery.  Shed
                # to the client instead.
                logger.warning(
                    "Backend %s idle-read timeout before response headers "
                    "(%s); shedding instead of replaying", url, e,
                )
                if tracer is not None:
                    _fail_spans()
                    tracer.finish(request_id, error="backend_timeout", server=url)
                return _error_response(
                    504,
                    "Serving engine produced no response bytes within the "
                    "idle-read timeout",
                    "backend_timeout",
                )
            if attempt + 1 < len(candidates):
                logger.warning(
                    "Backend %s unreachable (%s); failing over to %s",
                    url, e, candidates[attempt + 1],
                )
                continue
            logger.warning("Backend %s failed before response: %s", url, e)
            if tracer is not None:
                _fail_spans()
                tracer.finish(request_id, error="bad_gateway", server=url)
            return _error_response(
                502, "All serving engines for this model are unreachable",
                "bad_gateway",
            )

        # Only feed the store hook successful responses: backend error
        # bodies (429/503, or vLLM's {"object": "error"} shape) must never
        # be cached and replayed as hits.
        if (
            want_store
            and collected
            and body_json is not None
            and response is not None
            and response.status == 200
        ):
            try:
                await background(body_json, b"".join(collected))
            except Exception:
                logger.exception("post-response background hook failed")
        return response

    # Every candidate was skipped without an attempt (circuit open on all
    # of them, or the failover list ran dry on breaker skips alone).
    if tracer is not None:
        tracer.finish(request_id, error="circuit_open")
    return _error_response(
        503, "All serving engines for this model have open circuit breakers",
        "circuit_open",
    )
