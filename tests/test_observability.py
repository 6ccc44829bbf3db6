"""Observability-contract tests.

The reference ships dashboard panels charting metrics its router never
emits (vllm:router_queueing_delay_seconds, vllm:avg_prefill_length —
SURVEY.md section 5 "aspirational metric"); the round-2 verdict demands we
not repeat that.  These tests scrape the REAL surfaces — the JAX engine
server's /metrics and the live router's /metrics — and assert every metric
referenced by the Grafana dashboard, prometheus-adapter rule, and HPA
example is actually emitted, and that ServiceMonitor port names / label
selectors line up with what the Helm chart renders.
"""

import json
import os
import re

import yaml
from aiohttp.test_utils import TestClient, TestServer

from production_stack_tpu.testing.helm_render import render_chart

OBS_DIR = os.path.join(os.path.dirname(__file__), "..", "observability")
CHART_DIR = os.path.join(os.path.dirname(__file__), "..", "helm")

METRIC_TOKEN_RE = re.compile(r"[a-zA-Z_][a-zA-Z0-9_:]*")


def dashboard_metric_names():
    with open(os.path.join(OBS_DIR, "tpu-dashboard.json")) as f:
        dashboard = json.load(f)
    names = set()
    for panel in dashboard["panels"]:
        for target in panel.get("targets", []):
            for token in METRIC_TOKEN_RE.findall(target["expr"]):
                if token.startswith(("tpu:", "tpu_router:")):
                    names.add(token)
    return dashboard, names


async def scrape_engine_metrics():
    """Authoritative engine metric set: the real JAX engine server."""
    from production_stack_tpu.engine.config import config_from_preset
    from production_stack_tpu.engine.server.api_server import build_engine_app
    from production_stack_tpu.engine.server.async_engine import AsyncEngine

    config = config_from_preset(
        "tiny-llama", **{"cache.num_blocks": 64, "scheduler.max_num_seqs": 2,
                         "scheduler.prefill_buckets": (16, 32)}
    )
    engine = AsyncEngine(config)
    server = TestServer(build_engine_app(engine, "tiny-llama"))
    await server.start_server()
    client = TestClient(server)
    try:
        resp = await client.get("/metrics")
        return await resp.text()
    finally:
        await client.close()


async def scrape_router_metrics():
    from tests.test_router_e2e import start_fake_engine, start_router

    state, engine = await start_fake_engine()
    try:
        app, server, client = await start_router(
            [str(engine.make_url("")).rstrip("/")], ["fake/llama-3-8b"],
            # The dashboard's experimental-tier panels (semantic cache, PII)
            # must be backed by real metrics too, so scrape with both gates
            # live rather than relying on module-import side effects.
            extra_args=["--feature-gates", "SemanticCache=true,PIIDetection=true"],
        )
        try:
            # One proxied request so request-plane gauges materialize.
            await client.post(
                "/v1/completions",
                json={"model": "fake/llama-3-8b", "prompt": "x", "max_tokens": 1},
            )
            # Repeat chat question -> cache miss then hit; SSN -> PII block.
            chat = {
                "model": "fake/llama-3-8b",
                "messages": [{"role": "user", "content": "metrics probe"}],
                "max_tokens": 4,
            }
            await client.post("/v1/chat/completions", json=chat)
            await client.post("/v1/chat/completions", json=chat)
            await client.post("/v1/chat/completions", json={
                **chat,
                "messages": [{"role": "user", "content": "ssn 123-45-6789"}],
            })
            resp = await client.get("/metrics")
            return await resp.text()
        finally:
            await client.close()
    finally:
        await engine.close()


def emitted_names(metrics_text):
    names = set()
    for line in metrics_text.splitlines():
        if line.startswith("# TYPE "):
            # A TYPE header with zero series is still an emitted family:
            # label sets that are open (e.g. per-slice-member gauges on a
            # single-host engine) render the stable family header with no
            # samples — the documented scrape contract
            # (vocabulary.render_labeled_gauge/counter).  Headers carry
            # exact family names, so this keeps the no-truncation rule.
            parts = line.split()
            if len(parts) >= 3:
                names.add(parts[2])
            continue
        if line.startswith("#") or not line.strip():
            continue
        token = METRIC_TOKEN_RE.match(line)
        if token:
            names.add(token.group(0))
    return names


async def test_every_dashboard_expr_is_emitted():
    dashboard, referenced = dashboard_metric_names()
    assert len(dashboard["panels"]) >= 16  # parity with the reference's 16
    emitted = emitted_names(await scrape_engine_metrics())
    emitted |= emitted_names(await scrape_router_metrics())
    # Exact match only (plus histogram suffixes, should any appear later):
    # a startswith escape hatch would let truncated panel exprs pass.
    histogram_suffixes = ("_bucket", "_sum", "_count")
    missing = {
        name for name in referenced
        if name not in emitted
        and not any(name + s in emitted for s in histogram_suffixes)
    }
    assert not missing, f"dashboard references unemitted metrics: {missing}"


async def test_prom_adapter_rule_matches_engine_metric():
    """Every ENGINE-layer series the adapter queries must be live on the
    engine's /metrics output (the router families are covered by the
    router metrics tests; stackcheck SC708 additionally pins every
    series against the metric registry in CI)."""
    with open(os.path.join(OBS_DIR, "prom-adapter.yaml")) as f:
        adapter = yaml.safe_load(f)
    rules = adapter["rules"]["custom"]
    assert len(rules) >= 4, "queue/tokens/deadline/headroom signals expected"
    emitted = emitted_names(await scrape_engine_metrics())
    renames = {}
    for rule in rules:
        series = rule["seriesQuery"]
        renames[series] = rule["name"]["as"]
        # The HPA-facing rename drops the colon.
        assert ":" not in rule["name"]["as"]
        assert series in rule["metricsQuery"]
        if series.startswith("tpu:"):
            assert series in emitted, f"{series} not emitted by the engine"
    # The classic queue-depth rule survives the rewrite, and the new
    # SLO/fleet signals are exposed.
    from production_stack_tpu.router.stats import vocabulary

    assert renames[vocabulary.HPA_QUEUE_METRIC] == "tpu_num_requests_waiting"
    assert renames["tpu:deadline_expired_total"] == "tpu_deadline_miss_rate"
    assert (
        renames["tpu_router:fleet_headroom_slots"]
        == "tpu_router_fleet_headroom_slots"
    )


def test_hpa_example_consistent_with_adapter_and_chart():
    with open(os.path.join(OBS_DIR, "prom-adapter.yaml")) as f:
        adapter = yaml.safe_load(f)
    exposed = {r["name"]["as"] for r in adapter["rules"]["custom"]}
    with open(os.path.join(OBS_DIR, "hpa-example.yaml")) as f:
        hpas = [doc for doc in yaml.safe_load_all(f) if doc]
    assert len(hpas) == 2  # fused/decode queue-depth HPA + prefill HPA
    for hpa in hpas:
        # Every custom metric an HPA consumes must be an adapter rename
        # (the static twin of this check is stackcheck SC708).
        for m in hpa["spec"]["metrics"]:
            assert m["pods"]["metric"]["name"] in exposed
        # Target naming matches the chart's engine Deployment scheme.
        target = hpa["spec"]["scaleTargetRef"]
        assert target["kind"] == "Deployment"
        assert re.fullmatch(r".+-deployment-engine", target["name"])
    fused, prefill = hpas
    assert fused["spec"]["metrics"][0]["pods"]["metric"]["name"] == \
        "tpu_num_requests_waiting"
    assert prefill["spec"]["metrics"][0]["pods"]["metric"]["name"] == \
        "tpu_queued_prompt_tokens"


async def test_trace_propagation_and_debug_join():
    """Acceptance criterion: a request served through router + engine
    yields a joined /debug/requests/{id} timeline covering >= 6 phases
    whose durations sum to within 10% of wall-clock e2e latency; the
    trace context (x-request-id + traceparent) flows router -> engine."""
    import time

    from tests.test_router_e2e import start_fake_engine, start_router

    state, engine = await start_fake_engine(ttft=0.1, tokens_per_sec=100.0)
    try:
        app, server, client = await start_router(
            [str(engine.make_url("")).rstrip("/")], ["fake/llama-3-8b"]
        )
        try:
            trace_id = "ab" * 16
            t0 = time.time()
            resp = await client.post(
                "/v1/completions",
                json={"model": "fake/llama-3-8b", "prompt": "hello",
                      "max_tokens": 30, "stream": True},
                headers={"x-request-id": "req-trace-1",
                         "traceparent": f"00-{trace_id}-{'cd' * 8}-01"},
            )
            await resp.read()
            wall_e2e = time.time() - t0
            assert resp.status == 200
            # Request id echoed on the streaming response.
            assert resp.headers["x-request-id"] == "req-trace-1"
            # Context propagated to the engine: same id, same trace id.
            assert state.last_headers["x-request-id"] == "req-trace-1"
            assert state.last_headers["traceparent"].split("-")[1] == trace_id

            dresp = await client.get("/debug/requests/req-trace-1")
            assert dresp.status == 200
            joined = await dresp.json()
            assert joined["trace_id"] == trace_id
            assert joined["engine"] is not None
            assert joined["engine"]["trace_id"] == trace_id
            # >= 6 phases covered.
            assert set(joined["phase_s"]) >= {
                "router.queue", "router.backend_connect", "engine.queue",
                "engine.prefill", "engine.decode", "engine.detokenize",
            }
            # Attribution closes: phase sum within 10% of e2e.
            assert joined["total_s"] > 0
            assert (
                abs(joined["phase_sum_s"] - joined["total_s"])
                <= 0.10 * joined["total_s"]
            ), joined["phase_s"]
            # The debug total is the router's own e2e measurement; it must
            # agree with the client-observed wall clock too.
            assert abs(joined["total_s"] - wall_e2e) <= 0.10 * wall_e2e

            # The list endpoint shows the completed timeline.
            lresp = await client.get("/debug/requests")
            listing = await lresp.json()
            assert listing["enabled"] is True
            assert any(
                t["request_id"] == "req-trace-1" for t in listing["requests"]
            )
        finally:
            await client.close()
    finally:
        await engine.close()


async def test_request_id_echoed_on_all_paths():
    """Inbound X-Request-Id honored and echoed on success, error, and
    non-proxy paths; one is minted when absent."""
    from tests.test_router_e2e import start_fake_engine, start_router

    state, engine = await start_fake_engine()
    try:
        app, server, client = await start_router(
            [str(engine.make_url("")).rstrip("/")], ["fake/llama-3-8b"]
        )
        try:
            # Non-streaming success.
            resp = await client.post(
                "/v1/completions",
                json={"model": "fake/llama-3-8b", "prompt": "x",
                      "max_tokens": 1},
                headers={"x-request-id": "rid-ok"},
            )
            assert resp.headers["x-request-id"] == "rid-ok"
            # Error path (unknown model).
            resp = await client.post(
                "/v1/completions",
                json={"model": "nope", "prompt": "x"},
                headers={"x-request-id": "rid-err"},
            )
            assert resp.status == 400
            assert resp.headers["x-request-id"] == "rid-err"
            # Non-proxy endpoint.
            resp = await client.get(
                "/health", headers={"x-request-id": "rid-health"}
            )
            assert resp.headers["x-request-id"] == "rid-health"
            # Minted when absent.
            resp = await client.get("/v1/models")
            assert resp.headers.get("x-request-id")
        finally:
            await client.close()
    finally:
        await engine.close()


async def test_histogram_families_on_both_metrics():
    """Router and engine /metrics both expose the TTFT/ITL/e2e histogram
    families (and engine step phases) with sane bucket counts, while the
    pre-existing gauge names stay present."""
    import re as _re

    from production_stack_tpu.router.stats import vocabulary as vocab

    engine_text = await scrape_engine_metrics()
    router_text = await scrape_router_metrics()

    def bucket_counts(text, family):
        rows = []
        for line in text.splitlines():
            if line.startswith(f"{family}_bucket"):
                rows.append(float(line.rsplit(" ", 1)[1]))
        return rows

    for family in list(vocab.TPU_REQUEST_HISTOGRAMS.values()) + list(
        vocab.TPU_STEP_HISTOGRAMS.values()
    ):
        assert f"# TYPE {family} histogram" in engine_text, family
        rows = bucket_counts(engine_text, family)
        assert rows and rows == sorted(rows), family  # cumulative monotone
        count = float(
            _re.search(
                rf"^{_re.escape(family)}_count (\S+)$", engine_text, _re.M
            ).group(1)
        )
        assert rows[-1] == count  # +Inf bucket == count

    for family in vocab.ROUTER_HISTOGRAMS.values():
        assert f"# TYPE {family} histogram" in router_text, family
        rows = bucket_counts(router_text, family)
        assert rows and rows == sorted(rows), family
    # The proxied requests actually landed samples in the router's TTFT
    # and e2e families (not just empty renders).
    assert bucket_counts(router_text, "tpu_router:ttft_seconds")[-1] > 0
    assert bucket_counts(router_text, "tpu_router:e2e_latency_seconds")[-1] > 0
    # Pre-existing gauges unchanged alongside.
    for gauge in ("tpu_router:avg_ttft", "tpu_router:avg_itl",
                  "tpu_router:queueing_delay_seconds"):
        assert gauge in router_text
    assert "tpu:decode_host_gap_ms" in engine_text


async def test_mixed_window_families_on_engine_metrics():
    """The packed mixed-window families ride the engine scrape contract
    together: the prompts-per-window histogram renders (stable family
    header even at zero observations) next to the chunk-token and
    transfer-overlap counters, so dashboards keying the packing panel
    never see a partial family set."""
    from production_stack_tpu.router.stats import vocabulary as vocab

    engine_text = await scrape_engine_metrics()
    for family in (
        vocab.TPU_MIXED_WINDOW_CHUNK_TOKENS,
        vocab.TPU_WINDOW_TRANSFER_OVERLAP_SECONDS,
    ):
        assert f"# TYPE {family} counter" in engine_text, family
    hist_family = vocab.TPU_MIXED_WINDOW_PROMPTS
    assert f"# TYPE {hist_family} histogram" in engine_text
    assert f"{hist_family}_count" in engine_text


async def test_engine_debug_requests_real_engine():
    """The REAL JAX engine records a per-request span timeline: queue,
    prefill, decode, detokenize — served at /debug/requests/{id}."""
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.config import config_from_preset
    from production_stack_tpu.engine.server.api_server import build_engine_app
    from production_stack_tpu.engine.server.async_engine import AsyncEngine

    config = config_from_preset(
        "tiny-llama", **{"cache.num_blocks": 64, "scheduler.max_num_seqs": 2,
                         "scheduler.prefill_buckets": (16, 32)}
    )
    engine = AsyncEngine(config)
    server = TestServer(build_engine_app(engine, "tiny-llama"))
    await server.start_server()
    client = TestClient(server)
    try:
        resp = await client.post(
            "/v1/completions",
            json={"model": "tiny-llama", "prompt": "hi", "max_tokens": 4,
                  "ignore_eos": True},
            headers={"x-request-id": "eng-trace-1",
                     "traceparent": f"00-{'ef' * 16}-{'12' * 8}-01"},
        )
        assert resp.status == 200
        assert resp.headers["x-request-id"] == "eng-trace-1"
        dresp = await client.get("/debug/requests/eng-trace-1")
        assert dresp.status == 200
        trace = await dresp.json()
        assert trace["trace_id"] == "ef" * 16
        names = {s["name"] for s in trace["spans"]}
        assert {"engine.queue", "engine.prefill", "engine.decode",
                "engine.detokenize"} <= names
        # Spans nest inside the request window and carry sane durations.
        for span in trace["spans"]:
            assert span["duration_s"] >= 0
        assert trace["attrs"]["num_output_tokens"] == 4
        listing = await (await client.get("/debug/requests")).json()
        assert listing["enabled"] is True and listing["requests"]
    finally:
        await client.close()


def test_tracing_off_restores_fast_path():
    """obs.tracing=off: identical token streams, and ZERO observability
    state accrued per step — no histogram observations, no traces, no
    per-sequence obs bookkeeping (the no-new-allocations-style check the
    config gate promises)."""
    from production_stack_tpu.engine.config import config_from_preset
    from production_stack_tpu.engine.core.engine import LLMEngine
    from production_stack_tpu.engine.core.sequence import SamplingParams

    def run(tracing: bool):
        config = config_from_preset(
            "tiny-llama",
            **{"cache.num_blocks": 64, "scheduler.max_num_seqs": 2,
               "scheduler.prefill_buckets": (16, 32),
               "obs.tracing": tracing},
        )
        eng = LLMEngine(config)
        for i in range(2):
            eng.add_request(
                f"r{i}", prompt_token_ids=[3 + i, 5, 7, 11],
                sampling_params=SamplingParams(max_tokens=6, ignore_eos=True),
            )
        tokens = []
        while eng.has_unfinished():
            tokens.extend(
                (o.seq_id, o.new_token_id) for o in eng.step()
            )
        return eng, tokens

    eng_on, tokens_on = run(True)
    eng_off, tokens_off = run(False)
    # Greedy parity: the gate changes observability only, never outputs.
    assert tokens_on == tokens_off
    # Tracing on: state accrued.
    assert sum(h.count for h in eng_on.obs.step_hists.values()) > 0
    assert sum(h.count for h in eng_on.obs.request_hists.values()) > 0
    # Tracing on: every dispatch left a flight record.
    assert eng_on.obs.recorder.windows_recorded > 0
    # Tracing off: nothing accrued anywhere.
    assert not eng_off.obs.enabled
    assert sum(h.count for h in eng_off.obs.step_hists.values()) == 0
    assert sum(h.count for h in eng_off.obs.request_hists.values()) == 0
    assert eng_off.obs.tracer.completed() == []
    assert eng_off.obs.tracer.active_count() == 0
    # ... including the flight recorder and compile tracker (PR 17): the
    # recorder ring stays empty, on_dispatch returned None everywhere,
    # and jit entry points stayed the BARE callables (wrap() identity —
    # the byte-identical fast path, not a pass-through proxy).
    assert eng_off.obs.recorder.windows_recorded == 0
    assert eng_off.obs.recorder.snapshot() == []
    assert eng_off.obs.compile_tracker.compiled_shapes() == 0
    assert eng_off.obs.compile_tracker.snapshot() == []
    from production_stack_tpu.obs.compile_tracker import _TrackedJit
    assert not isinstance(eng_off._prefill_fn, _TrackedJit)
    assert not isinstance(eng_off._decode_fn, _TrackedJit)
    assert isinstance(eng_on._prefill_fn, _TrackedJit)
    # ... and the phase spans (PR 25): off, every phase() is ONE shared
    # null context, nothing is annotated, kept or stamped, and no launch
    # hook hangs on the tracker.
    off = eng_off.obs
    assert off.phase("build") is off.phase("collect", None, family=False)
    assert off._annotation is None and off.compile_tracker.on_launch is None
    assert off._depth == 0 and off._open_rec is None
    assert off.windows_payload()["phases"] == []
    assert off.windows_payload()["profile"] == {}
    # On: spans were kept, on the records and beside them, and all closed.
    on = eng_on.obs.windows_payload()
    assert on["phases"] and all(w["phases"] for w in on["windows"])
    assert eng_on.obs._depth == 0 and eng_on.obs._open_rec is None


async def test_idle_router_renders_histogram_family_headers():
    """Scrape-name stability: an idle router (no traffic yet) still
    exposes every tpu_router:*_seconds family header, so alert rules can
    tell 'no traffic' from 'metric gone'."""
    from production_stack_tpu.router.stats import vocabulary as vocab
    from tests.test_router_e2e import start_fake_engine, start_router

    state, engine = await start_fake_engine()
    try:
        app, server, client = await start_router(
            [str(engine.make_url("")).rstrip("/")], ["fake/llama-3-8b"]
        )
        try:
            text = await (await client.get("/metrics")).text()
            for family in vocab.ROUTER_HISTOGRAMS.values():
                assert f"# TYPE {family} histogram" in text, family
        finally:
            await client.close()
    finally:
        await engine.close()


async def test_router_no_tracing_flag():
    """--no-tracing: /debug/requests reports disabled, per-id lookups 404,
    but proxying, request-id echo, and histograms keep working."""
    from tests.test_router_e2e import start_fake_engine, start_router

    state, engine = await start_fake_engine()
    try:
        app, server, client = await start_router(
            [str(engine.make_url("")).rstrip("/")], ["fake/llama-3-8b"],
            extra_args=["--no-tracing"],
        )
        try:
            resp = await client.post(
                "/v1/completions",
                json={"model": "fake/llama-3-8b", "prompt": "x",
                      "max_tokens": 1},
                headers={"x-request-id": "rid-notrace"},
            )
            assert resp.status == 200
            assert resp.headers["x-request-id"] == "rid-notrace"
            listing = await (await client.get("/debug/requests")).json()
            assert listing == {"enabled": False, "requests": []}
            dresp = await client.get("/debug/requests/rid-notrace")
            assert dresp.status == 404
            text = await (await client.get("/metrics")).text()
            assert "tpu_router:ttft_seconds_bucket" in text
        finally:
            await client.close()
    finally:
        await engine.close()


def test_servicemonitors_match_chart_ports_and_labels():
    with open(os.path.join(OBS_DIR, "kube-prom-stack.yaml")) as f:
        prom = yaml.safe_load(f)
    monitors = {
        m["name"]: m
        for m in prom["prometheus"]["prometheusSpec"]["additionalServiceMonitors"]
    }
    with open(os.path.join(CHART_DIR, "values-tpu-example.yaml")) as f:
        values = yaml.safe_load(f)
    rendered = render_chart(CHART_DIR, values, release_name="mon")
    services = [
        doc for text in rendered.values() for doc in yaml.safe_load_all(text)
        if doc and doc.get("kind") == "Service"
    ]

    def service_matching(selector_labels):
        return [
            s for s in services
            if all(
                s["metadata"]["labels"].get(k) == v
                for k, v in selector_labels.items()
            )
        ]

    for name, port_owner in [
        ("tpu-engine-monitor", "engine-service"),
        ("tpu-router-monitor", "router-service"),
    ]:
        monitor = monitors[name]
        matched = service_matching(monitor["selector"]["matchLabels"])
        assert matched, f"{name} selector matches no chart Service"
        port_name = monitor["endpoints"][0]["port"]
        for service in matched:
            assert port_name in {
                p["name"] for p in service["spec"]["ports"]
            }, f"{name}: port {port_name} absent from {service['metadata']['name']}"
