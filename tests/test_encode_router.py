"""Router-side encode lane (docs/router.md "Encode lanes & semantic
cache") against fake engines — no jax:

* routing pool selection: prefer_encode_pool / encode_capable units and
  the per-lane admission pool (lane="encode" vs "generate");
* e2e: embed traffic lands on the dedicated encode-role backend while
  generation avoids it; pool="encode" headroom renders on /metrics;
* the semantic cache: repeat /v1/embeddings answered byte-identically
  with ZERO engine work (x-encode-cache: hit), rerank similarity tier
  through the embed-lane vectorizer, byte-bound eviction;
* FleetHarness mixed generation+embed replay completes both lanes.
"""

import asyncio

import pytest

from production_stack_tpu.router.capacity import FleetAdmission
from production_stack_tpu.router.routing.base import (
    exclude_prefill_role,
    prefer_encode_pool,
)
from production_stack_tpu.router.service_discovery import (
    EndpointInfo,
    encode_capable,
)
from production_stack_tpu.testing.fake_engine import fake_embedding
from production_stack_tpu.testing.fleet import FleetHarness

from tests.test_router_e2e import start_fake_engine, start_router


async def _encode_cache_stored(app, entries: int = 1) -> None:
    """The store runs as a background task after the response: wait for
    its outcome (the cache holds the entry), not for a length of time
    that a loaded machine overruns."""
    from production_stack_tpu.router.encode_cache import ENCODE_CACHE_SERVICE

    cache = app["registry"].get(ENCODE_CACHE_SERVICE)
    for _ in range(2000):
        if cache.size >= entries:
            return
        await asyncio.sleep(0.01)
    raise AssertionError(f"encode cache never stored {entries} entries")


def eps(*urls, roles=None):
    return [
        EndpointInfo(url=u, model_names=["m"], role=(roles[i] if roles else None))
        for i, u in enumerate(urls)
    ]


# -- pool selection units ----------------------------------------------------


def test_encode_pool_preference_order():
    fused = eps("http://fused")[0]
    enc = eps("http://enc", roles=["encode"])[0]
    pre = eps("http://pre", roles=["prefill"])[0]
    dec = eps("http://dec", roles=["decode"])[0]
    # Dedicated encode members win outright; fused is the fallback;
    # a role-less fleet passes through untouched.
    assert prefer_encode_pool([fused, enc, pre, dec]) == [enc]
    assert prefer_encode_pool([fused, pre, dec]) == [fused]
    assert prefer_encode_pool([pre, dec]) == [pre, dec]  # degrade, never 500
    # encode_capable = the admission view: dedicated + fused.
    assert encode_capable([fused, enc, pre, dec]) == [fused, enc]
    # Generation routing treats encode pools like prefill pools: out.
    assert exclude_prefill_role([fused, enc, pre, dec]) == [fused, dec]
    assert exclude_prefill_role([enc]) == [enc]  # degrade when nothing else
    # The two compose: a pure-encode pick still routes after the
    # generation filter degrades (no empty-candidate dead end).
    assert exclude_prefill_role(prefer_encode_pool([fused, enc])) == [enc]


def test_admission_pool_per_lane():
    fleet = eps(
        "http://fused", "http://enc", "http://pre", "http://dec",
        roles=[None, "encode", "prefill", "decode"],
    )
    pool_name, pool = FleetAdmission._admission_pool(fleet, "encode")
    assert pool_name == "encode"
    assert [e.url for e in pool] == ["http://fused", "http://enc"]
    pool_name, pool = FleetAdmission._admission_pool(fleet, "generate")
    assert pool_name == "decode"
    assert [e.url for e in pool] == ["http://fused", "http://dec"]
    # No encode-capable member at all: degrade to the whole fleet
    # rather than shedding everything against an empty pool.
    only_roles = eps("http://pre", "http://dec", roles=["prefill", "decode"])
    pool_name, pool = FleetAdmission._admission_pool(only_roles, "encode")
    assert pool_name == "fleet" and len(pool) == 2


# -- e2e: lane routing + headroom gauge --------------------------------------


async def test_embed_traffic_prefers_encode_pool_e2e():
    s_enc, e_enc = await start_fake_engine(model="m")
    s_gen, e_gen = await start_fake_engine(model="m")
    urls = [str(s.make_url("")).rstrip("/") for s in (e_enc, e_gen)]
    try:
        app, server, client = await start_router(
            urls, ["m", "m"],
            extra_args=("--static-backend-roles", "encode,"),
        )
        try:
            for _ in range(3):
                resp = await client.post(
                    "/v1/embeddings", json={"model": "m", "input": "doc"}
                )
                assert resp.status == 200
            resp = await client.post("/v1/chat/completions", json={
                "model": "m", "stream": False, "max_tokens": 4,
                "messages": [{"role": "user", "content": "hi"}],
            })
            assert resp.status == 200
            # Embeds all landed on the dedicated encode member;
            # generation avoided it.
            assert s_enc.encode_texts_total == 3
            assert s_gen.encode_texts_total == 0
            assert s_enc.total_finished == 0
            assert s_gen.total_finished == 1
            metrics = await (await client.get("/metrics")).text()
            assert 'tpu_router:fleet_headroom_slots{pool="encode"}' in metrics
        finally:
            await client.close()
            await server.close()
    finally:
        await e_enc.close()
        await e_gen.close()


# -- e2e: semantic cache -----------------------------------------------------


async def test_repeat_embeddings_served_from_cache_byte_identical():
    state, engine = await start_fake_engine(model="m")
    url = str(engine.make_url("")).rstrip("/")
    try:
        app, server, client = await start_router(
            [url], ["m"],
            extra_args=("--encode-cache-max-bytes", "1000000"),
        )
        try:
            body = {"model": "m", "input": ["repeat doc one", "repeat doc two"]}
            first = await client.post("/v1/embeddings", json=body)
            assert first.status == 200
            assert "x-encode-cache" not in first.headers
            first_bytes = await first.read()
            assert state.encode_texts_total == 2
            await _encode_cache_stored(app)

            async def cache_hits() -> float:
                # The counter is the process's: another test file on this
                # xdist worker may have counted hits before this one.
                text = await (await client.get("/metrics")).text()
                line = next(
                    (ln for ln in text.splitlines() if ln.startswith(
                        "tpu_router:semantic_cache_hits_total ")), "x 0")
                return float(line.split()[1])

            before = await cache_hits()
            second = await client.post("/v1/embeddings", json=body)
            assert second.status == 200
            assert second.headers.get("x-encode-cache") == "hit"
            assert await second.read() == first_bytes  # byte-identical
            assert state.encode_texts_total == 2  # ZERO extra engine work
            assert await cache_hits() == before + 1.0
        finally:
            await client.close()
            await server.close()
    finally:
        await engine.close()


async def test_cache_hits_are_engine_independent():
    """fake_embedding is a function of the text alone, so a cache entry
    stored from one engine is bit-identical to what any OTHER engine
    would have answered — the property that makes verbatim replay safe
    on a fleet."""
    s1, e1 = await start_fake_engine(model="m")
    s2, e2 = await start_fake_engine(model="m")
    urls = [str(s.make_url("")).rstrip("/") for s in (e1, e2)]
    try:
        app, server, client = await start_router(
            urls, ["m", "m"],
            extra_args=("--routing-logic", "roundrobin",
                        "--encode-cache-max-bytes", "1000000"),
        )
        try:
            body = {"model": "m", "input": "fleet-stable doc"}
            r1 = await client.post("/v1/embeddings", json=body)
            b1 = await r1.read()
            await _encode_cache_stored(app)
            r2 = await client.post("/v1/embeddings", json=body)
            b2 = await r2.read()
            assert r2.headers.get("x-encode-cache") == "hit"
            assert b1 == b2
            # And the underlying engines agree bit-for-bit anyway.
            assert fake_embedding("fleet-stable doc") == \
                fake_embedding("fleet-stable doc")
            assert s1.encode_texts_total + s2.encode_texts_total == 1
        finally:
            await client.close()
            await server.close()
    finally:
        await e1.close()
        await e2.close()


async def test_rerank_similarity_tier_e2e():
    """Same corpus, drifted query: answered from the similarity tier via
    ONE embed-lane forward (the query), not N+1."""
    state, engine = await start_fake_engine(model="m")
    url = str(engine.make_url("")).rstrip("/")
    # fake_embedding is deterministic, so these cosines are fixtures:
    # cos(q_stored, q_near) ~= 0.191, cos(q_stored, q_far) ~= -0.058.
    q_stored = "which document covers pricing"
    q_near = "what document covers pricing"
    q_far = "which doc covers pricing"
    near = sum(a * b for a, b in zip(
        fake_embedding(q_stored), fake_embedding(q_near)))
    far = sum(a * b for a, b in zip(
        fake_embedding(q_stored), fake_embedding(q_far)))
    assert far < 0.1 < near  # the threshold below separates them
    docs = ["pricing sheet", "security whitepaper"]
    try:
        app, server, client = await start_router(
            [url], ["m"],
            extra_args=("--encode-cache-max-bytes", "1000000",
                        "--encode-cache-similarity-threshold", "0.1"),
        )
        try:
            r = await client.post("/v1/rerank", json={
                "model": "m", "query": q_stored, "documents": docs,
            })
            assert r.status == 200
            stored_bytes = await r.read()
            # Background store vectorizes the query through the engine.
            await _encode_cache_stored(app)
            base_texts = state.encode_texts_total
            r = await client.post("/v1/rerank", json={
                "model": "m", "query": q_near, "documents": docs,
            })
            assert r.headers.get("x-encode-cache") == "similar"
            assert await r.read() == stored_bytes
            # The hit cost ONE embed forward (the lookup vectorize) —
            # not len(docs) + 1.
            assert state.encode_texts_total == base_texts + 1
            # Below-threshold query: full rerank at the engine.
            r = await client.post("/v1/rerank", json={
                "model": "m", "query": q_far, "documents": docs,
            })
            assert "x-encode-cache" not in r.headers
            assert r.status == 200
        finally:
            await client.close()
            await server.close()
    finally:
        await engine.close()


# -- mixed-workload replay ---------------------------------------------------


@pytest.mark.chaos
async def test_mixed_generation_embed_replay():
    """FleetHarness replay with an embed fraction: both lanes complete
    through the real router, repeat-heavy embeds land cache-serveable
    outcomes, and nothing is dropped."""
    h = FleetHarness(
        num_engines=3, seed=7, capacity=4, max_queued=16,
        tokens_per_sec=400.0, ttft=0.005,
        router_args=("--encode-cache-max-bytes", "1000000"),
    )
    await h.start(active=3)
    try:
        await h.replay(
            duration_s=2.0, base_qps=10.0, peak_qps=20.0,
            embed_frac=0.4, embed_repeat_pool=5,
        )
        await h.wait_background()
        rep = h.report()
        kinds = rep["by_kind"] if "by_kind" in rep else rep
        completed = sum(
            1 for o in h.outcomes
            if o.phase == "replay" and o.kind == "completed"
        )
        assert completed > 10, rep
        assert not any(o.kind in ("dropped", "error") for o in h.outcomes), rep
        # The repeat pool (5 docs) under dozens of embeds: the cache
        # must have absorbed repeats — engines saw fewer texts than the
        # embed requests sent.
        served = sum(be.state.encode_texts_total for be in h.backends)
        embed_outcomes = [
            o for o in h.outcomes if o.kind == "completed" and o.chunks == 1
        ]
        if len(embed_outcomes) >= 10:
            assert served < len(embed_outcomes), (
                served, len(embed_outcomes))
    finally:
        await h.close()
