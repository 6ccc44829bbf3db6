"""Fleet-level prefix-popularity routing + the multi-round-QA harness.

Tier-1 coverage for ISSUE 13: the popularity view's hot-classification /
replica-set mechanics as units, the pod-churn prune contract, the
scraped-truth reconcile, and the FleetHarness variant of the north-star
workload (``testing/multi_round.py``) with a seeded replay asserting
kv_aware+popularity >= session-affinity on fleet KV hit rate and that
the shared system prompt ends up resident on more than one backend.
"""

import dataclasses
from typing import Dict

import pytest

from production_stack_tpu.router.routing import build_routing_logic
from production_stack_tpu.router.routing.kv_aware import KVAwareRouter
from production_stack_tpu.router.service_discovery import EndpointInfo
from production_stack_tpu.router.stats.engine_stats import EngineStats


@dataclasses.dataclass
class FakeRequest:
    headers: Dict[str, str] = dataclasses.field(default_factory=dict)


def eps(*urls, model="m"):
    return [EndpointInfo(url=u, model_names=[model]) for u in urls]


def chat(text: str):
    return {"model": "m", "messages": [{"role": "user", "content": text}]}


SHARED = "shared system prompt " * 200          # ~4.2k chars, >3 chunks
def user_body(uid: int, rounds: int = 1):
    text = SHARED + f"For user {uid}: " + f"context-{uid} " * 150
    for r in range(2, rounds + 1):
        text += f" round-{r} answer words for user {uid} " * 40
    return chat(text)


class FakeClock:
    def __init__(self):
        self.t = 1000.0

    def __call__(self):
        return self.t


# -- popularity unit mechanics ----------------------------------------------


def test_shared_prefix_classified_by_divergence():
    """Chunks at/before a >=3-way chain divergence classify shared; the
    per-user tails never do."""
    r = build_routing_logic("kv_aware_popularity")
    endpoints = eps("http://a", "http://b", "http://c")
    for uid in range(1, 5):
        r.route_request(endpoints, {}, {}, FakeRequest(), user_body(uid))
    from production_stack_tpu.router.routing.kv_aware import (
        extract_prompt_text,
    )

    h = r._prefix_hashes(extract_prompt_text(user_body(1)))
    flags = [d in r._shared for d in h]
    # The shared head spans the leading chunks; the user tail is not shared.
    assert flags[0] is True
    assert flags[-1] is False
    # Shared is prefix-closed: once False, never True again.
    assert flags == sorted(flags, reverse=True)


def test_popularity_fixes_shared_head_flip_flop():
    """The single-owner LRU pathology: when user B routes elsewhere, the
    shared head's owner flips and user A's deep-tail affinity reads zero
    on the backend that HAS its whole history.  Popularity mode keeps
    the tail match alive (shared chunks are transparent)."""
    endpoints = eps("http://a", "http://b")
    clock = FakeClock()
    plain = KVAwareRouter(clock=clock)
    pop = KVAwareRouter(popularity=True, hot_threshold=2.0, clock=clock)

    for router in (plain, pop):
        # User 1 sticks to some backend over two rounds.
        first = router.route_request(
            endpoints, {}, {}, FakeRequest(), user_body(1))
        # Users 2..4 flip the shared head's ownership away.
        for uid in (2, 3, 4):
            stats = {first: EngineStats(num_running_requests=50)}
            router.route_request(
                endpoints, stats, {}, FakeRequest(), user_body(uid))
        hashes = router._prefix_hashes(
            __import__(
                "production_stack_tpu.router.routing.kv_aware",
                fromlist=["extract_prompt_text"],
            ).extract_prompt_text(user_body(1, rounds=2))
        )
        credit = router._matched_chunks(hashes, first, clock())
        if router is plain:
            # Head owner flipped -> the walk breaks at chunk 0.
            assert credit == 0.0
        else:
            # Tail match survives the head churn.
            assert credit >= 1.0


def test_replica_set_grows_under_load_and_decays():
    clock = FakeClock()
    r = KVAwareRouter(
        popularity=True, hot_threshold=2.0, load_tradeoff=2.0,
        hot_credit_cap=1.0, replica_ttl_s=60.0, clock=clock,
    )
    endpoints = eps("http://a", "http://b", "http://c")
    body = chat(SHARED)
    owner = r.route_request(endpoints, {}, {}, FakeRequest(), body)
    # Light load: requests keep landing on the owner (no growth).
    for _ in range(5):
        assert r.route_request(endpoints, {}, {}, FakeRequest(), body) == owner
    assert r.popularity_snapshot()["replica_set_max"] == 1
    # Owner degrades past tradeoff*cap: a non-member wins and JOINS.
    stats = {owner: EngineStats(num_running_requests=10)}
    second = r.route_request(endpoints, stats, {}, FakeRequest(), body)
    assert second != owner
    assert r.popularity_snapshot()["replica_set_max"] == 2
    # Decay shrink: members not routed to within the TTL drop out.
    clock.t += 120.0
    r.route_request(endpoints, {}, {}, FakeRequest(), body)
    assert r.popularity_snapshot()["replica_set_max"] == 1


def test_hot_promotion_counts_and_snapshot():
    r = KVAwareRouter(popularity=True, hot_threshold=2.0)
    endpoints = eps("http://a", "http://b")
    body = chat(SHARED)
    for _ in range(4):
        r.route_request(endpoints, {}, {}, FakeRequest(), body)
    snap = r.popularity_snapshot()
    assert snap["hot_prefixes"] >= 1
    assert snap["hot_promotions_total"] >= 1
    assert snap["replica_set_max"] >= 1


def test_prune_drops_departed_backends():
    """Pod churn: owners, replica-set members, and scraped-truth state
    for backends that left discovery are dropped (the CapacityModel
    .prune contract) — stale owners must not keep pulling affinity score
    toward dead endpoints."""
    r = build_routing_logic("kv_aware_popularity", hot_threshold=2.0)
    endpoints = eps("http://a", "http://b", "http://c")
    for uid in range(1, 5):
        r.route_request(endpoints, {}, {}, FakeRequest(), user_body(uid))
    used = set(r._prefix_owner.values()) | {
        u for reps in r._replicas.values() for u in reps
    }
    assert used  # routing recorded some state
    victim = sorted(used)[0]
    live = [ep.url for ep in endpoints if ep.url != victim]
    gone = r.prune(live)
    assert victim in gone
    assert victim not in set(r._prefix_owner.values())
    assert all(victim not in reps for reps in r._replicas.values())
    # Scoring no longer credits the departed backend.
    from production_stack_tpu.router.routing.kv_aware import (
        extract_prompt_text,
    )

    for uid in range(1, 5):
        h = r._prefix_hashes(extract_prompt_text(user_body(uid)))
        assert r._matched_chunks(h, victim, r._clock()) == 0.0


def test_reconcile_purges_backend_whose_cache_reset():
    """Scraped-truth correction: a backend whose tpu:prefix_cache_blocks
    collapsed between scrapes (engine restart) is purged from the owner
    map — the router must not route affinity toward an empty cache."""
    clock = FakeClock()
    r = KVAwareRouter(
        popularity=True, hot_threshold=2.0, reconcile_interval_s=0.0,
        clock=clock,
    )
    endpoints = eps("http://a", "http://b")
    healthy = {
        "http://a": EngineStats(prefix_cache_blocks=500.0),
        "http://b": EngineStats(prefix_cache_blocks=500.0),
    }
    served = r.route_request(
        endpoints, healthy, {}, FakeRequest(), user_body(1))
    clock.t += 1.0
    r.route_request(endpoints, healthy, {}, FakeRequest(), user_body(2))
    assert served in set(r._prefix_owner.values()) | {
        u for reps in r._replicas.values() for u in reps
    }
    from production_stack_tpu.router.routing.kv_aware import (
        extract_prompt_text,
    )

    user1_hashes = r._prefix_hashes(extract_prompt_text(user_body(1)))
    assert r._matched_chunks(user1_hashes, served, clock()) > 0
    # The serving backend restarts: cache size collapses.  The reconcile
    # pass (riding the next routed request) must purge every prefix the
    # router believed resident there — user 1's history included.  The
    # same request may legitimately re-record ITS OWN chain on the
    # purged backend afterward, so assert on user 1's digests, not on
    # global absence.
    reset = dict(healthy)
    reset[served] = EngineStats(prefix_cache_blocks=2.0)
    clock.t += 1.0
    r.route_request(endpoints, reset, {}, FakeRequest(), user_body(3))
    # User 1's full-credit tail is purged; at most the capped shared-head
    # credit remains (user 3's request may have re-replicated the head
    # onto the restarted backend, which is correct — it re-prefilled it).
    assert r._matched_chunks(user1_hashes, served, clock()) < 1.0


def test_plain_kv_aware_unchanged_by_popularity_plumbing():
    """popularity=False keeps legacy single-owner semantics: no hot
    state, no shared classification in scoring."""
    r = build_routing_logic("kv_aware")
    endpoints = eps("http://a", "http://b", "http://c")
    body = chat("sys" * 2000 + "tail-x " * 300)
    first = r.route_request(endpoints, {}, {}, FakeRequest(), body)
    assert r.route_request(endpoints, {}, {}, FakeRequest(), body) == first
    assert r.popularity_snapshot()["hot_prefixes"] == 0


def test_short_prompt_still_gets_affinity():
    """Sub-chunk prompts hash as one whole-text chunk (the full-chunks-
    only rule must not zero out short-prompt affinity)."""
    r = build_routing_logic("kv_aware")
    endpoints = eps("http://a", "http://b")
    body = chat("short question")
    first = r.route_request(endpoints, {}, {}, FakeRequest(), body)
    assert r.route_request(endpoints, {}, {}, FakeRequest(), body) == first


# -- the north-star workload on the FleetHarness ----------------------------


@pytest.mark.asyncio
async def test_multi_round_popularity_vs_session_fleet():
    """Seeded FleetHarness replay of the CI-scaled canonical workload:
    zero failures, the popularity view engaged, the shared-system-prompt
    prefix resident on >1 backend, and kv_aware+popularity's fleet KV hit
    rate level with session affinity's.

    What is compared is placement, in tokens.  Session affinity places by
    hash, so its hit rate moves by a chunk or two between runs (0.8315 to
    0.8316 of the queries); the popularity router places by the load it
    sees, which is the machine's as much as the fleet's, and on a loaded
    machine a user or two re-prefill a history elsewhere (one such move
    is 0.4 % of the query tokens: runs beside eight busy cores read
    0.8274-0.84).  So the bound is a few moves wide; what the popularity
    view is for — the single-owner router's flip-flop, 0.3 below — is the
    next test's."""
    from production_stack_tpu.testing.multi_round import (
        MultiRoundFleetConfig,
        run_fleet_multi_round,
    )

    cfg = MultiRoundFleetConfig(seed=0)
    session = await run_fleet_multi_round("session", cfg)
    pop = await run_fleet_multi_round("kv_aware_popularity", cfg)

    assert session["failed"] == 0 and pop["failed"] == 0
    assert pop["requests"] == cfg.num_users * cfg.num_rounds
    assert pop["kv_hit_rate"] >= session["kv_hit_rate"] - 0.02, (pop, session)
    assert pop["shared_prefix_backends"] > 1, pop
    # The popularity view actually engaged.
    assert pop["popularity"]["hot_prefixes"] >= 1
    assert pop["popularity"]["replica_set_max"] >= 2


@pytest.mark.asyncio
async def test_multi_round_popularity_beats_kv_aware_flip_flop():
    """The tentpole's motivating pathology, asserted at fleet scale: the
    single-owner kv_aware router loses the shared head to ownership
    flip-flop and lands FAR below popularity on both hit rate and TTFT
    p50 under the same seeded replay."""
    from production_stack_tpu.testing.multi_round import (
        MultiRoundFleetConfig,
        run_fleet_multi_round,
    )

    cfg = dataclasses.replace(
        MultiRoundFleetConfig(),
        num_engines=6, num_users=13, num_rounds=3, qps=14.0,
        join_window_s=2.0, seed=0,
    )
    kv = await run_fleet_multi_round("kv_aware", cfg)
    pop = await run_fleet_multi_round("kv_aware_popularity", cfg)
    assert pop["kv_hit_rate"] > kv["kv_hit_rate"] + 0.05, (pop, kv)
    assert pop["ttft_p50_ms"] < kv["ttft_p50_ms"], (pop, kv)


# -- fake-engine prefix/prefill cost model ----------------------------------


def test_fake_engine_chunked_prefix_accounting():
    from production_stack_tpu.testing.fake_engine import FakeEngineState

    st = FakeEngineState(prefix_chunk_chars=64)
    text = "x" * 640
    uncached, imported = st.note_prompt(text)
    assert uncached == 640 and imported == 0
    assert st.prefix_hit_tokens == 0
    assert st.prefix_query_tokens == 160
    # Same prompt again: full hit.
    uncached, _ = st.note_prompt(text)
    assert uncached == 0
    assert st.prefix_hit_tokens == 160
    # Extended prompt: only the extension is cold.
    uncached, _ = st.note_prompt(text + "y" * 128)
    assert uncached == 128
    assert st.prefix_cached_chunks == 12  # 10 + 2 extension chunks


def test_fake_engine_store_import_counts_as_hit():
    from production_stack_tpu.testing.fake_engine import FakeEngineState

    store: set = set()
    a = FakeEngineState(
        prefix_chunk_chars=64, shared_store=store, remote_store_import=True)
    b = FakeEngineState(
        prefix_chunk_chars=64, shared_store=store, remote_store_import=True)
    text = "z" * 640
    a.note_prompt(text)               # computes + exports to the store
    uncached, imported = b.note_prompt(text)
    assert imported == 640 and uncached == 0
    assert b.prefix_hit_tokens == 160  # imports land in the prefix cache


def test_fake_engine_prefill_cost_model_gated_off_by_default():
    from production_stack_tpu.testing.fake_engine import FakeEngineState

    st = FakeEngineState()
    assert st.prefill_seconds(100000, 0) == 0.0
    st2 = FakeEngineState(prefill_chars_per_sec=10000.0)
    assert st2.prefill_seconds(10000, 0) == pytest.approx(1.0)
