"""KV-cache-aware (prefix-affinity) routing, with an optional
fleet-level prefix-popularity view (``kv_aware_popularity``).

Not present in the reference: its only KV-locality mechanism is session
stickiness (routing_logic.py:79-172) + LMCache offload.  On TPU, prefix reuse
is the dominant TTFT lever (the multi-round-QA workload re-sends a 1,000-token
system prompt and up to 20,000 tokens of history every round, see
benchmarks/multi-round-qa/run.sh:43-48) — so the router itself tracks which
engine has most recently served each prompt prefix and routes to maximize
paged-KV prefix-cache hits, balanced against queue depth.

Mechanism: the request's prompt text is split into fixed-size chunks; each
cumulative chunk-prefix hash is remembered in a bounded LRU mapping to the
engine that served it.  Scoring an endpoint combines (matched prefix length)
against (engine load), so a hot engine does not melt down just because it
owns a popular prefix.

Popularity mode (``popularity=True``, routing logic
``kv_aware_popularity``): the single-owner LRU has an adversarial failure
under SHARED prefixes — the fleet's hottest prefix (the multi-round-QA
shared system prompt) is the head of EVERY user's chain, so whichever
backend served the last request owns the head, every other user's
affinity walk breaks at chunk 0, and the hot prefix both funnels onto
one replica (DistServe/Splitwise's locality warning) and flip-flops
ownership so even deep per-user tails score zero.  Popularity mode fixes
both: each digest carries a decayed request-frequency counter; digests
past ``hot_threshold`` are HOT and matched against a *replica set* of
owners instead of one backend.  The set grows when every current member
is degraded enough (queue/capacity score) that a non-member wins the
load-vs-affinity score — the new member cold-prefills once (or warms the
prefix through the shared KV store when one is configured: the PR-4
prefetch plane imports the exported chain instead of recomputing) and
serves it hot from then on; members idle past ``replica_ttl_s`` decay
out, and a digest whose popularity decays below half the threshold
demotes back to single-owner.  Long per-user tails stay effectively
session-sticky: their digests never get hot, so the deep chain match
keeps pulling a user to the backend holding their history unless it is
badly overloaded.

The owner map is additionally corrected against scraped REALITY, not
just the router's own routing history: the engine exports its
prefix-cache truth (``tpu:prefix_cache_blocks`` size gauge +
hit/query-token counters, threaded through ``EngineStats``), and a
backend whose cached-block count collapses between scrapes (restart,
cache flush) is purged from the owner map and every replica set — the
router must not keep scoring affinity toward a cache that no longer
exists.

Hash contract: with a ``tokenize`` callable the router derives its prefix
keys from the ENGINE'S OWN chain — ``prefix_block_hashes`` over token-id
blocks (engine/kv/block_pool.py: hashlib and numpy, no JAX; blake2b over
the previous digest and the block's ids as little-endian int32),
byte-identical to the engine's ``Sequence.prefix_chain`` and therefore to
the content keys
under which engines export/import KV blocks through the shared store.  A
silent divergence here would steer "affine" requests to replicas whose
store entries never match (tests/test_kv_prefetch.py asserts the
contract).  Without a tokenizer the router falls back to the text-chunk
heuristic, which still captures affinity but makes no key-equality
claim.
"""

from __future__ import annotations

import hashlib
import json
import threading
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional

from production_stack_tpu.router.routing.base import (
    RoutingInterface,
    effective_load,
    exclude_prefill_role,
    require_endpoints,
)
from production_stack_tpu.router.service_discovery import EndpointInfo


def extract_prompt_text(request_json: Optional[Dict[str, Any]]) -> str:
    """Canonical prompt text from a chat-completion or completion body."""
    if not request_json:
        return ""
    if "messages" in request_json:
        parts = []
        for msg in request_json.get("messages") or []:
            content = msg.get("content") if isinstance(msg, dict) else None
            if isinstance(content, str):
                parts.append(f"{msg.get('role', '')}:{content}")
            elif isinstance(content, list):  # multimodal content parts
                parts.append(json.dumps(content, sort_keys=True, default=str))
        return "\n".join(parts)
    prompt = request_json.get("prompt")
    if isinstance(prompt, str):
        return prompt
    if isinstance(prompt, list):
        return "\n".join(str(p) for p in prompt)
    return ""


class KVAwareRouter(RoutingInterface):
    def __init__(
        self,
        chunk_chars: int = 1024,
        max_tracked_prefixes: int = 65536,
        load_tradeoff: float = 2.0,
        tokenize=None,
        token_block_size: int = 16,
        popularity: bool = False,
        hot_threshold: float = 8.0,
        popularity_halflife_s: float = 60.0,
        max_replicas: int = 8,
        replica_ttl_s: float = 300.0,
        hot_credit_cap: float = 0.5,
        shared_threshold: float = 32.0,
        reconcile_interval_s: float = 5.0,
        clock=time.monotonic,
    ):
        self.chunk_chars = int(chunk_chars)
        self.max_tracked_prefixes = int(max_tracked_prefixes)
        # How many chunks of prefix-match one unit of queue depth is worth.
        self.load_tradeoff = float(load_tradeoff)
        # Optional exact-contract mode: tokenize(text) -> List[int]; the
        # prefix keys then ARE the engine's KV-block content-key chain
        # (module docstring), so affinity scoring tracks real store/
        # prefix-cache hits instead of a text heuristic.
        self.tokenize = tokenize
        self.token_block_size = int(token_block_size)
        # -- popularity view (module docstring) ---------------------------
        self.popularity = bool(popularity)
        self.hot_threshold = float(hot_threshold)
        self.popularity_halflife_s = float(popularity_halflife_s)
        self.max_replicas = int(max_replicas)
        self.replica_ttl_s = float(replica_ttl_s)
        # Affinity-credit cap for fleet-SHARED chunks (the >= 3-way
        # chain-divergence / shared_threshold classifier below): shared
        # content is cheap to replicate (one cold prefill — or a store
        # import, when a store is configured — and it serves hot
        # forever), so matching it must not let a replica hoard traffic
        # deep into queueing the way an irreplaceable per-user tail
        # legitimately does.  Non-shared chunks (tails) keep full
        # per-chunk credit even when hot: losing one means re-prefilling
        # a user's whole history somewhere else.  The cap IS the
        # replication pacing: a non-member wins the score (and joins the
        # replica set) once every member queues deeper than
        # ``load_tradeoff * hot_credit_cap``.
        self.hot_credit_cap = float(hot_credit_cap)
        # Decayed popularity past which a digest classifies fleet-SHARED
        # even before it spreads to 3 owners (the head crosses this
        # within the first seconds of fleet traffic; a per-user tail —
        # bumped once per conversation round — never gets near it).
        self.shared_threshold = float(shared_threshold)
        self.reconcile_interval_s = float(reconcile_interval_s)
        self._clock = clock
        self._lock = threading.Lock()
        self._prefix_owner: "OrderedDict[str, str]" = OrderedDict()
        # digest -> [decayed_count, stamp, successor_digests]; LRU-bounded
        # with the owner map.  ``successor_digests`` (capped small set)
        # counts the DISTINCT next-chunk digests observed after this one
        # — the structural fleet-shared classifier: a divergence point
        # where >= 3 different chains continue is the boundary of
        # genuinely shared content (the system prompt ends and per-user
        # text begins), and every chunk at or before such a boundary is
        # shared by construction.  A per-user tail chunk's successor is
        # the SAME digest every round (chain hashing is deterministic),
        # so tails never classify shared no matter how often one user
        # re-asks.
        self._pop: "OrderedDict[str, list]" = OrderedDict()
        # Digests known to be fleet-shared content (prefix-closed: a
        # divergence point marks itself and everything before it).
        self._shared: set = set()
        # Hot digests and their replica sets (digest -> url -> last stamp).
        self._hot: set = set()
        self._replicas: Dict[str, "OrderedDict[str, float]"] = {}
        # Monotonic promotion counter (tpu_router:prefix_hot_total feed).
        self.hot_promotions_total = 0
        # Scraped prefix-cache truth per url: last cached-blocks reading.
        self._truth_blocks: Dict[str, float] = {}
        self._last_reconcile = 0.0

    def _prefix_hashes(self, text: str) -> List[str]:
        if self.tokenize is not None:
            from production_stack_tpu.engine.kv.block_pool import (
                prefix_block_hashes,
            )

            return [
                digest.hex()
                for digest in prefix_block_hashes(
                    self.tokenize(text), self.token_block_size
                )
            ]
        # FULL chunks only, mirroring the engine's prefix_block_hashes
        # (full blocks, leave-one-token): a partial final chunk's digest
        # changes every time the conversation grows, so it never matches
        # anything next round — and worse, it manufactures a fresh
        # "successor" per round, which would falsely classify a per-user
        # tail as a fleet-shared divergence point (popularity mode).
        # Prompts shorter than one chunk hash as a single whole-text
        # chunk so short-prompt affinity still exists.
        hashes = []
        h = hashlib.blake2b(digest_size=8)
        n_full = len(text) // self.chunk_chars
        if n_full == 0 and text:
            h.update(text.encode("utf-8"))
            return [h.hexdigest()]
        for i in range(n_full):
            start = i * self.chunk_chars
            h.update(text[start : start + self.chunk_chars].encode("utf-8"))
            hashes.append(h.hexdigest())
        return hashes

    # -- popularity bookkeeping (all under self._lock) ---------------------

    def _decayed(self, digest: str, now: float) -> float:
        entry = self._pop.get(digest)
        if entry is None:
            return 0.0
        value, stamp = entry[0], entry[1]
        if now > stamp:
            value *= 0.5 ** ((now - stamp) / self.popularity_halflife_s)
        return value

    def _bump_popularity(self, hashes: List[str], now: float) -> None:
        """Decayed per-digest request counters + successor tracking;
        crossing ``hot_threshold`` promotes to hot (replica-set
        matching), decaying below half of it demotes back to
        single-owner.  Chunks at or before a divergence point (>= 3
        distinct successors) — or past ``shared_threshold`` popularity —
        classify as fleet-SHARED, which caps their affinity credit."""
        shared_upto = -1
        for i, digest in enumerate(hashes):
            entry = self._pop.get(digest)
            value = self._decayed(digest, now) + 1.0
            successors = entry[2] if entry is not None else set()
            if i + 1 < len(hashes) and len(successors) < 3:
                successors.add(hashes[i + 1])
            self._pop[digest] = [value, now, successors]
            self._pop.move_to_end(digest)
            if digest not in self._shared and (
                len(successors) >= 3 or value >= self.shared_threshold
            ):
                self._shared.add(digest)
            if digest in self._shared:
                shared_upto = i
            if digest not in self._hot and value >= self.hot_threshold:
                self._hot.add(digest)
                self.hot_promotions_total += 1
                reps: "OrderedDict[str, float]" = OrderedDict()
                # Seed from (and retire) the single-owner entry: a hot
                # digest is represented by its replica set alone.
                owner = self._prefix_owner.pop(digest, None)
                if owner is not None:
                    reps[owner] = now
                self._replicas[digest] = reps
                # Event-site metric (lazy: routing stays importable in
                # bare unit-test contexts; the services layer owns the
                # prometheus objects).
                try:
                    from production_stack_tpu.router.services import (
                        metrics_service as ms,
                    )

                    ms.prefix_hot_total.inc()
                except Exception:  # pragma: no cover - metrics optional
                    pass
        # Backward propagation: everything at or before the deepest
        # shared chunk in THIS chain is a prefix of shared content.
        for j in range(shared_upto + 1):
            self._shared.add(hashes[j])
        while len(self._pop) > self.max_tracked_prefixes:
            evicted, _ = self._pop.popitem(last=False)
            self._shared.discard(evicted)
            self._demote(evicted)

    def _demote(self, digest: str) -> None:
        self._hot.discard(digest)
        reps = self._replicas.pop(digest, None)
        if reps:
            # Fall back to single-owner = the most recently routed member.
            last_url = max(reps, key=lambda u: reps[u])
            self._prefix_owner[digest] = last_url
            self._prefix_owner.move_to_end(digest)

    def _live_replicas(self, digest: str, now: float):
        """The digest's replica set with TTL-expired members dropped
        (the decay-shrink half of the grow/shrink contract)."""
        reps = self._replicas.get(digest)
        if not reps:
            return None
        for url in [u for u, stamp in reps.items()
                    if now - stamp > self.replica_ttl_s]:
            del reps[url]
        return reps

    def _matched_chunks(self, hashes: List[str], url: str, now: float) -> float:
        """Affinity CREDIT (not raw chunk count) of ``url`` for this
        chain.  Non-SHARED chunks (user-private content, hot or cold)
        count 1.0 each; fleet-SHARED chunks (the >= 3-way-divergence /
        shared_threshold classifier) count toward an aggregate of at
        most ``hot_credit_cap`` — shared content is replicable, tails
        are not (see __init__).  Walk semantics: an unmatched private
        chunk BREAKS the walk (chain affinity ends there); an unmatched
        SHARED chunk is transparent (no credit, no break) so a private-
        tail match survives the shared head's ownership churn."""
        full = 0
        shared = 0
        for digest in hashes:
            # Fleet-SHARED content (at/before a >= 3-way chain
            # divergence, or past shared_threshold popularity) is
            # replicable, so (a) its match credit is capped, and (b) a
            # MISMATCH on it never breaks the walk: shared spans carry
            # no placement information — a user's round-2 request must
            # still reach its private-tail match on the backend that
            # served round 1 even while the shared head's ownership is
            # churning through its pre-promotion warmup.  A hot digest
            # that is NOT shared is a user's own re-requested tail: full
            # credit, with the replica set acting as MEMORY — a user
            # bounced between two backends can return to either without
            # the single-owner LRU forgetting the warm one.
            is_shared = self.popularity and digest in self._shared
            matched = False
            if self.popularity and digest in self._hot:
                reps = self._live_replicas(digest, now)
                matched = bool(reps) and url in reps
            else:
                matched = self._prefix_owner.get(digest) == url
            if matched:
                if is_shared:
                    shared += 1
                else:
                    full += 1
                continue
            if is_shared:
                continue  # transparent: no credit, no break
            break
        if not self.popularity:
            return float(full)
        return float(full) + min(float(shared), self.hot_credit_cap)

    def _note_route(self, hashes: List[str], url: str, now: float) -> None:
        """Record the routing decision: hot digests gain/refresh ``url``
        in their replica set (growth happens exactly when load made a
        non-member win the score); cold digests keep LRU single-owner
        semantics (per-user tails: latest backend owns the tail)."""
        for digest in hashes:
            if self.popularity and digest in self._hot:
                if self._decayed(digest, now) < self.hot_threshold / 2.0:
                    self._demote(digest)
                    self._prefix_owner[digest] = url
                    self._prefix_owner.move_to_end(digest)
                    continue
                reps = self._replicas.setdefault(digest, OrderedDict())
                reps[url] = now
                while len(reps) > self.max_replicas:
                    # Evict the stalest member (least recently routed).
                    stalest = min(reps, key=lambda u: reps[u])
                    del reps[stalest]
                continue
            self._prefix_owner[digest] = url
            self._prefix_owner.move_to_end(digest)
        while len(self._prefix_owner) > self.max_tracked_prefixes:
            self._prefix_owner.popitem(last=False)

    # -- scraped-truth reconcile + pod-churn prune -------------------------

    def _maybe_reconcile(self, engine_stats, now: float) -> None:
        """Correct the owner map against scraped prefix-cache truth: a
        backend whose ``tpu:prefix_cache_blocks`` collapsed between
        scrapes restarted (or flushed) — every prefix the router believes
        resident there is gone, so purge it from the owner map and the
        replica sets instead of routing affinity toward an empty cache."""
        if now - self._last_reconcile < self.reconcile_interval_s:
            return
        self._last_reconcile = now
        reset_urls = []
        for url, es in engine_stats.items():
            blocks = float(getattr(es, "prefix_cache_blocks", 0.0) or 0.0)
            prev = self._truth_blocks.get(url)
            self._truth_blocks[url] = blocks
            # A collapse (>75% drop from a non-trivial size) is a cache
            # reset; LRU churn shrinks gradually and never looks like
            # this between adjacent scrapes.
            if prev is not None and prev >= 8.0 and blocks < 0.25 * prev:
                reset_urls.append(url)
        for url in reset_urls:
            self._purge_url(url)

    def _purge_url(self, url: str) -> None:
        for digest in [d for d, u in self._prefix_owner.items() if u == url]:
            del self._prefix_owner[digest]
        for digest, reps in list(self._replicas.items()):
            reps.pop(url, None)

    def prune(self, live_urls) -> List[str]:
        """Drop owner-map/popularity state for backends that left
        discovery (pod churn) — same contract as ``CapacityModel.prune``
        / ``CircuitBreaker.prune``; returns the removed urls.  Without
        this, stale owners keep pulling affinity score toward dead
        endpoints and the replica sets grow unboundedly across churn."""
        live = set(live_urls)
        gone: set = set()
        with self._lock:
            for digest, url in list(self._prefix_owner.items()):
                if url not in live:
                    del self._prefix_owner[digest]
                    gone.add(url)
            for digest, reps in list(self._replicas.items()):
                for url in [u for u in reps if u not in live]:
                    del reps[url]
                    gone.add(url)
            for url in [u for u in self._truth_blocks if u not in live]:
                del self._truth_blocks[url]
                gone.add(url)
        return sorted(gone)

    def popularity_snapshot(self) -> Dict[str, float]:
        """Live popularity-view stats for the router /metrics render."""
        now = self._clock()
        with self._lock:
            sizes = []
            for digest in list(self._hot):
                reps = self._live_replicas(digest, now)
                sizes.append(len(reps) if reps else 0)
            return {
                "hot_prefixes": len(self._hot),
                "replica_set_max": max(sizes) if sizes else 0,
                "hot_promotions_total": self.hot_promotions_total,
            }

    # -- routing -----------------------------------------------------------

    def route_request(
        self,
        endpoints: List[EndpointInfo],
        engine_stats,
        request_stats,
        request,
        request_json: Optional[Dict[str, Any]] = None,
    ) -> str:
        # Prefix affinity is a DECODE-locality signal: learning a prefix
        # owner in the prefill pool would steer every affine follow-up to
        # a backend that never serves generations.
        endpoints = require_endpoints(exclude_prefill_role(endpoints))
        engine_stats = engine_stats or {}
        request_stats = request_stats or {}
        hashes = self._prefix_hashes(extract_prompt_text(request_json))
        now = self._clock()

        def load(url: str) -> float:
            # max(scraped queue depth, synchronous router-side in-flight)
            # — the shared stale-scrape-pileup guard (routing/base.py).
            return effective_load(url, engine_stats, request_stats)

        with self._lock:
            if self.popularity:
                self._bump_popularity(hashes, now)
                if engine_stats:
                    self._maybe_reconcile(engine_stats, now)
            best_url, best_score = None, float("inf")
            for ep in sorted(endpoints, key=lambda e: e.url):
                affinity = (
                    self._matched_chunks(hashes, ep.url, now) if hashes else 0
                )
                score = load(ep.url) - self.load_tradeoff * affinity
                if score < best_score:
                    best_url, best_score = ep.url, score
            assert best_url is not None
            self._note_route(hashes, best_url, now)
        return best_url


class PopularityKVAwareRouter(KVAwareRouter):
    """``kv_aware`` with the fleet prefix-popularity view on — registered
    as routing logic ``kv_aware_popularity`` so the A/B ladder, helm
    values, and dynamic config can select it by name."""

    def __init__(self, **kwargs):
        kwargs.setdefault("popularity", True)
        super().__init__(**kwargs)
