"""Fleet-scale multi-round-QA runner: the BASELINE.md north-star workload
(320 users x 10 rounds, 1000-token shared system prompt, growing per-user
histories) ported onto the FleetHarness so the whole routing ladder —
round-robin / session / kv_aware / kv_aware_popularity — is A/B-able in
CI with no accelerator (ROADMAP item 6; SURVEY §6, tutorials 07/08).

The fake engines run the chunk-chain prefix-cache simulation plus the
prefill cost model (testing/fake_engine.py): TTFT grows with the UNCACHED
prompt tail and stretches under oversubscription, so the three quantities
the paper's headline comparison reports — fleet KV hit rate, TTFT
percentiles, output tok/s — all respond to routing policy the way they
do on real engines:

* round-robin scatters every conversation; histories re-prefill
  everywhere (hit-rate floor).
* session affinity keeps each user sticky but places users by hash —
  load-blind, so hot backends stretch TTFT; and every backend
  cold-prefills the shared system prompt once.
* kv_aware's single-owner LRU flip-flops ownership of the SHARED chain
  head (every user's chunk 0), so deep tail matches break at the head
  and users scatter under load.
* kv_aware_popularity serves the hot shared prefix from a load-grown
  replica set while tails stay session-sticky — the concentration +
  balance the tentpole claims.

``fleet KV hit rate`` here is ground truth read directly from the fake
engines' token-weighted counters (sum hit / sum query), the same numbers
the router scrapes through ``tpu:prefix_cache_{hit,query}_tokens_total``.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Dict, List, Optional, Tuple

from production_stack_tpu.testing.fake_engine import fake_prefix_chain
from production_stack_tpu.testing.fleet import FleetHarness

# --routing-logic value + extra router argv per ladder rung.  The
# popularity rung carries its tuned knobs: strong per-user tail
# stickiness (tradeoff 10) with a low shared-credit cap (0.17), so the
# hot head replicates onto a new member once every current member queues
# ~2 deep (tradeoff x cap) while user histories stay pinned.
ROUTING_LADDER: Dict[str, Tuple[str, Tuple[str, ...]]] = {
    "roundrobin": ("roundrobin", ()),
    "session": ("session", ("--session-key", "x-user-id")),
    "kv_aware": ("kv_aware", ()),
    "kv_aware_popularity": (
        "kv_aware_popularity",
        ("--kv-affinity-tradeoff", "10",
         "--kv-popularity-hot-credit-cap", "0.17",
         "--kv-popularity-max-replicas", "12"),
    ),
}


def load_multi_round_module():
    """Import benchmarks/multi_round_qa/multi_round_qa.py (not a package)
    by file path."""
    import sys

    existing = sys.modules.get("multi_round_qa")
    if existing is not None and hasattr(existing, "run_benchmark"):
        return existing
    path = (
        Path(__file__).resolve().parents[2]
        / "benchmarks" / "multi_round_qa" / "multi_round_qa.py"
    )
    spec = importlib.util.spec_from_file_location("multi_round_qa", path)
    assert spec is not None and spec.loader is not None
    mod = importlib.util.module_from_spec(spec)
    # dataclass processing resolves the module through sys.modules; it
    # must be registered before exec.
    sys.modules["multi_round_qa"] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class MultiRoundFleetConfig:
    """CI-scaled rendition of the canonical workload (BASELINE.md: 320
    users x 10 rounds at 1000-token shared prompt; here shrunk to run in
    seconds while keeping the shape — many users per backend, a shared
    head every request re-sends, per-user tails that grow each round)."""

    num_engines: int = 12
    # NOT a multiple of num_engines: a user count divisible by the fleet
    # size makes round-robin accidentally session-sticky (the rotation
    # phase re-maps every user to the same engine each round) and the
    # baseline stops being a baseline.
    num_users: int = 26
    num_rounds: int = 5
    qps: float = 28.0
    system_prompt_len: int = 1000   # words of the SHARED head (~3k chars)
    user_info_len: int = 600        # words of per-user context (the tail)
    answer_len: int = 16            # fake tokens per round
    # Heterogeneous load: every k-th user streams long answers (real QA
    # answer lengths vary hugely) — the axis that separates load-aware
    # placement from hash placement: two heavy users hashed onto one
    # backend is a sustained hot pocket session affinity never repairs.
    heavy_answer_len: int = 96
    heavy_every: int = 4
    seed: int = 0
    # Fake-engine service model.  Deliberately SLOW simulated clock
    # (chunky token intervals, tens-of-ms prefill costs): TTFT signals
    # must dominate asyncio-loop scheduling noise for seeded percentile
    # comparisons to be stable in CI.
    capacity: int = 2
    max_queued: int = 16
    tokens_per_sec: float = 40.0
    ttft: float = 0.03
    prefill_chars_per_sec: float = 20000.0
    prefix_chunk_chars: int = 64
    # Spread user joins over this window (s): the canonical 320-user run
    # ramps users up over minutes; a continuous arrival stream is what
    # load-aware placement exploits (None = legacy one-gap stagger).
    join_window_s: Optional[float] = 4.0
    # Fixed backend ports: consistent-hash placement (the session arm)
    # hashes backend URLs, so ephemeral ports would re-roll session's
    # user placement every run and the seeded A/B would not be an A/B.
    base_port: int = 19360
    request_timeout: float = 30.0


def shared_prefix_digests(mod, config, chunk_chars: int) -> List[str]:
    """The chunk digests every user's round-1 prompt shares (the system-
    prompt head as the fake engines hash it): build two users' round-1
    prompt texts exactly as the workload will, take the common prefix,
    and chain-hash the fully-shared chunks."""
    u1 = mod.UserSession(config.init_user_id + 1, config)
    u2 = mod.UserSession(config.init_user_id + 2, config)
    t1 = json.dumps([{"role": "user", "content": u1._round_prompt(1)}])
    t2 = json.dumps([{"role": "user", "content": u2._round_prompt(1)}])
    common = 0
    for a, b in zip(t1, t2):
        if a != b:
            break
        common += 1
    n = common // chunk_chars
    return fake_prefix_chain(t1, chunk_chars)[:n]


async def run_fleet_multi_round(
    policy: str,
    cfg: Optional[MultiRoundFleetConfig] = None,
) -> Dict[str, object]:
    """One ladder rung: FleetHarness fleet + the multi-round-QA workload,
    measured on fleet KV hit rate / TTFT percentiles / output tok/s /
    shared-prefix residency."""
    cfg = cfg or MultiRoundFleetConfig()
    routing_logic, policy_args = ROUTING_LADDER[policy]
    mod = load_multi_round_module()

    engine_kwargs: Dict[str, object] = {
        "prefix_chunk_chars": cfg.prefix_chunk_chars,
        "prefill_chars_per_sec": cfg.prefill_chars_per_sec,
        "prefill_scales_with_load": True,
    }

    h = FleetHarness(
        num_engines=cfg.num_engines,
        seed=cfg.seed,
        capacity=cfg.capacity,
        max_queued=cfg.max_queued,
        tokens_per_sec=cfg.tokens_per_sec,
        ttft=cfg.ttft,
        max_tokens=cfg.answer_len,
        routing_logic=routing_logic,
        # Fleet admission stays out of the ladder comparison: the A/B
        # isolates ROUTING.
        fleet_admission=False,
        router_args=tuple(policy_args),
        engine_kwargs=engine_kwargs,
        base_port=cfg.base_port,
    )
    await h.start(active=cfg.num_engines)
    try:
        wl = mod.WorkloadConfig(
            base_url=str(h._router_server.make_url("")).rstrip("/"),
            model="fleet/fake-llama",
            num_users=cfg.num_users,
            num_rounds=cfg.num_rounds,
            qps=cfg.qps,
            system_prompt_len=cfg.system_prompt_len,
            user_info_len=cfg.user_info_len,
            answer_len=cfg.answer_len,
            heavy_answer_len=cfg.heavy_answer_len,
            heavy_every=cfg.heavy_every,
            request_timeout=cfg.request_timeout,
            join_window=cfg.join_window_s,
        )
        result = await mod.run_benchmark(wl)
        summary = result["summary"]
        records = result["records"]

        hit = sum(be.state.prefix_hit_tokens for be in h.backends)
        query = sum(be.state.prefix_query_tokens for be in h.backends)
        shared = shared_prefix_digests(mod, wl, cfg.prefix_chunk_chars)
        resident = 0
        if shared:
            # The DEEPEST fully-shared chunk proves the whole shared head
            # resident on a backend (digests chain).
            resident = sum(
                1 for be in h.backends if shared[-1] in be.state._seen_chunks
            )
        ttfts = sorted(r.ttft for r in records if r.error is None)

        def pct(p: float) -> float:
            if not ttfts:
                return 0.0
            return ttfts[min(len(ttfts) - 1, round(p / 100 * (len(ttfts) - 1)))]

        out: Dict[str, object] = {
            "policy": policy,
            "requests": summary["requests_finished"],
            "failed": summary["requests_failed"],
            "kv_hit_rate": round(hit / query, 4) if query else 0.0,
            "ttft_p50_ms": round(pct(50) * 1e3, 1),
            "ttft_p95_ms": round(pct(95) * 1e3, 1),
            "output_tok_s": summary["output_tokens_per_s"],
            "shared_prefix_backends": resident,
        }
        router_obj = h.registry.get("routing_logic")
        if hasattr(router_obj, "popularity_snapshot"):
            out["popularity"] = router_obj.popularity_snapshot()
        return out
    finally:
        await h.close()
