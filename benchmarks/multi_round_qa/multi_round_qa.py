"""Multi-round QA serving benchmark.

The measurement instrument of the stack (reference counterpart:
benchmarks/multi-round-qa/multi-round-qa.py — WorkloadConfig :17,
RequestExecutor :117, UserSession :179, UserSessionManager :341).  The
workload: N concurrent users hold M-round chats at a target aggregate QPS;
every user shares a long system prompt and carries a growing per-user
history, so TTFT under load is dominated by how well the stack reuses KV
(prefix cache + session-affinity routing + offload).

Re-designed rather than ported: one asyncio task per user session paced by
its request gap (the reference drives a 0.1 s polling loop over sessions
from a thread, :681-691), a raw aiohttp SSE client instead of the openai
package (not available on TPU images), and first-class percentile TTFT +
router-scraped KV hit-rate reporting (BASELINE.md north-star metrics; the
reference only prints mean TTFT).

Outputs: console summary, optional per-request CSV, and ONE final JSON
line for driver-style consumption.
"""

from __future__ import annotations

import argparse
import asyncio
import csv
import dataclasses
from collections import Counter
import json
import logging
import re
import statistics
import time
from typing import Dict, List, Optional

import aiohttp

logger = logging.getLogger("multi_round_qa")


@dataclasses.dataclass
class WorkloadConfig:
    """Knobs of the canonical workload (reference run.sh:43-85: 320 users x
    10 rounds, 1000-tok system prompt, 20000-tok history, 100-tok answers,
    QPS sweep)."""

    base_url: str
    model: str
    num_users: int = 10
    num_rounds: int = 5
    qps: float = 1.0
    system_prompt_len: int = 1000
    user_info_len: int = 2000
    answer_len: int = 100
    duration: Optional[float] = None  # measurement window (s); None = drain
    enable_user_id: bool = True  # x-user-id header for session routing
    api_key: str = "EMPTY"
    init_user_id: int = 0
    seed_history_rounds: int = 0  # pre-grown history (ramp-up equivalent)
    request_timeout: float = 120.0
    # Unrecorded sequential requests before the measurement clock starts
    # (reference warmup_engine, multi-round-qa.py:534-543).  Essential for
    # a JAX engine: the first hit on each prefill bucket / decode program
    # compiles (~tens of seconds) and must not land in TTFT percentiles.
    warmup_requests: int = 0
    # Heterogeneous answer lengths: every ``heavy_every``-th user gets
    # ``heavy_answer_len``-token answers (0 disables both).  Real QA
    # answers vary hugely; a few long-generation users are what separates
    # load-aware placement from hash placement — two heavy users hashed
    # onto one backend is a sustained hot pocket no rebalancing fixes.
    heavy_answer_len: int = 0
    heavy_every: int = 0
    # Spread user joins across this many seconds (the canonical run ramps
    # 320 users up over minutes, not at t=0; None keeps the legacy
    # one-gap stagger).  A continuous arrival stream is what lets
    # load-aware placement policies keep repairing fleet balance —
    # all-at-once joins freeze placement after round 1.
    join_window: Optional[float] = None
    # Replay real conversations instead of the synthetic workload
    # (reference ShareGPT mode, multi-round-qa.py:181-260,373-381): a JSON
    # list of {"num_round": int, "conversations": [{"value": str,
    # "num_tokens": int}, ...]} alternating human/assistant turns.  User
    # prompts come from the human turns; each round's max_tokens from the
    # matching assistant turn's num_tokens.
    sharegpt_path: Optional[str] = None


@dataclasses.dataclass
class RequestRecord:
    user_id: int
    round_id: int
    launch_time: float
    finish_time: float
    ttft: float
    generation_time: float
    prompt_tokens: int
    generation_tokens: int
    error: Optional[str] = None


def _dummy_text(num_tokens: int) -> str:
    return " ".join(["hi"] * num_tokens)


def load_sharegpt(path: str, num_rounds: int) -> List[Dict]:
    """Conversations with enough rounds for the configured workload
    (reference _load_sharegpt_data, multi-round-qa.py:373-381)."""
    with open(path, "r", encoding="utf-8") as f:
        data = json.load(f)
    # Filter on the ACTUAL turn count — num_round metadata can disagree
    # with the conversations list, and trusting it would crash mid-replay.
    usable = [
        d for d in data
        if len(d.get("conversations", [])) >= 2 * num_rounds
    ]
    if not usable:
        raise ValueError(
            f"{path}: no conversation has >= {2 * num_rounds} turns "
            f"({len(data)} total)"
        )
    logger.info("ShareGPT: %d/%d conversations usable", len(usable), len(data))
    return usable


class UserSession:
    """One user's multi-round conversation, self-paced."""

    def __init__(
        self,
        user_id: int,
        config: WorkloadConfig,
        dialogue: Optional[Dict] = None,  # one ShareGPT conversation
    ):
        self.user_id = user_id
        self.config = config
        self.dialogue = dialogue
        self.history: List[Dict[str, str]] = []
        self.records: List[RequestRecord] = []
        # Per-user pacing: num_users concurrent users at aggregate `qps`
        # means each user asks every num_users/qps seconds (reference
        # UserConfig.gap_between_requests, :73).
        self.gap = config.num_users / config.qps if config.qps > 0 else 0.0

    def _system_prompt(self) -> str:
        return (
            "Hi, here's some system prompt: "
            f"{_dummy_text(self.config.system_prompt_len)}. "
            f"For user {self.user_id}, here are some other context: "
            f"{_dummy_text(self.config.user_info_len)}."
        )

    def _question(self, round_id: int) -> str:
        return (
            f"Here's question #{round_id}: can you tell me "
            "a new long story with a happy ending?"
        )

    def _round_prompt(self, round_id: int) -> str:
        """Round round_id's user turn: the ShareGPT human turn when
        replaying, else synthetic (system prompt folded into round 1)."""
        if self.dialogue is not None:
            return self.dialogue["conversations"][2 * (round_id - 1)]["value"]
        prompt = self._question(round_id)
        if not self.history:
            prompt = self._system_prompt() + prompt
        return prompt

    def _round_max_tokens(self, round_id: int) -> int:
        """ShareGPT replay caps the answer at the real assistant turn's
        length (reference :254-262); synthetic mode uses answer_len."""
        if self.dialogue is not None:
            turn = self.dialogue["conversations"][2 * (round_id - 1) + 1]
            n = turn.get("num_tokens") or (len(turn.get("value", "")) // 4)
            return max(1, min(int(n), 2048))
        if (
            self.config.heavy_every
            and self.config.heavy_answer_len
            and self.user_id % self.config.heavy_every == 0
        ):
            return self.config.heavy_answer_len
        return self.config.answer_len

    def seed_history(self, rounds: int) -> None:
        """Pre-grow the chat history so mid-benchmark joins look like the
        steady state (the reference's ramp-up internal-state seeding,
        multi-round-qa.py:285-301)."""
        for round_id in range(1, rounds + 1):
            self.history.append(
                {"role": "user", "content": self._round_prompt(round_id)}
            )
            if self.dialogue is not None:
                answer = self.dialogue["conversations"][
                    2 * (round_id - 1) + 1
                ].get("value", "")
            else:
                answer = _dummy_text(self.config.answer_len)
            self.history.append({"role": "assistant", "content": answer})

    async def run(self, session: aiohttp.ClientSession, stop: asyncio.Event):
        start_round = len(self.history) // 2 + 1
        for round_id in range(start_round, self.config.num_rounds + 1):
            if stop.is_set():
                return
            round_start = time.time()
            self.history.append(
                {"role": "user", "content": self._round_prompt(round_id)}
            )
            record = await self._request(session, round_id)
            self.records.append(record)
            if record.error is None:
                self.history.append({"role": "assistant", "content": "".join(
                    record.body_parts)})
            else:
                self.history.pop()  # failed round: retract the user turn
            # Pace to the per-user gap (measured from round start).
            sleep = self.gap - (time.time() - round_start)
            if sleep > 0:
                try:
                    await asyncio.wait_for(stop.wait(), timeout=sleep)
                    return  # stop flagged during the gap
                except asyncio.TimeoutError:
                    pass

    async def _request(
        self, session: aiohttp.ClientSession, round_id: int
    ) -> RequestRecord:
        launch = time.time()
        headers = {"Authorization": f"Bearer {self.config.api_key}"}
        if self.config.enable_user_id:
            headers["x-user-id"] = str(self.user_id)
        body = {
            "model": self.config.model,
            "messages": self.history,
            "temperature": 0,
            "stream": True,
            "max_tokens": self._round_max_tokens(round_id),
            "stream_options": {"include_usage": True},
        }
        first_token_time = None
        parts: List[str] = []
        prompt_tokens = generation_tokens = 0
        record = RequestRecord(
            user_id=self.user_id, round_id=round_id, launch_time=launch,
            finish_time=0.0, ttft=0.0, generation_time=0.0,
            prompt_tokens=0, generation_tokens=0,
        )
        record.body_parts = parts
        try:
            timeout = aiohttp.ClientTimeout(total=self.config.request_timeout)
            async with session.post(
                f"{self.config.base_url}/v1/chat/completions",
                json=body, headers=headers, timeout=timeout,
            ) as resp:
                if resp.status != 200:
                    record.error = f"http_{resp.status}"
                    record.finish_time = time.time()
                    return record
                async for raw_line in resp.content:
                    line = raw_line.strip()
                    if not line.startswith(b"data:"):
                        continue
                    payload = line[len(b"data:"):].strip()
                    if payload == b"[DONE]":
                        break
                    chunk = json.loads(payload)
                    usage = chunk.get("usage")
                    if usage:
                        prompt_tokens = usage.get("prompt_tokens", 0)
                        generation_tokens = usage.get("completion_tokens", 0)
                    choices = chunk.get("choices") or []
                    if not choices:
                        continue
                    delta = choices[0].get("delta", {}).get("content")
                    if delta:
                        if first_token_time is None:
                            first_token_time = time.time()
                        parts.append(delta)
        except Exception as e:
            record.error = type(e).__name__
            record.finish_time = time.time()
            return record
        now = time.time()
        if first_token_time is None:
            first_token_time = now
        record.finish_time = now
        record.ttft = first_token_time - launch
        record.generation_time = max(now - first_token_time, 1e-9)
        record.prompt_tokens = prompt_tokens
        record.generation_tokens = generation_tokens or len(parts)
        return record


async def scrape_kv_hit_rate(
    session: aiohttp.ClientSession, base_url: str
) -> Optional[float]:
    """Mean engine prefix-cache hit rate from the router's /metrics mirror
    (tpu_router:engine_prefix_cache_hit_rate; BASELINE.md KV-hit-rate
    metric).  None if the router doesn't expose it."""
    try:
        async with session.get(f"{base_url}/metrics") as resp:
            text = await resp.text()
    except Exception:
        return None
    values = [
        float(m.group(1))
        for m in re.finditer(
            r'^tpu_router:engine_prefix_cache_hit_rate\{[^}]*\}\s+([0-9.eE+-]+)',
            text, re.M,
        )
    ]
    if not values:
        return None
    return sum(values) / len(values)


def summarize(records: List[RequestRecord], wall_time: float,
              kv_hit_rate: Optional[float]) -> Dict:
    ok = [r for r in records if r.error is None]
    failed = [r for r in records if r.error is not None]
    ttfts = sorted(r.ttft for r in ok)

    def pct(p: float) -> float:
        if not ttfts:
            return 0.0
        idx = min(len(ttfts) - 1, max(0, round(p / 100 * (len(ttfts) - 1))))
        return ttfts[idx]

    total_gen = sum(r.generation_tokens for r in ok)
    total_prompt = sum(r.prompt_tokens for r in ok)
    summary = {
        "requests_finished": len(ok),
        "requests_failed": len(failed),
        "wall_time_s": round(wall_time, 2),
        "finished_qps": round(len(ok) / wall_time, 3) if wall_time else 0.0,
        "ttft_p50_s": round(pct(50), 4),
        "ttft_p90_s": round(pct(90), 4),
        "ttft_p99_s": round(pct(99), 4),
        "ttft_mean_s": round(statistics.fmean(ttfts), 4) if ttfts else 0.0,
        "input_tokens_per_s": round(total_prompt / wall_time, 1) if wall_time else 0,
        "output_tokens_per_s": round(total_gen / wall_time, 1) if wall_time else 0,
        # Per-request generation throughput is only meaningful when the
        # answer streamed over a measurable interval; short answers can
        # arrive in one SSE chunk (generation_time ~ 0), which would make
        # the mean explode.  Those requests are excluded.
        "gen_throughput_per_request": round(
            statistics.fmean(
                r.generation_tokens / r.generation_time
                for r in ok
                if r.generation_time > 1e-3
            ), 2,
        ) if any(r.generation_time > 1e-3 for r in ok) else 0.0,
    }
    if kv_hit_rate is not None:
        summary["kv_hit_rate"] = round(kv_hit_rate, 4)
    if failed:
        # Failure breakdown: "18 failed" with no cause is undiagnosable
        # from a driver artifact.
        summary["errors"] = dict(Counter(r.error for r in failed))
    return summary


def write_csv(records: List[RequestRecord], path: str) -> None:
    fields = [
        "user_id", "round_id", "launch_time", "finish_time", "ttft",
        "generation_time", "prompt_tokens", "generation_tokens", "error",
    ]
    with open(path, "w", newline="") as f:
        writer = csv.DictWriter(f, fieldnames=fields)
        writer.writeheader()
        for r in records:
            writer.writerow({k: getattr(r, k) for k in fields})


async def run_benchmark(config: WorkloadConfig) -> Dict:
    """Drive the workload; returns the summary dict (importable from tests
    and run scripts)."""
    stop = asyncio.Event()
    dialogues: Optional[List[Dict]] = None
    if config.sharegpt_path:
        dialogues = load_sharegpt(config.sharegpt_path, config.num_rounds)
    connector = aiohttp.TCPConnector(limit=0)
    async with aiohttp.ClientSession(connector=connector) as session:
        if config.warmup_requests:
            # A throwaway user (id far outside the measured range) runs its
            # rounds back-to-back: round 1 prefills a workload-sized prompt
            # (compiling the big bucket), later rounds hit the decode path
            # again with grown history.  Records are discarded.
            warm_dialogue = dialogues[-1] if dialogues else None
            warm_rounds = config.warmup_requests
            if warm_dialogue is not None:
                # The dataset only guarantees num_rounds rounds per
                # conversation; don't index past the warmup dialogue.
                warm_rounds = min(
                    warm_rounds, len(warm_dialogue["conversations"]) // 2
                )
            warm = UserSession(
                config.init_user_id + 1_000_000,
                dataclasses.replace(config, num_rounds=warm_rounds),
                dialogue=warm_dialogue,
            )
            warm.gap = 0.0
            await warm.run(session, asyncio.Event())

        sessions: List[UserSession] = []
        # Ramp-up: stagger user joins across one full request gap so load
        # rises smoothly; late joiners get seeded history so their KV
        # footprint matches steady state.
        gap_between_users = (
            (config.num_users / config.qps) / config.num_users
            if config.qps > 0 else 0.0
        )
        if config.join_window is not None and config.num_users > 1:
            gap_between_users = config.join_window / (config.num_users - 1)
        start = time.time()

        async def launch_user(idx: int) -> UserSession:
            user = UserSession(
                config.init_user_id + idx + 1,
                config,
                dialogue=dialogues[idx % len(dialogues)] if dialogues else None,
            )
            if config.seed_history_rounds:
                user.seed_history(
                    min(config.seed_history_rounds, config.num_rounds - 1)
                )
            delay = idx * gap_between_users
            if delay > 0:
                await asyncio.sleep(delay)
            sessions.append(user)
            await user.run(session, stop)
            return user

        tasks = [
            asyncio.create_task(launch_user(i))
            for i in range(config.num_users)
        ]
        if config.duration:
            done, pending = await asyncio.wait(tasks, timeout=config.duration)
            stop.set()
            if pending:
                await asyncio.wait(pending, timeout=config.request_timeout)
        else:
            await asyncio.gather(*tasks)
        wall = time.time() - start
        kv_hit_rate = await scrape_kv_hit_rate(session, config.base_url)

    records = [r for u in sessions for r in u.records]
    return {"summary": summarize(records, wall, kv_hit_rate),
            "records": records}


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description="Multi-round QA benchmark")
    parser.add_argument("--base-url", required=True,
                        help="router base url, e.g. http://localhost:8001")
    parser.add_argument("--model", required=True)
    parser.add_argument("--num-users", type=int, default=10)
    parser.add_argument("--num-rounds", type=int, default=5)
    parser.add_argument("--qps", type=float, default=1.0)
    parser.add_argument("--shared-system-prompt", type=int, default=1000,
                        help="system prompt length (tokens-ish)")
    parser.add_argument("--user-history-prompt", type=int, default=2000,
                        help="per-user context length")
    parser.add_argument("--answer-len", type=int, default=100)
    parser.add_argument("--duration", type=float, default=None,
                        help="measurement window seconds (default: run to drain)")
    parser.add_argument("--seed-history-rounds", type=int, default=0)
    parser.add_argument("--init-user-id", type=int, default=0)
    parser.add_argument("--warmup-requests", type=int, default=0,
                        help="unrecorded warmup requests before the clock "
                        "starts (compiles JAX programs out-of-band)")
    parser.add_argument("--sharegpt", default=None, metavar="PATH",
                        help="replay conversations from a ShareGPT-format "
                        "JSON instead of the synthetic workload")
    parser.add_argument("--no-user-id-header", action="store_true")
    parser.add_argument("--output", default=None, help="per-request CSV path")
    parser.add_argument("--log-level", default="info")
    args = parser.parse_args(argv)

    logging.basicConfig(level=args.log_level.upper(),
                        format="%(asctime)s %(levelname)s %(message)s")
    config = WorkloadConfig(
        base_url=args.base_url.rstrip("/"),
        model=args.model,
        num_users=args.num_users,
        num_rounds=args.num_rounds,
        qps=args.qps,
        system_prompt_len=args.shared_system_prompt,
        user_info_len=args.user_history_prompt,
        answer_len=args.answer_len,
        duration=args.duration,
        enable_user_id=not args.no_user_id_header,
        init_user_id=args.init_user_id,
        seed_history_rounds=args.seed_history_rounds,
        warmup_requests=args.warmup_requests,
        sharegpt_path=args.sharegpt,
    )
    result = asyncio.run(run_benchmark(config))
    summary = result["summary"]
    if args.output:
        write_csv(result["records"], args.output)
        logger.info("Wrote %d request records to %s",
                    len(result["records"]), args.output)

    print("\n==================== Performance summary ======================")
    print(f"  QPS target:                   {config.qps:.2f} reqs/s")
    print(f"  Processing speed:             {summary['finished_qps']:.3f} reqs/s")
    print(f"  Requests finished / failed:   {summary['requests_finished']}"
          f" / {summary['requests_failed']}")
    print(f"  TTFT p50 / p90 / p99:         {summary['ttft_p50_s']:.3f} / "
          f"{summary['ttft_p90_s']:.3f} / {summary['ttft_p99_s']:.3f} s")
    print(f"  Input tokens per second:      {summary['input_tokens_per_s']}")
    print(f"  Output tokens per second:     {summary['output_tokens_per_s']}")
    print(f"  Gen throughput per request:   "
          f"{summary['gen_throughput_per_request']} tok/req/s")
    if "kv_hit_rate" in summary:
        print(f"  KV prefix-cache hit rate:     {summary['kv_hit_rate']:.2%}")
    print("===============================================================\n")
    print(json.dumps({"metric": "multi_round_qa", **summary}), flush=True)


if __name__ == "__main__":
    main()
