"""North-star serving benchmark: multi-round QA against the REAL engine.

BASELINE.md's target metrics are stack-level — multi-round-QA TTFT p50,
aggregate output tokens/s, and KV hit rate measured through the router in
front of a real serving engine (reference workload: run.sh:43-85,
tutorials/07-...:32-67).  Kernel microbenches can't evidence those; this
module boots the full serving stack in-process (JAX engine -> OpenAI
server -> router with session routing) on localhost and drives the
canonical workload at a configurable scale.

Used two ways:
* ``bench.py`` (the driver entry) calls :func:`run_serving_bench` on the
  real TPU chip and folds the summary into the BENCH JSON line.
* ``tests/test_serving_bench.py`` runs it on CPU with the tiny preset as a
  wiring test.
"""

from __future__ import annotations

import asyncio
import os
import sys
from typing import Dict, Optional

from aiohttp import web

sys.path.insert(
    0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "multi_round_qa")
)


async def _start_app(app: web.Application) -> tuple:
    runner = web.AppRunner(app)
    await runner.setup()
    site = web.TCPSite(runner, "127.0.0.1", 0)
    await site.start()
    port = site._server.sockets[0].getsockname()[1]
    return runner, f"http://127.0.0.1:{port}"


async def run_serving_bench(
    preset: str = "tiny-llama",
    *,
    num_users: int = 4,
    num_rounds: int = 3,
    qps: float = 2.0,
    system_prompt_len: int = 200,
    user_info_len: int = 200,
    answer_len: int = 32,
    max_num_seqs: int = 8,
    max_model_len: int = 2048,
    num_blocks: Optional[int] = None,
    duration: Optional[float] = None,
    num_scheduler_steps: int = 1,
    warmup_requests: int = 2,
) -> Dict:
    """Boot engine + router on localhost, run the workload, return summary.

    Returns the harness summary dict (benchmarks/multi_round_qa):
    ttft_p50/p90/p99, output_tokens_per_s, kv_hit_rate, error counts, ...
    """
    from multi_round_qa import WorkloadConfig, run_benchmark
    from production_stack_tpu.engine.config import config_from_preset
    from production_stack_tpu.engine.server.api_server import (
        build_engine_app,
    )
    from production_stack_tpu.engine.server.async_engine import AsyncEngine
    from production_stack_tpu.router.app import build_app as build_router_app
    from production_stack_tpu.router.parser import parse_args

    overrides = {
        "scheduler.max_num_seqs": max_num_seqs,
        "scheduler.max_model_len": max_model_len,
        "scheduler.num_scheduler_steps": num_scheduler_steps,
    }
    if num_blocks is not None:
        overrides["cache.num_blocks"] = num_blocks
    config = config_from_preset(preset, **overrides)
    engine = AsyncEngine(config)
    engine_app = build_engine_app(engine, served_model=preset)
    engine_runner, engine_url = await _start_app(engine_app)

    router_app = build_router_app(parse_args([
        "--static-backends", engine_url,
        "--static-models", preset,
        "--routing-logic", "session",
        "--session-key", "x-user-id",
        "--engine-stats-interval", "1",
    ]))
    router_runner, router_url = await _start_app(router_app)

    try:
        result = await run_benchmark(WorkloadConfig(
            base_url=router_url,
            model=preset,
            num_users=num_users,
            num_rounds=num_rounds,
            qps=qps,
            system_prompt_len=system_prompt_len,
            user_info_len=user_info_len,
            answer_len=answer_len,
            duration=duration,
            warmup_requests=warmup_requests,
        ))
        summary = result["summary"]
        # Engine-side context for the driver artifact — CUMULATIVE
        # counters only (run-level meaning): preemptions force KV offload
        # round-trips, prefix hits shorten prefills.  Gauges (duty cycle,
        # HBM usage) are trailing-window snapshots that read near-idle
        # after the drain, so they'd mislead here.
        es = engine.stats()
        summary["engine"] = {
            "prefix_cache_hit_rate": round(es["prefix_cache_hit_rate"], 4),
            "num_preemptions": es["num_preemptions"],
            "total_generated_tokens": es["total_generated_tokens"],
            # Per-step host serialization: ≈0 with the lookahead decode
            # pipeline feeding the device ahead of collection.
            "decode_host_gap_ms": round(es["decode_host_gap_ms"], 3),
        }
        return summary
    finally:
        await router_runner.cleanup()
        await engine_runner.cleanup()


async def _scrape_engine_counters(url: str) -> Dict:
    """Cumulative engine counters off the real /metrics endpoint (the
    same text Prometheus would scrape)."""
    import aiohttp

    from production_stack_tpu.router.stats import vocabulary as vocab

    wanted = {
        vocab.TPU_PREFIX_CACHE_HIT_RATE: "prefix_cache_hit_rate",
        vocab.TPU_NUM_PREEMPTIONS: "num_preemptions",
        vocab.TPU_TOTAL_GENERATED_TOKENS: "total_generated_tokens",
        vocab.TPU_DECODE_HOST_GAP_MS: "decode_host_gap_ms",
    }
    out: Dict = {}
    async with aiohttp.ClientSession() as session:
        async with session.get(f"{url}/metrics") as resp:
            text = await resp.text()
    for line in text.splitlines():
        if line.startswith("#") or " " not in line:
            continue
        name, _, value = line.rpartition(" ")
        if name in wanted:
            v = float(value)
            out[wanted[name]] = round(v, 4) if v != int(v) else int(v)
    return out


async def run_serving_bench_processes(
    preset: str = "tiny-llama",
    *,
    num_users: int = 4,
    num_rounds: int = 3,
    qps: float = 2.0,
    system_prompt_len: int = 200,
    user_info_len: int = 200,
    answer_len: int = 32,
    max_num_seqs: int = 8,
    max_model_len: int = 2048,
    num_blocks: Optional[int] = None,
    duration: Optional[float] = None,
    num_scheduler_steps: int = 1,
    warmup_requests: int = 2,
    boot_timeout_s: float = 240.0,
) -> Dict:
    """Like :func:`run_serving_bench`, but with REAL process boundaries:
    the engine OpenAI server and the router run as separate OS processes
    (the production data path — aiohttp server sockets, not in-process
    test transports), and the multi-round-QA harness drives the router
    over real HTTP.  This is the instrument BASELINE.md's north-star
    numbers come from (round-4 verdict weak #3).
    """
    import tempfile

    from multi_round_qa import WorkloadConfig, run_benchmark
    from production_stack_tpu.testing.procs import Child, free_port

    engine_port, router_port = free_port(), free_port()
    engine_url = f"http://127.0.0.1:{engine_port}"
    router_url = f"http://127.0.0.1:{router_port}"
    engine_cmd = [
        sys.executable, "-m", "production_stack_tpu.engine.server.api_server",
        "--model", preset, "--port", str(engine_port),
        "--max-num-seqs", str(max_num_seqs),
        "--max-model-len", str(max_model_len),
        "--num-scheduler-steps", str(num_scheduler_steps),
    ]
    if num_blocks is not None:
        engine_cmd += ["--num-blocks", str(num_blocks)]
    router_cmd = [
        sys.executable, "-m", "production_stack_tpu.router.app",
        "--port", str(router_port),
        "--static-backends", engine_url,
        "--static-models", preset,
        "--routing-logic", "session", "--session-key", "x-user-id",
        "--engine-stats-interval", "1",
    ]
    # Children's output goes to files; a child that dies at boot raises
    # ChildFailed with the end of its log, not a bare health timeout.
    log_dir = tempfile.mkdtemp(prefix="serving_bench_")
    engine = Child("engine", engine_cmd, log_dir)
    router = Child("router", router_cmd, log_dir)
    try:
        engine.start()
        await asyncio.to_thread(
            engine.wait_http_ok, f"{engine_url}/health", boot_timeout_s
        )
        router.start()
        await asyncio.to_thread(
            router.wait_http_ok, f"{router_url}/health", 60.0
        )

        result = await run_benchmark(WorkloadConfig(
            base_url=router_url,
            model=preset,
            num_users=num_users,
            num_rounds=num_rounds,
            qps=qps,
            system_prompt_len=system_prompt_len,
            user_info_len=user_info_len,
            answer_len=answer_len,
            duration=duration,
            warmup_requests=warmup_requests,
        ))
        summary = result["summary"]
        try:
            summary["engine"] = await _scrape_engine_counters(engine_url)
        except Exception as e:
            summary["engine"] = {"scrape_error": str(e)[:100]}
        summary["mode"] = "processes"
        return summary
    finally:
        router.stop(grace_s=10.0)
        engine.stop(grace_s=10.0)


def run_serving_bench_sync(**kwargs) -> Dict:
    """Entry for bench.py (which is synchronous)."""
    return asyncio.run(run_serving_bench(**kwargs))


def run_serving_bench_processes_sync(**kwargs) -> Dict:
    """Entry for bench.py: process-isolated variant."""
    return asyncio.run(run_serving_bench_processes(**kwargs))
