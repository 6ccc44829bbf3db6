"""Open loop: independent users, arrivals on a schedule whatever the server
does.  Parameters (traffic file): ``prompt_tokens`` and ``output_tokens`` as
{"median", "sigma", "min", "max"} of a clipped lognormal, ``base_seed``,
``preroll_s``, ``warmup``.  The cell file gives ``rate_rps``.

Every ``--seed`` gets the same arrival times and the same (prompt, output)
sizes at the same arrivals, both drawn from ``base_seed``: the seed draws only
the bytes, so no run finds another's prefix in a cache and every run does the
same work.  Near capacity this system tips into a backlog on some orders of
the same arrivals and not on others (PERF.md, PR 23): seeds that reordered
all arrivals moved the tails by a fifth, seeds that reordered sizes inside
blocks of four still tipped one order in six, and two runs of one seed
agreed within a few percent throughout.
"""

from __future__ import annotations

import asyncio
import time
from typing import Dict, List

import numpy as np

import generators.text as text
from generators.warm import until_stable


def _lognormal(rng, spec: Dict, n: int) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def schedule(traffic: Dict, rate_rps: float, seconds: float,
             seed: int) -> List[Dict]:
    """Arrivals of one stretch: [{"at", "prompt_tokens", "output_tokens",
    "text"}], ``at`` in seconds from the stretch's start.  Pure in
    (traffic, rate, seconds, seed)."""
    n = max(1, round(rate_rps * seconds))
    base = np.random.default_rng([traffic["base_seed"], n])
    gaps = base.exponential(1.0, n)
    gaps *= seconds / gaps.sum()
    prompts = _lognormal(base, traffic["prompt_tokens"], n)
    outputs = _lognormal(base, traffic["output_tokens"], n)
    rng = np.random.default_rng(seed)
    # A gap comes before its arrival and the last one is dropped, so the
    # first request is due at 0 and the last before the end.
    at = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    overhead = text.BOS_AND_TAIL_TOKENS + text.message_tokens("user", 0, True)
    return [
        {"at": float(at[i]), "prompt_tokens": int(prompts[i]),
         "output_tokens": int(outputs[i]),
         "text": text.random_text(rng, max(1, int(prompts[i]) - overhead))}
        for i in range(n)
    ]


async def _offer(client, phase: str, plan: List[Dict], t0: float) -> None:
    tasks = [
        asyncio.ensure_future(client.chat(
            phase, [{"role": "user", "content": p["text"]}],
            p["output_tokens"], due=t0 + p["at"],
        ))
        for p in plan
    ]
    await asyncio.gather(*tasks)


async def warmup(client, traffic: Dict, cell: Dict, stable) -> None:
    """The fixed warm-up requests, then the mix at a ladder of rates (low
    rates make the small decode batches, high ones the full batch)."""
    w = traffic["warmup"]

    async def ladder(cycle: int) -> None:
        for k, factor in enumerate(w["rate_factors"]):
            plan = schedule(traffic, cell["rate_rps"] * factor,
                            w["seconds_each"], w["seed"] + 1000 * cycle + k)
            await _offer(client, "warmup", plan, time.monotonic())

    await until_stable(client, w["bursts"], stable, ladder)


async def prepare(client, traffic: Dict, cell: Dict, seed: int):
    return None


async def measure(client, traffic: Dict, cell: Dict, seed: int, state,
                  t0: float, seconds: float) -> None:
    """Pre-roll from ``t0 - preroll_s`` (not counted: due before t0), then
    the window's own arrivals from ``t0``."""
    pre = traffic.get("preroll_s", 0)
    plans = []
    if pre:
        plans.append(_offer(
            client, "measure",
            schedule(traffic, cell["rate_rps"], pre, seed + 1), t0 - pre))
    plans.append(_offer(
        client, "measure",
        schedule(traffic, cell["rate_rps"], seconds, seed), t0))
    await asyncio.gather(*plans)
