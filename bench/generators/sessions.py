"""Closed loop of chat sessions with a shared system prompt and a growing
history (the reference stack's multi-round QA, whose session arithmetic is
in ``benchmarks/multi_round_qa``).  ``users`` sessions are live at a time;
each waits a think time, asks a question, reads the whole answer, and after
``rounds`` rounds a new user takes its place.

The history carries a seeded synthetic answer of ``answer_tokens`` bytes,
not the model's text: random weights emit ids the byte tokenizer has no
text for.  Think times are a fixed stratified sample of the exponential in an
order drawn from ``base_seed`` for each seat: the seed draws only the bytes
(histories, questions, answers), so every seed does the same work at the same
pace.  (With the order drawn from the seed, two seeds differed by 8 % in
tokens/s and two runs of one seed by none: PERF.md, PR 23.)
"""

from __future__ import annotations

import asyncio
import math
import time
from typing import Dict, List

import numpy as np

import generators.text as text
from generators.warm import until_stable


class Session:
    def __init__(self, traffic: Dict, system: str, rng, uid: str,
                 start_round: int):
        self.uid = uid
        self.traffic = traffic
        self.rng = rng
        self.round = start_round
        self.messages: List[Dict] = [
            {"role": "system", "content": system},
            {"role": "user",
             "content": text.random_text(rng, traffic["history_tokens"])},
        ]
        for _ in range(start_round):
            self.ask()
            self.answer()

    def ask(self) -> None:
        q = text.random_text(self.rng, self.traffic["question_tokens"])
        if self.messages[-1]["role"] == "user":
            # The first question rides the message that holds the history.
            self.messages[-1] = {
                "role": "user",
                "content": self.messages[-1]["content"] + "\n" + q,
            }
        else:
            self.messages.append({"role": "user", "content": q})

    def answer(self) -> None:
        self.messages.append({
            "role": "assistant",
            "content": text.random_text(
                self.rng, self.traffic["answer_tokens"]),
        })


def think_times(traffic: Dict, rng, n: int = 64) -> List[float]:
    mean = traffic["think_mean_s"]
    grid = [-mean * math.log(1.0 - (k + 0.5) / n) for k in range(n)]
    return [grid[i] for i in rng.permutation(n)]


def population(traffic: Dict, seed: int, label: str) -> Dict:
    """The first ``users`` sessions, user i at round i mod ``rounds``, each
    seat with a generator of its own for the users that follow.  Pure in
    (traffic, seed)."""
    # One system prompt for everybody, whatever the seed of the users.
    system = text.random_text(
        np.random.default_rng(traffic["base_seed"]), traffic["system_tokens"])
    rngs = [np.random.default_rng([seed, i]) for i in range(traffic["users"])]
    sessions = [
        Session(traffic, system, rngs[i], f"{label}{i}.0",
                i % traffic["rounds"])
        for i in range(traffic["users"])
    ]
    return {"rngs": rngs, "system": system, "sessions": sessions,
            "label": label}


async def _seed_cache(client, phase: str, pop: Dict, traffic: Dict) -> None:
    """Each starting history once, one token out, a few at a time: the
    cache then holds what a session at that round would have left."""
    gate = asyncio.Semaphore(traffic.get("seed_concurrency", 4))

    async def one(s: Session) -> None:
        async with gate:
            await client.chat(phase, s.messages, 1, meta={"user": s.uid})

    await asyncio.gather(*(one(s) for s in pop["sessions"]))


async def _user_loop(client, phase: str, pop: Dict, slot: int, traffic: Dict,
                     start: float, stop_at) -> None:
    """One seat: sessions one after another until ``stop_at()`` is true."""
    session = pop["sessions"][slot]
    rng = pop["rngs"][slot]
    thinks = think_times(
        traffic, np.random.default_rng([traffic["base_seed"], slot]))
    k = born = 0
    due = start + thinks[k % len(thinks)]
    while not stop_at(due):
        session.ask()
        rec = await client.chat(
            phase, session.messages, traffic["answer_tokens"], due=due,
            meta={"user": session.uid, "round": session.round},
        )
        session.answer()
        session.round += 1
        if session.round >= traffic["rounds"]:
            born += 1
            session = Session(traffic, pop["system"], rng,
                              f"{pop['label']}{slot}.{born}", 0)
        k += 1
        due = max(rec.ended, time.monotonic()) + thinks[k % len(thinks)]


async def warmup(client, traffic: Dict, cell: Dict, stable) -> None:
    """The fixed warm-up requests, then warm-up users of their own run the
    loop for a stretch."""
    w = traffic["warmup"]

    async def stretch(cycle: int) -> None:
        pop = population(traffic, w["seed"] + cycle, f"w{cycle}-")
        await _seed_cache(client, "warmup", pop, traffic)
        end = time.monotonic() + w["seconds_each"]
        await asyncio.gather(*(
            _user_loop(client, "warmup", pop, i, traffic, time.monotonic(),
                       lambda due: due >= end)
            for i in range(traffic["users"])
        ))

    await until_stable(client, w["bursts"], stable, stretch)


async def prepare(client, traffic: Dict, cell: Dict, seed: int):
    pop = population(traffic, seed, "u")
    await _seed_cache(client, "seed", pop, traffic)
    return pop


async def measure(client, traffic: Dict, cell: Dict, seed: int, state,
                  t0: float, seconds: float) -> None:
    start = t0 - traffic.get("preroll_s", 0)
    end = t0 + seconds
    await asyncio.gather(*(
        _user_loop(client, "measure", state, i, traffic, start,
                   lambda due: due >= end)
        for i in range(traffic["users"])
    ))
