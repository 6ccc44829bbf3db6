"""Warm-up shared by the generators: bursts that walk the decode batch
through its buckets, then the cell's own traffic once as the proof.

``bursts``: ``n`` requests sent at once are prefilled one after another
while the earlier ones decode, so the batch grows 1, 2, ... n and every
power-of-two bucket up to ``n`` runs; the long prompt that leads each burst
takes the largest prefill bucket, the others the smallest.  A burst larger
than ``--max-num-seqs`` leaves prompts waiting behind a full batch, which is
the one place the single-step decode program runs.  They meet every program
a window uses (PERF.md, PR 33: all 21 compile events, warm or cold).

``until_stable`` then replays the cell's own mix (``stretch``) and asks the
engine whether that compiled anything.  It should not, and then every run
takes the same two stretches; where it did, the run says so and replays
until a stretch compiled nothing, as before PR 33.
"""

from __future__ import annotations

import asyncio
import itertools
from typing import Awaitable, Callable, Dict

import numpy as np

import generators.text as text


async def bursts(client, spec: Dict) -> None:
    rng = np.random.default_rng(spec["seed"])
    for n in spec["sizes"]:
        lengths = [spec["long_prompt_tokens"]] + [spec["prompt_tokens"]] * (n - 1)
        await asyncio.gather(*(
            client.chat("warmup",
                        [{"role": "user", "content": text.random_text(rng, k)}],
                        spec["output_tokens"])
            for k in lengths
        ))


async def until_stable(client, spec: Dict, stable,
                       stretch: Callable[[int], Awaitable[None]]) -> None:
    await bursts(client, spec)
    await stable.check("programs")
    for cycle in itertools.count():
        await stretch(cycle)
        if await stable.check("traffic"):
            return
