"""Warm-up shared by the generators: bursts that walk the decode batch
through its buckets.  ``n`` requests sent at once are prefilled one after
another while the earlier ones decode, so the batch grows 1, 2, ... n and
every power-of-two bucket up to ``n`` runs; a long prompt in each burst
takes the largest prefill bucket."""

from __future__ import annotations

import asyncio
from typing import Dict

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
