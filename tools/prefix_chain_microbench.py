"""Time what a request's prefix chain costs the host, at the cells' contexts.

    python tools/prefix_chain_microbench.py [--root _parent]

One process times one checkout (``--root``: where ``production_stack_tpu``
is imported from, this repo by default).  Host code only: no JAX, no chip;
the numbers are this machine's CPU's, one thread.  For each context length
(400, 4,000 and 24,000 tokens, block 16: cells 1, 2 and 3-4), with every
block of the context already in the pool's prefix cache as in a session's
later rounds:

- ``admission_ms``: ``BlockPool.match_prefix`` of the prompt (and the free
  of what it claimed), what ``Scheduler._try_schedule_prefill`` pays on the
  step thread inside the ``schedule`` phase;
- ``finish_ms``: ``BlockPool.register_prefix`` of the prompt + 100
  generated tokens, what ``Scheduler.finish_seq`` pays inside ``collect``;
- ``handler_ms``: hashing the prompt's chain alone, what the API server's
  handler pays on the event-loop thread where the chain is handed over
  (``LLMEngine.prompt_prefix_chain``).

``handed`` false is a caller with no memo (each call hashes what it needs);
true is the served path, the prompt's chain filled beforehand.  A checkout
without the memo (``match_prefix`` takes no ``chain``) prints the unhanded
rows only.  One JSON object a row; the best of ``--repeats``.
"""

from __future__ import annotations

import argparse
import inspect
import json
import os
import random
import sys
import time

BLOCK = 16
CONTEXTS = (400, 4000, 24000)
GENERATED = 100


def _best(fn, repeats: int) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e3


def run(repeats: int) -> None:
    from production_stack_tpu.engine.kv import block_pool as bp

    has_memo = "chain" in inspect.signature(bp.BlockPool.match_prefix).parameters
    rng = random.Random(46)
    for context in CONTEXTS:
        prompt = [rng.randrange(32000) for _ in range(context)]
        outputs = [rng.randrange(32000) for _ in range(GENERATED)]
        all_ids = prompt + outputs
        n_blocks = (len(all_ids) + BLOCK) // BLOCK
        pool = bp.BlockPool(num_blocks=2 * n_blocks + 2, block_size=BLOCK)
        table = pool.allocate(n_blocks)
        pool.register_prefix(all_ids, table)
        pool.free(table)
        prompt_blocks = len(prompt) // BLOCK
        memo = []
        if has_memo:
            bp.extend_prefix_chain(memo, prompt, BLOCK, prompt_blocks)

        def kw(chain):  # a checkout without the memo takes no such argument
            return {"chain": chain} if has_memo else {}

        for handed in ((False, True) if has_memo else (False,)):
            row = {"context": context, "block": BLOCK, "handed": handed}
            if handed:
                row["handler_ms"] = _best(
                    lambda: bp.extend_prefix_chain(
                        [], prompt, BLOCK, prompt_blocks),
                    repeats,
                )

            def admit():
                blocks, cached = pool.match_prefix(
                    prompt, **kw(memo if handed else None))
                assert cached == (context - 1) // BLOCK * BLOCK
                pool.free(blocks)

            row["admission_ms"] = _best(admit, repeats)
            blocks, _ = pool.match_prefix(all_ids + [0])
            # The finish extends the memo: each repeat starts from the
            # prompt's.
            row["finish_ms"] = _best(
                lambda: pool.register_prefix(
                    all_ids, blocks, **kw(list(memo) if handed else None)),
                repeats,
            )
            pool.free(blocks)
            print(json.dumps({k: round(v, 4) if isinstance(v, float) else v
                              for k, v in row.items()}))


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--repeats", type=int, default=7)
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.root))
    run(args.repeats)
