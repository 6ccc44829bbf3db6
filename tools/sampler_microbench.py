"""Time ``sample_tokens`` alone on the chip, at the cells' shapes.

    chiprun -- python tools/sampler_microbench.py [--root _parent]

One process times one checkout (``--root``: where ``production_stack_tpu``
is imported from, this repo by default).  Each case runs the sampler as a
decode window does: ``STEPS`` iterations of one ``lax.scan`` in one jitted
program, the key of iteration t ``PRNGKey(base + t)``, each iteration's
tokens nudging the next one's logits so that nothing is hoisted out of the
loop, and prints the device-bound wall time as ms a call.  Shapes: the
decode buckets and vocabularies of the benchmark's cells ([4, 32000] cell
1, [16, 32000] cell 2, [16, 65536] cell 3).  Batches: what the rows can ask
for (all greedy; one sampling row with the defaults; one with ``top_p``
0.9; one with ``top_p`` 0.9 and ``top_k`` 50), the other rows greedy as
padding is.  The last lines time the two ``argsort`` of
``models/sarvam_mla.py: held_experts`` at a decode step's 16 x 8 = 128
(row, expert) keys, which the reduced trace books under the same name,
``sort``.  A CPU run refuses to time anything.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

STEPS = 32
SHAPES = [(4, 32000), (16, 32000), (16, 65536)]
# (name, temperature, top_p, top_k) of row 0; every other row is greedy.
BATCHES = [
    ("all-greedy", 0.0, 1.0, 0),
    ("one-row-defaults", 0.8, 1.0, 0),
    ("one-row-top_p", 0.8, 0.9, 0),
    ("one-row-top_p-top_k", 0.8, 0.9, 50),
]
HELD_KEYS, HELD_EXPERTS = 16 * 8, 32  # a decode step's pairs; experts held


def _timed(fn, args, iters):
    fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / iters / STEPS * 1e3


def run(root: str, iters: int) -> None:
    import jax
    import jax.numpy as jnp
    from production_stack_tpu.engine.sampling import sample_tokens

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"no TPU here ({dev.platform}): a CPU run times nothing")

    @jax.jit
    def window(logits, temps, top_ps, top_ks, min_ps, seeds, base):
        def body(logits, t):
            tok = sample_tokens(
                logits, temps, top_ps, top_ks, jax.random.PRNGKey(base + t),
                seeds, min_p=min_ps,
            )
            nudge = (tok % 2).astype(jnp.float32)[:, None] * 1e-3
            return logits + nudge, tok

        return jax.lax.scan(body, logits, jnp.arange(STEPS))[1]

    for S, V in SHAPES:
        logits = 3.0 * jax.random.normal(
            jax.random.PRNGKey(S + V), (S, V), jnp.float32)
        for name, temp, top_p, top_k in BATCHES:
            temps = np.zeros((S,), np.float32)
            top_ps = np.ones((S,), np.float32)
            top_ks = np.zeros((S,), np.int32)
            temps[0], top_ps[0], top_ks[0] = temp, top_p, top_k
            args = (
                logits, jnp.asarray(temps), jnp.asarray(top_ps),
                jnp.asarray(top_ks), jnp.zeros((S,), jnp.float32),
                jnp.arange(S, dtype=jnp.int32), jnp.int32(1000),
            )
            print(json.dumps({
                "root": root, "device": dev.device_kind, "rows": S,
                "vocab": V, "batch": name,
                "ms_per_call": round(_timed(window, args, iters), 4),
            }), flush=True)

    @jax.jit
    def held(expert):
        def body(expert, _):
            order = jnp.argsort(expert)      # by expert, stable
            back = jnp.argsort(order)        # back in (row, choice) order
            return (expert + back) % (HELD_EXPERTS + 1), back[0]

        return jax.lax.scan(body, expert, None, length=STEPS)[1]

    expert = jnp.asarray(
        np.random.default_rng(0).integers(0, HELD_EXPERTS + 1, HELD_KEYS),
        jnp.int32)
    print(json.dumps({
        "root": root, "device": dev.device_kind,
        "case": "held_experts: two argsort of 128 keys (one routed layer)",
        "ms_per_call": round(_timed(held, (expert,), iters), 4),
    }), flush=True)


def main() -> None:
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=here)
    ap.add_argument("--iters", type=int, default=20)
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.root))
    run(args.root, args.iters)


if __name__ == "__main__":
    main()
