"""Time ``flash_prefill_attention`` alone on the chip, at the served shapes.

    chiprun -- python tools/flash_prefill_microbench.py [--root _parent]

One process times one checkout (``--root``: where ``production_stack_tpu``
is imported from, this repo by default).  Each case runs the kernel 32
times in one jitted program (mistral-7b's 32 layers: H 32, K 8, D 128,
bf16), every call's output feeding the next call's queries, and prints
the device-bound wall time of that program: ms for 32 layers.  A CPU run
refuses to time anything.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

H, K, D, LAYERS = 32, 8, 128, 32
# (T, C, cached_len, valid_len): the engine's two prefill buckets, with
# the 8,192 gathered prefix slots it always passes and with none.
CASES = [
    (256, 8192, 0, 256), (256, 0, 0, 256), (256, 8192, 3500, 50),
    (2048, 8192, 0, 2048), (2048, 0, 0, 2048),
    (2048, 8192, 0, 600), (2048, 0, 0, 600),
    (2048, 8192, 1000, 2000),
]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.root))

    import jax
    import jax.numpy as jnp
    from production_stack_tpu.engine.ops.pallas.flash_prefill import (
        flash_prefill_attention,
    )

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"no TPU here ({dev.platform}): a CPU run times nothing")

    @jax.jit
    def layers(q, k, v, kp, vp, cached, valid):
        for _ in range(LAYERS):
            q = flash_prefill_attention(
                q, k, v, kp, vp, cached, valid, scale=D ** -0.5,
                sliding_window=4096,
            )
        return q

    for T, C, cached, valid in CASES:
        keys = jax.random.split(jax.random.PRNGKey(T + C), 5)
        mk = lambda key, n, h: jax.random.normal(  # noqa: E731
            key, (n, h, D), jnp.bfloat16)
        a = (mk(keys[0], T, H), mk(keys[1], T, K), mk(keys[2], T, K),
             mk(keys[3], C, K), mk(keys[4], C, K),
             jnp.int32(cached), jnp.int32(valid))
        layers(*a).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(args.iters):
            out = layers(*a)
        out.block_until_ready()
        ms = (time.perf_counter() - t0) / args.iters * 1e3
        print(json.dumps({
            "root": args.root, "device": dev.device_kind, "T": T, "C": C,
            "cached_len": cached, "valid_len": valid,
            "ms_32_layers": round(ms, 3),
        }), flush=True)


if __name__ == "__main__":
    main()
