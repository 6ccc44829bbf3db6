"""Time the dense prefill's attention alone on the chip, at the served shapes.

    chiprun -- python tools/flash_prefill_microbench.py [--root _parent]
        [--geometry mistral,jamba] [--layers 32]

One process times one checkout (``--root``: where ``production_stack_tpu``
is imported from, this repo by default).  Each case runs a chunk's attention
``--layers`` times (32) in one jitted program, every layer over K and V
pools of its own ``[N, 16, K, 128]`` (bf16) through one block table whose
blocks lie scattered in the pool as a served pool's do, every call's output
feeding the next call's queries, and prints the device-bound wall time of
that program: ms for that many layers.  What a layer does is what the
checkout's model does on a TPU: since PR 62 ``flash_prefill_attention`` with
the pools and the table (the kernel walks the pages); before, the
``gather_prefix_kv`` of the table's whole width and the kernel over the
gathered copy (its ``concatenate`` and ``pad`` inside) -- the tool tells the
two by the kernel's signature, so parent and change go into one call.

``--geometry``: ``mistral`` (H 32, K 8, window 4,096, a table of 512 blocks:
cells 1-2), ``solar`` (H 64, K 8, no window, 2,048 blocks: cell 5's one full
layer in four), ``jamba`` (H 20, K 1, 2,048 blocks: cell 6),
``laguna-window`` (H 64, K 8, window 512: cell 7's window layers, whose
512-row buffer is a pool of one 512-token page with the table ``[0]``; the
parent's side hands the kernel the buffer itself, nothing to gather).
Cases: 256 and 2,048 slots behind 0, 1k, 4k and 24k cached positions (those
the table holds; a window's buffer 0 and 512).  A CPU run refuses to time
anything.
"""

from __future__ import annotations

import argparse
import inspect
import json
import os
import sys
import time

D = 128
# name: (H, K, sliding window, blocks a table, block size, cached positions)
GEOMETRIES = {
    "mistral": (32, 8, 4096, 512, 16, (0, 1024, 4096)),
    "solar": (64, 8, None, 2048, 16, (0, 1024, 4096, 24000)),
    "jamba": (20, 1, None, 2048, 16, (0, 1024, 4096, 24000)),
    "laguna-window": (64, 8, 512, 1, 512, (0, 512)),
}
# (T, valid_len): the engine's two prefill buckets, full and padded.
CHUNKS = ((256, 256), (256, 50), (2048, 2048), (2048, 600))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--layers", type=int, default=32,
                    help="calls a program, each over pools of its own")
    ap.add_argument("--geometry", default="mistral,jamba")
    args = ap.parse_args()
    LAYERS = args.layers
    sys.path.insert(0, os.path.abspath(args.root))

    import jax
    import jax.numpy as jnp
    import numpy as np
    from production_stack_tpu.engine.ops.attention import gather_prefix_kv
    from production_stack_tpu.engine.ops.pallas.flash_prefill import (
        flash_prefill_attention,
    )

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"no TPU here ({dev.platform}): a CPU run times nothing")
    paged = "prefix_block_ids" in inspect.signature(
        flash_prefill_attention).parameters

    for name in args.geometry.split(","):
        H, K, window, P, BS, cached_lens = GEOMETRIES[name]
        # Blocks a layer's pool: twice what a table can name and the null
        # block; a window's buffer is the whole of its pool.
        N = 2 * P + 1 if P > 1 else 1

        def layer(q, k, v, k_pool, v_pool, ids, cached, valid):
            if paged:
                return flash_prefill_attention(
                    q, k, v, k_pool, v_pool, ids, cached, valid,
                    scale=D ** -0.5, sliding_window=window)
            if P > 1:
                kp, vp = gather_prefix_kv(k_pool, v_pool, ids, dtype=k.dtype)
            else:
                kp, vp = k_pool[0], v_pool[0]
            return flash_prefill_attention(
                q, k, v, kp, vp, cached, valid,
                scale=D ** -0.5, sliding_window=window)

        @jax.jit
        def layers(q, k, v, pools, ids, cached, valid):
            for k_pool, v_pool in pools:
                q = layer(q, k, v, k_pool, v_pool, ids, cached, valid)
            return q

        mk = lambda key, *shape: jax.random.normal(  # noqa: E731
            key, shape, jnp.bfloat16)
        keys = jax.random.split(jax.random.PRNGKey(P), 2 * LAYERS)
        pools = [(mk(keys[2 * i], N, BS, K, D), mk(keys[2 * i + 1], N, BS, K, D))
                 for i in range(LAYERS)]
        rng = np.random.default_rng(P)
        where = (rng.permutation(N - 1)[:P] + 1 if P > 1
                 else np.zeros(1, np.int64))
        for T, valid in CHUNKS:
            keys = jax.random.split(jax.random.PRNGKey(T), 3)
            q, k, v = mk(keys[0], T, H, D), mk(keys[1], T, K, D), mk(
                keys[2], T, K, D)
            for cached in cached_lens:
                # As the engine hands it: the live blocks, then null block 0.
                ids = np.zeros((P,), np.int32)
                live = -(-cached // BS)
                ids[:live] = where[:live]
                a = (q, k, v, pools, jnp.asarray(ids), jnp.int32(cached),
                     jnp.int32(valid))
                layers(*a).block_until_ready()
                t0 = time.perf_counter()
                for _ in range(args.iters):
                    out = layers(*a)
                out.block_until_ready()
                ms = (time.perf_counter() - t0) / args.iters * 1e3
                print(json.dumps({
                    "root": args.root, "device": dev.device_kind,
                    "paged": paged, "geometry": name,
                    "T": T, "table_blocks": P, "cached_len": cached,
                    "valid_len": valid, f"ms_{LAYERS}_layers": round(ms, 3),
                }), flush=True)


if __name__ == "__main__":
    main()
