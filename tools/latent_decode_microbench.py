"""Time the absorbed (decode) latent attention of models/sarvam_mla.py alone
on the chip, at the served shapes: one layer, S rows over a pool of latent
pages, contexts as the sessions-20k cell holds them.

    chiprun -- env PYTHONPATH=. python tools/latent_decode_microbench.py \
        [--rows 16] [--ctx 24000] [--kernel] [--chunk-blocks 16,32,64]

Prints microseconds a call and the share of 819 GB/s that the content of the
pages (576 values a position) makes of it, for the whole of
``_absorbed_attention`` as the module serves it on this device.  With
``--kernel`` it then times, on the same inputs and between the two weight
einsums only, the XLA walk (``_latent_walk``) and the Pallas kernel
(``ops/pallas/latent_attention.py``) at each ``--chunk-blocks`` (blocks a
stage; the kernel's own where not given) and each ``--buffers`` (slots of
its ring), and prints both, their ratio and the largest difference between
their outputs.  ``--page-tile`` overrides the module's PAGE_TILE (blocks a
tile of the XLA walk).  A CPU run refuses: a time comes from the chip alone.

PR 39 timed two other reads with it, since removed from the module: every
row's positions gathered to the block table's full width one by one (10.45
ms at 16 rows x 24,000) and by whole blocks (4.74 ms), against 1.79 ms for
the XLA walk (PAGE_TILE 128; 64 and 256 read the same to 2 %).

The kernel at 16 rows x 24,000 (my chip runs, PR 41, two calls; the XLA
walk beside it in the same processes 1,759 / 1,767 us, 30.7 % of 819 GB/s by
content):

    blocks a stage   ring of 2        ring of 3            ring of 4
    16               1,695 us (31.9)  1,003 us (53.9)      -
    32               1,320 us (40.9)  726 / 735 us (74.4)  722 us (74.8)
    48               -                718 us (75.2)        715 us (75.5)
    64               1,140 us (47.4)  717 / 716 us (75.3)  719 us (75.2)
    96               -                725 us (74.6)        723 us (74.7)

(in brackets the share of 819 GB/s by content; by the 640 lanes stored 716 us
is 83.8 %: the DMA engine's own rate for 20 kB copies, which no stage size or
ring depth passes).  The scheduler starts a stage's copies late in the
stage, so a ring of two idles the DMA engine for half of every stage; from
32 blocks and 3 slots on nothing moves by more than 2 %, and the kernel
serves with those (a row's last stage wastes half a stage on average: 256
positions of 24,000 at 32 blocks, 512 at 64).  Kernel against walk: the
largest difference 0.0078 where the largest value is 2.81, one bf16 ulp.
"""

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from production_stack_tpu.engine.config import PRESETS
from production_stack_tpu.engine.models import sarvam_mla as m


def _timed(fn, args, repeat):
    """(seconds a call, the last result) of ``fn(*args)`` once compiled."""
    got = fn(*args).block_until_ready()
    t = time.perf_counter()
    for _ in range(repeat):
        got = fn(*args)
    got.block_until_ready()
    return (time.perf_counter() - t) / repeat, got


def _ints(text):
    return [int(part) for part in text.split(",")]


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--rows", type=int, default=16)
    p.add_argument("--ctx", type=int, default=24000)
    p.add_argument("--blocks", type=int, default=30000)
    p.add_argument("--repeat", type=int, default=20)
    p.add_argument("--page-tile", type=int, default=None,
                   help="blocks a tile of the tiles read (the module's "
                   "PAGE_TILE where not given)")
    p.add_argument("--kernel", action="store_true",
                   help="time the Pallas kernel beside the XLA walk")
    p.add_argument("--chunk-blocks", type=_ints, default=None,
                   help="blocks a stage of the kernel, several with commas "
                   "(the kernel's CHUNK_BLOCKS where not given)")
    p.add_argument("--preset", default="sarvam-105b-ep4",
                   help="the latent preset whose widths are timed "
                   "(xing4.0-29b-a4b-stage: 32 query heads a row)")
    p.add_argument("--buffers", type=_ints, default=None,
                   help="slots of the kernel's ring, several with commas "
                   "(the kernel's BUFFERS where not given)")
    args = p.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("latent_decode_microbench: needs the chip")
    if args.page_tile:
        m.PAGE_TILE = args.page_tile
    cfg = dataclasses.replace(PRESETS[args.preset], num_layers=2)
    H = cfg.num_heads
    layer = m.init_params(cfg, jax.random.PRNGKey(0))["layers"][1]
    S, bs, bmax = args.rows, 16, cfg.max_model_len // 16
    lanes = m.cache_lanes(cfg)
    # Rows as the module writes them: content in the first lanes, zero pad.
    cache = jax.random.normal(
        jax.random.PRNGKey(1), (args.blocks, bs, lanes), jnp.bfloat16
    ) * (jnp.arange(lanes) < m.cache_width(cfg))
    rng = np.random.default_rng(0)
    tables = np.zeros((S, bmax), np.int32)
    need = -(-args.ctx // bs)
    for s in range(S):
        tables[s, :need] = rng.choice(args.blocks - 1, need, replace=False) + 1
    ctx = jnp.full((S,), args.ctx, jnp.int32)
    qn = jax.random.normal(jax.random.PRNGKey(2), (S, H, 128), jnp.bfloat16)
    qr = jax.random.normal(jax.random.PRNGKey(3), (S, H, 64), jnp.bfloat16)
    tables = jnp.asarray(tables)
    need_bytes = S * args.ctx * m.cache_width(cfg) * 2

    def share(seconds):
        return need_bytes / 819e9 / seconds

    seconds, got = _timed(
        jax.jit(lambda *a: m._absorbed_attention(layer, cfg, *a)),
        (qn, qr, cache, tables, ctx), args.repeat)
    print(json.dumps({
        "preset": args.preset, "heads": H,
        "rows": S, "ctx": args.ctx, "page_tile": m.PAGE_TILE,
        "served": ("pallas" if m.use_pallas_latent_decode(lanes)
                   else "xla-walk"),
        "us": seconds * 1e6, "share_of_819_GBs": share(seconds),
        "checksum": float(jnp.abs(got.astype(jnp.float32)).sum()),
        "device": str(jax.devices()[0])}))
    if not args.kernel:
        return

    from production_stack_tpu.engine.ops.pallas import latent_attention as la

    L, scale = cfg.kv_lora_rank, m.softmax_scale(cfg)
    q_lat = jax.random.normal(
        jax.random.PRNGKey(4), (S, H, lanes), jnp.bfloat16
    ) * (jnp.arange(lanes) < m.cache_width(cfg))
    inputs = (q_lat.astype(jnp.bfloat16), cache, tables, ctx)
    walk_s, want = _timed(
        jax.jit(lambda *a: m._latent_walk(*a, L, scale).astype(jnp.bfloat16)),
        inputs, args.repeat)
    want = np.asarray(want, np.float32)
    for buffers in args.buffers or [la.BUFFERS]:
        la.BUFFERS = buffers
        jax.clear_caches()  # BUFFERS is read when the kernel is traced
        for chunk in args.chunk_blocks or [la.CHUNK_BLOCKS]:
            kernel_s, got = _timed(
                lambda *a: la.latent_decode_attention_pallas(
                    *a, latent_rank=L, scale=scale, chunk_blocks=chunk),
                inputs, args.repeat)
            print(json.dumps({
                "chunk_blocks": chunk, "buffers": buffers,
                "kernel_us": kernel_s * 1e6,
                "kernel_share_of_819_GBs": share(kernel_s),
                "xla_walk_us": walk_s * 1e6,
                "xla_walk_share_of_819_GBs": share(walk_s),
                "walk_over_kernel": walk_s / kernel_s,
                "max_abs_diff": float(np.abs(
                    np.asarray(got, np.float32) - want).max()),
                "max_abs_xla_walk": float(np.abs(want).max())}))


if __name__ == "__main__":
    main()
