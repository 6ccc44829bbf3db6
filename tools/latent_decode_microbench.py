"""Time the absorbed (decode) latent attention of models/sarvam_mla.py alone
on the chip, at the served shapes: one layer, S rows over a pool of latent
pages, contexts as the sessions-20k cell holds them.

    chiprun -- python tools/latent_decode_microbench.py [--rows 16] [--ctx 24000]

Prints microseconds a call and the share of 819 GB/s that the content of the
pages (576 values a position) makes of it; ``--page-tile`` overrides the
module's PAGE_TILE (blocks a tile of its walk).  PR 39 timed two other reads
with it, since removed from the module: every row's positions gathered to the
block table's full width one by one (10.45 ms at 16 rows x 24,000) and by
whole blocks (4.74 ms), against 1.79 ms for the walk that is served.  A CPU
run refuses: a time comes from the chip alone.
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


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--rows", type=int, default=16)
    p.add_argument("--ctx", type=int, default=24000)
    p.add_argument("--blocks", type=int, default=30000)
    p.add_argument("--repeat", type=int, default=20)
    p.add_argument("--page-tile", type=int, default=None,
                   help="blocks a tile of the tiles read (the module's "
                   "PAGE_TILE where not given)")
    args = p.parse_args()
    if jax.default_backend() != "tpu":
        raise SystemExit("latent_decode_microbench: needs the chip")
    if args.page_tile:
        m.PAGE_TILE = args.page_tile
    cfg = dataclasses.replace(PRESETS["sarvam-105b-ep4"], num_layers=2)
    layer = m.init_params(cfg, jax.random.PRNGKey(0))["layers"][1]
    S, bs, bmax = args.rows, 16, cfg.max_model_len // 16
    cache = jax.random.normal(
        jax.random.PRNGKey(1), (args.blocks, bs, m.cache_lanes(cfg)),
        jnp.bfloat16)
    rng = np.random.default_rng(0)
    tables = np.zeros((S, bmax), np.int32)
    need = -(-args.ctx // bs)
    for s in range(S):
        tables[s, :need] = rng.choice(args.blocks - 1, need, replace=False) + 1
    ctx = jnp.full((S,), args.ctx, jnp.int32)
    qn = jax.random.normal(jax.random.PRNGKey(2), (S, 64, 128), jnp.bfloat16)
    qr = jax.random.normal(jax.random.PRNGKey(3), (S, 64, 64), jnp.bfloat16)
    tables = jnp.asarray(tables)
    fn = jax.jit(lambda *a: m._absorbed_attention(layer, cfg, *a))
    got = fn(qn, qr, cache, tables, ctx).block_until_ready()
    t = time.perf_counter()
    for _ in range(args.repeat):
        got = fn(qn, qr, cache, tables, ctx)
    got.block_until_ready()
    seconds = (time.perf_counter() - t) / args.repeat
    need_bytes = S * args.ctx * m.cache_width(cfg) * 2
    print(json.dumps({"rows": S, "ctx": args.ctx, "page_tile": m.PAGE_TILE,
                      "us": seconds * 1e6,
                      "share_of_819_GBs": need_bytes / 819e9 / seconds,
                      "checksum": float(jnp.abs(got.astype(jnp.float32)).sum()),
                      "device": str(jax.devices()[0])}))


if __name__ == "__main__":
    main()
