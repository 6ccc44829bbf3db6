"""Time ``paged_decode_attention_pallas`` alone on the chip, at the cells' shapes.

    chiprun -- python tools/paged_decode_microbench.py [--root _parent]

One process times one checkout (``--root``: where ``production_stack_tpu``
is imported from, this repo by default).  Each case runs the kernel 32
times in one jitted program (mistral-7b's 32 layers: H 32, K 8, D 128,
block 16, bf16, sliding window 4096), every call's output feeding the
next call's queries, over a pool of the served size whose block tables
are a random permutation, and prints the device-bound wall time as µs a
call, beside the share of 819 GB/s that is for the K and V bytes
``bench/reduce/kv_bytes.py`` counts for the call.  The last line fits
µs a call = a · chunks + b · live rows + c over the cases, a chunk being
128 positions of one row (524,288 bytes: 0.64 µs at 819 GB/s).  The first
case is also compared with the gather path, once.  A CPU run refuses to
time anything.

Another geometry and another pool (PR 54), e.g. jamba2-3b's two attention
layers as cell 6 serves them:

    ... --heads 20 --kv-heads 1 --window 0 --pool-blocks 525184 \
        --max-len 32768 --rows 16 --context 24000 --layout adjacent

``--layout`` says how a row's blocks lie in the pool: ``permuted`` (the
default: no two neighbours), ``adjacent`` (a row's blocks ascend one by
one, as the pool hands them out), ``mixed:<share>`` (that share of the
table's groups are single blocks from anywhere, the rest neighbours; a
group is what 32 kB holds of the geometry's pages).  Every line carries
``coalesced_pct``, the share of the live rows' groups that one descriptor
can fetch, by the kernel's own rule where ``--root`` has it.  ``--rows`` and
``--context`` replace the default cases by one.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

H, K, D, BS, LAYERS = 32, 8, 128, 16, 32
WINDOW, MAX_LEN, POOL_BLOCKS = 4096, 8192, 3738
HBM_BYTES_PER_S = 819e9  # bench/peaks.json, TPU v5e
CHUNK = 128  # positions: the unit of the fit, whatever the kernel's stage is
# (name, S, contexts of the live rows; the other rows are padding, ctx 0).
CASES = [
    # cell 2 (sessions-prefix): bucket 16, ten rows at 3.0-4.1k positions.
    ("cell2", 16, [3000 + 122 * i for i in range(10)]),
    # cell 1 (chat-steady): bucket 4, three or four rows near 900.
    ("cell1-3rows", 4, [700, 900, 1100]),
    ("cell1-4rows", 4, [650, 800, 950, 1200]),
    # what tells a from b: rows against positions.
    ("16x900", 16, [900] * 16),
    ("10x900-of-16", 16, [820 + 16 * i for i in range(10)]),
    ("1x4096-of-16", 16, [4096]),
    ("4x3500", 4, [3100, 3400, 3700, 4000]),
    # 16,384 positions as 4, 8 and 16 rows: the cost of a row alone.
    ("4x4096", 4, [4096] * 4),
    ("8x2048", 8, [2048] * 8),
    ("16x1024", 16, [1024] * 16),
]


def group_blocks() -> int:
    """Pages of this geometry that one descriptor of the kernel carries:
    the root's own rule (``paged_attention.py: blocks_per_descriptor``), or
    what 32 kB holds for a root from before the rule, so that a parent's
    lines carry ``coalesced_pct`` for their tables too."""
    page_bytes = BS * K * D * 2
    try:
        from production_stack_tpu.engine.ops.pallas.paged_attention import (
            blocks_per_descriptor,
        )
    except ImportError:
        return max(1, 32 * 1024 // page_bytes)
    return blocks_per_descriptor(page_bytes)


def make_case(rng: np.random.Generator, S: int, contexts, layout="permuted"):
    ctx = np.zeros(S, np.int32)
    ctx[:len(contexts)] = contexts
    tables = np.zeros((S, MAX_LEN // BS), np.int32)
    R = group_blocks()
    share = {"permuted": 1.0, "adjacent": 0.0}.get(layout)
    if share is None:
        share = float(layout.partition("mixed:")[2])
    # (row, first entry, entries, singles?) a group of the tables.
    groups = [(s, j0, min(R, nb - j0), share == 1.0
               or (share > 0.0 and rng.random() < share))
              for s, c in enumerate(ctx)
              for nb in [-(-int(c) // BS)] for j0 in range(0, nb, R)]
    # Runs ascend from block 1 (0 is the null block), one after another;
    # singles are drawn from a permutation of what the runs leave.
    in_runs = sum(n for *_, n, single in groups if not single)
    free = rng.permutation(np.arange(1 + in_runs, POOL_BLOCKS))
    used, next_run = 0, 1
    for s, j0, n, single in groups:
        if single:
            tables[s, j0:j0 + n] = free[used:used + n]
            used += n
        else:
            tables[s, j0:j0 + n] = np.arange(next_run, next_run + n)
            next_run += n
    assert used <= len(free), "the case does not fit the pool"
    return tables, ctx


def coalesced_pct(tables: np.ndarray, ctx: np.ndarray) -> float:
    """Share of the live rows' groups that are R ascending neighbours, by
    the root's ``whole_groups`` where it has one."""
    R = group_blocks()
    S, bmax = tables.shape
    t = tables[:, :bmax // R * R]
    try:
        from production_stack_tpu.engine.ops.pallas.paged_attention import (
            whole_groups,
        )
        whole = whole_groups(t, R, xp=np)
    except ImportError:
        t = t.reshape(S, -1, R)
        whole = (t == t[..., :1] + np.arange(R)).all(-1) & (t[..., -1] != 0)
    groups = -(-(-(-ctx // BS)) // R)
    live = np.arange(whole.shape[1])[None, :] < groups[:, None]
    return round(100.0 * (whole & live).sum() / max(live.sum(), 1), 2)


def run(kernel, root: str, iters: int, cases, layout: str) -> None:
    """Time ``kernel`` (the signature of ``paged_decode_attention_pallas``)
    over ``cases`` and print a line a case, then the fit."""
    import jax
    import jax.numpy as jnp
    from reduce.kv_bytes import kv_bytes_per_token
    from production_stack_tpu.engine.ops.attention import (
        paged_decode_attention,
    )

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"no TPU here ({dev.platform}): a CPU run times nothing")

    kw = dict(scale=D ** -0.5, sliding_window=WINDOW)
    window = WINDOW or MAX_LEN
    bytes_per_token = kv_bytes_per_token(
        {"num_hidden_layers": 1, "num_key_value_heads": K, "head_dim": D})

    @jax.jit
    def layers(q, k_cache, v_cache, tables, ctx):
        for _ in range(LAYERS):
            q = kernel(q, k_cache, v_cache, tables, ctx, **kw)
        return q

    rng = np.random.default_rng(0)
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    pool = (POOL_BLOCKS, BS, K, D)
    k_cache = jax.random.normal(keys[0], pool, jnp.bfloat16)
    v_cache = jax.random.normal(keys[1], pool, jnp.bfloat16)

    rows = []
    for name, S, contexts in cases:
        tables, ctx = make_case(rng, S, contexts, layout)
        q = jax.random.normal(keys[2], (S, H, D), jnp.bfloat16)
        a = (q, k_cache, v_cache, jnp.asarray(tables), jnp.asarray(ctx))
        line = {"root": root, "device": dev.device_kind, "case": name,
                "S": S, "live_rows": len(contexts), "layout": layout,
                "coalesced_pct": coalesced_pct(tables, ctx)}
        if not rows:
            got = kernel(*a, **kw)
            want = paged_decode_attention(*a, **kw)
            live = ctx > 0
            err = np.abs(np.asarray(got, np.float32)[live]
                         - np.asarray(want, np.float32)[live]).max()
            line["max_abs_err_vs_gather"] = float(err)
            line["max_abs_gather"] = float(
                np.abs(np.asarray(want, np.float32)[live]).max())
        layers(*a).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(iters):
            out = layers(*a)
        out.block_until_ready()
        us = (time.perf_counter() - t0) / iters / LAYERS * 1e6
        # What kv_bytes.py counts: min(context, window) in whole blocks.
        kv_tokens = sum(-(-min(c, window) // BS) * BS for c in contexts)
        chunks = sum(-(-c // CHUNK) for c in contexts)
        line.update({
            "positions": sum(contexts), "chunks": chunks,
            "us_per_call": round(us, 2),
            "hbm_share_pct": round(
                kv_tokens * bytes_per_token / HBM_BYTES_PER_S / us * 1e8, 2),
        })
        rows.append(line)
        print(json.dumps(line), flush=True)

    if len(rows) < 3:
        return  # one case: nothing to fit
    x = np.array([[r["chunks"], r["live_rows"], 1.0] for r in rows])
    y = np.array([r["us_per_call"] for r in rows])
    (a_us, b_us, c_us), *_ = np.linalg.lstsq(x, y, rcond=None)
    print(json.dumps({
        "root": root, "fit": "us_per_call = a*chunks + b*live_rows + c",
        "a_us_per_chunk": round(a_us, 4), "b_us_per_row": round(b_us, 3),
        "c_us_per_call": round(c_us, 3),
        "dma_us_per_chunk": round(CHUNK * bytes_per_token
                                  / HBM_BYTES_PER_S * 1e6, 4),
        "worst_residual_us": round(float(np.abs(x @ [a_us, b_us, c_us]
                                                 - y).max()), 2),
    }), flush=True)


def main() -> None:
    global H, K, WINDOW, POOL_BLOCKS, MAX_LEN
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ap = argparse.ArgumentParser()
    ap.add_argument("--root", default=here)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--heads", type=int, default=H)
    ap.add_argument("--kv-heads", type=int, default=K)
    ap.add_argument("--window", type=int, default=WINDOW,
                    help="sliding window; 0 for none")
    ap.add_argument("--pool-blocks", type=int, default=POOL_BLOCKS)
    ap.add_argument("--max-len", type=int, default=MAX_LEN)
    ap.add_argument("--layout", default="permuted",
                    help="permuted | adjacent | mixed:<share of single blocks>")
    ap.add_argument("--rows", type=int, default=16)
    ap.add_argument("--context", type=int, default=None,
                    help="one case of --rows rows at this context each")
    args = ap.parse_args()
    H, K, WINDOW = args.heads, args.kv_heads, args.window or None
    POOL_BLOCKS, MAX_LEN = args.pool_blocks, args.max_len
    cases = CASES if args.context is None else [
        (f"{args.rows}x{args.context}", args.rows,
         [args.context] * args.rows)]
    sys.path.insert(0, os.path.abspath(args.root))
    # The benchmark's own arithmetic, imported the way bench/run.py does.
    sys.path.append(os.path.join(here, "bench"))
    from production_stack_tpu.engine.ops.pallas.paged_attention import (
        paged_decode_attention_pallas,
    )

    run(paged_decode_attention_pallas, args.root, args.iters, cases,
        args.layout)


if __name__ == "__main__":
    main()
