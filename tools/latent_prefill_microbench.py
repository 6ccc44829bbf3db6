"""Time one layer's prefill attention of models/sarvam_mla.py alone on the
chip, at the served shapes: the XLA walk (``_expanded_attention``) against
the Pallas kernel path that serves on a TPU (``_prefill_attention``: the
query into the latent space, ``latent_prefill_attention_pallas``, ``W_UV``).

    chiprun -- env PYTHONPATH=. python tools/latent_prefill_microbench.py \
        [--preset sarvam-105b-ep4,xing4.0-29b-a4b-stage] [--q-rows 512,1024]
        [--chunk-blocks 32] [--layout permuted,ascending] [--repeat 10]
        [--expanded 8,16]

A line a case: a 256-slot chunk with 176 valid slots over 24,000 cached
positions (a round of ``sessions-20k``), a 2,048-slot chunk over 0, 8,192 and
20,480 (a history seeded in set-up); the block table permuted over the pool
or ascending.  ``walk_us`` / ``path_us`` / ``kernel_us``: microseconds a call
of the XLA walk, of the whole kernel path and of the kernel alone;
``*_us_a_tile``: the same over the call's key tiles of 512 (the prefix's and
the chunk's own); ``*_mxu``: the share of 197 TFLOP/s that the products each
form makes are of its time (the walk expands every tile by ``W_kvb`` for all
``T`` slots; the kernel scores 640 lanes and weighs 512 for the slots of the
query tiles that hold a valid one); ``max_abs_diff`` between the two outputs
beside the largest value.  ``--rehearse`` runs the tiny preset on the CPU
with the kernel interpreted and prints no time; any other CPU run refuses.

My chip runs, PR 57 (one v5e; bf16; 32 blocks a stage, a ring of three; the
fastest of three batches of 10 calls; also in PERF.md section 5, "One prefill
program"):

    heads  slots (valid) x cached   walk          kernel path    x     the form not taken
    64     256 (176) x 24,000       6.18 ms  55%  3.84 ms  85%   1.61  5.56 ms  61%  (1.11 x)
    64     2,048 x 0                6.87     15   3.89     83    1.77  2.22     47   (3.09)
    64     2,048 x 8,192            22.98    23   18.63    85    1.23  13.20    40   (1.74)
    64     2,048 x 20,480           47.14    24   39.97    87    1.18  29.52    39   (1.60)
    32     256 (176) x 24,000       3.26     52   2.13     83    1.53  2.81     61   (1.16)
    32     2,048 x 0                3.73     14   1.79     80    2.08  1.11     47   (3.35)
    32     2,048 x 8,192            11.78    22   9.17     84    1.29  6.57     40   (1.79)
    32     2,048 x 20,480           23.88    24   19.86    87    1.20  14.73    39   (1.62)

(a layer's call; beside each time the share of 197 TFLOP/s that the form's
own products are of it -- the kernel's of the kernel alone, which is the path
less 0.04-0.06 ms at 256 slots and 0.6-1.5 ms at 2,048 for the two weight
einsums; the permuted table; an ascending one reads the same to 0.3 % in every
row, as the decode kernel's did: a stage's 32 copies of 20 kB do not care
where the blocks lie.)  Per key tile at 64 heads x 256 slots: the walk 128.7
us, the path 79.9.  The walk's time is the traffic of its temporaries: its
products alone would take 71 us.  The kernel's is its products: 85 % of the
MXU's peak, and those are as many as the expanded form's at 176 valid slots
(1,152 multiply-adds a (query row, key) pair against 131,072 a key a head and
320 a pair).  Query tiles of 512 / 1,024 / 2,048 rows (``--q-rows``) read 4.19
/ 3.84 / 4.16 ms at 64 heads x 256 slots (the widest tile computes 192 slots
for 176 valid), 43.6 / 40.0 / 39.7 at 2,048 x 20,480 and, the kernel alone,
2.53 / 2.40 / 4.04 at 2,048 x nothing (an earlier call, means of 10): the
kernel serves with 1,024.  Largest
difference between the walk's output and the path's: 0.0005 where the largest
value is 0.09-0.12 (256 slots), 0.0078 of 1.84 (nothing cached): a bf16 ulp.

**The form not taken** (``--expanded 8,16``: heads a grid step; query tiles of
256 and 512 slots; 16 heads read 1-2 % under 8, the table has 16): expanded in
VMEM, built in this file alone.  It beats the walk everywhere and the served
kernel at 2,048 slots (29.5 against 40.0 ms over 20,480: a chunk that long
shares one expansion among eight times the queries) and loses to it at 256
slots (5.56 against 3.84), which is what a round of the cells runs; 2,048-slot
chunks run in set-up alone (ROADMAP S1 e).
"""

import argparse
import dataclasses
import functools
import json
import math
import time

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from production_stack_tpu.engine.config import PRESETS
from production_stack_tpu.engine.models import sarvam_mla as m
from production_stack_tpu.engine.ops.pallas import latent_attention as la

PEAK_FLOPS = 197e12  # one v5e, bf16 (bench/peaks.json)
# (slots, valid slots, cached positions)
CASES = ((256, 176, 24000), (2048, 2048, 0), (2048, 2048, 8192),
         (2048, 2048, 20480))
KEY_TILE = 512


def _timed(fn, args, repeat):
    """(seconds a call, the last result) of ``fn(*args)`` once compiled: the
    fastest of three batches of ``repeat`` calls (a stall of the machine's
    host sits in one batch in thirty)."""
    got = fn(*args).block_until_ready()
    best = float("inf")
    for _ in range(3):
        t = time.perf_counter()
        for _ in range(repeat):
            got = fn(*args)
        got.block_until_ready()
        best = min(best, (time.perf_counter() - t) / repeat)
    return best, got


def _list(kind):
    return lambda text: [kind(part) for part in text.split(",")]


def _walk_flops(cfg, T, tiles):
    """The products of ``_expanded_attention`` over ``tiles`` key tiles."""
    H, L = cfg.num_heads, cfg.kv_lora_rank
    nope, rope, v = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    return tiles * 2 * KEY_TILE * H * (
        L * (nope + v) + T * (nope + rope) + T * v)


def _kernel_flops(cfg, lanes, T, valid, cached, q_rows):
    """The products of the kernel: live query rows against the keys of the
    stages they are given, 640 lanes scored and ``latent_rank`` weighed."""
    H = cfg.num_heads
    Tq, own = la.prefill_tiling(T, H, q_rows)
    first = np.arange(-(-valid // Tq)) * Tq
    keys = len(first) * cached + int(((first // own + 1) * own).sum())
    return 2 * Tq * H * keys * (lanes + cfg.kv_lora_rank)


# -- the form not taken --------------------------------------------------------
# The same attention *expanded in VMEM* (ISSUE 57's other form), kept here and
# nowhere on the served path so that its reading can be made again
# (``--expanded``): a grid over (a group of heads, a query tile), the key walk
# innermost, ``latent_attention._walk_prefix``; a head at a time expands the
# stage's latents by its slice of ``W_kvb`` ([keys, 512] x [512, 256]), scores
# its queries against the expanded keys and the stage's rotary keys, and
# weighs the expanded values.  A query tile past ``valid_len`` is skipped; the
# slots inside a tile are not, because the expansion is shared by all of them.


def _expanded_kernel(
    ids_ref,  # [P] int32 SMEM
    lens_ref,  # [2] int32 SMEM: cached_len, valid_len
    qn_ref,  # [G, Tq, nope] VMEM: a group of heads' queries, head-major
    qr_ref,  # [G, Tq, lanes - L]: the rotary part, zero-padded
    w_ref,  # [G, L, nope + v]: the group's slice of W_kvb
    rows_ref,  # [T, lanes]: the chunk's own rows
    cache_hbm,  # [N, bs, lanes] HBM
    o_ref,  # [G, Tq, v]
    m_ref, l_ref, acc_ref,  # [G, Tq, 1], [G, Tq, 1], [G, Tq, v] fp32
    sems,
    *bufs,
    L: int,
    nope: int,
    scale: float,
    own: int,
):
    G, Tq, _ = qn_ref.shape
    dtype = bufs[0].dtype
    cached, valid = lens_ref[0], lens_ref[1]
    first = pl.program_id(1) * Tq
    nt = (((1,), (1,)), ((), ()))  # [q, d] x [k, d] -> [q, k]

    def fold(tile, live):
        latents, rotary = tile[:, :L], tile[:, L:]

        def head(h, carry):
            kv = jnp.dot(latents, w_ref[h],
                         preferred_element_type=jnp.float32).astype(dtype)
            s = (jax.lax.dot_general(qn_ref[h], kv[:, :nope], nt,
                                     preferred_element_type=jnp.float32)
                 + jax.lax.dot_general(qr_ref[h], rotary, nt,
                                       preferred_element_type=jnp.float32)
                 ) * scale
            s = jnp.where(live, s, la.NEG_INF)
            m = m_ref[h]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l_ref[h] = l_ref[h] * alpha + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = acc_ref[h] * alpha + jnp.dot(
                p.astype(dtype), kv[:, nope:],
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new
            return carry

        jax.lax.fori_loop(0, G, head, 0)

    @pl.when(first >= valid)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(first < valid)
    def _():
        m_ref[...] = jnp.full_like(m_ref, la.NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        la._walk_prefix(ids_ref, cached, cache_hbm, sems, bufs, fold)
        slot_of = first + jax.lax.broadcasted_iota(jnp.int32, (Tq, 1), 0)
        own_key = jax.lax.broadcasted_iota(jnp.int32, (1, own), 1)

        def own_stage(j, carry):
            k = j * own + own_key
            fold(rows_ref[pl.ds(pl.multiple_of(j * own, own), own), :],
                 (k <= slot_of) & la._live(k, valid))
            return carry

        jax.lax.fori_loop(0, (first + Tq - 1) // own + 1, own_stage, 0)
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("L", "scale", "heads_a_step", "q_tile", "chunk_blocks",
                     "interpret"),
)
def expanded_prefill(q_nope, q_rope, w, rows, cache, ids, cached_len,
                     valid_len, *, L, scale, heads_a_step=16, q_tile=256,
                     chunk_blocks=la.CHUNK_BLOCKS, interpret=False):
    """``q_nope`` [T, H, nope], ``q_rope`` [T, H, rope], ``w`` [L, H,
    nope + v] (``sarvam_mla._kv_b``) -> [T, H, v], as ``_expanded_attention``
    gives it."""
    T, H, nope = q_nope.shape
    v = w.shape[-1] - nope
    _, bs, lanes = cache.shape
    C = min(chunk_blocks, ids.shape[0])
    G, own = min(heads_a_step, H), min(512, T)
    # A query tile lies inside one own stage, or is whole stages.
    Tq = math.gcd(min(q_tile, T), own) if q_tile < own else min(q_tile, T)
    # Head-major, so that a head is a leading index inside the kernel.
    qn = q_nope.transpose(1, 0, 2)
    qr = jnp.pad(q_rope.transpose(1, 0, 2),
                 ((0, 0), (0, 0), (0, lanes - L - q_rope.shape[-1])))
    kernel = functools.partial(
        _expanded_kernel, L=L, nope=nope, scale=scale, own=own)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(H // G, T // Tq),
        in_specs=[
            pl.BlockSpec((G, Tq, nope), lambda g, i, *_: (g, i, 0)),
            pl.BlockSpec((G, Tq, lanes - L), lambda g, i, *_: (g, i, 0)),
            pl.BlockSpec((G, L, nope + v), lambda g, i, *_: (g, 0, 0)),
            pl.BlockSpec((T, lanes), lambda g, i, *_: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((G, Tq, v), lambda g, i, *_: (g, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, Tq, 1), jnp.float32),
            pltpu.VMEM((G, Tq, 1), jnp.float32),
            pltpu.VMEM((G, Tq, v), jnp.float32),
            pltpu.SemaphoreType.DMA((la.BUFFERS, C)),
            *[pltpu.VMEM((C, bs, lanes), cache.dtype)] * la.BUFFERS,
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((H, T, v), q_nope.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            disable_bounds_checks=True, vmem_limit_bytes=96 * 1024 * 1024),
        name="latent_prefill_expanded_experiment",
    )(ids, jnp.stack([jnp.asarray(cached_len, jnp.int32),
                      jnp.asarray(valid_len, jnp.int32)]),
      qn, qr, w.transpose(1, 0, 2), rows, cache)
    return out.transpose(1, 0, 2)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--preset", type=_list(str),
                   default=["sarvam-105b-ep4", "xing4.0-29b-a4b-stage"])
    p.add_argument("--layout", type=_list(str),
                   default=["permuted", "ascending"])
    p.add_argument("--q-rows", type=_list(int), default=[la.Q_ROWS],
                   help="query rows (slots x heads) a grid step")
    p.add_argument("--chunk-blocks", type=_list(int),
                   default=[la.CHUNK_BLOCKS], help="blocks a prefix stage")
    p.add_argument("--blocks", type=int, default=30000)
    p.add_argument("--repeat", type=int, default=10)
    p.add_argument("--expanded", type=_list(int), default=[],
                   help="also time the form not taken, expanded in VMEM, at "
                   "these many heads a grid step (e.g. 8,16)")
    p.add_argument("--rehearse", action="store_true",
                   help="the tiny preset on the CPU, interpreted: no time")
    args = p.parse_args()
    on_chip = jax.default_backend() == "tpu"
    if not on_chip and not args.rehearse:
        raise SystemExit("latent_prefill_microbench: needs the chip")
    presets = ["tiny-sarvam"] if args.rehearse else args.preset
    cases = ((32, 20, 100), (64, 64, 0)) if args.rehearse else CASES
    bs = 16
    for preset in presets:
        cfg = dataclasses.replace(PRESETS[preset], num_layers=2)
        H, L, lanes = cfg.num_heads, cfg.kv_lora_rank, m.cache_lanes(cfg)
        dtype = jnp.dtype(cfg.dtype)
        layer = m.init_params(cfg, jax.random.PRNGKey(0))["layers"][1]
        content = jnp.arange(lanes) < m.cache_width(cfg)
        blocks = 64 if args.rehearse else args.blocks
        cache = jax.random.normal(
            jax.random.PRNGKey(1), (blocks, bs, lanes), dtype) * content
        scale, w = m.softmax_scale(cfg), m._kv_b(layer, cfg)
        walk = jax.jit(lambda *a: m._expanded_attention(layer, cfg, *a))
        for T, valid, cached in cases:
            qn = jax.random.normal(
                jax.random.PRNGKey(2), (T, H, cfg.qk_nope_head_dim), dtype)
            qr = jax.random.normal(
                jax.random.PRNGKey(3), (T, H, cfg.qk_rope_head_dim), dtype)
            rows = jax.random.normal(
                jax.random.PRNGKey(4), (T, lanes), dtype) * content
            q_lat = m._into_latent(w, cfg, qn, qr, lanes)
            lens = (jnp.int32(cached), jnp.int32(valid))
            tiles = -(-cached // KEY_TILE) + -(-T // KEY_TILE)
            for layout in args.layout if cached else args.layout[:1]:
                ids = np.zeros((cfg.max_model_len // bs,), np.int32)
                need = -(-cached // bs)
                ids[:need] = 1 + (
                    np.arange(need) if layout == "ascending" else
                    np.random.default_rng(0).choice(
                        blocks - 1, need, replace=False))
                ids = jnp.asarray(ids)
                walk_s, want = _timed(
                    walk, (qn, qr, rows, cache, ids, *lens), args.repeat)
                want = np.asarray(want, np.float32)
                for q_rows in args.q_rows:
                    for chunk in args.chunk_blocks:
                        how = dict(latent_rank=L, scale=scale, q_rows=q_rows,
                                   chunk_blocks=chunk,
                                   interpret=not on_chip)
                        kernel_s, _ = _timed(
                            lambda *a: la.latent_prefill_attention_pallas(
                                *a, **how),
                            (q_lat, rows, cache, ids, *lens), args.repeat)
                        path_s, got = _timed(
                            jax.jit(lambda qn, qr, rows, cache, ids, c, v:
                                    m._out_of_latent(
                                        w, cfg,
                                        la.latent_prefill_attention_pallas(
                                            m._into_latent(
                                                w, cfg, qn, qr, lanes),
                                            rows, cache, ids, c, v, **how))),
                            (qn, qr, rows, cache, ids, *lens), args.repeat)
                        got = np.asarray(got, np.float32)
                        Tq = la.prefill_tiling(T, H, q_rows)[0]
                        line = {
                            "preset": preset, "heads": H, "slots": T,
                            "valid": valid, "cached": cached,
                            "layout": layout, "q_rows": q_rows,
                            "slots_a_query_tile": Tq,
                            "chunk_blocks": chunk, "buffers": la.BUFFERS,
                            "key_tiles": tiles,
                            "max_abs_diff": float(
                                np.abs(got - want)[:valid].max()),
                            "max_abs_walk": float(np.abs(want[:valid]).max()),
                            "padded_tiles_zero":
                                not got[-(-valid // Tq) * Tq:].any(),
                        }
                        if on_chip:
                            kf = _kernel_flops(
                                cfg, lanes, T, valid, cached, q_rows)
                            line.update({
                                "walk_us": walk_s * 1e6,
                                "path_us": path_s * 1e6,
                                "kernel_us": kernel_s * 1e6,
                                "walk_over_path": walk_s / path_s,
                                "walk_us_a_tile": walk_s * 1e6 / tiles,
                                "path_us_a_tile": path_s * 1e6 / tiles,
                                "walk_mxu": _walk_flops(cfg, T, tiles)
                                / PEAK_FLOPS / walk_s,
                                "kernel_mxu": kf / PEAK_FLOPS / kernel_s,
                                "device": str(jax.devices()[0]),
                            })
                        print(json.dumps(line), flush=True)
                for group in args.expanded:
                    q_tile = 256 if T <= 256 else 512
                    exp_s, got = _timed(
                        lambda *a: expanded_prefill(
                            *a, L=L, scale=scale, heads_a_step=group,
                            q_tile=q_tile, interpret=not on_chip),
                        (qn, qr, w, rows, cache, ids, *lens), args.repeat)
                    got = np.asarray(got, np.float32)
                    line = {
                        "preset": preset, "heads": H, "slots": T,
                        "valid": valid, "cached": cached, "layout": layout,
                        "form": "expanded in VMEM (not served)",
                        "heads_a_step": group, "slots_a_query_tile": q_tile,
                        "max_abs_diff": float(
                            np.abs(got - want)[:valid].max()),
                    }
                    if on_chip:
                        line.update({
                            "expanded_us": exp_s * 1e6,
                            "walk_us": walk_s * 1e6,
                            "walk_over_expanded": walk_s / exp_s,
                            "expanded_us_a_tile": exp_s * 1e6 / tiles,
                            "expanded_mxu": _walk_flops(cfg, T, tiles)
                            / PEAK_FLOPS / exp_s,
                        })
                    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
