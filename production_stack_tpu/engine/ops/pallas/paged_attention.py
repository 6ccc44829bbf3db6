"""Pallas TPU decode-attention kernel over the paged KV cache.

The decode step is HBM-bandwidth bound: each new token must read every live
KV block of its sequence once.  The pure-JAX gather path
(ops/attention.py:paged_decode_attention) pays that read **three times**
(gather-read, materialize-write, attention-read) and always over the full
``Bmax``-padded block table.  This kernel streams each sequence's actual
blocks HBM->VMEM exactly once with double-buffered async DMA and an online
softmax, and its loop bound is the *real* context length, so a 256-token
sequence in an 8k-token pool touches 16 blocks, not 512.

Blocks are fetched in stages of ``CHUNK_BLOCKS`` descriptors a side: one
16-token block is too small to amortize DMA issue latency or fill the MXU,
so each stage issues that many parallel DMAs per side and runs one
online-softmax update over the whole tile.  A descriptor carries 32 kB
(``DESCRIPTOR_BYTES``): issuing and retiring one costs the walk ~30 ns
whatever it holds, which a page of eight key heads (32 kB, 40 ns of bytes)
hides and a page of one key head (4 kB, 5 ns) does not.  So where a page is
smaller, ``R = blocks_per_descriptor(page bytes)`` pages that lie next to
each other in the pool -- which is how ``kv/block_pool.py`` hands them out
-- travel in one DMA, and a stage is ``CHUNK_BLOCKS * R`` blocks (one key
head: 8 pages a descriptor, 128 blocks = 2,048 positions a stage, the 512 kB
a side in flight that eight heads have).  ``R`` follows the shape the
kernel is given and nothing else; at ``R == 1`` the walk is the single-page
walk, operand for operand.  Which groups of ``R`` table entries are such
neighbours is read from the block table by XLA before the kernel
(``whole_groups``) and prefetched beside it; a group that is not goes page
by page, bit-equal.

One program walks every (row, stage) pair of the batch in order, and
while a stage computes, the walk's next stage is in flight into the other
buffer -- the next stage of the same row, or the first stage of the next
live row, so the DMA stream does not stop between rows and padded rows
(ctx 0) start and wait nothing.  The block table and context lengths
ride in SMEM via scalar prefetch so DMA source indices are computable
before the data arrives.

A stage's tiles go to the MXU as they lie in the cache: ``[T, K, D]`` read
as ``[T*K, D]`` against all ``H`` query heads at once, the scores of a
head under another KV head's keys masked like positions past the context
(their probabilities are exact zeros), which costs the MXU ``K`` times the
useful products, far under its rate, and spares the VPU the
``[T, K, D] -> [K, T, D]`` relayout of every tile.  Both dots take the
cache's own dtype (queries and probabilities in it too, as the gather
path rounds them) and accumulate fp32; scores, softmax statistics and the
output accumulator are fp32.  An int8 (data, scale) cache is dequantized
in VMEM by an fp32 multiply and its dots take the queries' dtype.

Replaces the role CUDA PagedAttention kernels play inside the reference's
external vLLM engine (the reference itself ships no kernels — SURVEY.md
preamble; its engine containers do, helm/templates/deployment-vllm-multi.yaml:57-64).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Descriptors a pipeline stage issues per side: blocks where a page is a
# descriptor of its own, groups of blocks_per_descriptor() pages where it is
# smaller.  16 x 32 kB a side = 1 MiB of K and V in flight behind the stage
# that computes, which is what keeps the DMA engine at its own rate on a v5e
# (8 left it waiting on the compute: tools/paged_decode_microbench.py);
# VMEM: 2 x CHUNK_BLOCKS x 32 kB a side.
CHUNK_BLOCKS = 16
# What one DMA descriptor should carry.  The walk cannot issue and retire a
# descriptor in under ~30 ns whatever it holds (PERF.md section 6, PR 54):
# a 32 kB page is 40 ns of bytes at 819 GB/s and hides that, a one-key-head
# page of 4 kB is 5 ns and does not.
DESCRIPTOR_BYTES = 32 * 1024


def blocks_per_descriptor(page_bytes: int, quantized: bool = False) -> int:
    """THE rule, page bytes -> ``R``: how many pool-adjacent pages one DMA
    of the decode walk carries.  The kernel's wrapper calls it with the
    page of the shape it is given, the engine with the served model's page
    to tell the block pool how long a run to keep together
    (``BlockPool(run=R)``), so the two cannot disagree.  An int8 (data,
    scale) cache keeps single pages: its scale planes ride the same
    pipeline."""
    if quantized:
        return 1
    return max(1, DESCRIPTOR_BYTES // max(page_bytes, 1))


def whole_groups(block_tables, group_blocks: int, xp=jnp):
    """``[S, Bmax // R]`` bool: which groups of ``R`` consecutive table
    entries (``Bmax`` a multiple of ``R``) are ``R`` ascending neighbours
    in the pool, the last of them allocated (block 0 is the null block) --
    one region of ``R`` pages, one DMA.  Read from the table alone (not
    from the contexts, which grow inside a window's scan while the tables
    are its constants); the host counts with the same function over its
    numpy tables (``xp=np``)."""
    S, bmax = block_tables.shape
    t = block_tables.reshape(S, bmax // group_blocks, group_blocks)
    run = t[..., :1] + xp.arange(group_blocks, dtype=t.dtype)
    return (t == run).all(-1) & (t[..., -1] != 0)


def _decode_kernel(
    # scalar prefetch (SMEM)
    block_tables_ref,  # [S, Bmax] int32
    ctx_lens_ref,  # [S] int32
    # [whole_ref [S, Bmax // R], stage_whole_ref [S, Bmax // C] int32 when
    # R > 1: whole_groups() a group and a stage]
    # inputs: q_ref [S, H, D], k_hbm, v_hbm[, ks_hbm, vs_hbm] (int8 scales)
    # outputs: o_ref [S, H, D]
    # scratch: k_buf, v_buf[, ks_buf, vs_buf], sems
    *refs,
    bs: int,
    chunk_blocks: int,
    group_blocks: int,
    num_kv_heads: int,
    scale: float,
    sliding_window: Optional[int],
    quantized: bool,
):
    R = group_blocks  # pages a descriptor; 1: the single-page walk
    if R > 1:
        whole_ref, stage_whole_ref, *refs = refs
    if quantized:
        (q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref,
         k_buf, v_buf, ks_buf, vs_buf, sems) = refs
    else:
        q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems = refs
        ks_hbm = vs_hbm = ks_buf = vs_buf = None
    S, H, D = q_ref.shape
    C, K = chunk_blocks, num_kv_heads
    G = H // K  # query heads a KV head (GQA)
    T = C * bs  # tokens per pipeline stage
    # What both dots take: the cache's dtype, or the queries' once an int8
    # cache is dequantized.
    operand_dtype = q_ref.dtype if quantized else k_buf.dtype

    streams = [(k_hbm, k_buf, 0), (v_hbm, v_buf, 1)]
    if quantized:
        # Scale planes ride the same pipeline (tiny: [bs, K] fp32/block).
        streams += [(ks_hbm, ks_buf, 2), (vs_hbm, vs_buf, 3)]

    def next_live(r):
        """First row at or after ``r`` with a context; S when none."""
        return jax.lax.while_loop(
            lambda r: (r < S) & (ctx_lens_ref[jnp.minimum(r, S - 1)] == 0),
            lambda r: r + 1, r)

    def start_stage(slot, s, stage):
        nb = (ctx_lens_ref[s] + bs - 1) // bs  # live KV blocks of the row
        for c in range(C):  # static unroll: C parallel DMA issues a side
            j = stage * C + c
            # Stage-tail blocks past nb read the row's first block: a
            # valid DMA source whose positions the mask drops.
            block = block_tables_ref[s, jnp.where(j < nb, j, 0)]
            for cache, buf, kv in streams:
                pltpu.make_async_copy(
                    cache.at[block], buf.at[slot, c], sems.at[kv, slot, c]
                ).start()

    def wait_stage(slot, s, stage):
        for c in range(C):
            for cache, buf, kv in streams:
                # A wait counts the destination's bytes; its source is
                # only a shape.
                pltpu.make_async_copy(
                    cache.at[0], buf.at[slot, c], sems.at[kv, slot, c]
                ).wait()

    # R > 1: a stage is Cg = C // R groups of R table entries.  A group the
    # table says is R ascending neighbours (whole_groups) is one region of
    # the pool and goes in one DMA a side; any other goes page by page as
    # above.  Either way the same bytes land at the same offsets of the
    # stage's tile and signal the (side, slot)'s one semaphore, and a wait
    # counts the destination's bytes, so what follows the wait does not
    # know which way a group came.
    #
    # A stage whose groups are all whole (stage_whole) is Cg descriptors a
    # side issued from a static unroll, no test a group, and one wait a side
    # for the whole tile: the scalar work of a stage has to stay under the
    # time its bytes take, and a loop with a branch a group does not (PERF.md
    # section 6, PR 54).  Any other stage -- a broken group in it, or a row's
    # last, whose table ends inside it -- takes the loop over the row's live
    # groups; what lies behind them is masked, and its V pages are zeroed
    # so that no stale page of another row multiplies the exact zeros of
    # the masked probabilities.
    Cg = C // R

    def live_groups(s, stage):
        nb = (ctx_lens_ref[s] + bs - 1) // bs
        return jnp.clip((nb + R - 1) // R - stage * Cg, 0, Cg)

    def start_group(slot, g, block):
        for cache, buf, kv in streams:
            pltpu.make_async_copy(
                cache.at[pl.ds(block, R)], buf.at[slot, pl.ds(g * R, R)],
                sems.at[kv, slot],
            ).start()

    def start_stage_grouped(slot, s, stage):
        all_whole = stage_whole_ref[s, stage] != 0

        @pl.when(all_whole)
        def _():
            for g in range(Cg):
                start_group(slot, g, block_tables_ref[s, stage * C + g * R])

        @pl.when(jnp.logical_not(all_whole))
        def _():
            nb = (ctx_lens_ref[s] + bs - 1) // bs
            live = live_groups(s, stage)

            def group(g, _):
                gi = stage * Cg + g
                whole = whole_ref[s, gi] != 0

                @pl.when(whole)
                def _():
                    start_group(slot, g, block_tables_ref[s, gi * R])

                @pl.when(jnp.logical_not(whole))
                def _():
                    for r in range(R):
                        j = gi * R + r
                        block = block_tables_ref[s, jnp.where(j < nb, j, 0)]
                        for cache, buf, kv in streams:
                            pltpu.make_async_copy(
                                cache.at[block], buf.at[slot, g * R + r],
                                sems.at[kv, slot],
                            ).start()

            jax.lax.fori_loop(0, live, group, None)

            def dead(g, _):
                at = (slot, pl.ds(g * R, R))
                v_buf[at] = jnp.zeros_like(v_buf[at])

            jax.lax.fori_loop(live, Cg, dead, None)

    def wait_stage_grouped(slot, s, stage):
        def wait(first, blocks):
            for cache, buf, kv in streams:
                pltpu.make_async_copy(
                    cache.at[pl.ds(0, blocks)],
                    buf.at[slot, pl.ds(first, blocks)], sems.at[kv, slot],
                ).wait()

        all_whole = stage_whole_ref[s, stage] != 0
        pl.when(all_whole)(lambda: wait(0, C))

        @pl.when(jnp.logical_not(all_whole))
        def _():
            jax.lax.fori_loop(0, live_groups(s, stage),
                              lambda g, _: wait(g * R, R), None)

    if R > 1:
        start_stage, wait_stage = start_stage_grouped, wait_stage_grouped

    # Every stage the walk visits is started once, by the stage before it
    # (the first one here), and waited once: a DMA nobody waits for would
    # leave its semaphore signalled for the next call's waits.
    first = next_live(0)

    @pl.when(first < S)
    def _():
        start_stage(0, first, 0)

    # Column t*K + k of a stage's scores is position t under KV head k;
    # query head h = k*G + g attends KV head k (GQA).
    col = jax.lax.broadcasted_iota(jnp.int32, (1, T * K), 1)
    tok = col // K
    own_head = col % K == jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0) // G

    def row(s, slot0):
        ctx = ctx_lens_ref[s]
        nc = (ctx + T - 1) // T  # dynamic trip count: only live stages
        following = next_live(s + 1)
        q = q_ref[s].astype(operand_dtype)  # [H, D]

        def stage(i, carry):
            m, l, acc = carry
            slot = jax.lax.rem(slot0 + i, 2)
            last = i + 1 == nc
            ahead = jnp.where(last, following, s)

            @pl.when(ahead < S)
            def _():
                start_stage(1 - slot, ahead, jnp.where(last, 0, i + 1))

            wait_stage(slot, s, i)
            # [C, bs, K, D] -> [T*K, D]: merging leading dims into the
            # sublane dim is layout-free, D stays the lane dim.
            k = k_buf[slot].reshape(T * K, D)
            v = v_buf[slot].reshape(T * K, D)
            if quantized:
                # Per-(token, head) scales: [C, bs, K] -> [T*K, 1].
                k = (k.astype(jnp.float32) * ks_buf[slot].astype(
                    jnp.float32).reshape(T * K, 1)).astype(operand_dtype)
                v = (v.astype(jnp.float32) * vs_buf[slot].astype(
                    jnp.float32).reshape(T * K, 1)).astype(operand_dtype)

            # [H, D] x [T*K, D] -> [H, T*K]
            scores = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            pos = i * T + tok
            mask = own_head & (pos < ctx)
            if sliding_window is not None:
                mask &= pos > ctx - 1 - sliding_window
            scores = jnp.where(mask, scores, NEG_INF)

            m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(scores - m_new)
            l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
            # [H, T*K] x [T*K, D] -> [H, D]
            pv = jax.lax.dot_general(
                p.astype(operand_dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            return m_new, l_new, acc * alpha + pv

        m0 = jnp.full((H, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((H, 1), jnp.float32)
        acc0 = jnp.zeros((H, D), jnp.float32)
        m, l, acc = jax.lax.fori_loop(0, nc, stage, (m0, l0, acc0))

        # Padded batch slots have ctx==0 -> l==0; emit zeros, not NaNs (their
        # logits are sliced off on the host, but NaN-free keeps debugging sane).
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[s] = (acc / l).astype(o_ref.dtype)
        return jax.lax.rem(slot0 + nc, 2)

    jax.lax.fori_loop(0, S, row, 0)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "sliding_window", "chunk_blocks",
                     "group_blocks", "interpret", "name"),
)
def paged_decode_attention_pallas(
    q: jax.Array,  # [S, H, D]
    k_cache: jax.Array,  # [N, bs, K, D]
    v_cache: jax.Array,  # [N, bs, K, D]
    block_tables: jax.Array,  # [S, Bmax] int32 (0 = null block)
    ctx_lens: jax.Array,  # [S] int32 (0 for padded slots)
    *,
    scale: float,
    sliding_window: Optional[int] = None,
    chunk_blocks: int = CHUNK_BLOCKS,
    group_blocks: Optional[int] = None,
    interpret: bool = False,
    name: str = "paged_decode_attention_pallas",
) -> jax.Array:
    """Decode attention over paged KV, streaming blocks HBM->VMEM.

    ``k_cache``/``v_cache`` may be int8 (data, scale) tuples
    (kv/quant.py): the scale planes stream through the same
    double-buffered pipeline and the dequantize (one VPU multiply per
    element) happens in VMEM — HBM traffic is the int8 bytes plus ~3%
    scales, the whole point of the mode.

    A stage is ``chunk_blocks`` descriptors a side, each of
    ``blocks_per_descriptor`` pages of the page this call is given.
    ``group_blocks`` is for the tests alone, which name 1 (every page
    alone, whatever the table says) to hold the grouped walk bit-equal to
    the single-page one; nothing that serves passes it.  ``name`` is what
    the device trace calls this call: a caller whose pages are no block
    pool's (``models/laguna.py``: a window layer's rolling buffer in a slot
    of the state pool, read as pages) gives its own, so that its seconds
    can be told from the walk over the block pool.
    """
    from production_stack_tpu.engine.kv import quant as kv_quant

    quantized = kv_quant.is_quantized(k_cache)
    S, H, D = q.shape
    N, bs, K, _ = kv_quant.cache_shape(k_cache)
    if D % 128 and not interpret:
        # The DMA slice needs a 128-lane-aligned head_dim on real TPU;
        # dispatch (ops/attention.py) keeps such models on the gather
        # path.  Interpret mode (CPU tests) has no tiling constraint.
        raise ValueError(f"pallas decode kernel requires head_dim%128==0, got {D}")
    itemsize = 1 if quantized else k_cache.dtype.itemsize
    R = group_blocks or blocks_per_descriptor(
        bs * K * D * itemsize, quantized)
    R = min(R, N)  # a pool of a few blocks (tests) has no region of more
    prefetch = [block_tables, ctx_lens]
    # Blocks a stage: chunk_blocks descriptors of R pages, at most the table.
    C = min(chunk_blocks * R, -(-block_tables.shape[1] // R) * R)
    if R > 1:
        # Whole groups and whole stages only: a table whose width the stage
        # does not divide gains null entries, which no context reaches.
        block_tables = jnp.pad(
            block_tables, ((0, 0), (0, -block_tables.shape[1] % C)))
        whole = whole_groups(block_tables, R)
        prefetch = [
            block_tables, ctx_lens, whole.astype(jnp.int32),
            whole.reshape(S, -1, C // R).all(-1).astype(jnp.int32)]

    kernel = functools.partial(
        _decode_kernel,
        bs=bs,
        chunk_blocks=C,
        group_blocks=R,
        num_kv_heads=K,
        scale=scale,
        sliding_window=sliding_window,
        quantized=quantized,
    )
    cache_in_specs = [pl.BlockSpec(memory_space=pl.ANY)] * (
        4 if quantized else 2
    )
    # One KV head (multi-query): a page goes in as [bs, D], the layout XLA
    # keeps [N, bs, 1, D] in anyway (the reshape is a bitcast).  Mosaic
    # tiles a memref's last two dims, and a second-minor dim of 1 is padded
    # to a tile of 2 that a one-head DMA slice is not aligned to.
    page = (bs, D) if K == 1 and not quantized else (bs, K, D)
    scratch = [
        pltpu.VMEM((2, C, *page),
                   jnp.int8 if quantized else k_cache.dtype),
        pltpu.VMEM((2, C, *page),
                   jnp.int8 if quantized else v_cache.dtype),
    ]
    if quantized:
        scratch += [
            pltpu.VMEM((2, C, bs, K), k_cache[1].dtype),
            pltpu.VMEM((2, C, bs, K), v_cache[1].dtype),
        ]
    # One semaphore a (stream, slot, page), or a (stream, slot) where
    # descriptors carry groups and waits count a stage's bytes.
    scratch.append(pltpu.SemaphoreType.DMA(
        (len(cache_in_specs), 2, C) if R == 1 else (len(cache_in_specs), 2)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(1,),  # one program walks the whole batch
        in_specs=[
            pl.BlockSpec((S, H, D), lambda i, *_: (0, 0, 0)),
            *cache_in_specs,  # caches (+ scale planes) stay in HBM
        ],
        out_specs=pl.BlockSpec((S, H, D), lambda i, *_: (0, 0, 0)),
        scratch_shapes=scratch,
    )
    inputs = (
        (q, k_cache[0], v_cache[0], k_cache[1], v_cache[1])
        if quantized else
        (q, k_cache.reshape(N, *page), v_cache.reshape(N, *page))
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, D), q.dtype),
        interpret=interpret,
        # What the device trace calls the kernel (%<name>.N on XLA Ops):
        # the benchmark's readers find it by this name.
        name=name,
    )(*prefetch, *inputs)
