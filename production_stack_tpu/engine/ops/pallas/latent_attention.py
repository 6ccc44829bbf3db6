"""Pallas TPU attention over the paged latent (MLA) cache: the decode kernel
and the prefill kernel of ``models/sarvam_mla.py``.

Both are the absorbed attention between the module's two weight einsums:
queries already in the latent space, ``[q~ ; q_rope ; 0]`` a head, against a
cache of ONE array a layer whose rows ``[c ; r ; 0]`` are key and value at
once.  ``score = q_lat . row`` over the stored lanes (the pad lanes of both
sides are zero), the probabilities weigh the first ``latent_rank`` lanes of
the same rows, and ``W_UV`` lifts the sum outside.  **What the two share:**
the walk over the pages -- block ids and lengths in SMEM by scalar prefetch, a
stage of ``chunk_blocks`` block copies HBM -> VMEM into one slot of a ring of
``BUFFERS`` VMEM arrays, the next stages in flight under the dots, no block
past the context read (a stage's tail re-reads a valid block whose positions
the mask drops), the copies' bounds checks off and every id clipped into the
pool -- and a stage's arithmetic: the tile goes to the MXU as it lies,
``[chunk_blocks * block_size, lanes]``, for both dots, in the cache's dtype
(queries and probabilities rounded to it) with fp32 accumulation; scores,
softmax statistics and the accumulator are fp32 (:func:`_live`,
:func:`_values`, :func:`_wait_stage`).

**The decode kernel** (``latent_decode_attention_pallas``, PR 41): one query
a row ``[S, H, lanes]``, each row its own block table.  The XLA walk it
replaces on a TPU (``sarvam_mla._latent_walk``) gathers each tile of pages
through HBM before it reads it; this kernel copies each live page exactly
once.  The walk is that of ``paged_attention.py``, with nothing shared between
the two (a K and a V array with a head axis, int8 scales and a sliding
window there; one headless array here).  One program visits every (row, stage)
pair of the batch in order.  A fetch cursor runs ``BUFFERS - 1`` stages ahead
of the compute, onto the same row's next stages or the next live row's first,
so the DMA stream does not drain between rows; a padded or frozen row (ctx 0)
is no stage of the walk: nothing is started or waited for it.

What the compiler's schedule for a v5e asked of it, beyond that walk
(PERF.md section 6, PR 41; each measured on the chip by
``tools/latent_decode_microbench.py --kernel``):

- **A VMEM array a ring slot, a branch a slot.**  In one array with a
  dynamic slot index the scheduler cannot tell the buffer a stage reads
  from the one its fetch fills, and puts every vector load after every DMA
  start; with static slots the copies' scalar work (a dozen bundles each)
  runs beside the dots.
- **No branch between the fetch and the dots.**  The cursor moves by
  selects over ``next_ref`` (the first live row after each row, worked out
  once in SMEM), and past the walk's end it stands still and fetches
  dummies (valid blocks nobody reads) that the kernel's end waits for:
  ``BUFFERS - 1`` stages a call, under a microsecond.
- **The copies' bounds checks are off** (ten of a copy's twenty-odd
  bundles) and every block id is clipped into the pool instead.
- **Three slots.**  The scheduler starts a stage's copies late in the
  stage, so with two slots the DMA engine idles half of every stage.

**The prefill kernel** (``latent_prefill_attention_pallas``, PR 57): one
chunk's queries ``[T, H, lanes]`` over ONE sequence -- its cached prefix
through the block table, then the chunk's own rows causally.  The XLA walk it
replaces on a TPU (``sarvam_mla._expanded_attention``) gathers the block
table's whole width first and keeps each key tile's expansion, scores and
probabilities in HBM (6.19 ms a layer at 64 heads x 256 slots over 24,000
positions, 55 % of the MXU; this kernel 3.84 ms at 85 %:
``tools/latent_prefill_microbench.py``).  The grid is the query tiles,
``Q_ROWS`` rows each: as many slots as make that many rows with all their
heads, read ``[slots, H, lanes] -> [slots x H, lanes]`` where they lie (so
``H`` fills whole sublane tiles).  A grid step walks the prefix's stages with
the ring above (:func:`_walk_prefix`; started and waited inside the step: a stage is 0.8 us of
copies against 6 us of dots, so the pages are read once a query tile and the
reads stay hidden), folds each into the running softmax ``(m, l, acc)`` kept
in VMEM scratch, then the chunk's own rows, resident in VMEM, a stage of
``OWN_TILE`` keys at a time up to the tile's causal frontier, and writes
``[slots, H, latent_rank]``.  **A query tile that holds no slot before
``valid_len`` reads nothing and writes zeros** (a padded slot's row still
flows through the later layers and into the pool, where a NaN would poison a
``0 x NaN``); a padded slot inside a live tile attends what the XLA walk's
padded slots attend.  Every row of a live tile has a live key in every stage
it is given, so the finite ``NEG_INF`` is safe (:func:`_latent_prefill_kernel`,
``fold``).  ``count_tiles`` is the same rule on the host, for the flight
records' ``kv_tiles_live`` / ``kv_tiles_grid``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from production_stack_tpu.engine.ops.pallas import each

NEG_INF = -1e30
# Blocks a stage fetches and slots in the ring, settled on the chip at the
# served shape, 16 rows x 24,000 positions (the microbenchmark's docstring
# has the table): from 32 blocks and 3 slots on, the kernel runs at the DMA
# engine's own rate for 20 kB copies.  VMEM: BUFFERS x CHUNK_BLOCKS x 20 kB.
CHUNK_BLOCKS = 32
BUFFERS = 3


def _live(pos, ctx):
    """The positions a row attends: those before its context's end."""
    return pos < ctx


def _values(tile, latent_rank: int):
    """What the probabilities weigh: a row's latent, its first lanes; not
    the rotary key, not the pad."""
    return tile[:, :latent_rank]


def _wait_stage(cache_hbm, buf, sems, slot: int, unrolled: bool = True):
    """Wait for every copy of the stage fetched into ring slot ``slot``."""
    # A wait counts the destination's bytes; its source is only a shape.
    each(buf.shape[0], lambda c: pltpu.make_async_copy(
        cache_hbm.at[0], buf.at[c], sems.at[slot, c]).wait(), unrolled)


def _latent_decode_kernel(
    # scalar prefetch (SMEM)
    block_tables_ref,  # [S, Bmax] int32
    ctx_lens_ref,  # [S] int32
    # inputs
    q_ref,  # [S, H, lanes] VMEM
    cache_hbm,  # [N, bs, lanes] HBM
    # outputs
    o_ref,  # [S, H, latent_rank] VMEM
    # scratch: next_ref [S] int32 SMEM, sems DMA semaphores [R, C], then
    # the ring's R slots, each a [C, bs, lanes] VMEM array of its own
    next_ref,
    sems,
    *bufs,
    latent_rank: int,
    scale: float,
):
    S, H, lanes = q_ref.shape
    R = len(bufs)
    C, bs, _ = bufs[0].shape
    T = C * bs  # positions a stage
    num_blocks = cache_hbm.shape[0]
    dtype = bufs[0].dtype

    # next_ref[s]: the first live row after row s, S when none.
    live = jnp.int32(S)
    for s in reversed(range(S)):
        next_ref[s] = live
        live = jnp.where(ctx_lens_ref[s] > 0, s, live)

    def fetch(slot: int, cursor, unrolled: bool = True):
        """Start the stage under the fetch cursor into ring slot ``slot``
        and move the cursor one stage on; past the walk's end (row S) the
        stage is a dummy, the last row's first block C times."""
        s, stage = cursor
        inside = s < S
        r = jnp.minimum(s, S - 1)
        ctx = jnp.where(inside, ctx_lens_ref[r], 0)
        nb = (ctx + bs - 1) // bs  # live blocks of the row
        first = stage * C

        def start(c):  # C parallel DMA issues
            j = first + c
            # A stage-tail block past nb reads the row's first block.
            block = block_tables_ref[r, jax.lax.select(j < nb, j, jnp.zeros_like(j))]
            block = jax.lax.clamp(0, block, num_blocks - 1)
            pltpu.make_async_copy(
                cache_hbm.at[block], bufs[slot].at[c], sems.at[slot, c]
            ).start()

        each(C, start, unrolled)
        more = (stage + 1) * T < ctx
        return (jnp.where(more, s, jnp.where(inside, next_ref[r], S)),
                jnp.where(more, stage + 1, 0))

    # Every stage of the walk is started once, BUFFERS - 1 stages before it
    # is computed on (the first ones here), and waited once: a DMA nobody
    # waits for would leave its semaphore signalled for the next call.
    cursor = (live, jnp.int32(0))
    for slot in range(R - 1):
        cursor = fetch(slot, cursor, unrolled=False)

    pos_in_stage = jax.lax.broadcasted_iota(jnp.int32, (1, T), 1)

    def row(s, carry):
        ctx = ctx_lens_ref[s]
        q = q_ref[s].astype(dtype)  # [H, lanes]

        def out_of(slot: int):
            """One stage of this row out of ring slot ``slot``."""

            def stage(i, m, l, acc, cursor):
                _wait_stage(cache_hbm, bufs[slot], sems, slot)
                cursor = fetch((slot + R - 1) % R, cursor)
                # [C, bs, lanes] -> [T, lanes]: merging leading dims into
                # the sublane dim is layout-free, the lanes stay the lanes.
                tile = bufs[slot][...].reshape(T, lanes)
                # [H, lanes] x [T, lanes] -> [H, T]
                scores = jax.lax.dot_general(
                    q, tile, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                ) * scale
                scores = jnp.where(
                    _live(i * T + pos_in_stage, ctx), scores, NEG_INF)
                m_new = jnp.maximum(
                    m, jnp.max(scores, axis=-1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(scores - m_new)
                l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
                # The same tile is the values:
                # [H, T] x [T, latent] -> [H, latent]
                pv = jax.lax.dot_general(
                    p.astype(dtype), _values(tile, latent_rank),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                return m_new, l_new, acc * alpha + pv, cursor

            return stage

        def stage(i, carry):
            m, l, acc, slot, cursor = carry
            m, l, acc, cursor = jax.lax.switch(
                slot, [out_of(k) for k in range(R)], i, m, l, acc, cursor)
            return m, l, acc, jax.lax.rem(slot + 1, R), cursor

        m0 = jnp.full((H, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((H, 1), jnp.float32)
        acc0 = jnp.zeros((H, latent_rank), jnp.float32)
        _m, l, acc, *carry = jax.lax.fori_loop(
            0, (ctx + T - 1) // T, stage, (m0, l0, acc0, *carry))
        # A padded row has ctx 0 -> l 0: zeros, not NaNs.
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[s] = (acc / l).astype(o_ref.dtype)
        return tuple(carry)

    slot, _cursor = jax.lax.fori_loop(0, S, row, (jnp.int32(0), cursor))
    # The dummies fetched past the walk's end lie in the R - 1 slots from
    # ``slot`` on.
    for k in range(R):
        @pl.when(jax.lax.rem(k - slot + R, R) < R - 1)
        def _():
            _wait_stage(cache_hbm, bufs[k], sems, k, unrolled=False)


@functools.partial(
    jax.jit,
    static_argnames=("latent_rank", "scale", "chunk_blocks", "interpret"),
)
def latent_decode_attention_pallas(
    q_lat: jax.Array,  # [S, H, lanes]
    cache: jax.Array,  # [N, bs, lanes]
    block_tables: jax.Array,  # [S, Bmax] int32 (0 = null block)
    ctx_lens: jax.Array,  # [S] int32 (0 for padded slots)
    *,
    latent_rank: int,
    scale: float,
    chunk_blocks: int = CHUNK_BLOCKS,
    interpret: bool = False,
) -> jax.Array:
    """Softmax of ``q_lat . row * scale`` over each row's first
    ``ctx_lens`` cached positions, weighing those rows' first
    ``latent_rank`` lanes: ``[S, H, latent_rank]`` in the queries' dtype,
    zeros for a row without context."""
    S, H, lanes = q_lat.shape
    _, bs, _ = cache.shape
    C = min(chunk_blocks, block_tables.shape[1])
    if lanes % 128 and not interpret:
        # A DMA'd row is whole 128-lane tiles on the chip; the module pads
        # its cache to them (sarvam_mla.cache_lanes) and keeps any other
        # width on the XLA walk.
        raise ValueError(
            f"pallas latent decode kernel requires lanes%128==0, got {lanes}")

    kernel = functools.partial(
        _latent_decode_kernel, latent_rank=latent_rank, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(1,),  # one program walks the whole batch
        in_specs=[
            pl.BlockSpec((S, H, lanes), lambda i, *_: (0, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # the cache stays in HBM
        ],
        out_specs=pl.BlockSpec((S, H, latent_rank), lambda i, *_: (0, 0, 0)),
        scratch_shapes=[
            pltpu.SMEM((S,), jnp.int32),
            pltpu.SemaphoreType.DMA((BUFFERS, C)),
            *[pltpu.VMEM((C, bs, lanes), cache.dtype)] * BUFFERS,
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, latent_rank), q_lat.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(disable_bounds_checks=True),
        # What the device trace calls the kernel (%<name>.N on XLA Ops):
        # the benchmark's reader finds it by this name.
        name="latent_decode_attention_pallas",
    )(block_tables, ctx_lens, q_lat, cache)


# -- the prefill kernel --------------------------------------------------------

# Query rows (slots x heads) a grid step holds, and the chunk's own keys a
# stage; settled on the chip by ``tools/latent_prefill_microbench.py``.
Q_ROWS = 1024
OWN_TILE = 512


def prefill_tiling(T: int, H: int, q_rows: int = Q_ROWS,
                   own_tile: int = OWN_TILE):
    """(slots a query tile, own keys a stage) for a chunk of ``T`` slots of
    ``H`` heads: as many slots as make ``q_rows`` rows of all their heads, a
    divisor of ``T`` and of the own stage, so that a query tile lies inside
    one own stage and the causal frontier is a whole number of stages."""
    own = min(own_tile, T)
    return math.gcd(own, max(q_rows // H, 1)), own


def count_tiles(bucket_len: int, cached_len: int, num_new_tokens: int, *,
                num_heads: int, prefix_blocks: int, block_size: int,
                q_rows: int = Q_ROWS, chunk_blocks: int = CHUNK_BLOCKS,
                own_tile: int = OWN_TILE):
    """((query tile, key stage) pairs the prefill kernel computes, pairs in
    its grid) for one call, a layer: host arithmetic by the kernel's own
    rule.  The grid: every query tile against every stage of the block
    table's whole width and of the chunk.  Computed: the tiles that hold a
    valid slot, against the prefix stages that hold a position before
    ``cached_len`` and the own stages up to the tile's causal frontier."""
    Tq, own = prefill_tiling(bucket_len, num_heads, q_rows, own_tile)
    stage = min(chunk_blocks, prefix_blocks) * block_size
    tiles = bucket_len // Tq
    grid = tiles * (-(-prefix_blocks * block_size // stage)
                    + bucket_len // own)
    live_tiles = min(-(-num_new_tokens // Tq), tiles)
    first = np.arange(live_tiles) * Tq        # each live tile's first slot
    live = live_tiles * -(-cached_len // stage) + int((first // own + 1).sum())
    return live, grid


def _walk_prefix(ids_ref, cached, cache_hbm, sems, bufs, fold):
    """``fold(tile [K, lanes], live [1, K])`` for every stage of one
    sequence's cached prefix, in order: the pages ``ids_ref`` names, the first
    ``cached`` positions of them live, copied HBM -> VMEM through the ring
    ``bufs`` with the next ``len(bufs) - 1`` stages in flight.  Every stage
    it starts it waits for; nothing past ``cached`` is read (the last stage's
    tail re-reads the first block, which the mask drops)."""
    R = len(bufs)
    C, bs, lanes = bufs[0].shape
    K = C * bs  # positions a stage
    num_blocks = cache_hbm.shape[0]
    stages = (cached + K - 1) // K
    nb = (cached + bs - 1) // bs  # live blocks of the prefix

    def fetch(slot: int, stage):
        @pl.when(stage < stages)
        def _():
            for c in range(C):  # C parallel DMA issues
                j = stage * C + c
                block = ids_ref[jax.lax.select(j < nb, j, jnp.zeros_like(j))]
                block = jax.lax.clamp(0, block, num_blocks - 1)
                pltpu.make_async_copy(
                    cache_hbm.at[block], bufs[slot].at[c], sems.at[slot, c]
                ).start()

    for slot in range(R - 1):
        fetch(slot, jnp.int32(slot))
    key = jax.lax.broadcasted_iota(jnp.int32, (1, K), 1)

    def stage(i, carry):
        # A branch a slot: the buffer a stage reads and the one its fetch
        # fills are different arrays to the scheduler.
        for slot in range(R):
            @pl.when(jax.lax.rem(i, R) == slot)
            def _():
                _wait_stage(cache_hbm, bufs[slot], sems, slot)
                fetch((slot + R - 1) % R, i + R - 1)
                fold(bufs[slot][...].reshape(K, lanes),
                     _live(i * K + key, cached))
        return carry

    jax.lax.fori_loop(0, stages, stage, 0)


def _latent_prefill_kernel(
    # scalar prefetch (SMEM)
    ids_ref,  # [P] int32: the prefix's blocks
    lens_ref,  # [2] int32: cached_len, valid_len
    # inputs
    q_ref,  # [Tq, H, lanes] VMEM: this query tile, every head
    rows_ref,  # [T, lanes] VMEM: the chunk's own rows, whole
    cache_hbm,  # [N, bs, lanes] HBM
    # outputs
    o_ref,  # [Tq, H, latent_rank] VMEM
    # scratch: running max and sum [Tq * H, 1], accumulator
    # [Tq * H, latent_rank] (fp32, VMEM), DMA semaphores [R, C], then the
    # ring's R slots, each a [C, bs, lanes] VMEM array of its own
    m_ref,
    l_ref,
    acc_ref,
    sems,
    *bufs,
    latent_rank: int,
    scale: float,
    own: int,
):
    Tq, H, lanes = q_ref.shape
    rows = Tq * H
    dtype = bufs[0].dtype
    cached, valid = lens_ref[0], lens_ref[1]
    first = pl.program_id(0) * Tq  # this tile's first slot

    def fold(tile, live):
        """``tile`` [k, lanes] keys (and values: their first lanes) under the
        running softmax; ``live`` [rows or 1, k].  Every row has a live key
        in every stage it is given (the prefix's mask is the same for every
        row and a stage holds a position before ``cached_len``; an own stage
        begins at or before the tile's first slot, which is valid), so the
        finite NEG_INF never meets itself in ``exp(s - m)``."""
        q = q_ref[...].reshape(rows, lanes).astype(dtype)
        s = jax.lax.dot_general(
            q, tile, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * scale
        s = jnp.where(live, s, NEG_INF)
        m = m_ref[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(dtype), _values(tile, latent_rank),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[...] = m_new

    # A tile of query rows that holds no valid slot: zeros, nothing read.
    @pl.when(first >= valid)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(first < valid)
    def _():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        _walk_prefix(ids_ref, cached, cache_hbm, sems, bufs, fold)
        # The chunk itself, causally, a stage of ``own`` keys at a time up
        # to this tile's frontier; slots past valid_len are padding.
        slot_of = first + jax.lax.broadcasted_iota(
            jnp.int32, (rows, 1), 0) // H
        own_key = jax.lax.broadcasted_iota(jnp.int32, (1, own), 1)

        def own_stage(j, carry):
            k = j * own + own_key
            fold(rows_ref[pl.ds(pl.multiple_of(j * own, own), own), :],
                 (k <= slot_of) & _live(k, valid))
            return carry

        jax.lax.fori_loop(0, first // own + 1, own_stage, 0)
        o_ref[...] = (acc_ref[...] / l_ref[...]).reshape(
            Tq, H, latent_rank).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("latent_rank", "scale", "q_rows", "chunk_blocks",
                     "own_tile", "interpret"),
)
def latent_prefill_attention_pallas(
    q_lat: jax.Array,  # [T, H, lanes]
    rows: jax.Array,  # [T, lanes]: the chunk's own cache rows
    cache: jax.Array,  # [N, bs, lanes]
    prefix_block_ids: jax.Array,  # [P] int32 (0-padded)
    cached_len: jax.Array,  # scalar int32
    valid_len: jax.Array,  # scalar int32
    *,
    latent_rank: int,
    scale: float,
    q_rows: int = Q_ROWS,
    chunk_blocks: int = CHUNK_BLOCKS,
    own_tile: int = OWN_TILE,
    interpret: bool = False,
) -> jax.Array:
    """One prefill chunk's absorbed attention: slot ``t``'s queries over the
    first ``cached_len`` positions of the pages ``prefix_block_ids`` names
    and over the chunk's own ``rows`` up to slot ``t``, those before
    ``valid_len``; softmax of ``q_lat . row * scale`` weighing the rows'
    first ``latent_rank`` lanes: ``[T, H, latent_rank]`` in the queries'
    dtype.  A tile of slots wholly past ``valid_len`` reads zeros."""
    T, H, lanes = q_lat.shape
    _, bs, _ = cache.shape
    C = min(chunk_blocks, prefix_block_ids.shape[0])
    Tq, own = prefill_tiling(T, H, q_rows, own_tile)
    if not interpret and (lanes % 128 or (Tq * H) % 16):
        # A DMA'd row is whole 128-lane tiles, and a tile's [Tq, H, lanes]
        # is read as [Tq * H, lanes] where it lies only if its rows fill
        # whole sublane tiles.
        raise ValueError(
            f"pallas latent prefill kernel requires lanes%128==0 and "
            f"heads%16==0, got lanes={lanes} heads={H}")

    kernel = functools.partial(
        _latent_prefill_kernel, latent_rank=latent_rank, scale=scale, own=own)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T // Tq,),
        in_specs=[
            pl.BlockSpec((Tq, H, lanes), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((T, lanes), lambda i, *_: (0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),  # the cache stays in HBM
        ],
        out_specs=pl.BlockSpec((Tq, H, latent_rank), lambda i, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Tq * H, 1), jnp.float32),
            pltpu.VMEM((Tq * H, 1), jnp.float32),
            pltpu.VMEM((Tq * H, latent_rank), jnp.float32),
            pltpu.SemaphoreType.DMA((BUFFERS, C)),
            *[pltpu.VMEM((C, bs, lanes), cache.dtype)] * BUFFERS,
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, H, latent_rank), q_lat.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            disable_bounds_checks=True,
            # A stage's fp32 scores and probabilities beside the query tile,
            # the accumulator and the ring pass the default 16 MB.
            vmem_limit_bytes=64 * 1024 * 1024,
        ),
        # What the device trace calls the kernel (%<name>.N on XLA Ops).
        name="latent_prefill_attention_pallas",
    )(
        prefix_block_ids,
        jnp.stack([jnp.asarray(cached_len, jnp.int32),
                   jnp.asarray(valid_len, jnp.int32)]),
        q_lat, rows, cache,
    )
