"""Pallas TPU decode attention over the paged latent (MLA) cache.

The absorbed decode attention of ``models/sarvam_mla.py`` between its two
weight einsums: queries already in the latent space, ``[q~ ; q_rope ; 0]``
a head, against a cache of ONE array a layer whose rows ``[c ; r ; 0]`` are
key and value at once.  ``score = q_lat . row`` over the stored lanes (the
pad lanes of both sides are zero), the probabilities weigh the first
``latent_rank`` lanes of the same rows, and ``W_UV`` lifts the sum outside.
The XLA walk this replaces on a TPU (``sarvam_mla._latent_walk``) gathers
each tile of pages through HBM before it reads it; this kernel copies each
live page HBM -> VMEM exactly once and uses it for both dots.

The walk is that of ``paged_attention.py``, with nothing shared between
the two (a K and a V array with a head axis, int8 scales and a sliding
window there; one headless array here).  Block table and context lengths
ride in SMEM by scalar prefetch.  One program visits every (row, stage)
pair of the batch in order, a stage being ``chunk_blocks`` block copies
into one slot of a ring of ``BUFFERS`` VMEM buffers.  A fetch cursor runs
``BUFFERS - 1`` stages ahead of the compute, onto the same row's next
stages or the next live row's first, so the DMA stream does not drain
between rows; a padded or frozen row (ctx 0) is no stage of the walk:
nothing is started or waited for it.  No block past a row's context is
read: the last stage's tail re-reads the row's first block, a valid source
whose positions the mask drops.

What the compiler's schedule for a v5e asked for, beyond that walk
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

A stage's tile goes to the MXU as it lies, ``[chunk_blocks * block_size,
lanes]`` against all ``H`` heads' queries at once.  Both dots take the
cache's dtype (queries and probabilities rounded to it, as the XLA walk
rounds them) and accumulate fp32; scores, softmax statistics and the
``[H, latent_rank]`` accumulator are fp32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

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


def _each(n: int, body, unrolled: bool):
    """``body(c)`` for every copy ``c`` of a stage.  Unrolled, the copies'
    scalar work is straight-line code the scheduler runs beside the stage's
    dots; as a loop it is a fraction of the text to trace and lower at every
    process start (the kernel's prologue and its end: once a call)."""
    if unrolled:
        for c in range(n):
            body(c)
        return

    def step(c, carry):
        body(c)
        return carry

    jax.lax.fori_loop(0, n, step, 0)


def _wait_stage(cache_hbm, buf, sems, slot: int, unrolled: bool = True):
    """Wait for every copy of the stage fetched into ring slot ``slot``."""
    # A wait counts the destination's bytes; its source is only a shape.
    _each(buf.shape[0], lambda c: pltpu.make_async_copy(
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

        _each(C, start, unrolled)
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
