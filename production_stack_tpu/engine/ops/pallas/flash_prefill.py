"""Pallas TPU flash-attention kernel for prefill (causal + cached prefix).

Why the dense path stalls at ~0.44 MFU: ``dense_prefill_attention``
(ops/attention.py) materializes the full fp32 score/prob tensors —
[K, G, T, C+T] is ~430 MB for a 2k-token llama-3.2-3b prefill, far beyond
VMEM, so XLA spills them to HBM and the MXU waits on bandwidth.  This kernel never materializes
scores in HBM: a 2-D grid (query tile x kv tile) streams keys/values
through VMEM in [Tk, K, D] slices while the online-softmax state
(running max, normalizer, and fp32 accumulator) lives in VMEM scratch
that persists across the kv dimension of the grid.  Nothing resident
scales with sequence length, so VMEM stays ~16 MB at any context.

**The cached prefix is read where it lies** (PR 62): the kernel takes the
layer's K and V pools ``[N, bs, K, D]``, left in HBM, and the prefix's block
ids by scalar prefetch, and a prefix kv tile is ``Tk / bs`` pages copied
HBM -> VMEM into one slot of a ring of two, as ``latent_attention.py:
_walk_prefix`` and the paged decode walk do: the next live prefix tile --
the same query tile's, or the next query tile's first -- is in flight while
this one is computed on, every copy that is started is waited for, every id
is clipped into the pool and the copies' bounds checks are off.  A tile's
pages past the table's end repeat its last entry: the mask drops their
positions, and all of a tile's pages are always fetched, so no score meets
what an earlier call left in VMEM.  The chunk's own ``k_new`` / ``v_new`` stay
a ``[T, K, D]`` operand, tiled by a BlockSpec, and are the grid's last tiles.
No gathered copy of the prefix, no ``concatenate`` and no ``pad`` exist
(before: ``max_model_len`` positions a side a layer, whatever ``cached_len``
was: 5.6 ms of mistral-7b's 27.8 ms program at 256 slots).  One key head (a
page is 4 kB) goes page by page like eight: the prefix there is 6 MB a layer,
the copies hide behind the dots, and the decode walk's grouping would be more
code.  A pool may have pages of any size: ``models/laguna.py`` hands a window
layer's 512-row rolling buffer as a pool of one 512-token page with the table
``[0]``, and a dense copy of a prefix -- a quantized cache's, dequantized;
Ulysses' redistributed one -- is a pool of 512-position pages in order
(``ops/attention.py: prefix_as_pool``).  Behind an empty table (the encode
lane) no pool is asked for.

Tile skipping: the grid is static — (T/Tq, prefix steps + new-key steps),
``ceil(len(prefix_block_ids) * bs / Tk)`` prefix steps whatever
``cached_len`` is — but a kv tile is *visited* only if one of its scores
survives the mask for one query of the query tile.  ``live_kv_tiles``
states that rule once, per query tile, as two ranges of grid steps:

* the query tile has a valid row (``i*Tq < valid_len``), else nothing;
* prefix tiles holding a position below ``cached_len``;
* new-key tiles holding a key below ``valid_len`` and not above the
  tile's causal frontier (its last query);
* with a sliding window, only tiles whose newest valid key is still
  inside the window of the tile's oldest query.

The rule is used four times: the compute fence (``pl.when``: a dead step
issues no DMA and no compute), the walk's lookahead (which prefix tile to
start next), the new keys' index map — every dead step maps to the block of
the nearest live new-key tile (dead query tiles to the one tile the last
live query tile ended on), so the block index repeats and Mosaic's revisit
elision issues no DMA — and, on the host, ``count_kv_tiles`` for the flight
records' ``kv_tiles_live`` / ``kv_tiles_grid`` / ``prefix_pages``.  Skipping
is exact: a fully masked tile's contribution is wiped by ``alpha =
exp(NEG_INF - m) = 0`` at the first live tile, and a query tile that saw no
live tile ends with ``l == 0`` and emits zeros.

Layout notes (Mosaic): blocks keep the (head, lane) dims whole — q tiles
are [Tq, H, D], kv tiles [Tk, K, D] — because Mosaic requires the last
two block dims divisible by (8, 128) or equal to the array's.  A prefix
tile's pages ``[Tk/bs, bs, K, D]`` read as ``[Tk, K, D]`` where they lie
(merging leading dims is layout-free); at one key head a page goes in as
``[bs, D]``, the layout XLA keeps ``[N, bs, 1, D]`` in anyway (a second-minor
dim of 1 is padded to a tile a one-head DMA slice is not aligned to).  GQA
regrouping happens in-register via swapaxes/reshape moves (the decode
kernel spares them: it has G rows a head, not Tq*G); both matmuls are
K-batched dot_generals contracting the lane dim, so no transposes are
materialized.  The softmax running max/normalizer are stored broadcast
across the 128-lane dim (scratch must be lane-tiled anyway) and read
back with a lane-reduce.

Position/validity semantics match the dense path exactly
(ops/attention.py: dense_prefill_attention): prefix position j is slot
``j % bs`` of page ``prefix_block_ids[j // bs]`` (valid while
j < cached_len), new token t sits at position cached_len + t (valid while
t < valid_len), as query row t does.

Replaces the role of FlashAttention prefill kernels inside the reference's
external vLLM engine (the reference ships no kernels — SURVEY.md preamble).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from production_stack_tpu.engine.ops.pallas import each

NEG_INF = -1e30
LANES = 128  # scratch lane width: fp32 scratch must tile to (8, 128)
Q_TILE = 128
KV_TILE = 512
SLOTS = 2  # the ring: one prefix tile computed on, the next in flight


def _tiling(T: int, P: int, bs: int, q_tile: int, kv_tile: int):
    """(Tq, pages a prefix tile, Tn, prefix steps, new-key steps) of the grid
    for T new tokens behind a table of P pages of bs positions: a prefix tile
    is whole pages, about ``kv_tile`` positions of them, a new-key tile
    divides T."""
    Tq = min(q_tile, T)
    Cp = max(1, min(kv_tile // bs, P))
    Tn = T if T <= kv_tile else math.gcd(T, kv_tile)
    return Tq, Cp, Tn, -(-P // Cp), T // Tn


def live_kv_tiles(i, cached_len, valid_len, *, Tq, Tp, Tn, NP,
                  sliding_window, xp=jnp):
    """The liveness rule (module docstring): which grid steps query tile
    ``i`` must visit, as ``(q_live, prefix_live, p_lo, p_hi, n_lo, n_hi)``
    — steps ``p_lo..p_hi`` (below ``NP``, ``Tp`` positions each) hold its
    visible prefix positions (only if ``prefix_live``), ``n_lo..n_hi`` (from
    ``NP`` on, ``Tn`` keys each) its visible new keys, and nothing is live
    unless ``q_live``.  Pure integer arithmetic on ``xp`` scalars or arrays:
    jnp inside the kernel and its index map, numpy on the host."""
    q0 = i * Tq  # the tile's oldest query, as a new-token index
    q_live = q0 < valid_len
    p_end = xp.minimum(cached_len, NP * Tp)  # valid prefix [p_start, p_end)
    n_end = xp.minimum(valid_len, q0 + Tq)  # valid causal keys [n_start, n_end)
    if sliding_window is None:
        p_start = n_start = 0
    else:
        # Oldest position the oldest query still sees; newer queries of
        # the tile see no further back.
        oldest = cached_len + q0 - sliding_window + 1
        p_start = xp.maximum(oldest, 0)
        n_start = xp.maximum(oldest - cached_len, 0)
    prefix_live = q_live & (p_start < p_end)
    return (
        q_live, prefix_live,
        p_start // Tp, (xp.maximum(p_end, 1) - 1) // Tp,
        NP + n_start // Tn, NP + (xp.maximum(n_end, 1) - 1) // Tn,
    )


def _tile_is_live(j, ranges):
    q_live, prefix_live, p_lo, p_hi, n_lo, n_hi = ranges
    return q_live & (
        (prefix_live & (p_lo <= j) & (j <= p_hi)) | ((n_lo <= j) & (j <= n_hi))
    )


def new_block_index(i, j, cached_len, valid_len, *, Tq, Tp, Tn, NP,
                    sliding_window, xp=jnp):
    """The block of ``k_new`` / ``v_new`` that grid step ``(i, j)`` holds.  A
    live new-key step holds its own tile; any other step of a live query tile
    — its prefix steps first — the nearest live one, so the tile after the
    prefix is there when the walk ends and Mosaic's revisit elision skips
    every repeat.  A dead query tile stays where the last live one ended."""
    q_live, _, _, _, n_lo, n_hi = live_kv_tiles(
        i, cached_len, valid_len,
        Tq=Tq, Tp=Tp, Tn=Tn, NP=NP, sliding_window=sliding_window, xp=xp,
    )
    parked = (xp.maximum(valid_len, 1) - 1) // Tn
    return xp.where(q_live, xp.clip(j, n_lo, n_hi) - NP, parked)


def count_kv_tiles(T: int, P: int, bs: int, cached_len: int, valid_len: int,
                   sliding_window: Optional[int] = None, *,
                   q_tile: int = Q_TILE, kv_tile: int = KV_TILE):
    """(kv tiles the kernel computes, kv tiles in its grid, pages of the
    prefix it fetches a side) for one call, per layer — the same rule on the
    host, arithmetic only.  A live prefix tile fetches all its pages."""
    Tq, Cp, Tn, NP, NN = _tiling(T, P, bs, q_tile, kv_tile)
    NQ = T // Tq
    ranges = live_kv_tiles(
        np.arange(NQ), cached_len, valid_len, Tq=Tq, Tp=Cp * bs, Tn=Tn,
        NP=NP, sliding_window=sliding_window, xp=np,
    )
    live = _tile_is_live(np.arange(NP + NN)[:, None], ranges)
    return int(live.sum()), NQ * (NP + NN), int(live[:NP].sum()) * Cp


def _flash_prefill_kernel(
    # scalar prefetch (SMEM)
    ids_ref,  # [max(P, 1)] int32: the prefix's pages
    lens_ref,  # [2] int32: cached_len, valid_len
    # inputs
    q_ref,  # [Tq, H, D] VMEM: this query tile, all heads
    kn_ref,  # [Tn, K, D] VMEM: this new-key tile
    vn_ref,  # [Tn, K, D]
    k_hbm,  # [N, bs, K, D] HBM ([N, bs, D] at one key head): the pools
    v_hbm,
    # outputs
    o_ref,  # [Tq, H, D]
    # scratch (persists across the grid)
    m_ref,  # [K, R, LANES] fp32 running max (lane-broadcast)
    l_ref,  # [K, R, LANES] fp32 running normalizer (lane-broadcast)
    acc_ref,  # [K, R, D] fp32 output accumulator
    k_buf,  # [SLOTS, Cp, *page] VMEM: the ring of prefix tiles
    v_buf,
    sems,  # DMA semaphores [2 sides, SLOTS, Cp]
    walked_ref,  # [1] int32 SMEM: prefix tiles this call has computed on
    *,
    Tq: int,
    Tn: int,
    P: int,
    NP: int,
    NQ: int,
    NKV: int,
    K: int,
    G: int,
    D: int,
    scale: float,
    sliding_window: Optional[int],
):
    i = pl.program_id(0)
    j = pl.program_id(1)
    cached, valid = lens_ref[0], lens_ref[1]
    R = Tq * G  # query rows per kv head after GQA regrouping
    Cp, bs = k_buf.shape[1], k_buf.shape[2]
    Tp = Cp * bs
    num_blocks = k_hbm.shape[0]
    rule = functools.partial(
        live_kv_tiles, cached_len=cached, valid_len=valid,
        Tq=Tq, Tp=Tp, Tn=Tn, NP=NP, sliding_window=sliding_window,
    )
    ranges = rule(i)
    live = _tile_is_live(j, ranges)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full((K, R, LANES), NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros((K, R, LANES), jnp.float32)
        acc_ref[...] = jnp.zeros((K, R, D), jnp.float32)

    def heads_first(x):
        """A kv tile [Tk, K, D] (at one key head a prefix tile is [Tk, D])
        -> [K, Tk, D] fp32."""
        x = x.astype(jnp.float32)
        return x[None] if x.ndim == 2 else x.swapaxes(0, 1)

    def fold(k, v, key_pos, key_valid):
        """One kv tile under the running softmax: ``k``, ``v`` [K, Tk, D],
        its keys' positions and validity [1, Tk]."""
        # [Tq, H, D] -> [K, Tq*G, D]: head h = k*G + g attends kv head k.
        q = q_ref[...].astype(jnp.float32) * scale
        q = q.reshape(Tq, K, G, D).swapaxes(0, 1).reshape(K, R, D)

        # [K, R, D] x [K, Tk, D] -> [K, R, Tk] (batch over kv heads).
        s = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )

        # Masks are built 2-D [R, Tk] and broadcast into the 3-D scores
        # (mask[None] — the exact pattern the decode kernel lowers with);
        # 4-D mask ops and bool-valued selects both stall Mosaic.  Query
        # row r = t*G + g is query token t = r // G.
        row_t = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0) // G
        q_pos = cached + i * Tq + row_t  # [R, 1]
        mask = key_valid & (key_pos <= q_pos)  # [R, Tk]
        if sliding_window is not None:
            mask &= key_pos > q_pos - sliding_window
        s = jnp.where(mask[None], s, NEG_INF)

        m_prev = jnp.max(m_ref[...], axis=-1, keepdims=True)  # [K, R, 1]
        l_prev = jnp.max(l_ref[...], axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        # [K, R, Tk] x [K, Tk, D] -> [K, R, D]
        pv = jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * alpha + pv
        m_ref[...] = jnp.broadcast_to(m_new, (K, R, LANES))
        l_ref[...] = jnp.broadcast_to(l_new, (K, R, LANES))

    if NP:
        sides = ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1))

        def start(tile, slot, unrolled: bool = True):
            """Start the copies of prefix tile ``tile`` into ring slot
            ``slot``: Cp pages a side, pages past the table its last."""
            def page(c):
                block = ids_ref[jnp.minimum(tile * Cp + c, P - 1)]
                block = jax.lax.clamp(0, block, num_blocks - 1)
                for hbm, buf, side in sides:
                    pltpu.make_async_copy(
                        hbm.at[block], buf.at[slot, c], sems.at[side, slot, c]
                    ).start()

            each(Cp, page, unrolled)

        @pl.when((i == 0) & (j == 0))
        def _():
            walked_ref[0] = 0

        @pl.when(live & (j < NP))
        def _prefix():
            walked = walked_ref[0]
            slot = jax.lax.rem(walked, SLOTS)

            # The walk's first tile starts itself; every other was started
            # by the live prefix step before it.
            @pl.when(walked == 0)
            def _():
                start(j, slot, unrolled=False)

            # The next live prefix step: this query tile's next, or the
            # next query tile's first.
            _, _, _, p_hi, _, _ = ranges
            q_next, prefix_next, p_lo_next, _, _, _ = rule(
                jnp.minimum(i + 1, NQ - 1))
            more = j < p_hi
            ahead = more | ((i + 1 < NQ) & q_next & prefix_next)

            @pl.when(ahead)
            def _():
                start(jnp.where(more, j + 1, p_lo_next), 1 - slot)

            for c in range(Cp):
                for hbm, buf, side in sides:
                    # A wait counts the destination's bytes; its source is
                    # only a shape.
                    pltpu.make_async_copy(
                        hbm.at[0], buf.at[slot, c], sems.at[side, slot, c]
                    ).wait()
            walked_ref[0] = walked + 1
            pos = j * Tp + jax.lax.broadcasted_iota(jnp.int32, (1, Tp), 1)
            # [Cp, bs, K, D] -> [Tp, K, D]: merging leading dims is free.
            tile = lambda buf: heads_first(
                buf[slot].reshape(Tp, *buf.shape[3:]))
            fold(tile(k_buf), tile(v_buf), pos, pos < cached)

    @pl.when(live & (j >= NP))
    def _new():
        t = (j - NP) * Tn + jax.lax.broadcasted_iota(jnp.int32, (1, Tn), 1)
        fold(heads_first(kn_ref[...]), heads_first(vn_ref[...]),
             cached + t, t < valid)

    @pl.when(j == NKV - 1)
    def _final():
        # A query tile wholly past valid_len visited no kv tile -> l == 0;
        # emit zeros, not NaNs (the caller slices padding rows off).
        l = jnp.max(l_ref[...], axis=-1, keepdims=True)
        l = jnp.where(l == 0.0, 1.0, l)
        out = (acc_ref[...] / l).reshape(K, Tq, G, D).swapaxes(0, 1)
        o_ref[...] = out.reshape(Tq, K * G, D).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "sliding_window", "q_tile", "kv_tile", "interpret"),
)
def flash_prefill_attention(
    q: jax.Array,  # [T, H, D]
    k_new: jax.Array,  # [T, K, D]
    v_new: jax.Array,  # [T, K, D]
    k_pool: Optional[jax.Array],  # [N, bs, K, D]: the layer's pages, read
    v_pool: Optional[jax.Array],  # where they lie; unread behind no table
    prefix_block_ids: jax.Array,  # [P] int32 (0-padded; P may be 0)
    cached_len: jax.Array,  # scalar int32
    valid_len: jax.Array,  # scalar int32
    *,
    scale: float,
    sliding_window: Optional[int] = None,
    q_tile: int = Q_TILE,
    kv_tile: int = KV_TILE,
    interpret: bool = False,
) -> jax.Array:
    """Flash causal prefill attention over the first ``cached_len`` positions
    of the pages ``prefix_block_ids`` names and the chunk's own keys (Pallas
    TPU)."""
    T, H, D = q.shape
    K = k_new.shape[1]
    P = prefix_block_ids.shape[0]
    if P == 0:
        # No prefix steps in the grid (the encode lane): the walk's operands
        # and its ring are there for the form alone, a row each.
        k_pool = v_pool = jnp.zeros((1, 1, K, D), k_new.dtype)
    N, bs = k_pool.shape[:2]
    if H % K:
        raise ValueError(f"H={H} not divisible by num_kv_heads={K}")
    G = H // K
    if D % 128 and not interpret:
        raise ValueError(f"flash prefill requires head_dim%128==0, got {D}")

    Tq, Cp, Tn, NP, NN = _tiling(T, P, bs, q_tile, kv_tile)
    if T % Tq:
        raise ValueError(f"T={T} not a multiple of q_tile={Tq}")
    NQ, NKV = T // Tq, NP + NN

    tiles = dict(
        Tq=Tq, Tp=Cp * bs, Tn=Tn, NP=NP, sliding_window=sliding_window)
    kernel = functools.partial(
        _flash_prefill_kernel,
        Tq=Tq, Tn=Tn, P=P, NP=NP, NQ=NQ, NKV=NKV, K=K, G=G, D=D, scale=scale,
        sliding_window=sliding_window,
    )

    def new_index(i, j, ids_ref, lens_ref):
        return new_block_index(i, j, lens_ref[0], lens_ref[1], **tiles), 0, 0

    R = Tq * G
    # One key head: a page goes in as [bs, D] (module docstring).
    page = (bs, D) if K == 1 else (bs, K, D)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(NQ, NKV),
        in_specs=[
            pl.BlockSpec((Tq, H, D), lambda i, j, *_: (i, 0, 0)),
            pl.BlockSpec((Tn, K, D), new_index),
            pl.BlockSpec((Tn, K, D), new_index),
            pl.BlockSpec(memory_space=pl.ANY),  # the pools stay in HBM
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((Tq, H, D), lambda i, j, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((K, R, LANES), jnp.float32),
            pltpu.VMEM((K, R, LANES), jnp.float32),
            pltpu.VMEM((K, R, D), jnp.float32),
            pltpu.VMEM((SLOTS, Cp, *page), k_pool.dtype),
            pltpu.VMEM((SLOTS, Cp, *page), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, SLOTS, Cp)),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, H, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # The walk's order is the grid's: a prefix tile is started a
            # live step before it is computed on.
            dimension_semantics=("arbitrary", "arbitrary"),
            disable_bounds_checks=True,
            # The fp32 score/prob intermediates ([K, R, Tk] each) plus the
            # online-softmax scratch exceed the compiler's default 16 MB
            # scoped VMEM at serving tile sizes; v5e/v6e have 128 MB, so
            # raise the cap rather than shrink tiles below MXU-efficient
            # shapes.
            vmem_limit_bytes=96 * 1024 * 1024,
        ),
        interpret=interpret,
        # What the device trace calls the kernel (%<name>.N on XLA Ops):
        # the benchmark's readers find it by this name.
        name="flash_prefill_attention",
    )(
        # An empty table (the encode lane) still needs an SMEM word.
        jnp.pad(prefix_block_ids.astype(jnp.int32), (0, int(P == 0))),
        jnp.stack([jnp.asarray(cached_len, jnp.int32),
                   jnp.asarray(valid_len, jnp.int32)]),
        q, k_new, v_new, k_pool.reshape(N, *page), v_pool.reshape(N, *page),
    )
