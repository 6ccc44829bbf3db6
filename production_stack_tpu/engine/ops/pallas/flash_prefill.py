"""Pallas TPU flash-attention kernel for prefill (causal + cached prefix).

Why the dense path stalls at ~0.44 MFU: ops/attention.py:prefill_attention
materializes the full fp32 score/prob tensors — [K, G, T, C+T] is ~430 MB
for a 2k-token llama-3.2-3b prefill, far beyond VMEM, so XLA spills them
to HBM and the MXU waits on bandwidth.  This kernel never materializes
scores in HBM: a 2-D grid (query tile x kv tile) streams keys/values
through VMEM in [Tk, K, D] slices while the online-softmax state
(running max, normalizer, and fp32 accumulator) lives in VMEM scratch
that persists across the kv dimension of the grid.  Nothing resident
scales with sequence length, so VMEM stays ~12 MB at any context
(a previous revision kept the whole [S_k, K, D] KV row resident, which
blew the 16 MB scoped-VMEM limit at 2k context on a 3B model).

Tile skipping: the grid is static — (T/Tq, ceil((C+T)/Tk)), and the
engine always gathers max_model_len prefix slots, so C is 8,192 whatever
``cached_len`` is — but a kv tile is *visited* only if one of its scores
survives the mask for one query of the query tile.  ``live_kv_tiles``
states that rule once, per query tile, as two ranges of kv tiles:

* the query tile has a valid row (``i*Tq < valid_len``), else nothing;
* prefix tiles holding a slot below ``min(C, cached_len)``;
* new-key tiles holding a key below ``valid_len`` and not above the
  tile's causal frontier (its last query);
* with a sliding window, only tiles whose newest valid key is still
  inside the window of the tile's oldest query.

A tile that straddles prefix and new keys (C need not be a multiple of
Tk) is live if either part is.  The rule is used three times: the
compute fence (``pl.when``), the kv index map — every dead step maps to
the block of the nearest live tile already fetched (dead query tiles to
the one tile the last live query tile ended on), so the block index
repeats and Mosaic's revisit elision issues no DMA — and, on the host,
``count_kv_tiles`` for the flight records' ``kv_tiles_live`` /
``kv_tiles_grid``.  Skipping is exact: a fully masked tile's
contribution is wiped by ``alpha = exp(NEG_INF - m) = 0`` at the first
live tile, and a query tile that saw no live tile ends with ``l == 0``
and emits zeros.

Layout notes (Mosaic): blocks keep the (head, lane) dims whole — q tiles
are [Tq, H, D], kv tiles [Tk, K, D] — because Mosaic requires the last
two block dims divisible by (8, 128) or equal to the array's.  GQA
regrouping happens in-register via swapaxes/reshape moves (the decode
kernel spares them: it has G rows a head, not Tq*G); both matmuls are
K-batched dot_generals contracting the lane dim, so no transposes are
materialized.  The softmax running max/normalizer are stored broadcast
across the 128-lane dim (scratch must be lane-tiled anyway) and read
back with a lane-reduce.

Position/validity semantics match the dense path exactly
(ops/attention.py:128-143): key j < C is prefix slot j (valid while
j < cached_len), key j >= C is new token j-C at position cached_len+(j-C)
(valid while j-C < valid_len); query row t sits at cached_len + t.

Replaces the role of FlashAttention prefill kernels inside the reference's
external vLLM engine (the reference ships no kernels — SURVEY.md preamble).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128  # scratch lane width: fp32 scratch must tile to (8, 128)
Q_TILE = 128
KV_TILE = 512


def _tiling(T: int, C: int, q_tile: int, kv_tile: int):
    """(Tq, Tk, query tiles, kv tiles) of the grid for T new tokens behind
    C gathered prefix slots."""
    Tq = min(q_tile, T)
    Tk = min(kv_tile, C + T)
    return Tq, Tk, T // Tq, -(-(C + T) // Tk)


def live_kv_tiles(i, cached_len, valid_len, *, Tq, Tk, C, sliding_window,
                  xp=jnp):
    """The liveness rule (module docstring): which kv tiles query tile
    ``i`` must visit, as ``(q_live, prefix_live, p_lo, p_hi, n_lo, n_hi)``
    — kv tiles ``p_lo..p_hi`` hold its visible prefix slots (only if
    ``prefix_live``), ``n_lo..n_hi`` its visible new keys, and nothing is
    live unless ``q_live``.  Pure integer arithmetic on ``xp`` scalars or
    arrays: jnp inside the kernel and its index map, numpy on the host."""
    q0 = i * Tq  # the tile's oldest query, as a new-token index
    q_live = q0 < valid_len
    p_end = xp.minimum(cached_len, C)  # valid prefix slots [p_start, p_end)
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
        p_start // Tk, (xp.maximum(p_end, 1) - 1) // Tk,
        (C + n_start) // Tk, (C + xp.maximum(n_end, 1) - 1) // Tk,
    )


def _tile_is_live(j, ranges):
    q_live, prefix_live, p_lo, p_hi, n_lo, n_hi = ranges
    return q_live & (
        (prefix_live & (p_lo <= j) & (j <= p_hi)) | ((n_lo <= j) & (j <= n_hi))
    )


def kv_block_index(i, j, cached_len, valid_len, *, Tq, Tk, C,
                   sliding_window, xp=jnp):
    """The kv block that grid step ``(i, j)`` holds.  A live step holds its
    own tile ``j``; a dead step repeats the block of the nearest live tile
    already fetched, so Mosaic's revisit elision skips its DMA: clamp into
    [first live, last live], and park the gap between the prefix range and
    the new-key range on the prefix range's end.  A dead query tile stays
    where the last live query tile ended."""
    q_live, prefix_live, p_lo, p_hi, n_lo, n_hi = live_kv_tiles(
        i, cached_len, valid_len,
        Tq=Tq, Tk=Tk, C=C, sliding_window=sliding_window, xp=xp,
    )
    idx = xp.clip(j, xp.where(prefix_live, p_lo, n_lo), n_hi)
    idx = xp.where(prefix_live & (idx > p_hi) & (idx < n_lo), p_hi, idx)
    parked = (C + xp.maximum(valid_len, 1) - 1) // Tk
    return xp.where(q_live, idx, parked)


def count_kv_tiles(T: int, C: int, cached_len: int, valid_len: int,
                   sliding_window: Optional[int] = None, *,
                   q_tile: int = Q_TILE, kv_tile: int = KV_TILE):
    """(kv tiles the kernel computes, kv tiles in its grid) for one call,
    per layer — the same rule on the host, arithmetic only."""
    Tq, Tk, NQ, NKV = _tiling(T, C, q_tile, kv_tile)
    ranges = live_kv_tiles(
        np.arange(NQ), cached_len, valid_len,
        Tq=Tq, Tk=Tk, C=C, sliding_window=sliding_window, xp=np,
    )
    live = _tile_is_live(np.arange(NKV)[:, None], ranges)
    return int(live.sum()), NQ * NKV


def _flash_prefill_kernel(
    # scalar prefetch (SMEM)
    cached_len_ref,  # [1] int32
    valid_len_ref,  # [1] int32
    # inputs (VMEM blocks)
    q_ref,  # [Tq, H, D] this query tile, all heads
    k_ref,  # [Tk, K, D] this kv tile
    v_ref,  # [Tk, K, D]
    # outputs
    o_ref,  # [Tq, H, D]
    # scratch (VMEM, persists across the kv grid dim)
    m_ref,  # [K, R, LANES] fp32 running max (lane-broadcast)
    l_ref,  # [K, R, LANES] fp32 running normalizer (lane-broadcast)
    acc_ref,  # [K, R, D] fp32 output accumulator
    *,
    Tq: int,
    Tk: int,
    C: int,
    NKV: int,
    K: int,
    G: int,
    D: int,
    scale: float,
    sliding_window: Optional[int],
):
    i = pl.program_id(0)
    j = pl.program_id(1)
    cached = cached_len_ref[0]
    valid = valid_len_ref[0]
    R = Tq * G  # query rows per kv head after GQA regrouping

    live = _tile_is_live(j, live_kv_tiles(
        i, cached, valid, Tq=Tq, Tk=Tk, C=C, sliding_window=sliding_window,
    ))

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full((K, R, LANES), NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros((K, R, LANES), jnp.float32)
        acc_ref[...] = jnp.zeros((K, R, D), jnp.float32)

    @pl.when(live)
    def _compute():
        # [Tq, H, D] -> [K, Tq*G, D]: head h = k*G + g attends kv head k.
        q = q_ref[...].astype(jnp.float32) * scale
        q = q.reshape(Tq, K, G, D).swapaxes(0, 1).reshape(K, R, D)
        k = k_ref[...].astype(jnp.float32).swapaxes(0, 1)  # [K, Tk, D]
        v = v_ref[...].astype(jnp.float32).swapaxes(0, 1)

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
        flat = j * Tk + jax.lax.broadcasted_iota(jnp.int32, (1, Tk), 1)
        is_prefix = flat < C
        key_pos = jnp.where(is_prefix, flat, cached + flat - C)  # int select
        key_valid = (is_prefix & (flat < cached)) | (
            ~is_prefix & (flat - C < valid)
        )
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
    k_prefix: jax.Array,  # [C, K, D] gathered cached prefix (may be C=0)
    v_prefix: jax.Array,  # [C, K, D]
    cached_len: jax.Array,  # scalar int32
    valid_len: jax.Array,  # scalar int32
    *,
    scale: float,
    sliding_window: Optional[int] = None,
    q_tile: int = Q_TILE,
    kv_tile: int = KV_TILE,
    interpret: bool = False,
) -> jax.Array:
    """Flash causal prefill attention with prefix (Pallas TPU)."""
    T, H, D = q.shape
    K = k_new.shape[1]
    C = k_prefix.shape[0]
    if H % K:
        raise ValueError(f"H={H} not divisible by num_kv_heads={K}")
    G = H // K
    if D % 128 and not interpret:
        raise ValueError(f"flash prefill requires head_dim%128==0, got {D}")

    Tq, Tk, NQ, NKV = _tiling(T, C, q_tile, kv_tile)
    if T % Tq:
        raise ValueError(f"T={T} not a multiple of q_tile={Tq}")

    keys = jnp.concatenate([k_prefix, k_new], axis=0)  # [C+T, K, D]
    values = jnp.concatenate([v_prefix, v_new], axis=0)
    if NKV * Tk != C + T:
        pad = [(0, NKV * Tk - (C + T)), (0, 0), (0, 0)]
        keys = jnp.pad(keys, pad)  # padded keys are masked (j-C >= valid)
        values = jnp.pad(values, pad)

    kernel = functools.partial(
        _flash_prefill_kernel,
        Tq=Tq, Tk=Tk, C=C, NKV=NKV, K=K, G=G, D=D,
        scale=scale, sliding_window=sliding_window,
    )

    def kv_index(i, j, cached_ref, valid_ref):
        return (
            kv_block_index(
                i, j, cached_ref[0], valid_ref[0],
                Tq=Tq, Tk=Tk, C=C, sliding_window=sliding_window,
            ), 0, 0,
        )

    R = Tq * G
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(NQ, NKV),
        in_specs=[
            pl.BlockSpec((Tq, H, D), lambda i, j, *_: (i, 0, 0)),
            pl.BlockSpec((Tk, K, D), kv_index),
            pl.BlockSpec((Tk, K, D), kv_index),
        ],
        out_specs=pl.BlockSpec((Tq, H, D), lambda i, j, *_: (i, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((K, R, LANES), jnp.float32),
            pltpu.VMEM((K, R, LANES), jnp.float32),
            pltpu.VMEM((K, R, D), jnp.float32),
        ],
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T, H, D), q.dtype),
        # The fp32 score/prob intermediates ([K, R, Tk] each) plus the
        # online-softmax scratch exceed the compiler's default 16 MB scoped
        # VMEM at serving tile sizes; v5e/v6e have 128 MB, so raise the cap
        # rather than shrink tiles below MXU-efficient shapes.
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=96 * 1024 * 1024
        ),
        interpret=interpret,
        # What the device trace calls the kernel (%<name>.N on XLA Ops):
        # the benchmark's readers find it by this name.
        name="flash_prefill_attention",
    )(
        jnp.asarray(cached_len, jnp.int32).reshape(1),
        jnp.asarray(valid_len, jnp.int32).reshape(1),
        q, keys, values,
    )
