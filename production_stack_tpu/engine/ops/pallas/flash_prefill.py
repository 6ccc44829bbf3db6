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

Causal skipping: kv tiles wholly above a query tile's frontier are
skipped two ways — compute is fenced with ``pl.when``, and the kv
index map clamps to the last visible tile so Mosaic's revisit-elision
skips the DMA too (the block index doesn't change, so nothing is
re-fetched).

Layout notes (Mosaic): blocks keep the (head, lane) dims whole — q tiles
are [Tq, H, D], kv tiles [Tk, K, D] — because Mosaic requires the last
two block dims divisible by (8, 128) or equal to the array's.  GQA
regrouping happens in-register via the same swapaxes/reshape moves the
decode kernel uses (paged_attention.py:114-115); both matmuls are
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
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
LANES = 128  # scratch lane width: fp32 scratch must tile to (8, 128)


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

    # Last kv tile any query in this tile can see: the tile's last query
    # sits at cached + (i+1)*Tq - 1 and sees prefix keys (flat < C) plus
    # new keys with flat index < C + (i+1)*Tq.
    last = (C + (i + 1) * Tq - 1) // Tk

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full((K, R, LANES), NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros((K, R, LANES), jnp.float32)
        acc_ref[...] = jnp.zeros((K, R, D), jnp.float32)

    @pl.when(j <= last)
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
        # Rows past valid_len (padding) have every key masked -> l == 0;
        # emit zeros, not NaNs (the caller slices them off).
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
    q_tile: int = 128,
    kv_tile: int = 512,
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

    Tq = min(q_tile, T)
    if T % Tq:
        raise ValueError(f"T={T} not a multiple of q_tile={Tq}")

    keys = jnp.concatenate([k_prefix, k_new], axis=0)  # [C+T, K, D]
    values = jnp.concatenate([v_prefix, v_new], axis=0)
    S_raw = C + T
    Tk = min(kv_tile, S_raw)
    S_k = -(-S_raw // Tk) * Tk
    if S_k != S_raw:
        pad = [(0, S_k - S_raw), (0, 0), (0, 0)]
        keys = jnp.pad(keys, pad)  # padded keys are masked (j-C >= valid)
        values = jnp.pad(values, pad)
    NKV = S_k // Tk

    kernel = functools.partial(
        _flash_prefill_kernel,
        Tq=Tq, Tk=Tk, C=C, NKV=NKV, K=K, G=G, D=D,
        scale=scale, sliding_window=sliding_window,
    )

    def kv_index(i, j, *_):
        # Clamp to the tile's causal frontier: for skipped steps the block
        # index repeats, so Mosaic's revisit-elision skips the DMA.
        last = (C + (i + 1) * Tq - 1) // Tk
        return (jnp.minimum(j, last), 0, 0)

    R = Tq * G
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(T // Tq, NKV),
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
