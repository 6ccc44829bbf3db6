"""Paged attention (pure-JAX reference path).

Design notes (TPU-first):

* All shapes are static.  Prefill lengths are bucketed, decode batch is
  padded to the scheduler's ``max_num_seqs``; invalid slots are masked, and
  their KV writes land in the reserved *null block* 0 (never read).
* Both dots take their operands in the cache's dtype (bf16 when serving:
  the queries, and the probabilities rounded to it for the second dot) and
  accumulate fp32 on the MXU; scores, the softmax and its statistics are
  fp32 on the VPU; outputs are the queries' dtype -- on this path and in
  the Pallas decode kernel alike.
* The gather-based decode path below materializes [S, max_ctx, K, D] in HBM
  — correct everywhere (CPU tests, interpret mode) and fast enough for
  moderate contexts.  ``decode_attention`` dispatches to the Pallas kernel
  in pallas/paged_attention.py on TPU backends (set
  ``PSTPU_DISABLE_PALLAS=1`` to force the gather path, e.g. for A/B
  benchmarking); under a multi-device mesh the kernel runs per-shard via
  shard_map (batch over dp, heads over tp).

KV cache layout per layer: ``[num_blocks, block_size, num_kv_heads, head_dim]``
— block-major so one block is a contiguous DMA unit for both the decode
kernel and host offload (kv/offload.py).
"""

from __future__ import annotations

import math
import os
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30  # large-but-finite: keeps masked softmax rows NaN-free

# Quantized (int8) cache sides are (data, scale) tuples — kv/quant.py.
from production_stack_tpu.engine.kv import quant as kv_quant


def pallas_disabled() -> bool:
    """The A/B switch that sends every step down the XLA gather/dense
    path; the engine says at boot when it is set."""
    return bool(os.environ.get("PSTPU_DISABLE_PALLAS"))


def use_pallas_decode(num_kv_heads: int = 128, head_dim: int = 128) -> bool:
    """Trace-time dispatch check for the streaming decode kernel.

    Needs a real TPU and a 128-lane-aligned head_dim: the kernel splits
    the DMA'd KV row back into heads in VMEM, and Mosaic only lowers that
    shape cast when head_dim is a multiple of the 128-lane tile.  Covers
    llama-3-8b / llama-3.2-3b / mistral-7b (D=128); head_dim-64 models
    (llama-3.2-1b) and the tiny test models use the gather path.  A page of
    more than eight key heads has to be whole bf16 tiles of 16 rows: the
    device keeps 30 heads in 32 and Mosaic refuses a DMA of 30 of them
    (``models/olmo_hybrid.py`` makes its pages with 32)."""
    if pallas_disabled():
        return False
    if num_kv_heads < 1 or head_dim % 128:
        return False
    if num_kv_heads > 8 and num_kv_heads % 16:
        return False
    return jax.default_backend() == "tpu"


def decode_attention(
    q: jax.Array,  # [S, H, D]
    k_cache: jax.Array,  # [N, bs, K, D]
    v_cache: jax.Array,
    block_tables: jax.Array,  # [S, Bmax]
    ctx_lens: jax.Array,  # [S]
    *,
    scale: float,
    sliding_window: Optional[int] = None,
    mesh: Optional[Mesh] = None,
) -> jax.Array:
    """Decode attention with backend dispatch (Pallas on TPU, gather else).

    Under a multi-device mesh the Pallas kernel runs per-shard inside
    shard_map: the decode batch (and its block table / context rows) is
    sharded over dp, heads over tp; the KV pool's block axis is replicated
    so per-shard block ids stay valid.
    """
    from production_stack_tpu.engine.parallel.mesh import AXES

    quantized = kv_quant.is_quantized(k_cache)
    K, D = kv_quant.cache_shape(k_cache)[2:4]
    # Under tp the kernel sees K/tp heads per shard; alignment must hold
    # for the per-shard KV row.
    tp = mesh.shape[AXES.TP] if mesh is not None and mesh.size > 1 else 1
    if not use_pallas_decode(K // tp, D):
        return paged_decode_attention(
            q, k_cache, v_cache, block_tables, ctx_lens,
            scale=scale, sliding_window=sliding_window,
        )
    from production_stack_tpu.engine.ops.pallas.paged_attention import (
        paged_decode_attention_pallas,
    )

    kernel = partial(
        paged_decode_attention_pallas, scale=scale, sliding_window=sliding_window
    )
    if mesh is None or mesh.size == 1:
        return kernel(q, k_cache, v_cache, block_tables, ctx_lens)

    cache_spec = (
        (P(None, None, AXES.TP, None), P(None, None, AXES.TP))
        if quantized else P(None, None, AXES.TP, None)
    )
    return shard_map(
        kernel,
        mesh=mesh,
        in_specs=(
            P(AXES.DP, AXES.TP, None),  # q: batch over dp, heads over tp
            cache_spec,  # k_cache: kv heads over tp (scales follow)
            cache_spec,  # v_cache
            P(AXES.DP, None),  # block_tables rows follow the batch
            P(AXES.DP),  # ctx_lens
        ),
        out_specs=P(AXES.DP, AXES.TP, None),
        check_vma=False,
    )(q, k_cache, v_cache, block_tables, ctx_lens)


def use_pallas_prefill(num_heads: int, num_kv_heads: int, head_dim: int,
                       num_tokens: int) -> bool:
    """Trace-time dispatch check for the flash prefill kernel: real TPU,
    128-lane-aligned head_dim, GQA-divisible heads, and a power-of-two-ish
    token bucket the q tiling divides (engine buckets are powers of two)."""
    if pallas_disabled():
        return False
    if head_dim % 128 or num_heads % max(num_kv_heads, 1):
        return False
    if num_tokens % min(256, num_tokens):
        return False
    return jax.default_backend() == "tpu"


def prefill_attention(
    q: jax.Array,  # [T, H, D]
    k_new: jax.Array,  # [T, K, D]
    v_new: jax.Array,  # [T, K, D]
    k_cache,  # [N, bs, K, D], or (data, scale) when int8, or None at P == 0
    v_cache,
    prefix_block_ids: jax.Array,  # [P] int32 (0-padded; P may be 0)
    cached_len: jax.Array,  # scalar int: valid prefix tokens (<= P * bs)
    valid_len: jax.Array,  # scalar int: valid new tokens (<= T)
    *,
    scale: float,
    sliding_window: Optional[int] = None,
    mesh: Optional[Mesh] = None,
) -> jax.Array:
    """Causal attention for one sequence's prefill chunk over its cached
    prefix -- the first ``cached_len`` positions of the pages
    ``prefix_block_ids`` names -- and the new tokens themselves, with backend
    dispatch.  ``k_cache`` / ``v_cache`` may be None behind an empty table.

    On a single TPU device the Pallas flash kernel reads the prefix's pages
    where they lie in the pool (pallas/flash_prefill.py): nothing is gathered.
    A quantized (data, scale) cache keeps the kernel too: its prefix is
    gathered and dequantized, as it always was, and that copy goes in as a
    pool of its own (:func:`prefix_as_pool`).  The dense statement below,
    over a gathered ``[P * bs, K, D]`` copy of the prefix, stays where the
    kernel cannot serve, by what is seen at trace time: a multi-device mesh
    (GSPMD partitions its einsums across tp automatically, while a bare
    pallas_call cannot be auto-partitioned; the sp>1 case never reaches here
    -- llama.prefill routes it to ring attention) and off a TPU."""
    T, H, D = q.shape
    single_device = mesh is None or mesh.size == 1
    kernel = single_device and use_pallas_prefill(H, k_new.shape[1], D, T)
    if kernel and not kv_quant.is_quantized(k_cache):
        pool = k_cache, v_cache, prefix_block_ids
    else:
        dense = (k_new[:0], v_new[:0]) if k_cache is None else (
            gather_prefix_kv(
                k_cache, v_cache, prefix_block_ids, dtype=k_new.dtype))
        if not kernel:
            return dense_prefill_attention(
                q, k_new, v_new, *dense, cached_len, valid_len,
                scale=scale, sliding_window=sliding_window,
            )
        pool = prefix_as_pool(*dense)
    from production_stack_tpu.engine.ops.pallas.flash_prefill import (
        flash_prefill_attention,
    )

    return flash_prefill_attention(
        q, k_new, v_new, *pool, cached_len, valid_len,
        scale=scale, sliding_window=sliding_window,
    )


def prefix_as_pool(
    k_prefix: jax.Array,  # [C, K, D]: a prefix's positions in order
    v_prefix: jax.Array,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """A dense copy of a prefix as ``prefill_attention`` takes one: a pool of
    its own, ``(k_pool, v_pool, prefix_block_ids)`` with pages of up to 512
    positions in order (a dequantized copy; Ulysses' redistributed prefix)."""
    C = k_prefix.shape[0]
    page = math.gcd(C, 512)
    as_pool = lambda x: x.reshape(C // page, page, *x.shape[1:])  # noqa: E731
    return (as_pool(k_prefix), as_pool(v_prefix),
            jnp.arange(C // page, dtype=jnp.int32))


def dense_prefill_attention(
    q: jax.Array,  # [T, H, D]
    k_new: jax.Array,  # [T, K, D]
    v_new: jax.Array,  # [T, K, D]
    k_prefix: jax.Array,  # [C_max, K, D] gathered cached prefix (may be empty)
    v_prefix: jax.Array,  # [C_max, K, D]
    cached_len: jax.Array,  # scalar int: valid prefix tokens (< C_max)
    valid_len: jax.Array,  # scalar int: valid new tokens (<= T)
    *,
    scale: float,
    sliding_window: Optional[int] = None,
) -> jax.Array:
    """The plain statement of prefill attention: one sequence's new tokens
    attend a gathered cached prefix plus themselves, causally.  It
    materializes [K, G, T, C_max+T] fp32 scores (which spill to HBM for long
    prompts -- see pallas/flash_prefill.py): what the kernel is held against,
    and the path of :func:`prefill_attention`'s fallbacks."""
    T, H, D = q.shape
    C_max = k_prefix.shape[0]
    K = k_new.shape[1]
    G = H // K

    keys = jnp.concatenate([k_prefix, k_new], axis=0)  # [C_max+T, K, D]
    values = jnp.concatenate([v_prefix, v_new], axis=0)

    # Positions: query i sits at cached_len + i; prefix key j at j; new key
    # j' at cached_len + j'.  Build key-position array of shape [C_max+T].
    prefix_pos = jnp.arange(C_max)
    new_pos = cached_len + jnp.arange(T)
    key_pos = jnp.concatenate([prefix_pos, new_pos])  # [C_max+T]
    q_pos = cached_len + jnp.arange(T)  # [T]

    # Valid keys: prefix slots < cached_len, new slots < valid_len.
    key_valid = jnp.concatenate(
        [prefix_pos < cached_len, jnp.arange(T) < valid_len]
    )

    mask = key_pos[None, :] <= q_pos[:, None]  # causal
    mask &= key_valid[None, :]
    if sliding_window is not None:
        mask &= key_pos[None, :] > (q_pos[:, None] - sliding_window)

    qg = q.reshape(T, K, G, D)
    # [T, K, G, D] x [S_k, K, D] -> [K, G, T, S_k]
    scores = jnp.einsum(
        "tkgd,skd->kgts", qg, keys, preferred_element_type=jnp.float32
    )
    scores = scores * scale
    scores = jnp.where(mask[None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "kgts,skd->tkgd", probs.astype(values.dtype), values,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(T, H, D).astype(q.dtype)


def paged_decode_attention(
    q: jax.Array,  # [S, H, D] one new token per sequence
    k_cache: jax.Array,  # [N, bs, K, D]
    v_cache: jax.Array,  # [N, bs, K, D]
    block_tables: jax.Array,  # [S, Bmax] int32 (0 = null block)
    ctx_lens: jax.Array,  # [S] int32: tokens in context incl. current
    *,
    scale: float,
    sliding_window: Optional[int] = None,
) -> jax.Array:
    """Decode attention over paged KV via gather (reference path)."""
    S, H, D = q.shape
    N, bs, K, _ = kv_quant.cache_shape(k_cache)
    Bmax = block_tables.shape[1]
    G = H // K

    if kv_quant.is_quantized(k_cache):
        k = kv_quant.dequantize(
            k_cache[0][block_tables], k_cache[1][block_tables]
        ).reshape(S, Bmax * bs, K, D)
        v = kv_quant.dequantize(
            v_cache[0][block_tables], v_cache[1][block_tables]
        ).reshape(S, Bmax * bs, K, D)
    else:
        k = k_cache[block_tables].reshape(S, Bmax * bs, K, D)
        v = v_cache[block_tables].reshape(S, Bmax * bs, K, D)

    key_pos = jnp.arange(Bmax * bs)[None, :]  # [1, max_ctx]
    mask = key_pos < ctx_lens[:, None]  # [S, max_ctx]
    if sliding_window is not None:
        mask &= key_pos > (ctx_lens[:, None] - 1 - sliding_window)

    qg = q.reshape(S, K, G, D)
    scores = jnp.einsum(
        "skgd,stkd->skgt", qg, k, preferred_element_type=jnp.float32
    )
    scores = scores * scale
    scores = jnp.where(mask[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "skgt,stkd->skgd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(S, H, D).astype(q.dtype)


def write_prefill_kv(
    k_cache: jax.Array,  # [N, bs, K, D]
    v_cache: jax.Array,
    k_new: jax.Array,  # [T, K, D], T = num_new_blocks * bs
    v_new: jax.Array,
    new_block_ids: jax.Array,  # [T // bs] int32; padding slots -> 0 (null)
) -> Tuple[jax.Array, jax.Array]:
    """Scatter freshly computed prefill KV into the paged cache."""
    N, bs, K, D = kv_quant.cache_shape(k_cache)
    nb = new_block_ids.shape[0]
    if kv_quant.is_quantized(k_cache):
        kd, ks = kv_quant.quantize_vectors(k_new.reshape(nb, bs, K, D))
        vd, vs = kv_quant.quantize_vectors(v_new.reshape(nb, bs, K, D))
        k_cache = (
            k_cache[0].at[new_block_ids].set(kd),
            k_cache[1].at[new_block_ids].set(ks),
        )
        v_cache = (
            v_cache[0].at[new_block_ids].set(vd),
            v_cache[1].at[new_block_ids].set(vs),
        )
        return k_cache, v_cache
    k_blocks = k_new.reshape(nb, bs, K, D).astype(k_cache.dtype)
    v_blocks = v_new.reshape(nb, bs, K, D).astype(v_cache.dtype)
    k_cache = k_cache.at[new_block_ids].set(k_blocks)
    v_cache = v_cache.at[new_block_ids].set(v_blocks)
    return k_cache, v_cache


def append_decode_kv(
    k_cache: jax.Array,  # [N, bs, K, D]
    v_cache: jax.Array,
    k: jax.Array,  # [S, K, D] one token per sequence
    v: jax.Array,
    slot_block_ids: jax.Array,  # [S] int32 block holding this token (0=null)
    slot_offsets: jax.Array,  # [S] int32 offset within the block
) -> Tuple[jax.Array, jax.Array]:
    """Scatter one new token's KV per sequence into the paged cache."""
    if kv_quant.is_quantized(k_cache):
        kd, ks = kv_quant.quantize_vectors(k)  # [S, K, D] -> + [S, K]
        vd, vs = kv_quant.quantize_vectors(v)
        k_cache = (
            k_cache[0].at[slot_block_ids, slot_offsets].set(kd),
            k_cache[1].at[slot_block_ids, slot_offsets].set(ks),
        )
        v_cache = (
            v_cache[0].at[slot_block_ids, slot_offsets].set(vd),
            v_cache[1].at[slot_block_ids, slot_offsets].set(vs),
        )
        return k_cache, v_cache
    k_cache = k_cache.at[slot_block_ids, slot_offsets].set(k.astype(k_cache.dtype))
    v_cache = v_cache.at[slot_block_ids, slot_offsets].set(v.astype(v_cache.dtype))
    return k_cache, v_cache


def gather_prefix_kv(
    k_cache: jax.Array,  # [N, bs, K, D] (or (data, scale) when int8)
    v_cache: jax.Array,
    prefix_block_ids: jax.Array,  # [P] int32 (0-padded)
    dtype=None,  # dequantization target for quantized caches (fp32 default)
) -> Tuple[jax.Array, jax.Array]:
    """Gather a cached prefix as [P*bs, K, D] for the dense forms of
    prefill attention (``prefill_attention``'s fallbacks, ring, ulysses);
    the flash kernel reads the pages itself.

    Quantized caches dequantize here — the dense forms are
    precision-agnostic.
    """
    N, bs, K, D = kv_quant.cache_shape(k_cache)
    P = prefix_block_ids.shape[0]
    if kv_quant.is_quantized(k_cache):
        k = kv_quant.dequantize(
            k_cache[0][prefix_block_ids], k_cache[1][prefix_block_ids],
            dtype=dtype,
        ).reshape(P * bs, K, D)
        v = kv_quant.dequantize(
            v_cache[0][prefix_block_ids], v_cache[1][prefix_block_ids],
            dtype=dtype,
        ).reshape(P * bs, K, D)
        return k, v
    k = k_cache[prefix_block_ids].reshape(P * bs, K, D)
    v = v_cache[prefix_block_ids].reshape(P * bs, K, D)
    return k, v
