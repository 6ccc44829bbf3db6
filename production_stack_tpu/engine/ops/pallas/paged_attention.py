"""Pallas TPU decode-attention kernel over the paged KV cache.

The decode step is HBM-bandwidth bound: each new token must read every live
KV block of its sequence once.  The pure-JAX gather path
(ops/attention.py:paged_decode_attention) pays that read **three times**
(gather-read, materialize-write, attention-read) and always over the full
``Bmax``-padded block table.  This kernel streams each sequence's actual
blocks HBM->VMEM exactly once with double-buffered async DMA and an online
softmax, and its per-sequence loop bound is the *real* context length, so a
256-token sequence in an 8k-token pool touches 16 blocks, not 512.

Blocks are fetched in chunks of ``chunk_blocks`` per pipeline stage: one
16-token block is too small to amortize DMA issue latency or fill the MXU,
so each stage issues ``chunk_blocks`` parallel block DMAs (their latencies
overlap in the DMA engine) and runs one online-softmax update over the
whole ``chunk_blocks * block_size``-token tile.

Grid: one program per sequence.  The block table and context lengths ride
in SMEM via scalar prefetch so DMA source indices are computable before the
body runs.  Accumulation is fp32 (softmax on the VPU, score/value matmuls
on the MXU).

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


def _decode_kernel(
    # scalar prefetch (SMEM)
    block_tables_ref,  # [S, Bmax] int32
    ctx_lens_ref,  # [S] int32
    # inputs: q_ref, k_hbm, v_hbm[, ks_hbm, vs_hbm] (int8 cache scales)
    # outputs: o_ref
    # scratch: k_buf, v_buf[, ks_buf, vs_buf], sems
    *refs,
    bs: int,
    chunk_blocks: int,
    num_kv_heads: int,
    q_per_kv: int,
    head_dim: int,
    scale: float,
    sliding_window: Optional[int],
    quantized: bool,
):
    if quantized:
        (q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref,
         k_buf, v_buf, ks_buf, vs_buf, sems) = refs
    else:
        q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems = refs
        ks_hbm = vs_hbm = ks_buf = vs_buf = None
    s = pl.program_id(0)
    ctx = ctx_lens_ref[s]
    nb = (ctx + bs - 1) // bs  # live KV blocks for this sequence
    C = chunk_blocks
    nc = (nb + C - 1) // C  # dynamic trip count: only live chunks
    K, G, D = num_kv_heads, q_per_kv, head_dim
    T = C * bs  # tokens per pipeline stage

    # fp32 query, pre-scaled; head h = k*G + g attends kv head k (GQA).
    q = (q_ref[0].reshape(K, G, D).astype(jnp.float32)) * scale

    def block_id(j):
        # Chunk-tail blocks past nb read table slot 0 (the null block) —
        # a valid, masked-out DMA source (tables are 0-padded).
        return block_tables_ref[s, jnp.minimum(j, nb - 1) * (j < nb)]

    def dma(cache, buf, kv, slot, c, j):
        return pltpu.make_async_copy(
            cache.at[block_id(j)], buf.at[slot, c], sems.at[kv, slot, c]
        )

    streams = [(k_hbm, k_buf, 0), (v_hbm, v_buf, 1)]
    if quantized:
        # Scale planes ride the same pipeline (tiny: [bs, K] fp32/block).
        streams += [(ks_hbm, ks_buf, 2), (vs_hbm, vs_buf, 3)]

    def start_chunk(slot, chunk):
        for c in range(C):  # static unroll: C parallel DMA issues
            for cache, buf, kv in streams:
                dma(cache, buf, kv, slot, c, chunk * C + c).start()

    def wait_chunk(slot, chunk):
        for c in range(C):
            for cache, buf, kv in streams:
                dma(cache, buf, kv, slot, c, chunk * C + c).wait()

    # Padded batch slots (ctx == 0) must not start DMAs: an un-waited DMA
    # leaves its semaphore signaled and poisons the next grid step's waits.
    @pl.when(nc > 0)
    def _():
        start_chunk(0, 0)

    def body(i, carry):
        m, l, acc = carry
        slot = jax.lax.rem(i, 2)
        nxt = jax.lax.rem(i + 1, 2)

        @pl.when(i + 1 < nc)
        def _():
            start_chunk(nxt, i + 1)

        wait_chunk(slot, i)
        # [C, bs, K, D] -> [K, T, D] (Mosaic needs lhs/rhs batch dims in
        # matching positions, so the kv-head axis moves to the front;
        # merging the leading dims is layout-free, D stays the lane dim).
        k = k_buf[slot].astype(jnp.float32).reshape(T, K, D).swapaxes(0, 1)
        v = v_buf[slot].astype(jnp.float32).reshape(T, K, D).swapaxes(0, 1)
        if quantized:
            # Per-(token, head) scales: [C, bs, K] -> [K, T, 1].
            ks = ks_buf[slot].astype(jnp.float32).reshape(T, K) \
                .swapaxes(0, 1)[..., None]
            vs = vs_buf[slot].astype(jnp.float32).reshape(T, K) \
                .swapaxes(0, 1)[..., None]
            k = k * ks
            v = v * vs

        # [K, G, D] x [K, T, D] -> [K, G, T]  (batch over kv heads)
        scores = jax.lax.dot_general(
            q, k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        pos = i * T + jax.lax.broadcasted_iota(jnp.int32, (1, 1, T), 2)
        mask = pos < ctx
        if sliding_window is not None:
            mask &= pos > ctx - 1 - sliding_window
        scores = jnp.where(mask, scores, NEG_INF)

        m_new = jnp.maximum(m, jnp.max(scores, axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        # [K, G, T] x [K, T, D] -> [K, G, D]
        pv = jax.lax.dot_general(
            p, v, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )
        return m_new, l_new, acc * alpha + pv

    m0 = jnp.full((K, G, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((K, G, 1), jnp.float32)
    acc0 = jnp.zeros((K, G, D), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, nc, body, (m0, l0, acc0))

    # Padded batch slots have ctx==0 -> l==0; emit zeros, not NaNs (their
    # logits are sliced off on the host, but NaN-free keeps debugging sane).
    l = jnp.where(l == 0.0, 1.0, l)
    o_ref[0] = (acc / l).reshape(K * G, D).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("scale", "sliding_window", "chunk_blocks", "interpret"),
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
    chunk_blocks: int = 8,
    interpret: bool = False,
) -> jax.Array:
    """Decode attention over paged KV, streaming blocks HBM->VMEM.

    ``k_cache``/``v_cache`` may be int8 (data, scale) tuples
    (kv/quant.py): the scale planes stream through the same
    double-buffered pipeline and the dequantize (one VPU multiply per
    element) happens in VMEM — HBM traffic is the int8 bytes plus ~3%
    scales, the whole point of the mode.
    """
    from production_stack_tpu.engine.kv import quant as kv_quant

    quantized = kv_quant.is_quantized(k_cache)
    S, H, D = q.shape
    N, bs, K, _ = kv_quant.cache_shape(k_cache)
    G = H // K
    C = min(chunk_blocks, block_tables.shape[1])
    if D % 128 and not interpret:
        # The DMA slice needs a 128-lane-aligned head_dim on real TPU;
        # dispatch (ops/attention.py) keeps such models on the gather
        # path.  Interpret mode (CPU tests) has no tiling constraint.
        raise ValueError(f"pallas decode kernel requires head_dim%128==0, got {D}")

    kernel = functools.partial(
        _decode_kernel,
        bs=bs,
        chunk_blocks=C,
        num_kv_heads=K,
        q_per_kv=G,
        head_dim=D,
        scale=scale,
        sliding_window=sliding_window,
        quantized=quantized,
    )
    cache_in_specs = [pl.BlockSpec(memory_space=pl.ANY)] * (
        4 if quantized else 2
    )
    scratch = [
        pltpu.VMEM((2, C, bs, K, D),
                   jnp.int8 if quantized else k_cache.dtype),
        pltpu.VMEM((2, C, bs, K, D),
                   jnp.int8 if quantized else v_cache.dtype),
    ]
    if quantized:
        scratch += [
            pltpu.VMEM((2, C, bs, K), k_cache[1].dtype),
            pltpu.VMEM((2, C, bs, K), v_cache[1].dtype),
        ]
    scratch.append(pltpu.SemaphoreType.DMA((4 if quantized else 2, 2, C)))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, H, D), lambda s, *_: (s, 0, 0)),
            *cache_in_specs,  # caches (+ scale planes) stay in HBM
        ],
        out_specs=pl.BlockSpec((1, H, D), lambda s, *_: (s, 0, 0)),
        scratch_shapes=scratch,
    )
    inputs = (
        (q, k_cache[0], v_cache[0], k_cache[1], v_cache[1])
        if quantized else (q, k_cache, v_cache)
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((S, H, D), q.dtype),
        interpret=interpret,
        # What the device trace calls the kernel (%<name>.N on XLA Ops):
        # the benchmark's readers find it by this name.
        name="paged_decode_attention_pallas",
    )(block_tables, ctx_lens, *inputs)
