"""Ulysses-style sequence parallelism: all-to-all head redistribution.

The second sequence-parallel strategy next to ring attention
(ring_attention.py).  Where the ring rotates K/V shards around the mesh
(sp-1 ppermute hops, online-softmax merging), Ulysses performs ONE
all-to-all that re-shards the tensors from sequence-split to head-split —
each device then holds the FULL sequence for a subset of heads and runs
plain (or flash-kernel) attention locally, followed by the inverse
all-to-all.  Trade-offs on TPU:

* ring: O(sp) neighbor hops riding ICI, memory bounded by one KV shard —
  scales to contexts where even one head's full-sequence KV won't fit.
* ulysses: 2 collective phases total and the LOCAL attention is whole —
  so the single-device Pallas flash kernel applies per shard unchanged —
  but each device must hold full-sequence K/V for its head subset, and
  the kv-head count must divide: (num_kv_heads / tp) % sp == 0.

Same mask semantics as ops/attention.py::prefill_attention (causal over
cached prefix + new tokens, validity bounds); selected via
``ParallelConfig.sequence_parallel_mode = "ulysses"``.

The reference stack has no sequence parallelism at all (SURVEY.md section
2.7); both strategies here are TPU-native capability on top of parity.
"""

from __future__ import annotations

from typing import Optional

import jax
from jax import lax

from production_stack_tpu.engine.ops.attention import (
    prefill_attention,
    prefix_as_pool,
)


def _seq_to_heads(x: jax.Array, axis_name: str) -> jax.Array:
    """[Tl, h, D] sequence-sharded -> [T, h/sp, D] head-sharded.

    tiled all-to-all keeps chunk order, so row i*Tl+t is global position
    i*Tl+t — consecutive positions, which is what the dense attention's
    position math assumes."""
    return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=0, tiled=True)


def _heads_to_seq(x: jax.Array, axis_name: str) -> jax.Array:
    """Inverse: [T, h/sp, D] -> [Tl, h, D]."""
    return lax.all_to_all(x, axis_name, split_axis=0, concat_axis=1, tiled=True)


def ulysses_prefill_with_prefix(
    q: jax.Array,  # [Tl, H, D] local query shard (new tokens)
    k: jax.Array,  # [Tl, K, D] local key shard (new tokens)
    v: jax.Array,  # [Tl, K, D]
    k_prefix: jax.Array,  # [Cl, K, D] local shard of the cached prefix
    v_prefix: jax.Array,  # [Cl, K, D]
    cached_len: jax.Array,  # scalar int32: valid prefix tokens (global)
    valid_len: jax.Array,  # scalar int32: valid new tokens (global)
    *,
    axis_name: str,
    scale: float,
    sliding_window: Optional[int] = None,
) -> jax.Array:
    """Sequence-parallel prefill attention via head redistribution; the
    sp>1 Ulysses counterpart of prefill_attention, called inside
    ``shard_map`` by models/llama.py.

    GQA alignment: the head axis is split into sp contiguous chunks, so q
    chunk j covers query-head groups [j*K/sp, (j+1)*K/sp) — exactly the
    kv heads in kv chunk j — provided K % sp == 0 (validated at engine
    startup, parallel/shardings.py)."""
    q_full = _seq_to_heads(q, axis_name)  # [T, H/sp, D]
    k_full = _seq_to_heads(k, axis_name)  # [T, K/sp, D]
    v_full = _seq_to_heads(v, axis_name)
    kp_full = _seq_to_heads(k_prefix, axis_name)  # [C, K/sp, D]
    vp_full = _seq_to_heads(v_prefix, axis_name)

    # Full-sequence attention on the local head subset; single-device
    # dispatch applies (Pallas flash kernel on TPU, dense elsewhere).  The
    # redistributed prefix is a pool of its own: pages in order.
    out_full = prefill_attention(
        q_full, k_full, v_full, *prefix_as_pool(kp_full, vp_full),
        cached_len, valid_len, scale=scale, sliding_window=sliding_window,
    )
    return _heads_to_seq(out_full, axis_name)  # [Tl, H, D]
