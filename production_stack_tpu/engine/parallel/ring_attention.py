"""Ring attention: causal self-attention sharded over the sequence axis.

Long-context prefill that exceeds one chip's HBM runs with the sequence
split over the ``sp`` mesh axis: each device keeps its query shard resident
while K/V shards rotate around the ring via ``lax.ppermute`` (ICI
neighbor-to-neighbor), accumulating with an online-softmax (flash-style
log-sum-exp merge).  Compute on the current shard overlaps the transfer of
the next — XLA pipelines the ppermute with the einsum.

The reference stack has no sequence parallelism anywhere (SURVEY.md section
2.7: long context is handled purely by KV offload); this is a TPU-native
capability on top of parity.

Called inside ``shard_map`` over the mesh, e.g.:

    out = shard_map(
        lambda q, k, v: ring_self_attention(q, k, v, axis_name="sp", scale=s),
        mesh=mesh,
        in_specs=(P("sp", None, None),) * 3,
        out_specs=P("sp", None, None),
    )(q, k, v)
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _chunk_attention(
    q: jax.Array,  # [Tq, H, D]
    k: jax.Array,  # [Tk, K, D]
    v: jax.Array,  # [Tk, K, D]
    q_pos: jax.Array,  # [Tq] global positions
    k_pos: jax.Array,  # [Tk] global positions
    scale: float,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Partial attention of one KV chunk: returns (scores_max, exp_sum,
    weighted_values) for online-softmax merging.  Shapes:
    m [H, Tq], l [H, Tq], o [Tq, H, D]."""
    Tq, H, D = q.shape
    K = k.shape[1]
    G = H // K
    qg = q.reshape(Tq, K, G, D)
    scores = jnp.einsum("tkgd,skd->kgts", qg, k, preferred_element_type=jnp.float32)
    scores = scores * scale  # [K, G, Tq, Tk]
    mask = k_pos[None, None, None, :] <= q_pos[None, None, :, None]
    scores = jnp.where(mask, scores, NEG_INF)
    m = jnp.max(scores, axis=-1)  # [K, G, Tq]
    # Guard fully-masked rows: exp(NEG_INF - NEG_INF) would be 1.
    safe_m = jnp.maximum(m, -1e29)
    p = jnp.exp(scores - safe_m[..., None])  # [K, G, Tq, Tk]
    p = jnp.where(mask, p, 0.0)
    l = jnp.sum(p, axis=-1)  # [K, G, Tq]
    o = jnp.einsum(
        "kgts,skd->tkgd", p.astype(v.dtype), v, preferred_element_type=jnp.float32
    )  # [Tq, K, G, D]
    return m, l, o.astype(jnp.float32)


def _merge_partials(m_acc, l_acc, o_acc, m_new, l_new, o_new):
    """Online-softmax merge of two partial-attention accumulators."""
    K, G, Tl = m_acc.shape
    _, H, D = o_acc.shape
    m_tot = jnp.maximum(m_acc, m_new)
    safe = jnp.maximum(m_tot, -1e29)
    alpha = jnp.exp(m_acc - safe)  # [K, G, Tq]
    beta = jnp.exp(m_new - safe)
    l_tot = l_acc * alpha + l_new * beta
    o_scale_old = alpha.transpose(2, 0, 1)[..., None]  # [Tq, K, G, 1]
    o_scale_new = beta.transpose(2, 0, 1)[..., None]
    o_tot = (
        o_acc.reshape(Tl, K, G, D) * o_scale_old
        + o_new.reshape(Tl, K, G, D) * o_scale_new
    ).reshape(Tl, H, D)
    return m_tot, l_tot, o_tot


def _ring_partials(
    q, k, v, q_pos, *, axis_name, scale, valid_len, key_pos_base, init
):
    """Run one ring: rotate K/V shards via ppermute, accumulating partial
    attention against ``q`` with online softmax.  ``key_pos_base`` is the
    global position of the ring's first key (shard s holds keys at
    key_pos_base + s*Tk + arange(Tk)); ``valid_len`` counts valid keys
    within the ring; ``init`` seeds the accumulator (e.g. with a previous
    ring's partials).  Returns unnormalized (m, l, o)."""
    Tl, H, D = q.shape
    Tk = k.shape[0]
    # lax.axis_size is jax>=0.5; psum of 1 over the axis is the portable
    # spelling (constant-folded at trace time).
    sp = getattr(lax, "axis_size", lambda a: lax.psum(1, a))(axis_name)
    my_idx = lax.axis_index(axis_name)

    def body(step, carry):
        m_acc, l_acc, o_acc, k_cur, v_cur = carry
        src_idx = (my_idx - step) % sp  # whose shard we currently hold
        local_idx = src_idx * Tk + jnp.arange(Tk)
        k_pos = key_pos_base + local_idx
        if valid_len is not None:
            k_pos = jnp.where(local_idx < valid_len, k_pos, jnp.int32(2**30))
        m_new, l_new, o_new = _chunk_attention(q, k_cur, v_cur, q_pos, k_pos, scale)
        m_tot, l_tot, o_tot = _merge_partials(
            m_acc, l_acc, o_acc, m_new, l_new, o_new.reshape(Tl, H, D)
        )
        # Rotate K/V to the next device; compute on the current shard
        # overlaps the transfer of the next.
        perm = [(i, (i + 1) % sp) for i in range(sp)]
        k_next = lax.ppermute(k_cur, axis_name, perm)
        v_next = lax.ppermute(v_cur, axis_name, perm)
        return m_tot, l_tot, o_tot, k_next, v_next

    m_f, l_f, o_f, _, _ = lax.fori_loop(0, sp, body, (*init, k, v))
    return m_f, l_f, o_f


def _normalize(q, l_f, o_f):
    Tl, H, D = q.shape
    K, G, _ = l_f.shape
    denom = jnp.maximum(l_f, 1e-20).transpose(2, 0, 1)[..., None]  # [Tq, K, G, 1]
    out = o_f.reshape(Tl, K, G, D) / denom
    return out.reshape(Tl, H, D).astype(q.dtype)


def ring_self_attention(
    q: jax.Array,  # [Tl, H, D] local query shard
    k: jax.Array,  # [Tl, K, D] local key shard
    v: jax.Array,  # [Tl, K, D] local value shard
    *,
    axis_name: str,
    scale: float,
    valid_len: Optional[jax.Array] = None,  # global valid token count
) -> jax.Array:
    """Causal self-attention with K/V rotating around the ring."""
    Tl, H, D = q.shape
    K = k.shape[1]
    G = H // K
    my_idx = lax.axis_index(axis_name)

    q_pos = my_idx * Tl + jnp.arange(Tl)
    if valid_len is not None:
        # Mask padded queries by pushing their positions before all keys.
        q_pos = jnp.where(q_pos < valid_len, q_pos, -1)

    init = (
        jnp.full((K, G, Tl), NEG_INF, jnp.float32),
        jnp.zeros((K, G, Tl), jnp.float32),
        jnp.zeros((Tl, H, D), jnp.float32),
    )
    _, l_f, o_f = _ring_partials(
        q, k, v, q_pos,
        axis_name=axis_name, scale=scale, valid_len=valid_len,
        key_pos_base=jnp.int32(0), init=init,
    )
    return _normalize(q, l_f, o_f)


def ring_prefill_with_prefix(
    q: jax.Array,  # [Tl, H, D] local query shard (new tokens)
    k: jax.Array,  # [Tl, K, D] local key shard (new tokens)
    v: jax.Array,  # [Tl, K, D] local value shard
    k_prefix: jax.Array,  # [Cl, K, D] local shard of the cached prefix
    v_prefix: jax.Array,  # [Cl, K, D]
    cached_len: jax.Array,  # scalar int32: valid prefix tokens (global)
    valid_len: jax.Array,  # scalar int32: valid new tokens (global)
    *,
    axis_name: str,
    scale: float,
) -> jax.Array:
    """Sequence-parallel paged prefill attention: queries attend to the
    cached prefix plus all causally-visible new tokens.  BOTH the prefix
    and the new tokens' K/V are sharded over the sp ring (no device holds
    the full prefix — at max_model_len-sized prefixes a replicated prefix
    would reintroduce exactly the memory wall the ring avoids), rotating
    via ppermute in two chained rings that share one online-softmax
    accumulator.  This is the sp>1 counterpart of
    ops/attention.py::prefill_attention (same mask semantics), called
    inside ``shard_map`` by models/llama.py when the engine mesh has an sp
    axis."""
    Tl, H, D = q.shape
    K = k.shape[1]
    G = H // K
    my_idx = lax.axis_index(axis_name)

    local_new_idx = my_idx * Tl + jnp.arange(Tl)  # index among new tokens
    q_pos = cached_len + local_new_idx
    # Padded queries (beyond valid_len) attend to nothing; their rows are
    # never read (engine samples from position valid_len-1).
    q_pos = jnp.where(local_new_idx < valid_len, q_pos, -1)

    # Ring 1: the cached prefix (global positions 0..cached_len; shard s
    # holds prefix tokens s*Cl..(s+1)*Cl).
    init = (
        jnp.full((K, G, Tl), NEG_INF, jnp.float32),
        jnp.zeros((K, G, Tl), jnp.float32),
        jnp.zeros((Tl, H, D), jnp.float32),
    )
    init = _ring_partials(
        q, k_prefix, v_prefix, q_pos,
        axis_name=axis_name, scale=scale, valid_len=cached_len,
        key_pos_base=jnp.int32(0), init=init,
    )

    # Ring 2: the new tokens' K/V shards (positions cached_len + i).
    _, l_f, o_f = _ring_partials(
        q, k, v, q_pos,
        axis_name=axis_name, scale=scale, valid_len=valid_len,
        key_pos_base=cached_len, init=init,
    )
    return _normalize(q, l_f, o_f)


def ring_prefill_attention(mesh, q, k, v, *, scale: float, valid_len=None):
    """Convenience wrapper: shard T over the sp axis and run the ring."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from production_stack_tpu.engine.parallel.mesh import AXES

    fn = lambda q_, k_, v_: ring_self_attention(  # noqa: E731
        q_, k_, v_, axis_name=AXES.SP, scale=scale, valid_len=valid_len
    )
    return shard_map(
        fn,
        mesh=mesh,
        in_specs=(P(AXES.SP), P(AXES.SP), P(AXES.SP)),
        out_specs=P(AXES.SP),
        check_vma=False,
    )(q, k, v)
