"""Pallas TPU kernels for the gated delta rule with a decay a channel
(``models/solar_kda.py``): a head's ``[D, D]`` float32 state ``S`` (key
channel by value channel) under

    ``S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T``,
    ``o_t = S_t^T q_t``.

**``kda_decode_pallas``: one token a row.**  The state pool is aliased in and
out; a grid step owns ``HEADS`` heads of one row's slot (the slot from the
scalar-prefetched ``slots``), so a row's state is read once and written once,
in place, and nothing else of the pool moves.  With ``S' = Diag(e^g) S``:
``S_t = S' + (beta k)(v - S'^T k)^T`` and ``o = S_t^T q`` are elementwise
products and reductions over the key channel, which lies on the sublanes: what
multiplies along it (``e^g``, ``k``, ``beta k``, ``q``) is handed as columns,
``[D, 4 HEADS]`` a block, made by XLA; ``v`` and ``o`` are rows.  A dead row
(``beta`` 0, ``g`` 0) writes back the bits it read.

**``kda_prefill_pallas``: the chunkwise form over one sequence.**  Grid (head,
token tile); a head's state stays in VMEM over all tiles of the call (the
output block of the state does not move along the tile axis) and is read and
written once.  Inside a tile, ``CHUNK`` tokens at a time, the algebra of
``solar_kda.kda_chunk_plain``, on the transposed state ``S^T`` so that every
product but one contracts the lanes of both operands (the state comes in and
goes out as the pool keeps it, ``[k, v]``: the kernel transposes a head's tile
in VMEM at the first tile's load and the last tile's store -- a transpose left
to XLA beside the scatter into the pool is folded into the *pool's* layout,
two copies of the whole pool a layer): the pseudo-values
``U = (I + A)^-1 (beta v - (beta k e^G) S_0)``, ``A`` strictly lower
triangular, its inverse the product ``(I - A)(I + A^2)(I + A^4)(I + A^8)``.
The state after ``snapshot_len`` tokens (a multiple of ``CHUNK``; negative:
none) is kept beside the last.  Float32 throughout, ``HIGHEST`` products: the
decays are referred to a chunk's start, so the operands span ``e^+-27`` at the
seeded gates and a bf16 pass would round them to 2^-8.

Both kernels are named for the device trace (``kda_decode_pallas``,
``kda_prefill_pallas``): the benchmark's readers find them by these names.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 16     # tokens a step of the chunkwise form
TILE = 256     # tokens a grid step of the prefill kernel
HEADS = 16     # heads a grid step of the decode kernel

_HI = jax.lax.Precision.HIGHEST


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                               preferred_element_type=jnp.float32)


def _nn(a, b):   # a @ b
    return _dot(a, b, ((1,), (0,)))


def _nt(a, b):   # a @ b^T
    return _dot(a, b, ((1,), (1,)))


def _tn(a, b):   # a^T @ b
    return _dot(a, b, ((0,), (0,)))


# -- decode ------------------------------------------------------------------


def _decode_kernel(slots_ref, cols_ref, v_ref, state_ref, o_ref, out_ref,
                   *, heads: int):
    del slots_ref   # read by the index maps
    for i in range(heads):
        col = lambda j: cols_ref[0, 0, :, j * heads + i:j * heads + i + 1]
        S = col(0) * state_ref[0, i]                         # Diag(e^g) S
        u = v_ref[0, i:i + 1, :] - jnp.sum(col(1) * S, axis=0, keepdims=True)
        S = S + col(2) * u
        out_ref[0, i] = S
        o_ref[0, i:i + 1, :] = jnp.sum(col(3) * S, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_decode_pallas(q, k, v, g, beta, state, slots, *,
                      interpret: bool = False):
    """``q, k, v, g`` [R, H, D] float32, ``beta`` [R, H], ``state`` [slots, H,
    D, D] float32, ``slots`` [R] int32 -> (o [R, H, D], the pool
    with each row's slot advanced one token).  Rows that share a slot must be
    dead rows."""
    R, H, D = q.shape
    hb = min(HEADS, H)
    nb = H // hb
    # [R, nb, D, 4 hb]: e^g, k, beta k, q of a block's heads, as columns.
    cols = jnp.stack([jnp.exp(g), k, beta[..., None] * k, q], axis=1)
    cols = cols.reshape(R, 4, nb, hb, D).transpose(0, 2, 4, 1, 3).reshape(
        R, nb, D, 4 * hb)
    pool = pl.BlockSpec((1, hb, D, D), lambda r, b, s: (s[r], b, 0, 0))
    rows = pl.BlockSpec((1, hb, D), lambda r, b, s: (r, b, 0))
    o, state = pl.pallas_call(
        functools.partial(_decode_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(R, nb),
            in_specs=[
                pl.BlockSpec((1, 1, D, 4 * hb), lambda r, b, s: (r, b, 0, 0)),
                rows, pool],
            out_specs=[rows, pool]),
        out_shape=[jax.ShapeDtypeStruct((R, H, D), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        # Operand 3 (after the prefetched slots, the columns and v) is the
        # pool; it is result 1.
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="kda_decode_pallas",
    )(slots.astype(jnp.int32), cols, v, state)
    return o, state


# -- prefill -----------------------------------------------------------------


def _prefill_kernel(snap_at_ref, q_ref, k_ref, kb_ref, vb_ref, g_ref, s0_ref,
                    o_ref, s1_ref, snap_ref, *, tile: int, chunk: int):
    t = pl.program_id(1)
    C = chunk

    @pl.when(t == 0)
    def _():
        s1_ref[0] = s0_ref[0].T
        snap_ref[0] = s0_ref[0].T

    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    column = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    below = row > column
    upto = (row >= column).astype(jnp.float32)
    eye = (row == column).astype(jnp.float32)
    snap_at = snap_at_ref[0]

    def step(c, carry):
        ST, snap = carry                       # the state, transposed [v, k]
        at = pl.multiple_of(c * C, C)
        snap = jnp.where(t * tile + at == snap_at, ST, snap)
        rows = pl.ds(at, C)
        qc, kc, kbc = q_ref[0, rows, :], k_ref[0, rows, :], kb_ref[0, rows, :]
        G = _nn(upto, g_ref[0, rows, :])       # the running sum of g
        eG = jnp.exp(G)
        kn, kbt, qt = kc * jnp.exp(-G), kbc * eG, qc * eG
        X = jnp.where(below, -_nt(kbt, kn), 0.0)
        inv, power = eye + X, X
        m = 2
        while m < C:
            power = _nn(power, power)
            inv = inv + _nn(inv, power)
            m *= 2
        U = _nn(inv, vb_ref[0, rows, :] - _nt(kbt, ST))
        B = jnp.where(row >= column, _nt(qt, kn), 0.0)
        o_ref[0, rows, :] = _nt(qt, ST) + _nn(B, U)
        last = G[C - 1:C, :]
        ST = ST * jnp.exp(last) + _tn(U, kc * jnp.exp(last - G))
        return ST, snap

    ST, snap = jax.lax.fori_loop(
        0, tile // C, step, (s1_ref[0], snap_ref[0]))
    s1_ref[0] = ST
    snap_ref[0] = snap

    @pl.when(t == pl.num_programs(1) - 1)
    def _():
        s1_ref[0] = ST.T
        snap_ref[0] = snap.T


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_prefill_pallas(q, k, v, g, beta, s0, snapshot_len=None, *,
                       interpret: bool = False):
    """``q, k, v, g`` [T, H, D] float32 (``q`` scaled, ``g`` the log decay),
    ``beta`` [T, H], ``s0`` [H, D, D] -> (o [T, H, D], the state after T
    tokens, the state after ``snapshot_len`` tokens or None), as
    ``solar_kda.kda_chunk_plain``."""
    T, H, D = q.shape
    tile = min(TILE, T)
    heads = lambda a: a.transpose(1, 0, 2)                  # [H, T, D]
    b = beta[..., None]
    tokens = pl.BlockSpec((1, tile, D), lambda h, t, s: (h, t, 0))
    whole = pl.BlockSpec((1, D, D), lambda h, t, s: (h, 0, 0))
    snap_at = jnp.full((1,), -1 if snapshot_len is None else snapshot_len,
                       jnp.int32)
    o, s1, snap = pl.pallas_call(
        functools.partial(_prefill_kernel, tile=tile, chunk=CHUNK),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(H, T // tile),
            in_specs=[tokens] * 5 + [whole],
            out_specs=[tokens, whole, whole]),
        out_shape=[jax.ShapeDtypeStruct((H, T, D), jnp.float32),
                   jax.ShapeDtypeStruct((H, D, D), jnp.float32),
                   jax.ShapeDtypeStruct((H, D, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="kda_prefill_pallas",
    )(snap_at, heads(q), heads(k), heads(b * k), heads(b * v), heads(g), s0)
    return (o.transpose(1, 0, 2), s1,
            None if snapshot_len is None else snap)
