"""Pallas TPU kernels for the gated delta rule: a head's ``[Dk, Dv]`` float32
state ``S`` (key channel by value channel; ``models/solar_kda.py``: square, a
decay a key channel; ``models/olmo_hybrid.py``: 96 x 192, a decay a head) under

    ``S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T``,
    ``o_t = S_t^T q_t``.

``g`` is ``[T, H, Dk]`` (a channel) or ``[T, H]`` (a head: ``Diag(exp g)`` a
multiple of the identity).

**``kda_decode_pallas``: one token a row.**  The state pool is aliased in and
out; a grid step owns ``HEADS`` heads of one row's slot (the slot from the
scalar-prefetched ``slots``), so a row's state is read once and written once,
in place, and nothing else of the pool moves.  With ``S' = Diag(e^g) S``:
``S_t = S' + (beta k)(v - S'^T k)^T`` and ``o = S_t^T q`` are elementwise
products and reductions over the key channel, which lies on the sublanes: what
multiplies along it (``e^g``, ``k``, ``beta k``, ``q``) is handed as columns,
``[Dk, 4 HEADS]`` a block, made by XLA (a decay a head is one number down its
column: 4 Dk bytes a head a row, nothing beside the state); ``v`` and ``o`` are
rows.  A dead row (``beta`` 0, ``g`` 0) writes back the bits it read.  A grid
step's heads are :func:`head_block` of the head count, the largest block up to
``HEADS`` that divides it (30 heads: 15), so every head is computed; a count
no block of two or more divides is an error.

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

A decay a channel over a state that is not square is refused there: the
transposed state is carried in the state's own output block, and no
configuration asks.

**``gdn_prefill_pallas``: the chunkwise form under a decay a head.**  The
same grid and the same pseudo-values, but a pair of tokens' decay
``e^{G_t - G_s}`` is ONE number, so ``A = (beta k k^T) . e^{G_t - G_s}`` and
``B`` likewise take a ``[CHUNK, CHUNK]`` matrix of ratios (every exponent
<= 0: exact whatever ``g`` is, where the form above refers both factors to the
chunk's start and spans ``e^+-27``), no ``k e^{-G}`` a channel exists, and the
state is a scalar multiple a chunk: it stays ``[k, v]`` as the pool keeps it
from the load to the store, no transpose anywhere.  ``G`` as a column's and a
row's broadcast are two ``[CHUNK, CHUNK]`` products of ``g`` with triangles of
ones.

The kernels are named for the device trace (``kda_decode_pallas``,
``kda_prefill_pallas``, ``gdn_prefill_pallas``): the benchmark's readers find
them by these names.
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
                   *absmax_ref, heads: int):
    del slots_ref   # read by the index maps
    for i in range(heads):
        col = lambda j: cols_ref[0, 0, :, j * heads + i:j * heads + i + 1]
        S = col(0) * state_ref[0, i]                         # Diag(e^g) S
        u = v_ref[0, 0, i:i + 1, :] - jnp.sum(col(1) * S, axis=0,
                                              keepdims=True)
        S = S + col(2) * u
        out_ref[0, i] = S
        o_ref[0, 0, i:i + 1, :] = jnp.sum(col(3) * S, axis=0, keepdims=True)
        if absmax_ref:
            absmax_ref[0][0, 0, i:i + 1, :] = jnp.max(
                jnp.abs(S), axis=0, keepdims=True)


def head_block(num_heads: int, heads: int = HEADS) -> int:
    """Heads a grid step of the decode kernel owns: the largest count up to
    ``heads`` that divides ``num_heads`` (64: 16; 30: 15), so that the blocks
    cover every head.  A count that only 1 divides (a prime over ``heads``) is
    an error, not a floor that drops the heads past the last whole block."""
    for hb in range(min(heads, num_heads), 0, -1):
        if num_heads % hb == 0 and (hb > 1 or num_heads == 1):
            return hb
    raise ValueError(
        f"kda_decode_pallas: no block of 2 to {heads} heads divides "
        f"{num_heads} heads")


@functools.partial(jax.jit, static_argnames=("absmax", "interpret"))
def kda_decode_pallas(q, k, v, g, beta, state, slots, *,
                      absmax: bool = False, interpret: bool = False):
    """``q, k`` [R, H, Dk] and ``v`` [R, H, Dv] float32, ``g`` [R, H, Dk] or
    [R, H], ``beta`` [R, H], ``state`` [slots, H, Dk, Dv] float32, ``slots``
    [R] int32 -> (o [R, H, Dv], the pool with each row's slot advanced one
    token).  Rows that share a slot must be dead rows.  With ``absmax`` one
    more result after ``o``: the largest ``|S|`` down each value channel of the
    states written, [R, H, Dv] (a counter's, ``models/olmo_hybrid.py``)."""
    R, H, D = q.shape
    Dv = v.shape[-1]
    hb = head_block(H)
    nb = H // hb
    decay = jnp.exp(g)
    if g.ndim == 2:
        decay = jnp.broadcast_to(decay[..., None], k.shape)
    # [R, nb, D, 4 hb]: e^g, k, beta k, q of a block's heads, as columns.
    cols = jnp.stack([decay, k, beta[..., None] * k, q], axis=1)
    cols = cols.reshape(R, 4, nb, hb, D).transpose(0, 2, 4, 1, 3).reshape(
        R, nb, D, 4 * hb)
    pool = pl.BlockSpec((1, hb, D, Dv), lambda r, b, s: (s[r], b, 0, 0))
    # v and o a block of heads: [R, nb, hb, Dv], so that a block's last two
    # dimensions are the array's whatever hb is (15 is no multiple of 8).
    rows = pl.BlockSpec((1, 1, hb, Dv), lambda r, b, s: (r, b, 0, 0))
    o, state, *most = pl.pallas_call(
        functools.partial(_decode_kernel, heads=hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(R, nb),
            in_specs=[
                pl.BlockSpec((1, 1, D, 4 * hb), lambda r, b, s: (r, b, 0, 0)),
                rows, pool],
            out_specs=[rows, pool] + [rows] * absmax),
        out_shape=[jax.ShapeDtypeStruct((R, nb, hb, Dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)]
        + [jax.ShapeDtypeStruct((R, nb, hb, Dv), jnp.float32)] * absmax,
        # Operand 3 (after the prefetched slots, the columns and v) is the
        # pool; it is result 1.
        input_output_aliases={3: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="kda_decode_pallas",
    )(slots.astype(jnp.int32), cols, v.reshape(R, nb, hb, Dv), state)
    return (o.reshape(R, H, Dv), *(m.reshape(R, H, Dv) for m in most),
            state)


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
    if v.shape[-1] != D or g.ndim != 3:
        raise ValueError(
            "kda_prefill_pallas: a decay a channel over a square state; a "
            "decay a head (any state) is gdn_prefill_pallas")
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


def _gdn_prefill_kernel(snap_at_ref, q_ref, k_ref, kb_ref, vb_ref, g_ref,
                        s0_ref, o_ref, s1_ref, snap_ref, *, tile: int,
                        chunk: int):
    t = pl.program_id(1)
    C = chunk

    @pl.when(t == 0)
    def _():
        s1_ref[0] = s0_ref[0]
        snap_ref[0] = s0_ref[0]

    row = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    column = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    below = row > column
    upto = (row >= column).astype(jnp.float32)
    until = (row <= column).astype(jnp.float32)
    eye = (row == column).astype(jnp.float32)
    down = jnp.ones((s0_ref.shape[1], C), jnp.float32)
    snap_at = snap_at_ref[0]

    def step(c, carry):
        S, snap = carry                        # the state as it lies, [k, v]
        at = pl.multiple_of(c * C, C)
        snap = jnp.where(t * tile + at == snap_at, S, snap)
        rows = pl.ds(at, C)
        qc, kc, kbc = q_ref[0, rows, :], k_ref[0, rows, :], kb_ref[0, rows, :]
        gb = jnp.broadcast_to(g_ref[0, rows, :], (C, C))   # [r, .] = g_r
        Gt = _nn(upto, gb)                     # [t, .] = G_t
        Gs = _tn(gb, until)                    # [., s] = G_s
        ratio = jnp.where(row >= column, jnp.exp(jnp.minimum(Gt - Gs, 0.0)),
                          0.0)                 # e^{G_t - G_s}, s <= t
        eG = jnp.exp(Gt[:, :1])
        X = jnp.where(below, -_nt(kbc, kc) * ratio, 0.0)
        inv, power = eye + X, X
        m = 2
        while m < C:
            power = _nn(power, power)
            inv = inv + _nn(inv, power)
            m *= 2
        U = _nn(inv, vb_ref[0, rows, :] - _nn(kbc * eG, S))
        o_ref[0, rows, :] = _nn(qc * eG, S) + _nn(_nt(qc, kc) * ratio, U)
        # G_C as a column down the key channels (Mosaic broadcasts along
        # the lanes or the sublanes, not a [1, 1] along both).
        whole = jnp.exp(_nn(down, g_ref[0, rows, :]))
        S = S * whole + _tn(kc * jnp.exp(Gt[C - 1:C, :1] - Gt[:, :1]), U)
        return S, snap

    S, snap = jax.lax.fori_loop(
        0, tile // C, step, (s1_ref[0], snap_ref[0]))
    s1_ref[0] = S
    snap_ref[0] = snap


@functools.partial(jax.jit, static_argnames=("interpret",))
def gdn_prefill_pallas(q, k, v, g, beta, s0, snapshot_len=None, *,
                       interpret: bool = False):
    """``q, k`` [T, H, Dk] and ``v`` [T, H, Dv] float32 (``q`` scaled), ``g``
    [T, H] (the log decay a head), ``beta`` [T, H], ``s0`` [H, Dk, Dv] ->
    (o [T, H, Dv], the state after T tokens, the state after ``snapshot_len``
    tokens or None), as ``solar_kda.kda_chunk_plain`` under a decay a head."""
    T, H, Dk = q.shape
    Dv = v.shape[-1]
    tile = min(TILE, T)
    heads = lambda a: a.transpose(1, 0, 2)                  # [H, T, .]
    b = beta[..., None]
    keys = pl.BlockSpec((1, tile, Dk), lambda h, t, s: (h, t, 0))
    values = pl.BlockSpec((1, tile, Dv), lambda h, t, s: (h, t, 0))
    whole = pl.BlockSpec((1, Dk, Dv), lambda h, t, s: (h, 0, 0))
    snap_at = jnp.full((1,), -1 if snapshot_len is None else snapshot_len,
                       jnp.int32)
    o, s1, snap = pl.pallas_call(
        functools.partial(_gdn_prefill_kernel, tile=tile, chunk=CHUNK),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(H, T // tile),
            in_specs=[keys, keys, keys, values,
                      pl.BlockSpec((1, tile, 1), lambda h, t, s: (h, t, 0)),
                      whole],
            out_specs=[values, whole, whole]),
        out_shape=[jax.ShapeDtypeStruct((H, T, Dv), jnp.float32),
                   jax.ShapeDtypeStruct((H, Dk, Dv), jnp.float32),
                   jax.ShapeDtypeStruct((H, Dk, Dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="gdn_prefill_pallas",
    )(snap_at, heads(q), heads(k), heads(b * k), heads(b * v),
      heads(g[..., None]), s0)
    return (o.transpose(1, 0, 2), s1,
            None if snapshot_len is None else snap)
