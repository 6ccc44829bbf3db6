"""Pallas TPU kernels for the selective state-space recurrence (Mamba-1,
``models/jamba.py``): a sequence's state ``h`` ``[N, Di]`` float32, the ``N``
states of a channel on the sublanes and the ``Di`` channels on the lanes, under

    ``h_t = exp(dt_t (x) A) . h_{t-1} + (dt_t . c_t) (x) B_t``,
    ``y_t = (h_t^T C_t + D . c_t) . SiLU(z_t)``,        ``A = -exp(A_log)``

with ``dt_t``, ``c_t``, ``z_t`` rows over the channels and ``B_t``, ``C_t``
columns over the states.  A token whose ``dt`` is 0 is the identity on the
state (``exp(0) = 1``, nothing added): a padded slot, a dead row.

**``ssm_prefill_pallas``: the scan over one sequence's chunk.**  Grid (channel
block, token tile); a block's ``[N, LANES]`` state stays in VMEM, and in
registers inside a tile, over all the tiles of the call (the state's output
block does not move along the tile axis) and is read and written once: no
``[T, Di, N]`` array of decays or of states exists anywhere.  Tokens go
``STEP`` at a time: their ``dt``, ``c``, ``z`` rows are one aligned tile of
sublanes, their ``B`` and ``C`` columns one ``[N, STEP]`` block that XLA laid
out (``[T / STEP, N, STEP]``), so that a token's column is a static lane.  The
state after ``snapshot_len`` tokens (a multiple of ``STEP``; negative: none)
is kept beside the last.

**``ssm_decode_pallas``: one token a row.**  The state pool is aliased in and
out; a grid step owns one row's slot of one layer (the slot from the
scalar-prefetched ``slots``), so a row's state is read once and written once,
in place, and nothing else of the pool moves.  Beside ``y`` it hands back the
largest ``|h|`` a channel was left with, which the module's counters read
without a second pass over the pool.

Float32 throughout; everything runs on the vector unit and the EUP (one
``exp`` a state element a token), nothing on the MXU.  Both kernels are named
for the device trace (``ssm_prefill_pallas``, ``ssm_decode_pallas``): the
benchmark's readers find them by these names.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

STEP = 8       # tokens a step of the prefill kernel's loop: one sublane tile
TILE = 256     # tokens a grid step of the prefill kernel
LANES = 512    # channels a grid step of the prefill kernel, a pass of decode


def _advance(h, A, dt, c, B):
    """One token: ``h`` [N, L], ``A`` [N, L], ``dt``, ``c`` [1, L], ``B``
    [N, 1] -> the new state."""
    return jnp.exp(dt * A) * h + (dt * c) * B


def _gated(h, C, skip, c, z):
    """``(h^T C + D . c) . SiLU(z)`` -> [1, L]."""
    y = jnp.sum(h * C, axis=0, keepdims=True) + skip * c
    return y * (z * jax.nn.sigmoid(z))


# -- prefill -----------------------------------------------------------------


def _prefill_kernel(snap_at_ref, c_ref, dt_ref, z_ref, b_ref, cc_ref, alog_ref,
                    skip_ref, s0_ref, y_ref, s1_ref, snap_ref, *, tile: int):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _():
        s1_ref[...] = s0_ref[...]
        snap_ref[...] = s0_ref[...]

    A = -jnp.exp(alog_ref[...])
    skip = skip_ref[...]
    snap_at = snap_at_ref[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (STEP, 1), 0)

    def step(i, h):
        at = pl.multiple_of(i * STEP, STEP)

        @pl.when(t * tile + at == snap_at)
        def _():
            snap_ref[...] = h

        rows = pl.ds(at, STEP)
        cs, dts, zs = c_ref[rows, :], dt_ref[rows, :], z_ref[rows, :]
        Bs, Cs = b_ref[i], cc_ref[i]                      # [N, STEP]
        y = jnp.zeros_like(cs)
        for j in range(STEP):
            one = slice(j, j + 1)
            h = _advance(h, A, dts[one], cs[one], Bs[:, one])
            y = jnp.where(row == j,
                          _gated(h, Cs[:, one], skip, cs[one], zs[one]), y)
        y_ref[rows, :] = y
        return h

    s1_ref[...] = jax.lax.fori_loop(0, tile // STEP, step, s1_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_prefill_pallas(c, dt, z, B, C, A_log, skip, s0, snapshot_len=None, *,
                       interpret: bool = False):
    """``c, dt, z`` [T, Di] float32 (``dt`` after the softplus, 0 where a slot
    is padding), ``B, C`` [T, N], ``A_log`` [N, Di], ``skip`` [Di], ``s0``
    [N, Di] -> (y [T, Di], the state after T tokens, the state after
    ``snapshot_len`` tokens or None), as ``jamba.ssm_scan_plain``."""
    T, Di = c.shape
    N = B.shape[1]
    tile, lanes = min(TILE, T), min(LANES, Di)
    # [T / STEP, N, STEP]: a step's columns, a token a lane.
    cols = lambda a: a.reshape(T // STEP, STEP, N).transpose(0, 2, 1)
    tokens = pl.BlockSpec((tile, lanes), lambda b, t, s: (t, b))
    columns = pl.BlockSpec((tile // STEP, N, STEP), lambda b, t, s: (t, 0, 0))
    whole = pl.BlockSpec((N, lanes), lambda b, t, s: (0, b))
    snap_at = jnp.full((1,), -1 if snapshot_len is None else snapshot_len,
                       jnp.int32)
    y, s1, snap = pl.pallas_call(
        functools.partial(_prefill_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(Di // lanes, T // tile),
            in_specs=[tokens] * 3 + [columns] * 2 + [
                whole, pl.BlockSpec((1, lanes), lambda b, t, s: (0, b)), whole],
            out_specs=[tokens, whole, whole]),
        out_shape=[jax.ShapeDtypeStruct((T, Di), jnp.float32),
                   jax.ShapeDtypeStruct((N, Di), jnp.float32),
                   jax.ShapeDtypeStruct((N, Di), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="ssm_prefill_pallas",
    )(snap_at, c, dt, z, cols(B), cols(C), A_log, skip[None], s0)
    return y, s1, (None if snapshot_len is None else snap)


# -- decode ------------------------------------------------------------------


def _decode_kernel(slots_ref, rows_ref, cols_ref, alog_ref, skip_ref,
                   state_ref, out_ref, new_ref, *, lanes: int):
    del slots_ref   # read by the index maps
    B, C = cols_ref[0, :, 0:1], cols_ref[0, :, 1:2]
    for lo in range(0, state_ref.shape[2], lanes):
        at = slice(lo, lo + lanes)
        c, dt, z = (rows_ref[0, j:j + 1, at] for j in range(3))
        h = _advance(state_ref[0, :, at], -jnp.exp(alog_ref[:, at]), dt, c, B)
        new_ref[0, :, at] = h
        out_ref[0, 0:1, at] = _gated(h, C, skip_ref[:, at], c, z)
        out_ref[0, 1:2, at] = jnp.max(jnp.abs(h), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_decode_pallas(c, dt, z, B, C, A_log, skip, state, slots, *,
                      interpret: bool = False):
    """``c, dt, z`` [R, Di] float32 (``dt`` 0 for a dead row), ``B, C``
    [R, N], ``A_log`` [N, Di], ``skip`` [Di], ``state`` [slots, N, Di]
    float32, ``slots`` [R] int32 -> (y [R, Di], the largest |h| a channel of
    each row was left with [R, Di], the pool with each row's slot advanced
    one token).  Rows that share a slot must be dead rows."""
    R, Di = c.shape
    N = B.shape[1]
    a_row = lambda width: pl.BlockSpec((1, width, Di), lambda r, s: (r, 0, 0))
    layer = lambda height: pl.BlockSpec((height, Di), lambda r, s: (0, 0))
    pool = pl.BlockSpec((1, N, Di), lambda r, s: (s[r], 0, 0))
    out, state = pl.pallas_call(
        functools.partial(_decode_kernel, lanes=min(LANES, Di)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(R,),
            in_specs=[a_row(3), pl.BlockSpec((1, N, 2), lambda r, s: (r, 0, 0)),
                      layer(N), layer(1), pool],
            out_specs=[a_row(2), pool]),
        out_shape=[jax.ShapeDtypeStruct((R, 2, Di), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        # Operand 5 (after the prefetched slots, the rows, the columns, A_log
        # and D) is the pool; it is result 1.
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="ssm_decode_pallas",
    )(slots.astype(jnp.int32), jnp.stack([c, dt, z], axis=1),
      jnp.stack([B, C], axis=2), A_log, skip[None], state)
    return out[:, 0], out[:, 1], state
