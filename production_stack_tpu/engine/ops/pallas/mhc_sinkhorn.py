"""Pallas TPU kernel for the normalisation of the residual streams' mixing
matrices (``models/sarvam_mla.py: _mhc``): ``hc_sinkhorn_iters`` times rows
then columns of a positive ``n x n`` matrix a token, ``hc_eps`` in every
divisor.

The work is a few hundred bytes a token; what it costs in XLA is its shape.
Written as sums over an ``[n, n, T]`` array the twenty unrolled iterations are
forty reductions, and the compiler cuts a fusion at every one: 87 small
programs a mapping, 12 mappings a decode step, and 2 s of compilation a
mapping in each of the module's thirteen step programs; written entry by entry
they fuse, into a text twice as long that compiles no faster (PERF.md
section 6, PR 44).  Here a mapping's normalisation is one call whose twenty
iterations are a loop in the kernel's own text.

Layout: an entry of the matrix is a ``[T / 128, 128]`` array of tokens, whole
(8, 128) tiles (``T`` padded to 1,024: a decode batch of 16 is one vector
register an entry), so every operation of an iteration is elementwise over 16
such arrays: 12 + 4 additions and 16 divisions a half-iteration.  No grid: the
whole of a 2,048-slot chunk is 128 kB of VMEM each way.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

TOKENS = 1024   # tokens a whole (8, 128) tile of an entry


def _kernel(e_ref, out_ref, *, n: int, iters: int, eps: float):
    def once(_, M):
        M = [list(M[i * n:(i + 1) * n]) for i in range(n)]
        rows = [functools.reduce(jnp.add, M[i]) + eps for i in range(n)]
        M = [[M[i][j] / rows[i] for j in range(n)] for i in range(n)]
        cols = [functools.reduce(jnp.add, [M[i][j] for i in range(n)]) + eps
                for j in range(n)]
        return tuple(M[i][j] / cols[j] for i in range(n) for j in range(n))

    M = jax.lax.fori_loop(
        0, iters, once, tuple(e_ref[r] for r in range(n * n)))
    for r in range(n * n):
        out_ref[r] = M[r]


@functools.partial(jax.jit, static_argnames=("iters", "eps", "interpret"))
def mhc_sinkhorn_pallas(E: jax.Array, *, iters: int, eps: float,
                        interpret: bool = False) -> jax.Array:
    """``E`` [n, n, T] float32, positive -> the same shape, each token's
    matrix after ``iters`` row-then-column normalisations."""
    n, _, T = E.shape
    padded = -(-T // TOKENS) * TOKENS
    tiles = jnp.pad(E.reshape(n * n, T), ((0, 0), (0, padded - T)),
                    constant_values=1.0).reshape(n * n, padded // 128, 128)
    out = pl.pallas_call(
        functools.partial(_kernel, n=n, iters=iters, eps=eps),
        out_shape=jax.ShapeDtypeStruct(tiles.shape, jnp.float32),
        interpret=interpret,
        # What the device trace calls the kernel: the benchmark's reader
        # finds it by this name.
        name="mhc_sinkhorn_pallas",
    )(tiles)
    return out.reshape(n * n, padded)[:, :T].reshape(n, n, T)
