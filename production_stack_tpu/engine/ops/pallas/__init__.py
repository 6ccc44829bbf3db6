"""Pallas TPU kernels for the hot serving ops.

paged_attention: decode-phase attention streaming paged KV blocks
HBM->VMEM with double-buffered DMA (selected on TPU backends by
ops/attention.py; the pure-JAX gather path stays as the reference
implementation and the CPU/test path).

flash_prefill: prefill attention over the cached prefix -- its pages read
through the block table where they lie in the pool -- and the chunk's own
keys, tile by tile (selected by ops/attention.py too).

latent_attention: decode-phase attention over the paged latent (MLA) cache
of models/sarvam_mla.py, one array a layer that is key and value at once
(selected on TPU backends by that module; its XLA walk stays as the CPU/test
path).

:func:`each` is what the kernels that copy pages themselves share.
"""

import jax


def each(n: int, body, unrolled: bool):
    """``body(c)`` for every copy ``c`` of a stage.  Unrolled, the copies'
    scalar work is straight-line code the scheduler runs beside the stage's
    dots; as a loop it is a fraction of the text to trace and lower at every
    process start (the kernel's prologue and its end: once a call)."""
    if unrolled:
        for c in range(n):
            body(c)
        return

    def step(c, carry):
        body(c)
        return carry

    jax.lax.fori_loop(0, n, step, 0)
