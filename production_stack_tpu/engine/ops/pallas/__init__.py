"""Pallas TPU kernels for the hot serving ops.

paged_attention: decode-phase attention streaming paged KV blocks
HBM->VMEM with double-buffered DMA (selected on TPU backends by
ops/attention.py; the pure-JAX gather path stays as the reference
implementation and the CPU/test path).

flash_prefill: prefill attention over the cached prefix and the chunk's own
keys, tile by tile (selected by ops/attention.py too).

latent_attention: decode-phase attention over the paged latent (MLA) cache
of models/sarvam_mla.py, one array a layer that is key and value at once
(selected on TPU backends by that module; its XLA walk stays as the CPU/test
path).
"""
