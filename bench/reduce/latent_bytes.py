"""Bytes of latent cache a decode step has to read, from shapes: the
arithmetic behind the cache term of the latent configurations'
``decode_step_bw_share``, kept with the benchmark so that no later PR can
move it."""

from __future__ import annotations

from typing import Dict


def latent_bytes_per_token(hp: Dict, dtype_bytes: int = 2) -> float:
    """One position in every layer: the latent and the rotary key, no V.
    What the algorithm needs (576 values a layer for ``sarvam_mla``), not
    what the device's tiling pads it to."""
    return ((hp["kv_lora_rank"] + hp["qk_rope_head_dim"]) * dtype_bytes
            * hp["num_hidden_layers"])


def decode_read_bytes(hp: Dict, kv_tokens: int, steps: float,
                      dtype_bytes: int = 2) -> float:
    """What ``steps`` decode steps of one dispatch must read: ``kv_tokens``
    is the record's, the positions its rows attend at dispatch rounded up to
    whole blocks; the token a row gains a step is left out (a lower bound)."""
    return steps * kv_tokens * latent_bytes_per_token(hp, dtype_bytes)
