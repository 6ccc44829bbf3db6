"""Bytes and operations from shapes: the arithmetic behind roofline shares,
kept with the benchmark so that no later PR can move it."""

from __future__ import annotations

from typing import Dict, Optional


def streamed_weight_bytes(hp: Dict, quantization: Optional[str],
                          chips: int = 1) -> float:
    """Bytes of weights one decode step has to read on each chip: every
    projection of every layer and the output head (the embedding is only
    gathered from, and the norms are a few kilobytes).  ``hp`` holds the
    sizes the chip holds (``harness/sizes.py: held``).  int8: one byte a weight plus a float32 scale per
    output channel; otherwise bf16.  Tensor parallelism splits every
    projection evenly."""
    h, inter = hp["hidden_size"], hp["intermediate_size"]
    head_dim = hp.get("head_dim") or h // hp["num_attention_heads"]
    q_out = hp["num_attention_heads"] * head_dim
    kv_out = hp["num_key_value_heads"] * head_dim
    # (in, out) of q, k, v, o, gate, up, down
    layer = [(h, q_out), (h, kv_out), (h, kv_out), (q_out, h),
             (h, inter), (h, inter), (inter, h)]
    mats = layer * hp["num_hidden_layers"] + [(h, hp["vocab_size"])]
    if quantization == "int8":
        total = sum(i * o + 4 * o for i, o in mats)
    else:
        total = sum(2 * i * o for i, o in mats)
    return total / chips
