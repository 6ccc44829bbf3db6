"""Bytes of weights a decode step of a routed model held by share has to
read, from shapes: the arithmetic behind this configuration's
``decode_step_bw_share``, kept with the benchmark so that no later PR can
move it.  ``hp`` holds the sizes the chip holds (``harness/sizes.py:
held``), under the keys of a ``sarvam_mla`` configuration; bf16 weights."""

from __future__ import annotations

from typing import Dict


def routed_layers(hp: Dict) -> int:
    return hp["num_hidden_layers"] - hp["first_k_dense_replace"]


def expert_bytes(hp: Dict, dtype_bytes: int = 2) -> float:
    """One routed expert: gate, up and down of ``moe_intermediate_size``."""
    return 3 * hp["hidden_size"] * hp["moe_intermediate_size"] * dtype_bytes


def non_expert_bytes(hp: Dict, dtype_bytes: int = 2) -> float:
    """What every decode step reads whatever the router chose: each layer's
    attention projections (W_q, W_kva, W_kvb, W_o), the leading dense
    layers' SwiGLU, each routed layer's router and shared experts, and the
    held columns of the head (the embedding is only gathered from, the
    norms and the bias are kilobytes)."""
    h, H = hp["hidden_size"], hp["num_attention_heads"]
    nope, rope = hp["qk_nope_head_dim"], hp["qk_rope_head_dim"]
    latent, v = hp["kv_lora_rank"], hp["v_head_dim"]
    attention = (h * H * (nope + rope) + h * (latent + rope)
                 + latent * H * (nope + v) + H * v * h)
    dense = 3 * h * hp["intermediate_size"]
    routed = (h * hp["published"]["num_experts"]
              + 3 * h * hp["moe_intermediate_size"] * hp["num_shared_experts"])
    return dtype_bytes * (
        attention * hp["num_hidden_layers"]
        + dense * hp["first_k_dense_replace"]
        + routed * routed_layers(hp) + h * hp["vocab_size"])
