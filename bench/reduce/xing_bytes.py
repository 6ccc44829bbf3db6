"""Bytes of weights a decode step of a ``xing4_0`` model has to read, from
shapes: the arithmetic behind this configuration's ``decode_step_bw_share``,
kept with the benchmark so that no later PR can move it.  ``hp`` holds the
sizes the chip holds (``harness/sizes.py: held``) under the keys of a ``xing4_0``
configuration: every routed expert is held, the query goes through a latent
of ``q_lora_rank``, and each sub-layer reads a float32 mapping of its
``hc_mult`` residual streams.  Weights bf16 unless said."""

from __future__ import annotations

from typing import Dict


def routed_layers(hp: Dict) -> int:
    return hp["num_hidden_layers"] - hp["first_k_dense_replace"]


def expert_bytes(hp: Dict, dtype_bytes: int = 2) -> float:
    """One routed expert: gate, up and down of ``moe_intermediate_size``."""
    return 3 * hp["hidden_size"] * hp["moe_intermediate_size"] * dtype_bytes


def mapping_bytes(hp: Dict) -> float:
    """A layer's two mappings, float32: ``W [n d, 2 n + n^2]`` each (their
    scalars and biases are bytes)."""
    n = hp["hc_mult"]
    return 2 * n * hp["hidden_size"] * (2 * n + n * n) * 4


def non_expert_bytes(hp: Dict, dtype_bytes: int = 2) -> float:
    """What every decode step reads whatever the router chose: each layer's
    attention projections (W_qa, W_qb, W_kva, W_kvb, W_o) and its two
    mappings, the leading dense layers' SwiGLU, each routed layer's router
    and shared experts, and the head (the embedding is only gathered from,
    the norms and the bias are kilobytes)."""
    h, H = hp["hidden_size"], hp["num_attention_heads"]
    nope, rope = hp["qk_nope_head_dim"], hp["qk_rope_head_dim"]
    latent, v, q = hp["kv_lora_rank"], hp["v_head_dim"], hp["q_lora_rank"]
    attention = (h * q + q * H * (nope + rope) + h * (latent + rope)
                 + latent * H * (nope + v) + H * v * h)
    dense = 3 * h * hp["intermediate_size"]
    routed = (h * hp["n_routed_experts"]
              + 3 * h * hp["moe_intermediate_size"] * hp["n_shared_experts"])
    return dtype_bytes * (
        attention * hp["num_hidden_layers"]
        + dense * hp["first_k_dense_replace"]
        + routed * routed_layers(hp) + h * hp["vocab_size"]
    ) + mapping_bytes(hp) * hp["num_hidden_layers"]


def sinkhorn_bytes(hp: Dict, tokens: int) -> float:
    """What one call of the normalisation kernel must move for ``tokens``
    live tokens: an ``hc_mult x hc_mult`` float32 matrix a token, read once
    and written once (the kernel pads the tokens to 1,024: not counted)."""
    return 2 * tokens * hp["hc_mult"] ** 2 * 4
