"""Bytes and operations a ``solar_open2`` model's steps have to move, from
shapes: the arithmetic behind this configuration's ``decode_step_bw_share``,
``kda_decode_bw_share`` and ``kda_prefill_roofline_share``,
kept with the benchmark so that no later PR can move it.  ``hp`` holds the
sizes the chip holds (``harness/sizes.py: held``) under the keys of a
``solar_open2`` configuration: ``gqa_layers`` lists the held layers that are
softmax layers, every other held layer is a delta-rule layer of
``linear_attn_config``; the router stays ``hp["published"]["n_routed_experts"]``
wide.  Weights bf16, the recurrent state float32, unless said."""

from __future__ import annotations

from typing import Dict


def gqa_layers(hp: Dict) -> int:
    return sum(1 for i in hp["gqa_layers"] if i < hp["num_hidden_layers"])


def kda_layers(hp: Dict) -> int:
    return hp["num_hidden_layers"] - gqa_layers(hp)


def expert_bytes(hp: Dict, dtype_bytes: int = 2) -> float:
    """One routed expert: gate, up and down of ``moe_intermediate_size``."""
    return 3 * hp["hidden_size"] * hp["moe_intermediate_size"] * dtype_bytes


def non_expert_bytes(hp: Dict, dtype_bytes: int = 2) -> float:
    """What every decode step reads whatever the router chose: a softmax
    layer's W_q, W_k, W_v, its gate (a column an output channel) and W_o; a
    delta-rule layer's three projections, W_o, the two low-rank pairs (rank =
    the linear head's width), W_beta and the convolution's taps; each layer's
    router and shared expert; the held columns of the head (the embedding is
    only gathered from; norms, A_log and the biases are kilobytes)."""
    h = hp["hidden_size"]
    H, K, hd = (hp["num_attention_heads"], hp["num_key_value_heads"],
                hp["head_dim"])
    lin = hp["linear_attn_config"]
    Hl, D, taps = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    softmax = h * H * hd * (3 if hp.get("use_gqa_gate") else 2) + 2 * h * K * hd
    delta = (4 * h * Hl * D + 2 * (h * D + D * Hl * D) + h * Hl
             + taps * 3 * Hl * D)
    routed = (h * hp["published"]["n_routed_experts"]
              + 3 * h * hp["moe_intermediate_size"] * hp["n_shared_experts"])
    return dtype_bytes * (
        softmax * gqa_layers(hp) + delta * kda_layers(hp)
        + routed * hp["num_hidden_layers"] + h * hp["vocab_size"])


def kv_bytes_per_token(hp: Dict, dtype_bytes: int = 2) -> float:
    """K and V of one position in the softmax layers alone: a delta-rule
    layer keeps no keys."""
    return (2 * hp["num_key_value_heads"] * hp["head_dim"] * dtype_bytes
            * gqa_layers(hp))


def state_bytes(hp: Dict) -> float:
    """One sequence's state in ONE delta-rule layer: heads x D x D float32."""
    lin = hp["linear_attn_config"]
    return lin["num_heads"] * lin["head_dim"] ** 2 * 4


def conv_bytes(hp: Dict, dtype_bytes: int = 2) -> float:
    """The last ``kernel - 1`` pre-activation rows of a layer's three
    convolutions."""
    lin = hp["linear_attn_config"]
    return ((lin["short_conv_kernel_size"] - 1) * 3 * lin["num_heads"]
            * lin["head_dim"] * dtype_bytes)


def decode_state_bytes(hp: Dict, rows: int, steps: float) -> float:
    """What ``steps`` decode steps of ``rows`` rows move of recurrent state:
    every row's state and convolution rows read once and written once, in
    every delta-rule layer."""
    return steps * rows * kda_layers(hp) * 2 * (state_bytes(hp) + conv_bytes(hp))


def decode_read_bytes(hp: Dict, kv_tokens: int, steps: float) -> float:
    """The softmax layers' K and V for ``steps`` decode steps: ``kv_tokens``
    is the record's (positions attended at dispatch, whole blocks)."""
    return steps * kv_tokens * kv_bytes_per_token(hp)


def recurrence_flops(hp: Dict, tokens: int) -> float:
    """The delta rule, token by token, in ONE layer: a head's step decays the
    state (D^2), reads it by k (2 D^2), adds the outer product (2 D^2) and
    reads it by q (2 D^2): 7 D^2.  Counted from the recurrence and not from a
    chunkwise form, whose extra products are that form's cost."""
    lin = hp["linear_attn_config"]
    return 7.0 * tokens * lin["num_heads"] * lin["head_dim"] ** 2


def recurrence_bytes(hp: Dict, tokens: int) -> float:
    """What one call over ``tokens`` tokens of ONE layer must move: q, k, v,
    g (D each) and beta in, o (D) out, float32, a token a head; the state in
    and out once."""
    lin = hp["linear_attn_config"]
    return (tokens * lin["num_heads"] * (5 * lin["head_dim"] + 1) * 4
            + 2 * state_bytes(hp))
