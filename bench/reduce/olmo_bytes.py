"""Bytes and operations an ``olmo_hybrid`` model's steps have to move, from
shapes: the arithmetic behind this configuration's ``decode_step_bw_share``,
``kda_decode_bw_share`` and ``gdn_prefill_roofline_share``, kept with the
benchmark so that no later PR can move it.  ``hp`` holds the sizes the chip
holds (``harness/sizes.py: held``) under the keys of an ``olmo_hybrid``
configuration: layer ``i`` is what ``layer_types[i]`` says.  Weights bf16, the
recurrent state float32, unless said.  **What the algorithm needs**: 30 key
heads a page and a 96 x 192 state, not the 32 heads and the 256 lanes the
device's tiles keep, so padding in a pool shows as a lower share and not as
more bytes."""

from __future__ import annotations

from typing import Dict


def full_layers(hp: Dict) -> int:
    return sum(kind == "full_attention" for kind in hp["layer_types"])


def linear_layers(hp: Dict) -> int:
    return sum(kind == "linear_attention" for kind in hp["layer_types"])


def _linear(hp: Dict):
    """(heads, key channels, value channels) of a delta-rule layer."""
    return (hp["linear_num_value_heads"], hp["linear_key_head_dim"],
            hp["linear_value_head_dim"])


def conv_channels(hp: Dict) -> int:
    H, Dk, Dv = _linear(hp)
    return H * (2 * Dk + Dv)


def linear_params(hp: Dict) -> int:
    """One delta-rule layer's mix: W_q, W_k, W_v, the convolutions' taps, W_a
    with dt_bias and A_log, W_b, W_g, the head norm's scale, W_o."""
    h = hp["hidden_size"]
    H, _Dk, Dv = _linear(hp)
    return (h * conv_channels(hp) + hp["linear_conv_kernel_dim"]
            * conv_channels(hp) + h * H + 2 * H + h * H + h * H * Dv + Dv
            + H * Dv * h)


def full_params(hp: Dict) -> int:
    """One softmax layer's mix: W_q, W_k, W_v, W_o and the two whole-width
    norms' scales."""
    h, hd = hp["hidden_size"], hp["head_dim"]
    H, K = hp["num_attention_heads"], hp["num_key_value_heads"]
    return 2 * h * H * hd + 2 * h * K * hd + H * hd + K * hd


def mlp_params(hp: Dict) -> int:
    return 3 * hp["hidden_size"] * hp["intermediate_size"]


def params(hp: Dict) -> int:
    """What is held: the embedding, the untied head, every layer's mix, MLP
    and two norms, the final norm."""
    h = hp["hidden_size"]
    return (2 * hp["vocab_size"] * h + h
            + linear_layers(hp) * linear_params(hp)
            + full_layers(hp) * full_params(hp)
            + hp["num_hidden_layers"] * (mlp_params(hp) + 2 * h))


def weight_bytes(hp: Dict, dtype_bytes: int = 2) -> float:
    """What every decode step reads of the weights: the layers and the head
    once; the embedding's gather for the rows' tokens is kilobytes, so its
    table is left out.  A_log and dt_bias are float32, 60 numbers a layer."""
    return (params(hp) - hp["vocab_size"] * hp["hidden_size"]) * dtype_bytes


def kv_bytes_per_token(hp: Dict, dtype_bytes: int = 2) -> float:
    """K and V of one position in the softmax layers alone (a delta-rule layer
    keeps no keys), at the key heads the model has."""
    return (2 * hp["num_key_value_heads"] * hp["head_dim"] * dtype_bytes
            * full_layers(hp))


def state_bytes(hp: Dict) -> float:
    """One sequence's state in ONE delta-rule layer: heads x key channels x
    value channels float32."""
    H, Dk, Dv = _linear(hp)
    return H * Dk * Dv * 4


def conv_bytes(hp: Dict, dtype_bytes: int = 2) -> float:
    """The last ``kernel - 1`` pre-activation rows of a layer's convolutions."""
    return ((hp["linear_conv_kernel_dim"] - 1) * conv_channels(hp)
            * dtype_bytes)


def slot_bytes(hp: Dict) -> float:
    """One sequence's slot of the state pool, over every delta-rule layer."""
    return linear_layers(hp) * (state_bytes(hp) + conv_bytes(hp))


def decode_state_bytes(hp: Dict, rows: int, steps: float) -> float:
    """What ``steps`` decode steps of ``rows`` rows move of recurrent state:
    every row's slot read once and written once."""
    return steps * rows * 2 * slot_bytes(hp)


def decode_read_bytes(hp: Dict, kv_tokens: int, steps: float) -> float:
    """The softmax layers' K and V for ``steps`` decode steps: ``kv_tokens``
    is the record's (positions attended at dispatch, whole blocks)."""
    return steps * kv_tokens * kv_bytes_per_token(hp)


def recurrence_flops(hp: Dict, tokens: int) -> float:
    """The recurrence, token by token, in ONE layer: a state element decays
    (1), is read by k (a product and a sum), gains beta k u (a product and a
    sum) and is read by q (a product and a sum): 7 a token a head a
    key-by-value element.  Counted from the recurrence and not from the chunk
    form, so another chunk size is held to the same yardstick."""
    H, Dk, Dv = _linear(hp)
    return 7.0 * tokens * H * Dk * Dv


def recurrence_bytes(hp: Dict, tokens: int) -> float:
    """What one call over ``tokens`` tokens of ONE layer must move: q and k
    in (a key channel each), v in and o out (a value channel each), g and
    beta in (one each), float32, a token a head; the state in and out once."""
    H, Dk, Dv = _linear(hp)
    return tokens * H * (2 * Dk + 2 * Dv + 2) * 4 + 2 * state_bytes(hp)
