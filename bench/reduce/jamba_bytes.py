"""Bytes and operations a ``jamba`` model's steps have to move, from shapes:
the arithmetic behind this configuration's ``decode_step_bw_share``,
``ssm_decode_bw_share`` and ``ssm_prefill_roofline_share``,
kept with the benchmark so that no later PR can move it.  ``hp`` holds the
sizes the chip holds (``harness/sizes.py: held``) under the keys of a ``jamba``
configuration: layer ``i`` is attention where ``i % attn_layer_period ==
attn_layer_offset``, every other layer a Mamba-1 mixer.  Weights bf16, the
recurrent state float32, unless said."""

from __future__ import annotations

from typing import Dict


def attention_layers(hp: Dict) -> int:
    return sum(1 for i in range(hp["num_hidden_layers"])
               if i % hp["attn_layer_period"] == hp["attn_layer_offset"])


def mamba_layers(hp: Dict) -> int:
    return hp["num_hidden_layers"] - attention_layers(hp)


def inner(hp: Dict) -> int:
    return hp["mamba_expand"] * hp["hidden_size"]


def mixer_params(hp: Dict) -> int:
    """One Mamba mixer: W_in, the convolution's taps and bias, W_x, the three
    inner norms, W_dt and its bias, A_log, D, W_out."""
    h, Di, N = hp["hidden_size"], inner(hp), hp["mamba_d_state"]
    R, K = hp["mamba_dt_rank"], hp["mamba_d_conv"]
    return (h * 2 * Di + K * Di + (Di if hp["mamba_conv_bias"] else 0)
            + Di * (R + 2 * N) + R + 2 * N + R * Di + Di + N * Di + Di
            + Di * h)


def attention_params(hp: Dict) -> int:
    h, hd = hp["hidden_size"], hp["head_dim"]
    H, K = hp["num_attention_heads"], hp["num_key_value_heads"]
    return 2 * h * H * hd + 2 * h * K * hd


def mlp_params(hp: Dict) -> int:
    return 3 * hp["hidden_size"] * hp["intermediate_size"]


def params(hp: Dict) -> int:
    """The whole model: the embedding (the head is the same array), every
    layer's mix, MLP and two norms, the final norm."""
    h = hp["hidden_size"]
    return (hp["vocab_size"] * h + h
            + mamba_layers(hp) * mixer_params(hp)
            + attention_layers(hp) * attention_params(hp)
            + hp["num_hidden_layers"] * (mlp_params(hp) + 2 * h))


def weight_bytes(hp: Dict, dtype_bytes: int = 2) -> float:
    """What every decode step reads of the weights: all of them once (the
    tied embedding as the head; its gather for the rows' tokens is
    kilobytes).  A_log, D and b_dt are float32, 92 k of 41 M a mixer:
    counted at ``dtype_bytes`` like the rest, under by 0.2 %."""
    return params(hp) * dtype_bytes


def kv_bytes_per_token(hp: Dict, dtype_bytes: int = 2) -> float:
    """K and V of one position in the attention layers alone: a mixer keeps
    no keys."""
    return (2 * hp["num_key_value_heads"] * hp["head_dim"] * dtype_bytes
            * attention_layers(hp))


def state_bytes(hp: Dict) -> float:
    """One sequence's state in ONE mixer: states x channels float32."""
    return hp["mamba_d_state"] * inner(hp) * 4


def conv_bytes(hp: Dict, dtype_bytes: int = 2) -> float:
    """The last ``mamba_d_conv - 1`` pre-activation rows of a mixer's
    convolution."""
    return (hp["mamba_d_conv"] - 1) * inner(hp) * dtype_bytes


def slot_bytes(hp: Dict) -> float:
    """One sequence's slot of the state pool, over every mixer."""
    return mamba_layers(hp) * (state_bytes(hp) + conv_bytes(hp))


def decode_state_bytes(hp: Dict, rows: int, steps: float) -> float:
    """What ``steps`` decode steps of ``rows`` rows move of recurrent state:
    every row's slot read once and written once."""
    return steps * rows * 2 * slot_bytes(hp)


def decode_read_bytes(hp: Dict, kv_tokens: int, steps: float) -> float:
    """The attention layers' K and V for ``steps`` decode steps: ``kv_tokens``
    is the record's (positions attended at dispatch, whole blocks)."""
    return steps * kv_tokens * kv_bytes_per_token(hp)


def recurrence_flops(hp: Dict, tokens: int) -> float:
    """The recurrence, token by token, in ONE mixer: a state element's step
    multiplies dt by A, takes the exponential (counted as one), decays,
    forms (dt c) B, adds, and is read by C (a product and a sum): 7; the
    skip and the gate are a few a channel.  On the vector unit, not the
    MXU the published FLOP/s are the matrix unit's: the share this feeds is
    a lower bound by construction."""
    return 7.0 * tokens * hp["mamba_d_state"] * inner(hp)


def recurrence_bytes(hp: Dict, tokens: int) -> float:
    """What one call over ``tokens`` tokens of ONE mixer must move: c, dt
    and z in and y out (a channel each), B and C in (a state each), float32,
    a token; the state in and out once."""
    return (tokens * (4 * inner(hp) + 2 * hp["mamba_d_state"]) * 4
            + 2 * state_bytes(hp))
