"""Bytes a ``laguna`` model's decode step has to move, from shapes: the
arithmetic behind this configuration's ``decode_step_bw_share``,
``paged_decode_bw_share``, ``window_decode_bw_share`` and
``routed_decode_bw_share``, kept with the benchmark so that no
later PR can move it.  ``hp`` holds the sizes the chip holds
(``harness/sizes.py: held``) under the keys of a ``laguna`` configuration:
layer ``i`` is ``layer_types[i]`` (``full_attention``: keys in pages, every
position read; ``sliding_attention``: the last ``sliding_window`` positions in
a rolling buffer of the state pool) with ``num_attention_heads_per_layer[i]``
query heads, its MLP ``mlp_layer_types[i]`` (``dense`` | ``sparse``); the
router stays ``hp["published"]["num_experts"]`` wide.  Weights and caches
bf16 unless said."""

from __future__ import annotations

from typing import Dict


def layers_of(hp: Dict, kind: str) -> int:
    return hp["layer_types"][:hp["num_hidden_layers"]].count(kind)


def sparse_layers(hp: Dict) -> int:
    return hp["mlp_layer_types"][:hp["num_hidden_layers"]].count("sparse")


def expert_bytes(hp: Dict, dtype_bytes: int = 2) -> float:
    """One routed expert: gate, up and down of ``moe_intermediate_size``."""
    return 3 * hp["hidden_size"] * hp["moe_intermediate_size"] * dtype_bytes


def attention_params(hp: Dict, heads: int) -> int:
    """One layer's W_q and W_o at ``heads`` query heads, W_k and W_v, and the
    gate a head (a column a head; a padded head is no parameter)."""
    h, K, hd = hp["hidden_size"], hp["num_key_value_heads"], hp["head_dim"]
    return (2 * h * heads * hd + 2 * h * K * hd
            + (h * heads if hp.get("gating") else 0))


def routed_fixed_params(hp: Dict) -> int:
    """What a sparse layer reads whatever the router chose: the router at its
    published width and the shared expert."""
    h = hp["hidden_size"]
    return (h * hp["published"]["num_experts"]
            + 3 * h * hp["shared_expert_intermediate_size"])


def non_expert_bytes(hp: Dict, dtype_bytes: int = 2) -> float:
    """What every decode step reads whatever the router chose: each layer's
    attention at its own head count, a dense layer's SwiGLU, a sparse layer's
    router and shared expert, the held columns of the head (the embedding is
    only gathered from; norms are kilobytes)."""
    h, n = hp["hidden_size"], hp["num_hidden_layers"]
    attention = sum(attention_params(hp, heads)
                    for heads in hp["num_attention_heads_per_layer"][:n])
    dense = (n - sparse_layers(hp)) * 3 * h * hp["intermediate_size"]
    return dtype_bytes * (
        attention + dense + sparse_layers(hp) * routed_fixed_params(hp)
        + h * hp["vocab_size"])


def kv_bytes_per_position(hp: Dict, dtype_bytes: int = 2) -> float:
    """K and V of one position in ONE layer, of either kind."""
    return 2 * hp["num_key_value_heads"] * hp["head_dim"] * dtype_bytes


def paged_read_bytes(hp: Dict, kv_tokens: int, kv_tokens_slots: int,
                     steps: float) -> float:
    """The full layers' K and V pages for ``steps`` decode steps: the
    record's ``kv_tokens`` (positions attended at dispatch, a layer of each
    kind, whole blocks) less its ``kv_tokens_slots`` (the window kind's part)
    is what ONE full layer reads."""
    return (steps * (kv_tokens - kv_tokens_slots) * kv_bytes_per_position(hp)
            * layers_of(hp, "full_attention"))


def window_read_bytes(hp: Dict, kv_tokens_slots: int, steps: float) -> float:
    """The window layers' rolling buffers for ``steps`` decode steps:
    ``kv_tokens_slots`` is what ONE window layer reads (a row: min(context,
    window), whole pages)."""
    return (steps * kv_tokens_slots * kv_bytes_per_position(hp)
            * layers_of(hp, "sliding_attention"))


def routed_bytes(hp: Dict, experts_touched: int, steps: float) -> float:
    """The sparse layers' part of ``steps`` decode steps: the record's
    ``experts_touched`` (held experts with a row, summed over layers and
    steps: a touched expert once a step) x one expert, and every step each
    sparse layer's router and shared expert."""
    return (experts_touched * expert_bytes(hp)
            + steps * sparse_layers(hp) * routed_fixed_params(hp) * 2)


def decode_step_bytes(hp: Dict, record: Dict) -> float:
    """Everything the ``k`` decode steps of one window record must move."""
    k, slots = record["k"], record.get("kv_tokens_slots", 0)
    fixed = non_expert_bytes(hp) - sparse_layers(hp) * routed_fixed_params(hp) * 2
    return (k * fixed + routed_bytes(hp, record["experts_touched"], k)
            + paged_read_bytes(hp, record["kv_tokens"], slots, k)
            + window_read_bytes(hp, slots, k))
