"""Bytes a ``longcat`` model's decode step has to move, from shapes: the
arithmetic behind this configuration's ``decode_step_bw_share`` and
``latent_decode_bw_share``, kept with the benchmark so that no later PR can
move it.  ``hp`` holds the sizes the chip holds (``harness/sizes.py: held``)
under the keys of the source's ``config.json``: ``num_layers`` layers, each two
latent attentions (a cache array each), two dense SwiGLU of
``ffn_hidden_size`` and one routed FFN of ``n_routed_experts`` held experts of
``expert_ffn_hidden_size`` behind a router that stays
``hp["published"]["n_routed_experts"] + zero_expert_num`` wide (the identity
experts hold no weights).  Weights and caches bf16 unless said."""

from __future__ import annotations

from typing import Dict

ATTENTIONS = 2      # a layer: latent attentions, dense FFNs, cache arrays


def expert_bytes(hp: Dict, dtype_bytes: int = 2) -> float:
    """One routed expert: gate, up and down of ``expert_ffn_hidden_size``."""
    return 3 * hp["hidden_size"] * hp["expert_ffn_hidden_size"] * dtype_bytes


def attention_params(hp: Dict) -> int:
    """One latent attention: W_qa, W_qb, W_kva, W_kvb, W_o."""
    h, H = hp["hidden_size"], hp["num_attention_heads"]
    nope, rope = hp["qk_nope_head_dim"], hp["qk_rope_head_dim"]
    latent, v, q = hp["kv_lora_rank"], hp["v_head_dim"], hp["q_lora_rank"]
    return (h * q + q * H * (nope + rope) + h * (latent + rope)
            + latent * H * (nope + v) + H * v * h)


def router_width(hp: Dict) -> int:
    return hp["published"]["n_routed_experts"] + hp["zero_expert_num"]


def layer_params(hp: Dict) -> int:
    """One layer outside its experts: two attentions, two dense SwiGLU, the
    router at its published width (norms and the bias are kilobytes)."""
    h = hp["hidden_size"]
    return (ATTENTIONS * attention_params(hp)
            + ATTENTIONS * 3 * h * hp["ffn_hidden_size"]
            + h * router_width(hp))


def non_expert_bytes(hp: Dict, dtype_bytes: int = 2) -> float:
    """What every decode step reads whatever the router chose: every layer
    outside its experts and the held columns of the head (the embedding is
    only gathered from)."""
    return dtype_bytes * (hp["num_layers"] * layer_params(hp)
                          + hp["hidden_size"] * hp["vocab_size"])


def cache_arrays(hp: Dict) -> int:
    return ATTENTIONS * hp["num_layers"]


def latent_bytes_per_token(hp: Dict, dtype_bytes: int = 2) -> float:
    """One position in every cache array: the latent and the rotary key, no
    V.  What the algorithm needs (576 values an array), not what the device's
    tiling pads it to."""
    return ((hp["kv_lora_rank"] + hp["qk_rope_head_dim"]) * dtype_bytes
            * cache_arrays(hp))


def latent_read_bytes(hp: Dict, kv_tokens: int, steps: float,
                      dtype_bytes: int = 2) -> float:
    """What ``steps`` decode steps of one dispatch must read of the cache:
    ``kv_tokens`` is the record's, the positions its rows attend at dispatch
    in ONE array, whole blocks; the token a row gains a step is left out (a
    lower bound)."""
    return steps * kv_tokens * latent_bytes_per_token(hp, dtype_bytes)


def decode_step_bytes(hp: Dict, record: Dict) -> float:
    """Everything the ``k`` decode steps of one window record must move: the
    non-expert weights a step, a touched expert once a step it is touched
    (``experts_touched``: held experts with a row, summed over layers and
    steps), the latent pages of all arrays a step."""
    k = record["k"]
    return (k * non_expert_bytes(hp)
            + record["experts_touched"] * expert_bytes(hp)
            + latent_read_bytes(hp, record["kv_tokens"], k))
