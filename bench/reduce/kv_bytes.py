"""Bytes of keys and values the paged decode kernel has to read, from
shapes and the flight records' counts: the arithmetic behind
``paged_decode_bw_share``, kept with the benchmark so that no later PR can
move it."""

from __future__ import annotations

from typing import Dict


def kv_shards(config: Dict) -> int:
    """Over how many chips one engine splits its KV heads: the
    ``--tensor-parallel`` of the configuration's ``engine_argv``.  Not the
    cell's chips: four one-chip replicas hold the whole cache each."""
    argv = config.get("engine_argv", [])
    if "--tensor-parallel" in argv:
        return int(argv[argv.index("--tensor-parallel") + 1])
    return 1


def kv_bytes_per_token(hp: Dict, shards: int = 1, dtype_bytes: int = 2) -> float:
    """K and V of one token position in every layer, on one chip: tensor
    parallelism splits the KV heads evenly over ``shards`` chips.  ``hp``
    holds the sizes the chip holds (``harness/sizes.py: held``); bf16 cache
    unless told otherwise."""
    head_dim = hp.get("head_dim") or (
        hp["hidden_size"] // hp["num_attention_heads"])
    return (2 * hp["num_hidden_layers"] * hp["num_key_value_heads"] / shards
            * head_dim * dtype_bytes)


def decode_read_bytes(hp: Dict, kv_tokens: int, steps: float,
                      shards: int = 1, dtype_bytes: int = 2) -> float:
    """What ``steps`` decode steps of one dispatch must read on each chip.

    ``kv_tokens`` is the record's: the positions each row attends at
    dispatch, min(context, sliding window) rounded up to whole blocks (the
    kernel reads whole blocks), summed over the rows.  Every step reads at
    least that; the token a row gains a step, which now and then opens a
    block, is left out, so this is what the algorithm needs at the least
    and the share it feeds is a lower bound."""
    return steps * kv_tokens * kv_bytes_per_token(hp, shards, dtype_bytes)
