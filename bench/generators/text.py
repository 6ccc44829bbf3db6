"""Seeded prompt text for the byte tokenizer (one token a byte)."""

from __future__ import annotations

import numpy as np

_ALPHABET = np.frombuffer(
    b"abcdefghijklmnopqrstuvwxyz ABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789", np.uint8
)

# The engine's default chat template around the messages: BOS, "<|role|>"
# before each message, "\n" between messages, "\n<|assistant|>" at the end
# (engine/tokenizer.py ByteTokenizer.apply_chat_template).
BOS_AND_TAIL_TOKENS = 1 + len("\n<|assistant|>")


def message_tokens(role: str, content_len: int, first: bool) -> int:
    return len(f"<|{role}|>") + content_len + (0 if first else 1)


def random_text(rng: np.random.Generator, n: int) -> str:
    """``n`` ASCII bytes; two draws share no 16-token block in practice."""
    return _ALPHABET[rng.integers(0, len(_ALPHABET), n)].tobytes().decode()
