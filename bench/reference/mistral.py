"""Mistral-7B's forward pass, written out plainly: the reference the served
model is held to.

Follows the published architecture (Mistral-7B-v0.1 ``config.json`` and the
Hugging Face ``MistralForCausalLM``): token embedding, ``num_hidden_layers``
pre-norm blocks of grouped-query attention with rotate-half RoPE and a
sliding window, a SwiGLU feed-forward, a final RMSNorm and an untied output
head.  ``jax.numpy`` in float32 under ``default_matmul_precision("highest")``
(a TPU otherwise multiplies float32 in bf16 passes); no cache, no kernels,
no batching: the whole sequence in one pass, logits for every position.
Attention takes ``QUERY_ROWS`` query rows at a time against all keys, so that
a prompt longer than the window fits: the arithmetic of a row is the same.

Weights come in the engine's tree (``embed_tokens``, ``layers[i].q_proj``
..., ``norm``, ``lm_head``; projections stored [in, out]).  An int8
projection {"q", "s"} is read as the float32 matrix ``q * s`` it stands
for: the reference checks the arithmetic of the served path, not the
quantizer.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


QUERY_ROWS = 1024   # [heads, rows, T] float32 scores held at once


def _weight(w) -> jax.Array:
    if isinstance(w, dict):
        return w["q"].astype(jnp.float32) * w["s"].astype(jnp.float32)
    return w.astype(jnp.float32)


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _rope(x, positions, theta):
    """x: [T, heads, head_dim]; rotate-half convention."""
    half = x.shape[-1] // 2
    inv_freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, None, :]
    rotated = jnp.concatenate([-x[..., half:], x[..., :half]], -1)
    return x * cos + rotated * sin


def forward(params: Dict, hp: Dict, tokens: jax.Array) -> jax.Array:
    """tokens [T] int32 -> logits [T, vocab] float32.  ``hp`` holds the
    published keys: num_attention_heads, num_key_value_heads, head_dim,
    rope_theta, rms_norm_eps, sliding_window."""
    with jax.default_matmul_precision("highest"):
        H, K, hd = (hp["num_attention_heads"], hp["num_key_value_heads"],
                    hp["head_dim"])
        T = tokens.shape[0]
        pos = jnp.arange(T)
        mask = pos[None, :] <= pos[:, None]
        if hp.get("sliding_window"):
            mask &= pos[None, :] > pos[:, None] - hp["sliding_window"]
        x = _weight(params["embed_tokens"])[tokens]
        for layer in params["layers"]:
            h = _rms_norm(x, _weight(layer["input_layernorm"]),
                          hp["rms_norm_eps"])
            q = (h @ _weight(layer["q_proj"])).reshape(T, H, hd)
            k = (h @ _weight(layer["k_proj"])).reshape(T, K, hd)
            v = (h @ _weight(layer["v_proj"])).reshape(T, K, hd)
            q = _rope(q, pos, hp["rope_theta"])
            k = _rope(k, pos, hp["rope_theta"])
            # Grouped queries: head i reads key/value head i // (H // K).
            k = jnp.repeat(k, H // K, axis=1)
            v = jnp.repeat(v, H // K, axis=1)
            attn = []
            for lo in range(0, T, QUERY_ROWS):
                rows = slice(lo, lo + QUERY_ROWS)
                scores = jnp.einsum("qhd,khd->hqk", q[rows], k)
                scores = jnp.where(mask[None, rows],
                                   scores / jnp.sqrt(float(hd)), -jnp.inf)
                attn.append(jnp.einsum(
                    "hqk,khd->qhd", jax.nn.softmax(scores, -1), v))
            attn = jnp.concatenate(attn)
            x = x + attn.reshape(T, H * hd) @ _weight(layer["o_proj"])
            h = _rms_norm(x, _weight(layer["post_attention_layernorm"]),
                          hp["rms_norm_eps"])
            gate = jax.nn.silu(h @ _weight(layer["gate_proj"]))
            x = x + (gate * (h @ _weight(layer["up_proj"]))) @ _weight(
                layer["down_proj"])
        x = _rms_norm(x, _weight(params["norm"]), hp["rms_norm_eps"])
        return x @ _weight(params["lm_head"])
