"""A routed model's forward pass, written out plainly: the reference a
configuration with routed experts is held to.

Token embedding, pre-norm blocks of grouped-query attention with rotate-half
RoPE and a sliding window (``reference/mistral.py``'s), then in place of a
dense feed-forward the experts: the router reads the post-attention norm,
softmax over all experts, the best ``moe_num_active_primary_experts`` kept
and renormalised, each expert ``down(silu(gate x) * up x)``; a final RMSNorm
and an untied head.  Float32 under ``default_matmul_precision("highest")``,
keys of the configuration's own naming.

The forward pass holds a discrete choice, the top k.  Two sound computations
in different precisions can disagree on it where the k-th and the (k+1)-th
expert are all but tied, and a position where they do reads another model's
error (``tools/flip_rate.py`` measures how often and how far; PERF.md, PR 34).

So that the real widths fit beside the engine's weights, nothing large is
upcast at once: one expert at a time (``lax.scan`` over the stacked weights),
the head in blocks of ``HEAD_COLUMNS`` of the vocabulary (``head`` is a
function of its own, so that a caller can ask for some rows' logits alone),
the embedding after its rows are gathered.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from reference.mistral import _rms_norm, _rope, _weight

QUERY_ROWS = 512       # [heads, rows, T] float32 scores held at once
HEAD_COLUMNS = 32768   # columns of the head upcast at once


def _route(logits, top: int) -> jax.Array:
    """Router logits [T, E] -> each expert's share of a position [T, E]:
    softmax over all, the best ``top`` renormalised, zero outside them."""
    probs = jax.nn.softmax(logits, -1)
    best, who = jax.lax.top_k(probs, top)
    best = best / best.sum(-1, keepdims=True)
    rows = jnp.arange(logits.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, who].set(best)


def _experts(layer: Dict, h, shares):
    """sum over experts of share * down(silu(gate h) * up h): every expert
    over every position, as the definition reads."""
    def one(out, e):
        gate, up, down, share = e
        y = (jax.nn.silu(h @ _weight(gate)) * (h @ _weight(up))) @ _weight(down)
        return out + y * share[:, None], None

    return jax.lax.scan(one, jnp.zeros_like(h), (
        layer["experts_gate"], layer["experts_up"], layer["experts_down"],
        shares.T))[0]


def _columns(w, lo: int, hi: int):
    if isinstance(w, dict):   # an int8 head: its scales are one a column
        return {"q": w["q"][:, lo:hi], "s": w["s"][..., lo:hi]}
    return w[:, lo:hi]


def hidden(params: Dict, hp: Dict, tokens: jax.Array) -> jax.Array:
    """tokens [T] -> the residual stream after the last block [T, hidden]."""
    with jax.default_matmul_precision("highest"):
        H, K, hd = (hp["num_attention_heads"], hp["num_key_value_heads"],
                    hp["head_dim"])
        T = tokens.shape[0]
        pos = jnp.arange(T)
        mask = (pos[None, :] <= pos[:, None]) & (
            pos[None, :] > pos[:, None] - hp["sliding_window_size"])
        x = _weight(params["embed_tokens"][tokens])
        top = hp["moe_num_active_primary_experts"]
        for layer in params["layers"]:
            h = _rms_norm(x, _weight(layer["input_layernorm"]),
                          hp["rms_norm_eps"])
            q = _rope((h @ _weight(layer["q_proj"])).reshape(T, H, hd),
                      pos, hp["rope_theta"])
            k = _rope((h @ _weight(layer["k_proj"])).reshape(T, K, hd),
                      pos, hp["rope_theta"])
            v = (h @ _weight(layer["v_proj"])).reshape(T, K, hd)
            k, v = (jnp.repeat(a, H // K, axis=1) for a in (k, v))
            attn = []
            for lo in range(0, T, QUERY_ROWS):
                rows = slice(lo, lo + QUERY_ROWS)
                scores = jnp.einsum("qhd,khd->hqk", q[rows], k)
                scores = jnp.where(mask[None, rows],
                                   scores / jnp.sqrt(float(hd)), -jnp.inf)
                attn.append(jnp.einsum(
                    "hqk,khd->qhd", jax.nn.softmax(scores, -1), v))
            x = x + jnp.concatenate(attn).reshape(T, H * hd) @ _weight(
                layer["o_proj"])
            h = _rms_norm(x, _weight(layer["post_attention_layernorm"]),
                          hp["rms_norm_eps"])
            shares = _route(h @ _weight(layer["gate"]), top)
            x = x + _experts(layer, h, shares)
        return x


def head(params: Dict, hp: Dict, x: jax.Array) -> jax.Array:
    """Residual stream [n, hidden] -> logits [n, vocab]."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, _weight(params["norm"]), hp["rms_norm_eps"])
        w = params["lm_head"]
        return jnp.concatenate([
            x @ _weight(_columns(w, lo, lo + HEAD_COLUMNS))
            for lo in range(0, hp["vocab_size"], HEAD_COLUMNS)], -1)


def forward(params: Dict, hp: Dict, tokens: jax.Array) -> jax.Array:
    """tokens [T] int32 -> logits [T, vocab] float32."""
    return head(params, hp, hidden(params, hp, tokens))
