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
So ``forward`` can be handed the program's own ``choice``: each block then
uses those experts, with the shares its own float32 scores give them,
renormalised, and says how far the choice lay from its own (``shortfall``).
The selection score is the router's logit: the softmax keeps its order.

The router keeps its published width, ``hp["published"]``'s expert count;
``hp``'s own count is how many of them, the first, are held here.  What the
others would have added is left out, as the program that holds a share
leaves it out.

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


def _route(logits, top: int, choice=None):
    """Router logits [T, E] -> (each expert's share of a position [T, E],
    shortfall [T]): softmax over all, the best ``top`` renormalised, zero
    outside them.  With ``choice`` [T, top], another computation's experts
    take the place of the best, with this softmax's shares; the shortfall is
    how far the weakest of them lies below the reference's own ``top``-th
    logit, in standard deviations of the position's logits: 0 where the two
    agree, +inf for an id out of range or repeated."""
    probs = jax.nn.softmax(logits, -1)
    best, who = jax.lax.top_k(probs, top)
    shortfall = jnp.zeros(logits.shape[0])
    if choice is not None:
        who = jnp.clip(choice, 0, logits.shape[1] - 1)
        ranked = jnp.sort(who, -1)
        bad = jnp.any(who != choice, -1) | jnp.any(
            ranked[:, 1:] == ranked[:, :-1], -1)
        own = jax.lax.top_k(logits, top)[0][:, -1]
        weakest = jnp.take_along_axis(logits, who, -1).min(-1)
        shortfall = jnp.where(
            bad, jnp.inf, (own - weakest) / jnp.std(logits, -1))
        best = jnp.take_along_axis(probs, who, -1)
    best = best / best.sum(-1, keepdims=True)
    rows = jnp.arange(logits.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, who].set(best), shortfall


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


def hidden(params: Dict, hp: Dict, tokens: jax.Array, choice=None):
    """tokens [T] -> (the residual stream after the last block [T, hidden],
    shortfall [blocks, T]); ``choice`` [blocks, T, top] as :func:`_route`'s."""
    with jax.default_matmul_precision("highest"):
        held = hp["moe_num_primary_experts"]
        shortfall = []
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
            shares, short = _route(
                h @ _weight(layer["gate"]), top,
                None if choice is None else choice[len(shortfall)])
            shortfall.append(short)
            x = x + _experts(layer, h, shares[:, :held])
        return x, jnp.stack(shortfall)


def head(params: Dict, hp: Dict, x: jax.Array) -> jax.Array:
    """Residual stream [n, hidden] -> logits [n, vocab]."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, _weight(params["norm"]), hp["rms_norm_eps"])
        w = params["lm_head"]
        return jnp.concatenate([
            x @ _weight(_columns(w, lo, lo + HEAD_COLUMNS))
            for lo in range(0, hp["vocab_size"], HEAD_COLUMNS)], -1)


def forward(params: Dict, hp: Dict, tokens: jax.Array, choice=None,
            rows=None):
    """tokens [T] int32 -> logits [T, vocab] float32, or of the positions
    ``rows`` alone (a long prompt's logits over a large vocabulary do not
    fit).  With ``choice`` [blocks, T, top] int32, expert ids over the
    router's whole width, the blocks follow it and the result is
    ``(logits, shortfall [blocks, T])``."""
    x, shortfall = hidden(params, hp, tokens, choice)
    logits = head(params, hp, x if rows is None else x[rows])
    return logits if choice is None else (logits, shortfall)
