"""The ``laguna`` model type's forward pass, written out plainly: window and
full softmax attention 3:1 with head counts and rotary forms by layer kind, a
gate a head, over routed experts behind a leading dense layer: the reference
``laguna-xs.2-ep2`` is held to.

Pre-norm blocks (RMSNorm, no biases, no norm on queries or keys): ``r = x +
Attn(norm1 x)``, ``y = r + FFN(norm2 r)``; a final RMSNorm, an untied head.

Attention of layer ``l``, kind ``layer_types[l]``: ``H =
num_attention_heads_per_layer[l]`` query heads over ``num_key_value_heads``
key/value heads of ``head_dim``.  Rotary by kind, ``rope_parameters[kind]``,
rotate-half pairing over the first ``partial_rotary_factor x head_dim``
dimensions, the rest passed through: ``sliding_attention`` plain frequencies
``theta^(-2i/d)``; ``full_attention`` YaRN as ``transformers`` computes it
(``_compute_yarn_parameters``): the plain frequencies and those over
``factor`` blended by a linear ramp between the two correction dimensions
(``beta_fast`` and ``beta_slow`` turns over ``original_max_position_
embeddings``, truncated to whole dimensions), cos and sin times
``attention_factor``.  Scores ``q . k / sqrt(head_dim)``, causal, full softmax
``QUERY_ROWS`` query rows at a time over the WHOLE sequence; a
``sliding_attention`` layer's query at position p sees keys ``p -
sliding_window + 1 .. p``, by a mask.  Head h's output times ``sigmoid(x
W_g)_h`` (``gating``; assumed a gate a head from the layer's input), then W_o.

FFN: ``mlp_layer_types[l]`` ``dense``: a SwiGLU of ``intermediate_size``;
``sparse``: ``shared(x) + sum_{i in T} g_i E_i(x)``, ``s = sigmoid(x W_r)``
over the router's published width, ``T`` the ``num_experts_per_tok`` largest,
``g_i = moe_routed_scaling_factor s_i / sum_T s`` (assumed: the config names no
scoring function), the weights on the experts' outputs.  ``hp["num_experts"]``
is how many experts, the first, are held; ``held`` overrides the range (the
test that adds the shares up).  With ``choice`` [routed blocks, T, k] the
blocks follow another computation's experts with this one's own scores, and
say how far its weakest lay below the reference's own k-th, in standard
deviations of the position's scores (``reference/routed.py``'s protocol).

``FAULT``, where a test or a tool sets it, plants one: ``"whole_context"``
(the window's mask left out: a window layer attends every earlier position),
``"no_gate"``, ``"rotate_all"`` (a full layer rotates all of a head).

Float32 under ``default_matmul_precision("highest")``.  Departures from the
published description: seeded weights; the experts not held and the
vocabulary rows not held are left out, as the program leaves them out; the two
forms the config only switches on (the gate, the router's scoring) as said
above; no cache, no rolling buffer, no chunks, no kernels, no batching.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

QUERY_ROWS = 512       # [heads, rows, T] float32 scores held at once
HEAD_COLUMNS = 32768   # columns of the head upcast at once
FAULT = None           # a test's or a tool's: see the docstring


def _f32(w) -> jax.Array:
    return w.astype(jnp.float32)


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)


def inv_freq(rope: Dict, dim: int):
    """(inverse frequencies [dim / 2], what cos and sin are multiplied by) of
    one kind's ``rope_parameters`` over ``dim`` rotated dimensions."""
    base = rope["rope_theta"]
    plain = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if rope.get("rope_type", "default") == "default":
        return plain, 1.0
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")
    factor, orig = rope["factor"], rope["original_max_position_embeddings"]

    def correction_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(base))

    # transformers' find_correction_range with truncate true (its default).
    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp   # the share of a dimension that is not interpolated
    blended = plain / factor * (1.0 - keep) + plain * keep
    return blended, rope.get("attention_factor", 0.1 * math.log(factor) + 1.0)


def rotate(x, rope: Dict, kind: str):
    """x [T, heads, D] at positions 0..T-1, the first ``partial_rotary_factor
    x D`` dimensions rotated (rotate-half pairing), the rest as they are."""
    T, _heads, D = x.shape
    share = 1.0 if (FAULT == "rotate_all" and kind == "full_attention") \
        else rope.get("partial_rotary_factor", 1.0)
    dim = int(D * share)
    freqs, amp = inv_freq(rope, dim)
    angles = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs   # [T, dim/2]
    emb = jnp.concatenate([angles, angles], -1)[:, None, :]
    cos, sin = jnp.cos(emb) * amp, jnp.sin(emb) * amp
    turn, rest = x[..., :dim], x[..., dim:]
    half = jnp.concatenate([-turn[..., dim // 2:], turn[..., :dim // 2]], -1)
    return jnp.concatenate([turn * cos + half * sin, rest], -1)


def attention(layer: Dict, hp: Dict, h, kind: str, heads: int):
    """One layer's attention of the normed input ``h`` [T, hidden]."""
    T = h.shape[0]
    K, hd = hp["num_key_value_heads"], hp["head_dim"]
    rope = hp["rope_parameters"][kind]
    q = rotate((h @ _f32(layer["q_proj"])).reshape(T, heads, hd), rope, kind)
    k = rotate((h @ _f32(layer["k_proj"])).reshape(T, K, hd), rope, kind)
    v = (h @ _f32(layer["v_proj"])).reshape(T, K, hd)
    k, v = (jnp.repeat(a, heads // K, axis=1) for a in (k, v))
    pos = jnp.arange(T)
    out = []
    for lo in range(0, T, QUERY_ROWS):
        rows = slice(lo, lo + QUERY_ROWS)
        seen = pos[None, :] <= pos[rows, None]
        if kind == "sliding_attention" and FAULT != "whole_context":
            seen &= pos[None, :] > pos[rows, None] - hp["sliding_window"]
        scores = jnp.einsum("qhd,khd->hqk", q[rows], k) * hd ** -0.5
        scores = jnp.where(seen[None], scores, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v))
    out = jnp.concatenate(out)                                  # [T, H, hd]
    if hp.get("gating") and FAULT != "no_gate":
        out = out * jax.nn.sigmoid(h @ _f32(layer["g_proj"]))[..., None]
    return out.reshape(T, heads * hd) @ _f32(layer["o_proj"])


def _route(layer: Dict, hp: Dict, h, choice=None):
    """(each expert's weight at a position [T, E] over the router's whole
    width, shortfall [T])."""
    top = hp["num_experts_per_tok"]
    s = jax.nn.sigmoid(h @ _f32(layer["router"]))
    who = jax.lax.top_k(s, top)[1]
    shortfall = jnp.zeros(h.shape[0])
    if choice is not None:
        who = jnp.clip(choice, 0, s.shape[1] - 1)
        ranked = jnp.sort(who, -1)
        bad = jnp.any(who != choice, -1) | jnp.any(
            ranked[:, 1:] == ranked[:, :-1], -1)
        own = jax.lax.top_k(s, top)[0][:, -1]
        weakest = jnp.take_along_axis(s, who, -1).min(-1)
        shortfall = jnp.where(bad, jnp.inf, (own - weakest) / jnp.std(s, -1))
    chosen = jnp.take_along_axis(s, who, -1)
    g = hp["moe_routed_scaling_factor"] * chosen / chosen.sum(-1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, who].set(g), shortfall


def _experts(layer: Dict, h, weights):
    def one(out, e):
        gate, up, down, weight = e
        return out + _swiglu(h, gate, up, down) * weight[:, None], None

    return jax.lax.scan(one, jnp.zeros_like(h), (
        layer["experts_gate"], layer["experts_up"], layer["experts_down"],
        weights.T))[0]


def routed_ffn(layer: Dict, hp: Dict, h, choice=None, held=None,
               shared: bool = True):
    """One sparse block's FFN(h) and its shortfall.  ``held`` = (first, count)
    of the router's experts whose weights ``layer`` stacks (default: the first
    ``hp["num_experts"]``); ``shared`` false leaves the shared expert out."""
    first, count = held or (0, hp["num_experts"])
    weights, short = _route(layer, hp, h, choice)
    out = _experts(layer, h, weights[:, first:first + count])
    if shared:
        out = out + _swiglu(h, layer["shared_gate"], layer["shared_up"],
                            layer["shared_down"])
    return out, short


def hidden(params: Dict, hp: Dict, tokens: jax.Array, choice=None):
    """tokens [T] -> (the residual stream after the last block [T, hidden],
    shortfall [sparse blocks, T]); ``choice`` [sparse blocks, T, k]."""
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed_tokens"][tokens])
        shortfall = []
        eps = hp["rms_norm_eps"]
        for i, layer in enumerate(params["layers"]):
            x = x + attention(
                layer, hp, _rms_norm(x, _f32(layer["input_layernorm"]), eps),
                hp["layer_types"][i], hp["num_attention_heads_per_layer"][i])
            h = _rms_norm(x, _f32(layer["post_attention_layernorm"]), eps)
            if hp["mlp_layer_types"][i] == "dense":
                x = x + _swiglu(h, layer["gate_proj"], layer["up_proj"],
                                layer["down_proj"])
                continue
            y, short = routed_ffn(
                layer, hp, h,
                None if choice is None else choice[len(shortfall)])
            shortfall.append(short)
            x = x + y
        return x, jnp.stack(shortfall)


def head(params: Dict, hp: Dict, x: jax.Array) -> jax.Array:
    """Residual stream [n, hidden] -> logits [n, held vocabulary]."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, _f32(params["norm"]), hp["rms_norm_eps"])
        w = params["lm_head"]
        return jnp.concatenate([
            x @ _f32(w[:, lo:lo + HEAD_COLUMNS])
            for lo in range(0, w.shape[1], HEAD_COLUMNS)], -1)


def forward(params: Dict, hp: Dict, tokens: jax.Array, choice=None,
            rows=None):
    """tokens [T] int32 -> logits [T, vocab] float32, or of the positions
    ``rows`` alone.  With ``choice`` [sparse blocks, T, k] int32, expert ids
    over the router's whole width, the blocks follow it and the result is
    ``(logits, shortfall [sparse blocks, T])``; without it the logits alone,
    which is what ``harness/compare.py`` expects of a reference."""
    x, shortfall = hidden(params, hp, tokens, choice)
    logits = head(params, hp, x if rows is None else x[rows])
    return logits if choice is None else (logits, shortfall)
