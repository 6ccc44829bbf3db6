"""``sarvam_mla``'s forward pass, written out plainly: latent attention over
routed experts, the reference ``sarvam-105b-ep4`` is held to.

Pre-norm blocks (RMSNorm): ``h = x + Attn(norm1 x)``, ``y = h + FFN(norm2 h)``.

Attention, token t, head h: ``q = W_q x`` (nope + rope wide);
``[c ; r] = W_kva x``; ``c <- RMSNorm(c)``; with ``use_qk_norm`` each query
head is RMS-normed over its whole width and ``r`` over its own before the
rotary parts are rotated (``assumed`` in the configuration's file); rotation
is rotate-half with ``deepseek_yarn`` frequencies; ``[k ; v] = W_kvb c`` per
head; ``score = (q_nope . k + q_rope . r) * (nope + rope)^-1/2 * m^2``,
``m = 0.1 * mscale_all_dim * ln(factor) + 1``; causal softmax; the heads'
outputs through ``W_o``.  Full (expanded) attention only, ``QUERY_ROWS``
query rows at a time: no cache, no absorbed form, no kernels, no batching.

Feed-forward: the first ``first_k_dense_replace`` layers a SwiGLU; the others
``shared(x) + sum_{i in T} g_i E_i(x)`` with ``s = sigmoid(W_r x)``, ``T`` the
``num_experts_per_tok`` largest of ``s + b`` and
``g_i = routed_scaling_factor * s_i / sum_{j in T} s_j``.  The router keeps
its published width (``hp["published"]["num_experts"]``); ``hp["num_experts"]``
is how many of the experts, the first, are held here, and what the others
would have added is left out, as the program that holds a share leaves it
out (``held`` overrides the range: the test that adds the four shares up
hands each share's in turn).

The top k is a discrete choice that two sound computations in different
precisions can make differently at a near-tie, so ``forward`` can be handed
the program's own ``choice`` (``reference/routed.py``'s protocol): each
routed block then uses those experts with the shares its own float32 scores
give them, and says how far the weakest lay below its own k-th in
``shortfall``.  A selection score is ``s + b``.

Float32 under ``default_matmul_precision("highest")``; one expert and one
block of the head upcast at a time, so that the real widths fit beside the
engine's weights.  Departure from the issue's signature: without ``choice``
the result is the logits alone, as ``reference/routed.py``'s, which is what
``harness/compare.py`` expects of a reference.
"""

from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp

QUERY_ROWS = 512       # [heads, rows, T] float32 scores held at once
HEAD_COLUMNS = 32768   # columns of the head upcast at once


def _f32(w) -> jax.Array:
    return w.astype(jnp.float32)


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _inv_freq(dim: int, theta: float, scaling: Optional[Dict]):
    """``deepseek_yarn``: a dimension's frequency is the plain one where it
    turns more than ``beta_fast`` times over the original context, the plain
    one over ``factor`` where it turns fewer than ``beta_slow`` times, and a
    linear blend over the dimensions between."""
    plain = theta ** -(jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if not scaling:
        return plain
    orig = scaling["original_max_position_embeddings"]

    def dim_of(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dim_of(scaling["beta_fast"])), 0)
    high = min(math.ceil(dim_of(scaling["beta_slow"])), dim - 1)
    blend = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low)
        / (0.001 if high == low else high - low), 0, 1)
    return (1 - blend) * plain + blend * plain / scaling["factor"]


def _rope(x, positions, inv_freq, amp):
    """x [T, heads, dim]; rotate-half: dimension i pairs with i + dim/2."""
    half = x.shape[-1] // 2
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, None, :] * amp
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, None, :] * amp
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]], -1) * sin


def _attention(layer: Dict, hp: Dict, h):
    T, H = h.shape[0], hp["num_attention_heads"]
    L, nope, rope, vd = (hp["kv_lora_rank"], hp["qk_nope_head_dim"],
                         hp["qk_rope_head_dim"], hp["v_head_dim"])
    eps, scaling = hp["rms_norm_eps"], hp.get("rope_scaling") or {}
    factor = scaling.get("factor", 1)
    m = _yarn_mscale(factor, scaling.get("mscale_all_dim", 0))
    amp = _yarn_mscale(factor, scaling.get("mscale", 1)) / m
    inv_freq = _inv_freq(rope, hp["rope_theta"], scaling)
    pos = jnp.arange(T)

    q = (h @ _f32(layer["q_proj"])).reshape(T, H, nope + rope)
    kva = h @ _f32(layer["kv_a_proj"])
    c = _rms_norm(kva[:, :L], _f32(layer["kv_a_layernorm"]), eps)
    r = kva[:, None, L:]
    if hp.get("use_qk_norm"):
        q = _rms_norm(q, _f32(layer["q_norm"]), eps)
        r = _rms_norm(r, _f32(layer["k_rope_norm"]), eps)
    q_rope = _rope(q[..., nope:], pos, inv_freq, amp)
    r = _rope(r, pos, inv_freq, amp)[:, 0]
    kv = (c @ _f32(layer["kv_b_proj"])).reshape(T, H, nope + vd)
    k, v = kv[..., :nope], kv[..., nope:]
    scale = (nope + rope) ** -0.5 * m * m
    out = []
    for lo in range(0, T, QUERY_ROWS):
        rows = slice(lo, lo + QUERY_ROWS)
        scores = (jnp.einsum("qhd,khd->hqk", q[rows, :, :nope], k)
                  + jnp.einsum("qhd,kd->hqk", q_rope[rows], r)) * scale
        scores = jnp.where((pos[None, :] <= pos[rows, None])[None],
                           scores, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v))
    return jnp.concatenate(out).reshape(T, H * vd) @ _f32(layer["o_proj"])


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)


def _route(layer: Dict, hp: Dict, h, choice=None):
    """h [T, hidden] -> (each expert's share of a position [T, E] over the
    router's whole width, shortfall [T]).  With ``choice`` [T, k] those
    experts take the place of the reference's own, with this computation's
    scores; the shortfall is how far the weakest of them lies below the
    reference's own k-th selection score ``s + b``, in standard deviations
    of the position's selection scores: 0 where the two agree, +inf for an
    id out of range or repeated."""
    top = hp["num_experts_per_tok"]
    s = jax.nn.sigmoid(h @ _f32(layer["router"]))
    select = s + _f32(layer["router_bias"])
    who = jax.lax.top_k(select, top)[1]
    shortfall = jnp.zeros(h.shape[0])
    if choice is not None:
        who = jnp.clip(choice, 0, s.shape[1] - 1)
        ranked = jnp.sort(who, -1)
        bad = jnp.any(who != choice, -1) | jnp.any(
            ranked[:, 1:] == ranked[:, :-1], -1)
        own = jax.lax.top_k(select, top)[0][:, -1]
        weakest = jnp.take_along_axis(select, who, -1).min(-1)
        shortfall = jnp.where(
            bad, jnp.inf, (own - weakest) / jnp.std(select, -1))
    chosen = jnp.take_along_axis(s, who, -1)
    g = hp["routed_scaling_factor"] * chosen / chosen.sum(-1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, who].set(g), shortfall


def _experts(layer: Dict, h, shares):
    """sum over the experts at hand of share * E(h): every expert over every
    position, as the definition reads; ``shares`` [T, experts at hand]."""
    def one(out, e):
        gate, up, down, share = e
        return out + _swiglu(h, gate, up, down) * share[:, None], None

    return jax.lax.scan(one, jnp.zeros_like(h), (
        layer["experts_gate"], layer["experts_up"], layer["experts_down"],
        shares.T))[0]


def routed_ffn(layer: Dict, hp: Dict, h, choice=None, held=None,
               shared: bool = True):
    """One routed block's FFN(h) and its shortfall.  ``held`` = (first, count)
    of the router's experts whose weights ``layer`` stacks (default: the first
    ``hp["num_experts"]``); ``shared`` false leaves the shared expert out."""
    first, count = held or (0, hp["num_experts"])
    shares, short = _route(layer, hp, h, choice)
    out = _experts(layer, h, shares[:, first:first + count])
    if shared:
        out = out + _swiglu(h, layer["shared_gate"], layer["shared_up"],
                            layer["shared_down"])
    return out, short


def hidden(params: Dict, hp: Dict, tokens: jax.Array, choice=None):
    """tokens [T] -> (the residual stream after the last block [T, hidden],
    shortfall [routed blocks, T]); ``choice`` [routed blocks, T, k]."""
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed_tokens"][tokens])
        shortfall = []
        for i, layer in enumerate(params["layers"]):
            eps = hp["rms_norm_eps"]
            x = x + _attention(
                layer, hp, _rms_norm(x, _f32(layer["input_layernorm"]), eps))
            h = _rms_norm(x, _f32(layer["post_attention_layernorm"]), eps)
            if i < hp["first_k_dense_replace"]:
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
    ``rows`` alone.  With ``choice`` [routed blocks, T, k] int32, expert ids
    over the router's whole width, the blocks follow it and the result is
    ``(logits, shortfall [routed blocks, T])``."""
    x, shortfall = hidden(params, hp, tokens, choice)
    logits = head(params, hp, x if rows is None else x[rows])
    return logits if choice is None else (logits, shortfall)
