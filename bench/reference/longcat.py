"""LongCat-Flash's language model, written out plainly: the reference
``longcat-flash-omni-ep32`` is held to (arXiv:2509.01322 and the released
modelling code, which the source's ``config.json`` carries the flags for).

One layer, ``x`` the residual, RMSNorm with a learned scale, no biases:

    ``a = x + Attn_0(norm_a0 x)``
    ``u = norm_f0 a``
    ``m = MoE(u)``                 the shortcut: taken up only at the end
    ``b = a + FFN_0(u)``           dense SwiGLU of ``ffn_hidden_size``
    ``c = b + Attn_1(norm_a1 b)``
    ``y = c + FFN_1(norm_f1 c) + m``

then a final RMSNorm and an untied head.  A layer's weights: ``attn`` and
``ffn``, a list each, an entry an attention (with its ``input_layernorm``) and
the dense FFN after it (with its ``post_attention_layernorm``), and the routed
FFN's ``router``, ``router_bias`` and ``experts_*`` stacks.

Attention (both alike), token t, head h: ``q = W_qb RMSNorm(W_qa x)`` split
``qk_nope_head_dim`` + ``qk_rope_head_dim``, times ``(hidden_size /
q_lora_rank)^1/2`` where ``mla_scale_q_lora``; ``[c ; r] = W_kva x``,
``c <- RMSNorm(c)`` times ``(hidden_size / kv_lora_rank)^1/2`` where
``mla_scale_kv_lora``; the rotary parts rotated (rotate-half, plain
frequencies of base ``rope_theta``: the source has no ``rope_scaling``);
``[k ; v] = W_kvb c`` per head; ``score = (q_nope . k + q_rope . r) (nope +
rope)^-1/2``; causal softmax; the heads' outputs through ``W_o``.  Expanded
attention only, ``QUERY_ROWS`` query rows at a time: no cache, no absorbed
form, no kernels, no batching.

MoE: ``p = softmax(W_r u)`` over ``published n_routed_experts +
zero_expert_num`` outputs; ``T`` the ``moe_topk`` largest of ``p + b``;
``g_i = routed_scaling_factor p_i``, not renormalised; ``MoE(u) = sum_{i in T,
i real} g_i E_i(u) + (sum_{i in T, i identity} g_i) u``: ids from the published
real width on name an identity (``zero_expert_type: identity``).
``hp["n_routed_experts"]`` is how many of the real experts, the first, are held
here; what the others would have added is left out, as the program that holds a
share leaves it out (``held`` overrides the range, ``identity`` false leaves
the identity term out: the test that adds the shares up counts it once).

``forward`` can be handed the program's own ``choice`` (``reference/routed.py``'s
protocol) and then says how far the weakest expert handed in lay below its own
k-th in ``shortfall``; a selection score is ``p + b``.

Float32 under ``default_matmul_precision("highest")``; one expert and one block
of the head upcast at a time.  Without ``choice`` the result is the logits
alone, as every reference's here.  ``FAULT`` makes this reference wrong in one
way (``tools/compare_rows_longcat.py``): ``no_identity`` (the identity experts
add nothing) | ``renormalised`` (the chosen shares divided by their sum) |
``no_scale`` (both latent scale factors left out).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

QUERY_ROWS = 512       # [heads, rows, T] float32 scores held at once
HEAD_COLUMNS = 32768   # columns of the head upcast at once
FAULT = None


def _f32(w) -> jax.Array:
    return w.astype(jnp.float32)


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _rope(x, positions, theta):
    """x [T, heads, dim]; rotate-half: dimension i pairs with i + dim/2."""
    dim = x.shape[-1]
    inv_freq = theta ** -(jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, -1)[:, None, :]
    half = dim // 2
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]], -1) * sin


def _attention(w: Dict, hp: Dict, h):
    T, H = h.shape[0], hp["num_attention_heads"]
    L, nope, rope, vd = (hp["kv_lora_rank"], hp["qk_nope_head_dim"],
                         hp["qk_rope_head_dim"], hp["v_head_dim"])
    eps, scaled = hp["rms_norm_eps"], FAULT != "no_scale"
    pos = jnp.arange(T)
    q = _rms_norm(h @ _f32(w["q_a_proj"]), _f32(w["q_a_layernorm"]), eps)
    q = (q @ _f32(w["q_b_proj"])).reshape(T, H, nope + rope)
    if hp["mla_scale_q_lora"] and scaled:
        q = q * (hp["hidden_size"] / hp["q_lora_rank"]) ** 0.5
    kva = h @ _f32(w["kv_a_proj"])
    c = _rms_norm(kva[:, :L], _f32(w["kv_a_layernorm"]), eps)
    if hp["mla_scale_kv_lora"] and scaled:
        c = c * (hp["hidden_size"] / L) ** 0.5
    q_rope = _rope(q[..., nope:], pos, hp["rope_theta"])
    r = _rope(kva[:, None, L:], pos, hp["rope_theta"])[:, 0]
    kv = (c @ _f32(w["kv_b_proj"])).reshape(T, H, nope + vd)
    k, v = kv[..., :nope], kv[..., nope:]
    out = []
    for lo in range(0, T, QUERY_ROWS):
        rows = slice(lo, lo + QUERY_ROWS)
        scores = (jnp.einsum("qhd,khd->hqk", q[rows, :, :nope], k)
                  + jnp.einsum("qhd,kd->hqk", q_rope[rows], r)
                  ) * (nope + rope) ** -0.5
        scores = jnp.where((pos[None, :] <= pos[rows, None])[None],
                           scores, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v))
    return jnp.concatenate(out).reshape(T, H * vd) @ _f32(w["o_proj"])


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)


def real_width(hp: Dict) -> int:
    """The router's outputs that name a SwiGLU expert; the
    ``zero_expert_num`` after them name an identity."""
    return hp["published"]["n_routed_experts"]


def route(layer: Dict, hp: Dict, u, choice=None):
    """u [T, hidden] -> (each output's share of a position [T, width] over the
    router's whole width, identities included; shortfall [T]).  With
    ``choice`` [T, k] those outputs take the place of the reference's own, with
    this computation's scores; the shortfall is how far the weakest of them
    lies below the reference's own k-th selection score ``p + b``, in standard
    deviations of the position's selection scores: 0 where the two agree,
    +inf for an id out of range or repeated."""
    top = hp["moe_topk"]
    p = jax.nn.softmax(u @ _f32(layer["router"]), -1)
    assert p.shape[1] == real_width(hp) + hp["zero_expert_num"], p.shape
    select = p + _f32(layer["router_bias"])
    who = jax.lax.top_k(select, top)[1]
    shortfall = jnp.zeros(u.shape[0])
    if choice is not None:
        who = jnp.clip(choice, 0, p.shape[1] - 1)
        ranked = jnp.sort(who, -1)
        bad = jnp.any(who != choice, -1) | jnp.any(
            ranked[:, 1:] == ranked[:, :-1], -1)
        own = jax.lax.top_k(select, top)[0][:, -1]
        weakest = jnp.take_along_axis(select, who, -1).min(-1)
        shortfall = jnp.where(
            bad, jnp.inf, (own - weakest) / jnp.std(select, -1))
    g = hp["routed_scaling_factor"] * jnp.take_along_axis(p, who, -1)
    if FAULT == "renormalised":
        g = g / g.sum(-1, keepdims=True) * hp["routed_scaling_factor"]
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(p).at[rows, who].set(g), shortfall


def _experts(layer: Dict, u, shares):
    """sum over the experts at hand of share * E(u): every expert over every
    position, as the definition reads; ``shares`` [T, experts at hand]."""
    def one(out, e):
        gate, up, down, share = e
        return out + _swiglu(u, gate, up, down) * share[:, None], None

    return jax.lax.scan(one, jnp.zeros_like(u), (
        layer["experts_gate"], layer["experts_up"], layer["experts_down"],
        shares.T))[0]


def moe(layer: Dict, hp: Dict, u, choice=None, held=None,
        identity: bool = True):
    """One layer's ``MoE(u)``, its shortfall [T] and the identity picks a
    position [T].  ``held`` = (first, count) of the real experts whose weights
    ``layer`` stacks (default: the first ``hp["n_routed_experts"]``);
    ``identity`` false leaves the identity term out."""
    first, count = held or (0, hp["n_routed_experts"])
    shares, short = route(layer, hp, u, choice)
    out = _experts(layer, u, shares[:, first:first + count])
    zero = shares[:, real_width(hp):]
    if identity and FAULT != "no_identity":
        out = out + zero.sum(-1, keepdims=True) * u
    return out, short, (zero > 0).sum(-1)


def hidden(params: Dict, hp: Dict, tokens: jax.Array, choice=None):
    """tokens [T] -> (the residual stream after the last layer [T, hidden],
    shortfall [layers, T], identity picks [layers, T]); ``choice`` [layers,
    T, k]."""
    eps = hp["rms_norm_eps"]
    norm = lambda x, w: _rms_norm(x, _f32(w), eps)
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed_tokens"][tokens])
        shortfall, zeros = [], []
        for i, layer in enumerate(params["layers"]):
            (attn0, attn1), (ffn0, ffn1) = layer["attn"], layer["ffn"]
            a = x + _attention(attn0, hp, norm(x, attn0["input_layernorm"]))
            u = norm(a, ffn0["post_attention_layernorm"])
            m, short, picked = moe(
                layer, hp, u, None if choice is None else choice[i])
            b = a + _swiglu(u, ffn0["gate_proj"], ffn0["up_proj"],
                            ffn0["down_proj"])
            c = b + _attention(attn1, hp, norm(b, attn1["input_layernorm"]))
            v = norm(c, ffn1["post_attention_layernorm"])
            x = c + _swiglu(v, ffn1["gate_proj"], ffn1["up_proj"],
                            ffn1["down_proj"]) + m
            shortfall.append(short)
            zeros.append(picked)
        return x, jnp.stack(shortfall), jnp.stack(zeros)


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
    ``rows`` alone.  With ``choice`` [layers, T, k] int32, ids over the
    router's whole width, the layers follow it and the result is ``(logits,
    shortfall [layers, T])``."""
    x, shortfall, _zeros = hidden(params, hp, tokens, choice)
    logits = head(params, hp, x if rows is None else x[rows])
    return logits if choice is None else (logits, shortfall)
