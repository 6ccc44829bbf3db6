"""``xing4_0``'s forward pass, written out plainly: four residual streams
mixed by a Sinkhorn-normalised matrix (manifold-constrained hyper-connections)
around a latent attention with a low-rank query path and routed experts held
whole: the reference ``xing4.0-29b-a4b-stage`` is held to.

**The residual path.**  A token carries ``n = hc_mult`` streams ``X [n, d]``.
Each sub-layer ``F`` (attention; then the dense SwiGLU or the routed block)
owns ``W [n d, 2 n + n^2]``, three scalars ``a`` and a bias a column ``b``::

    x~     = RMSNorm(vec X)                       (no learned scale, eps rms_norm_eps)
    p, q, r = split(x~ W)                          n, n, n^2 wide
    H_pre  = sigmoid(a_pre p + b_pre)
    H_post = 2 sigmoid(a_post q + b_post)
    M      = exp(clamp(a_res mat(r) + b_res, mhc_h_res_clamp_min, .._max))
    hc_sinkhorn_iters times:  M <- M / (rowsum M + hc_eps);  M <- M / (colsum M + hc_eps)
    h      = H_pre X;   y = F(RMSNorm_layer h);   X' = M X + H_post^T y

``assumed`` in the configuration's file, since no key of the config fixes
them: the embedding copied into every stream and the streams summed before
the final norm; rows before columns and ``hc_eps`` inside each divisor; the
clamp before ``exp``; a mapping a sub-layer (two a layer); ``mat(r)`` row-major.

**Attention**, token t, head h: ``c_q = RMSNorm(W_qa x)`` (``q_lora_rank``,
a learned scale), ``q = W_qb c_q`` (nope + rope wide; no norm a head);
``[c ; r] = W_kva x``, ``c <- RMSNorm(c)``; rotation is rotate-half
(``assumed``) with yarn frequencies read as ``deepseek_yarn`` (``assumed``);
``[k ; v] = W_kvb c`` per head; ``score = (q_nope . k + q_rope . r) *
(nope + rope)^-1/2 * m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``; causal
softmax; the heads' outputs through ``W_o``.  Expanded attention only,
``QUERY_ROWS`` query rows at a time: no cache, no absorbed form, no kernels.

**Feed-forward**: the first ``first_k_dense_replace`` layers a SwiGLU; the
others ``shared(x) + sum_{i in T} g_i E_i(x)``, ``s = sigmoid(W_r x)``, ``T``
the ``num_experts_per_tok`` largest of ``s + b`` (``noaux_tc``; ``n_group`` 1:
no groups), ``g_i = routed_scaling_factor s_i / sum_T s`` (``norm_topk_prob``).
All ``n_routed_experts`` are held.  The top k is a discrete choice two sound
computations can make differently at a near-tie, so ``forward`` can be handed
the program's own ``choice`` (``reference/routed.py``'s protocol) and says how
far its weakest expert lay below the reference's own k-th in ``shortfall``.

The multi-token-prediction block (``num_nextn_predict_layers``) is not part
of the model's logits and is not built.  Float32 under
``default_matmul_precision("highest")``; one expert and one block of the head
upcast at a time.  Nothing here comes from ``production_stack_tpu``.
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


# -- the residual path -------------------------------------------------------


def mapping(layer: Dict, hp: Dict, sub: str, X):
    """``X`` [T, n, d] -> (H_pre [T, n], H_post [T, n], H_res [T, n, n]) of
    the sub-layer ``sub`` ("attn" | "ffn")."""
    T, n, _d = X.shape
    x = X.reshape(T, -1)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                          + hp["rms_norm_eps"])
    z = x @ _f32(layer[f"hc_{sub}_w"])
    a, b = _f32(layer[f"hc_{sub}_alpha"]), _f32(layer[f"hc_{sub}_bias"])
    h_pre = jax.nn.sigmoid(a[0] * z[:, :n] + b[:n])
    h_post = 2 * jax.nn.sigmoid(a[1] * z[:, n:2 * n] + b[n:2 * n])
    R = (a[2] * z[:, 2 * n:] + b[2 * n:]).reshape(T, n, n)
    M = jnp.exp(jnp.clip(R, hp["mhc_h_res_clamp_min"],
                         hp["mhc_h_res_clamp_max"]))
    for _ in range(hp["hc_sinkhorn_iters"]):
        M = M / (M.sum(-1, keepdims=True) + hp["hc_eps"])
        M = M / (M.sum(-2, keepdims=True) + hp["hc_eps"])
    return h_pre, h_post, M


def sub_layer(layer: Dict, hp: Dict, sub: str, X, F):
    """``X' = H_res X + H_post^T F(H_pre X)``."""
    h_pre, h_post, h_res = mapping(layer, hp, sub, X)
    y = F(jnp.einsum("tn,tnd->td", h_pre, X))
    return jnp.einsum("tij,tjd->tid", h_res, X) + h_post[..., None] * y[:, None]


# -- attention ---------------------------------------------------------------


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

    c_q = _rms_norm(h @ _f32(layer["q_a_proj"]),
                    _f32(layer["q_a_layernorm"]), eps)
    q = (c_q @ _f32(layer["q_b_proj"])).reshape(T, H, nope + rope)
    kva = h @ _f32(layer["kv_a_proj"])
    c = _rms_norm(kva[:, :L], _f32(layer["kv_a_layernorm"]), eps)
    q_rope = _rope(q[..., nope:], pos, inv_freq, amp)
    r = _rope(kva[:, None, L:], pos, inv_freq, amp)[:, 0]
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


# -- the feed-forward halves -------------------------------------------------


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)


def _route(layer: Dict, hp: Dict, h, choice=None):
    """h [T, hidden] -> (each expert's share of a position [T, E], shortfall
    [T]).  With ``choice`` [T, k] those experts take the place of the
    reference's own, with this computation's scores; the shortfall is how far
    the weakest of them lies below the reference's own k-th selection score
    ``s + b``, in standard deviations of the position's selection scores: 0
    where the two agree, +inf for an id out of range or repeated."""
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


def routed_ffn(layer: Dict, hp: Dict, h, choice=None):
    """One routed block's FFN(h) and its shortfall: every expert over every
    position, as the definition reads, one expert's weights upcast at a
    time, and the shared expert."""
    shares, short = _route(layer, hp, h, choice)

    def one(out, e):
        gate, up, down, share = e
        return out + _swiglu(h, gate, up, down) * share[:, None], None

    out = jax.lax.scan(one, jnp.zeros_like(h), (
        layer["experts_gate"], layer["experts_up"], layer["experts_down"],
        shares.T))[0]
    return out + _swiglu(h, layer["shared_gate"], layer["shared_up"],
                         layer["shared_down"]), short


# -- the model ---------------------------------------------------------------


def hidden(params: Dict, hp: Dict, tokens: jax.Array, choice=None):
    """tokens [T] -> (the summed streams after the last block [T, hidden],
    shortfall [routed blocks, T]); ``choice`` [routed blocks, T, k]."""
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed_tokens"][tokens])
        X = jnp.broadcast_to(x[:, None], (x.shape[0], hp["hc_mult"],
                                          x.shape[1]))
        eps, shortfall = hp["rms_norm_eps"], []
        for i, layer in enumerate(params["layers"]):
            X = sub_layer(layer, hp, "attn", X, lambda h: _attention(
                layer, hp, _rms_norm(h, _f32(layer["input_layernorm"]), eps)))

            def feed(h):
                h = _rms_norm(h, _f32(layer["post_attention_layernorm"]), eps)
                if i < hp["first_k_dense_replace"]:
                    return _swiglu(h, layer["gate_proj"], layer["up_proj"],
                                   layer["down_proj"])
                y, short = routed_ffn(
                    layer, hp, h,
                    None if choice is None else choice[len(shortfall)])
                shortfall.append(short)
                return y

            X = sub_layer(layer, hp, "ffn", X, feed)
        return X.sum(1), jnp.stack(shortfall)


def head(params: Dict, hp: Dict, x: jax.Array) -> jax.Array:
    """Summed streams [n, hidden] -> logits [n, vocabulary]."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, _f32(params["norm"]), hp["rms_norm_eps"])
        w = params["lm_head"]
        return jnp.concatenate([
            x @ _f32(w[:, lo:lo + HEAD_COLUMNS])
            for lo in range(0, w.shape[1], HEAD_COLUMNS)], -1)


def forward(params: Dict, hp: Dict, tokens: jax.Array, choice=None,
            rows=None):
    """tokens [T] int32 -> ``(logits, shortfall)``: logits [T, vocabulary]
    float32, or of the positions ``rows`` alone, and shortfall [routed
    blocks, T].  With ``choice`` [routed blocks, T, k] int32 the blocks follow
    it; without, the shortfall is zero."""
    x, shortfall = hidden(params, hp, tokens, choice)
    return head(params, hp, x if rows is None else x[rows]), shortfall
