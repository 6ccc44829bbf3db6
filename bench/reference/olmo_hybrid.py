"""The ``olmo_hybrid`` model type's forward pass, written out plainly: three
gated delta-rule layers with a decay a head to one multi-head softmax layer
without position encoding, over a dense SwiGLU, in blocks that norm what a
sub-layer returns: the reference ``olmo-hybrid-7b-stage`` is held to.

Blocks (RMSNorm, the Olmo 2/3 family's reordered norm: assumed, the config has
no key for it): ``h = x + norm1(Mix(x))``, ``y = h + norm2(MLP(h))``, ``MLP(v)
= (SiLU(v W_g) . v W_u) W_d``; a final RMSNorm, an untied head.  Layer ``i`` of
the held layers is what ``hp["layer_types"][i]`` says.

``full_attention``: ``num_attention_heads`` query heads over
``num_key_value_heads`` key/value heads of ``head_dim`` (``hidden_size /
num_attention_heads`` where the config leaves it out), no bias; an RMSNorm
with a learned scale over the whole width of ``q`` and of ``k`` before the
split into heads (assumed); NO position encoding of any kind
(``rope_parameters.rope_theta`` null: assumed to mean none); scale
``head_dim^-1/2``, causal, full softmax ``QUERY_ROWS`` query rows at a time.

``linear_attention`` (``H = linear_num_value_heads`` heads, ``Dk =
linear_key_head_dim``, ``Dv = linear_value_head_dim``, ``K =
linear_conv_kernel_dim``): ``u = x [W_q ; W_k ; W_v]``; ``q, k, v =
SiLU(conv(u))``, ``conv`` causal and depthwise over the last ``K`` positions
(zeros before the first), no bias; ``q`` and ``k`` L2-normalised a head, ``q``
times ``Dk^-1/2``; ``g_t = -exp(A_log) softplus(x W_a + dt_bias)``, one number a
head; ``beta_t = 2 sigmoid(x W_b)`` (the 2: ``linear_allow_neg_eigval``); then
**token by token**, a ``lax.scan`` and not the chunkwise form, so that it
shares nothing with the kernels it judges:

    ``S' = e^{g_t} S_{t-1}``;  ``S_t = S' + beta_t k_t (v_t - S'^T k_t)^T``;
    ``o_t = S_t^T q_t``

``S`` ``[Dk, Dv]`` a head; out ``= (RMSNorm_head(o_t) . SiLU(x W_g)) W_o``.  The
state is float32; ``STATE_DTYPE``, where a tool sets it, rounds it to that type
after every token.  ``FAULT``, where a test or a tool sets it, plants one:
``"beta_not_doubled"`` (``beta = sigmoid``), ``"decay_a_channel"`` (the head's
decay applied with the channel's index as a factor: ``g_t (c + 1) / Dk`` down
the key channels, what reading the decay a channel would do to a head's one
number), ``"norm_before"`` (the block as ``x + Mix(norm(x))``, the norm in
front).

Float32 under ``default_matmul_precision("highest")``.  Departures from the
model type: seeded weights; the three projections of a delta-rule layer are one
matrix's column blocks and their three convolutions one ``[K, channels]``
array (as the served module keeps them); no cache, no chunks, no kernels, no
batching.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

QUERY_ROWS = 512       # [heads, rows, T] float32 scores held at once
HEAD_COLUMNS = 32768   # columns of the head upcast at once
STATE_DTYPE = None     # a tool's: the state rounded to it after every token
FAULT = None           # a test's or a tool's: see the docstring


def _f32(w) -> jax.Array:
    return w.astype(jnp.float32)


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _softmax_mix(layer: Dict, hp: Dict, h):
    T, eps = h.shape[0], hp["rms_norm_eps"]
    H, K, hd = (hp["num_attention_heads"], hp["num_key_value_heads"],
                hp["head_dim"])
    q = _rms_norm(h @ _f32(layer["q_proj"]), _f32(layer["q_norm"]), eps)
    k = _rms_norm(h @ _f32(layer["k_proj"]), _f32(layer["k_norm"]), eps)
    q, k = q.reshape(T, H, hd), k.reshape(T, K, hd)
    v = (h @ _f32(layer["v_proj"])).reshape(T, K, hd)
    k, v = (jnp.repeat(a, H // K, axis=1) for a in (k, v))
    pos = jnp.arange(T)
    out = []
    for lo in range(0, T, QUERY_ROWS):
        rows = slice(lo, lo + QUERY_ROWS)
        scores = jnp.einsum("qhd,khd->hqk", q[rows], k) * hd ** -0.5
        scores = jnp.where((pos[None, :] <= pos[rows, None])[None],
                           scores, -jnp.inf)
        out.append(jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v))
    return jnp.concatenate(out).reshape(T, H * hd) @ _f32(layer["o_proj"])


def delta_rule(q, k, v, g, beta, state=None):
    """The recurrence, one token at a time.  ``q, k`` [T, H, Dk], ``v`` [T, H,
    Dv], ``g, beta`` [T, H] -> (o [T, H, Dv], the last state [H, Dk, Dv])."""
    T, H, Dk = q.shape
    channel = (jnp.arange(Dk) + 1.0) / Dk

    def step(S, x):
        qt, kt, vt, gt, bt = x
        decay = jnp.exp(gt)[:, None, None]
        if FAULT == "decay_a_channel":
            decay = jnp.exp(gt[:, None] * channel)[:, :, None]
        S = decay * S
        u = bt[:, None] * (vt - jnp.einsum("hk,hkv->hv", kt, S))
        S = S + kt[:, :, None] * u[:, None, :]
        if STATE_DTYPE is not None:
            S = S.astype(STATE_DTYPE).astype(jnp.float32)
        return S, jnp.einsum("hk,hkv->hv", qt, S)

    if state is None:
        state = jnp.zeros((H, Dk, v.shape[-1]), jnp.float32)
    f = lambda a: a.astype(jnp.float32)
    state, o = jax.lax.scan(step, state, (f(q), f(k), f(v), f(g), f(beta)))
    return o, state


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def delta_inputs(layer: Dict, hp: Dict, h):
    """The block's input [T, hidden] -> (q, k [T, H, Dk], v [T, H, Dv], g,
    beta [T, H])."""
    H, Dk, Dv, K = (hp["linear_num_value_heads"], hp["linear_key_head_dim"],
                    hp["linear_value_head_dim"], hp["linear_conv_kernel_dim"])
    assert hp["linear_num_key_heads"] == H
    T = h.shape[0]
    u = h @ _f32(layer["qkv_proj"])
    padded = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), u.dtype), u])
    taps = _f32(layer["conv"])
    mixed = jax.nn.silu(sum(padded[j:j + T] * taps[j] for j in range(K)))
    q = mixed[:, :H * Dk].reshape(T, H, Dk)
    k = mixed[:, H * Dk:2 * H * Dk].reshape(T, H, Dk)
    v = mixed[:, 2 * H * Dk:].reshape(T, H, Dv)
    q, k = _l2(q) * Dk ** -0.5, _l2(k)
    g = -jnp.exp(_f32(layer["A_log"])) * jax.nn.softplus(
        h @ _f32(layer["a_proj"]) + _f32(layer["dt_bias"]))
    beta = jax.nn.sigmoid(h @ _f32(layer["b_proj"]))
    if hp.get("linear_allow_neg_eigval") and FAULT != "beta_not_doubled":
        beta = 2.0 * beta
    return q, k, v, g, beta


def _delta_mix(layer: Dict, hp: Dict, h):
    T = h.shape[0]
    o, _state = delta_rule(*delta_inputs(layer, hp, h))
    o = _rms_norm(o, _f32(layer["o_norm"]), hp["rms_norm_eps"])
    gate = jax.nn.silu(h @ _f32(layer["g_proj"]))
    return (o.reshape(T, -1) * gate) @ _f32(layer["o_proj"])


def _mlp(layer: Dict, h):
    return (jax.nn.silu(h @ _f32(layer["gate_proj"]))
            * (h @ _f32(layer["up_proj"]))) @ _f32(layer["down_proj"])


def hidden(params: Dict, hp: Dict, tokens: jax.Array):
    """tokens [T] -> the residual stream after the last block [T, hidden]."""
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed_tokens"][tokens])
        eps = hp["rms_norm_eps"]
        for kind, layer in zip(hp["layer_types"], params["layers"]):
            mix = _softmax_mix if kind == "full_attention" else _delta_mix
            n1 = _f32(layer["post_attention_layernorm"])
            n2 = _f32(layer["post_feedforward_layernorm"])
            if FAULT == "norm_before":
                x = x + mix(layer, hp, _rms_norm(x, n1, eps))
                x = x + _mlp(layer, _rms_norm(x, n2, eps))
            else:
                x = x + _rms_norm(mix(layer, hp, x), n1, eps)
                x = x + _rms_norm(_mlp(layer, x), n2, eps)
        return x


def head(params: Dict, hp: Dict, x: jax.Array) -> jax.Array:
    """Residual stream [n, hidden] -> logits [n, vocabulary]."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, _f32(params["norm"]), hp["rms_norm_eps"])
        w = params["lm_head"]
        return jnp.concatenate([
            x @ _f32(w[:, lo:lo + HEAD_COLUMNS])
            for lo in range(0, w.shape[1], HEAD_COLUMNS)], -1)


def forward(params: Dict, hp: Dict, tokens: jax.Array) -> jax.Array:
    """tokens [T] int32 -> logits [T, vocab] float32."""
    return head(params, hp, hidden(params, hp, tokens))
