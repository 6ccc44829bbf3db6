"""The ``jamba`` model type's forward pass, written out plainly: selective
state-space (Mamba-1) mixers with an RMSNorm on the step size, ``B`` and
``C``, beside multi-query softmax attention without position encoding, over a
dense SwiGLU: the reference ``jamba2-3b`` is held to.

Pre-norm blocks (RMSNorm): ``r = x + Mix(norm1 x)``, ``y = r + MLP(norm2 r)``,
``MLP(v) = (SiLU(v W_g) . v W_u) W_d``; a final RMSNorm, then the embedding
transposed as the head (``tie_word_embeddings``).  Layer ``i`` is a softmax
layer where ``i % attn_layer_period == attn_layer_offset``, else a mixer.

Softmax layer: ``num_attention_heads`` query heads over
``num_key_value_heads`` key/value heads of ``head_dim``, no bias, NO position
encoding of any kind, scale ``head_dim^-1/2``, causal, full softmax
``QUERY_ROWS`` query rows at a time.

Mixer (``Di = mamba_expand x hidden_size``, ``N = mamba_d_state``, ``K =
mamba_d_conv``, ``R = mamba_dt_rank``), per token:

    ``[u_t, z_t] = x_t W_in``
    ``c_t = SiLU(sum_{j<K} w_conv[j] . u_{t-K+1+j} + b_conv)``   (zeros before the first)
    ``[d_t, B_t, C_t] = c_t W_x``;  ``d, B, C`` each RMS-normed, learned scale
    ``dt_t = softplus(d_t W_dt + b_dt)``;  ``A = -exp(A_log)``
    ``h_t = exp(dt_t (x) A) . h_{t-1} + (dt_t . c_t) (x) B_t``
    ``y_t = h_t C_t + D . c_t``;  out ``= (y_t . SiLU(z_t)) W_out``

**token by token**, a ``lax.scan``, so that it shares nothing with the kernels
it judges.  The state is float32; ``STATE_DTYPE``, where a tool sets it,
rounds it to that type after every token.  ``FAULT``, where a test or a tool
sets it, plants one: ``"no_inner_norm"`` (``d``, ``B``, ``C`` taken as they
come), ``"no_dt_bias"``, ``"conv_shifted"`` (the convolution reads one
position further back).

Float32 under ``default_matmul_precision("highest")``.  Departures from the
``jamba`` model type: seeded weights; ``A_log`` is kept ``[N, Di]``, the
published ``[Di, N]`` transposed (as the served module keeps it); the
convolution's taps are ``[K, Di]``; ``num_experts`` 1 is a dense MLP in every
layer and ``expert_layer_*`` are read by nothing; no cache, no chunks, no
kernels, no batching.
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
    T = h.shape[0]
    H, K, hd = (hp["num_attention_heads"], hp["num_key_value_heads"],
                hp["head_dim"])
    q = (h @ _f32(layer["q_proj"])).reshape(T, H, hd)
    k = (h @ _f32(layer["k_proj"])).reshape(T, K, hd)
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


def selective_scan(c, dt, B, C, A, state=None):
    """The recurrence, one token at a time.  ``c, dt`` [T, Di], ``B, C``
    [T, N], ``A`` [N, Di] -> (h_t C_t [T, Di], the last state [N, Di])."""
    def step(h, x):
        ct, dtt, Bt, Ct = x
        h = jnp.exp(dtt[None, :] * A) * h + (dtt * ct)[None, :] * Bt[:, None]
        if STATE_DTYPE is not None:
            h = h.astype(STATE_DTYPE).astype(jnp.float32)
        return h, Ct @ h

    if state is None:
        state = jnp.zeros(A.shape, jnp.float32)
    state, y = jax.lax.scan(step, state, (c, dt, B, C))
    return y, state


def mixer_inputs(layer: Dict, hp: Dict, h):
    """The normed input [T, hidden] -> (c, dt [T, Di], B, C [T, N], z)."""
    K, N, R = hp["mamba_d_conv"], hp["mamba_d_state"], hp["mamba_dt_rank"]
    T, eps = h.shape[0], hp["rms_norm_eps"]
    u, z = jnp.split(h @ _f32(layer["in_proj"]), 2, axis=-1)
    lead = K if FAULT == "conv_shifted" else K - 1
    padded = jnp.concatenate([jnp.zeros((lead, u.shape[1]), u.dtype), u])
    taps = _f32(layer["conv"])
    c = sum(padded[j:j + T] * taps[j] for j in range(K))
    if hp.get("mamba_conv_bias"):
        c = c + _f32(layer["conv_bias"])
    c = jax.nn.silu(c)
    d, B, C = jnp.split(c @ _f32(layer["x_proj"]), [R, R + N], axis=-1)
    if FAULT != "no_inner_norm":
        d = _rms_norm(d, _f32(layer["dt_norm"]), eps)
        B = _rms_norm(B, _f32(layer["b_norm"]), eps)
        C = _rms_norm(C, _f32(layer["c_norm"]), eps)
    d = d @ _f32(layer["dt_proj"])
    if FAULT != "no_dt_bias":
        d = d + _f32(layer["dt_bias"])
    return c, jax.nn.softplus(d), B, C, z


def _mamba_mix(layer: Dict, hp: Dict, h):
    c, dt, B, C, z = mixer_inputs(layer, hp, h)
    y, _state = selective_scan(c, dt, B, C, -jnp.exp(_f32(layer["A_log"])))
    y = (y + _f32(layer["D"]) * c) * jax.nn.silu(z)
    return y @ _f32(layer["o_proj"])


def _mlp(layer: Dict, h):
    return (jax.nn.silu(h @ _f32(layer["gate_proj"]))
            * (h @ _f32(layer["up_proj"]))) @ _f32(layer["down_proj"])


def is_attention(hp: Dict, i: int) -> bool:
    return i % hp["attn_layer_period"] == hp["attn_layer_offset"]


def hidden(params: Dict, hp: Dict, tokens: jax.Array):
    """tokens [T] -> the residual stream after the last block [T, hidden]."""
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed_tokens"][tokens])
        eps = hp["rms_norm_eps"]
        for i, layer in enumerate(params["layers"]):
            mix = _softmax_mix if is_attention(hp, i) else _mamba_mix
            x = x + mix(
                layer, hp, _rms_norm(x, _f32(layer["input_layernorm"]), eps))
            x = x + _mlp(layer, _rms_norm(
                x, _f32(layer["post_attention_layernorm"]), eps))
        return x


def head(params: Dict, hp: Dict, x: jax.Array) -> jax.Array:
    """Residual stream [n, hidden] -> logits [n, vocabulary]: the embedding
    transposed."""
    with jax.default_matmul_precision("highest"):
        x = _rms_norm(x, _f32(params["norm"]), hp["rms_norm_eps"])
        w = params["embed_tokens"]
        return jnp.concatenate([
            x @ _f32(w[lo:lo + HEAD_COLUMNS]).T
            for lo in range(0, w.shape[0], HEAD_COLUMNS)], -1)


def forward(params: Dict, hp: Dict, tokens: jax.Array) -> jax.Array:
    """tokens [T] int32 -> logits [T, vocab] float32."""
    return head(params, hp, hidden(params, hp, tokens))
