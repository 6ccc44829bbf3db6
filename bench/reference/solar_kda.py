"""``solar_open2``'s forward pass, written out plainly: three gated delta-rule
layers to one gated softmax layer without position encoding, over routed
experts: the reference ``solar-open2-250b-ep8`` is held to.

Pre-norm blocks (RMSNorm): ``h = x + Mix(norm1 x)``, ``y = h + FFN(norm2 h)``.
Layer ``i`` of the held layers is a softmax layer where ``i`` is in
``hp["gqa_layers"]``, else a delta-rule layer.

Softmax layer: ``num_attention_heads`` query heads over
``num_key_value_heads`` key/value heads of ``head_dim``, NO rotary
(``use_rope`` false), scale ``head_dim^-1/2``, causal, full softmax
``QUERY_ROWS`` query rows at a time; with ``use_gqa_gate`` the heads' output
times ``sigmoid(x W_gate)`` elementwise (assumed: a column an output channel)
before ``W_o``.

Delta-rule layer (``linear_attn_config``: heads ``H`` of ``D``, kernel ``K``):
``u = x [W_q ; W_k ; W_v]``; ``q, k, v = SiLU(conv(u))``, ``conv`` causal and
depthwise over the last ``K`` positions (zeros before the first); ``q`` and
``k`` L2-normalised a head, ``q`` times ``D^-1/2``;
``g_t = -exp(A_log) softplus(x W_fa W_fb + dt_bias)``;
``beta_t = 2 sigmoid(x W_beta)`` (the 2: ``kda_allow_neg_eigval``); then
**token by token**, a ``lax.scan`` and not the chunkwise form, so that it
shares nothing with the kernel it judges:

    ``S' = Diag(exp g_t) S_{t-1}``;  ``S_t = S' + beta_t k_t (v_t - S'^T k_t)^T``;
    ``o_t = S_t^T q_t``

(the same ``S_t = (I - beta k k^T) Diag(exp g) S_{t-1} + beta k v^T``); out
``= (RMSNorm_head(o_t) * sigmoid(x W_ga W_gb)) W_o``.  The state is float32;
``STATE_DTYPE``, where a tool sets it, rounds it to that type after every
token: the reading ``compare.logits_rtol`` has to refuse.

Feed-forward, every layer: ``shared(x) + sum_{i in T} g_i E_i(x)``,
``s = sigmoid(W_r x)``, ``T`` the ``num_experts_per_tok`` largest of ``s + b``,
``g_i = routed_scaling_factor s_i / sum_T s`` (``reference/sarvam_mla.py``'s
router, its protocol for a followed ``choice`` and its ``shortfall``).  The
router keeps its published width ``hp["published"]["n_routed_experts"]``;
``hp["n_routed_experts"]`` is how many, the first, are held; ``held`` overrides
the range (the test that adds the eight shares up).

Float32 under ``default_matmul_precision("highest")``.  Departures from the
published description: seeded weights; the experts not held and the
vocabulary rows not held are left out, as the program leaves them out; no
cache, no chunks, no kernels, no batching.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

QUERY_ROWS = 512       # [heads, rows, T] float32 scores held at once
HEAD_COLUMNS = 32768   # columns of the head upcast at once
STATE_DTYPE = None     # a tool's: the state rounded to it after every token


def _f32(w) -> jax.Array:
    return w.astype(jnp.float32)


def _rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * weight


def _swiglu(h, gate, up, down):
    return (jax.nn.silu(h @ _f32(gate)) * (h @ _f32(up))) @ _f32(down)


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
    out = jnp.concatenate(out).reshape(T, H * hd)
    if hp.get("use_gqa_gate"):
        out = out * jax.nn.sigmoid(h @ _f32(layer["gate_proj"]))
    return out @ _f32(layer["o_proj"])


def delta_rule(q, k, v, g, beta, state=None):
    """The recurrence, one token at a time.  ``q, k, v, g`` [T, H, D], ``beta``
    [T, H] -> (o [T, H, D], the last state [H, D, D])."""
    T, H, D = q.shape

    def step(S, x):
        qt, kt, vt, gt, bt = x
        S = jnp.exp(gt)[:, :, None] * S
        u = bt[:, None] * (vt - jnp.einsum("hk,hkv->hv", kt, S))
        S = S + kt[:, :, None] * u[:, None, :]
        if STATE_DTYPE is not None:
            S = S.astype(STATE_DTYPE).astype(jnp.float32)
        return S, jnp.einsum("hk,hkv->hv", qt, S)

    if state is None:
        state = jnp.zeros((H, D, D), jnp.float32)
    f = lambda a: a.astype(jnp.float32)
    state, o = jax.lax.scan(step, state, (f(q), f(k), f(v), f(g), f(beta)))
    return o, state


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def delta_inputs(layer: Dict, hp: Dict, h):
    """The normed input [T, hidden] -> (q, k, v, g [T, H, D], beta [T, H])."""
    lin = hp["linear_attn_config"]
    H, D, K = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    T = h.shape[0]
    u = h @ _f32(layer["qkv_proj"])
    padded = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), u.dtype), u])
    taps = _f32(layer["conv"])
    mixed = jax.nn.silu(sum(padded[j:j + T] * taps[j] for j in range(K)))
    q, k, v = (mixed[:, i * H * D:(i + 1) * H * D].reshape(T, H, D)
               for i in range(3))
    q, k = _l2(q) * D ** -0.5, _l2(k)
    f = (h @ _f32(layer["f_a"])) @ _f32(layer["f_b"])
    g = -jnp.exp(_f32(layer["A_log"]))[None, :, None] * jax.nn.softplus(
        f + _f32(layer["dt_bias"])).reshape(T, H, D)
    beta = jax.nn.sigmoid(h @ _f32(layer["beta_proj"]))
    if hp.get("kda_allow_neg_eigval"):
        beta = 2.0 * beta
    return q, k, v, g, beta


def _delta_mix(layer: Dict, hp: Dict, h):
    T = h.shape[0]
    o, _state = delta_rule(*delta_inputs(layer, hp, h))
    o = _rms_norm(o, _f32(layer["o_norm"]), hp["rms_norm_eps"])
    gate = jax.nn.sigmoid((h @ _f32(layer["g_a"])) @ _f32(layer["g_b"]))
    return (o.reshape(T, -1) * gate) @ _f32(layer["o_proj"])


def _route(layer: Dict, hp: Dict, h, choice=None):
    """``reference/sarvam_mla.py: _route``: (each expert's share of a position
    [T, E] over the router's whole width, shortfall [T])."""
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
    def one(out, e):
        gate, up, down, share = e
        return out + _swiglu(h, gate, up, down) * share[:, None], None

    return jax.lax.scan(one, jnp.zeros_like(h), (
        layer["experts_gate"], layer["experts_up"], layer["experts_down"],
        shares.T))[0]


def routed_ffn(layer: Dict, hp: Dict, h, choice=None, held=None,
               shared: bool = True):
    """One block's FFN(h) and its shortfall.  ``held`` = (first, count) of the
    router's experts whose weights ``layer`` stacks (default: the first
    ``hp["n_routed_experts"]``); ``shared`` false leaves the shared expert
    out."""
    first, count = held or (0, hp["n_routed_experts"])
    shares, short = _route(layer, hp, h, choice)
    out = _experts(layer, h, shares[:, first:first + count])
    if shared:
        out = out + _swiglu(h, layer["shared_gate"], layer["shared_up"],
                            layer["shared_down"])
    return out, short


def hidden(params: Dict, hp: Dict, tokens: jax.Array, choice=None):
    """tokens [T] -> (the residual stream after the last block [T, hidden],
    shortfall [blocks, T]); ``choice`` [blocks, T, k]."""
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed_tokens"][tokens])
        shortfall = []
        eps = hp["rms_norm_eps"]
        for i, layer in enumerate(params["layers"]):
            mix = _softmax_mix if i in hp["gqa_layers"] else _delta_mix
            x = x + mix(
                layer, hp, _rms_norm(x, _f32(layer["input_layernorm"]), eps))
            h = _rms_norm(x, _f32(layer["post_attention_layernorm"]), eps)
            y, short = routed_ffn(
                layer, hp, h, None if choice is None else choice[i])
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
    ``rows`` alone.  With ``choice`` [blocks, T, k] int32, expert ids over the
    router's whole width, the blocks follow it and the result is ``(logits,
    shortfall [blocks, T])``; without it the logits alone, which is what
    ``harness/compare.py`` expects of a reference."""
    x, shortfall = hidden(params, hp, tokens, choice)
    logits = head(params, hp, x if rows is None else x[rows])
    return logits if choice is None else (logits, shortfall)
