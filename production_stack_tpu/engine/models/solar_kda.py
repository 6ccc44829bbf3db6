"""Gated delta-rule linear attention beside gated softmax attention without
position encoding, over routed experts held by share (functional JAX): the
``solar_open2`` architecture.

Pre-norm blocks, RMSNorm: ``h = x + Mix(norm1(x))``, ``y = h + FFN(norm2(h))``.
``cfg.layer_kinds`` names each layer's mix (a period of it is tiled over
``cfg.num_layers``); every layer's FFN is routed.

**``"gqa"``: softmax attention with no position encoding.**  ``cfg.num_heads``
query heads over ``cfg.num_kv_heads`` key/value heads, scale
``head_dim^-1/2``, causal, NO rotary (``cfg.use_rope`` False: the order of the
keys reaches a query through the mask alone).  With ``cfg.use_gqa_gate`` the
heads' output is multiplied elementwise by ``sigmoid(x W_gate)``, a column an
output channel, before ``W_o``.  K and V lie in pages as ``models/llama.py``'s
do and go through the same two kernels (``ops/attention.py``).

**``"kda"``: the gated delta rule with a decay a channel.**  ``u = [x W_q ;
x W_k ; x W_v]``; ``q, k, v = SiLU(conv(u))``, the convolution causal and
depthwise over the last ``cfg.linear_conv_kernel`` positions; ``q`` and ``k``
L2-normalised a head, ``q`` scaled by ``D^-1/2``;
``g_t = -exp(A_log) softplus(x W_fa W_fb + dt_bias)`` a head and a key
channel; ``beta_t = 2 sigmoid(x W_beta)`` a head (the 2 where
``cfg.kda_allow_neg_eigval``);

    ``S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T``

``S`` ``[D, D]`` a head, float32; ``o_t = S_t^T q_t``; out ``= (RMSNorm_head(o_t)
* sigmoid(x W_ga W_gb)) W_o``.  **Such a layer keeps no keys**: a sequence owns
one *slot* of ``(S [heads, D, D] float32, the last kernel - 1 rows of u)``,
whatever its length.

**One cache tree, two kinds of state** (:func:`init_cache`): a ``(K, V)`` pair
of pages for a ``gqa`` layer, a ``(state, conv)`` pair of slots for a ``kda``
layer.  Pages are addressed by block ids as ever.  Slots are addressed by the
keyword arguments ``state_slot`` / ``state_from`` / ``snapshot_slot`` /
``snapshot_len`` of :func:`prefill` and ``state_slots`` of :func:`decode``: the
engine hands them (``kv/state_pool.py``).  A caller that hands none (the
benchmark's compare, which may pass the cache and nothing else) gets
:func:`default_slot`: the first block id of the row's table modulo the slots
there are; a chunk with ``cached_len == 0`` starts from zeros and a later one
goes on from its slot.

A padded slot of a chunk and a dead row of a decode batch are the identity on
the state (``beta`` 0, decay 1) and do not shift the convolution rows.

**Two kernels, two plain forms.**  Prefill runs the chunkwise form of the
recurrence, :data:`CHUNK` tokens at a time (:func:`kda_chunk_plain`; on a TPU
``ops/pallas/kda.py: kda_prefill_pallas``, a head's state in VMEM over the
whole call); decode one step (:func:`kda_step_plain`; ``kda_decode_pallas``,
a row's state read once and written once, in place).

**Routed FFN**: ``models/sarvam_mla.py``'s ``route`` / ``held_experts`` /
``_swiglu``, imported, with its counters (same names).

Offers the engine (``models/registry.py``): ``init_params``,
``quantize_params`` (identity), ``prefill``, ``decode``, ``init_cache``,
``cache_bytes_per_token`` (the ``gqa`` layers' alone), ``state_bytes_per_slot``,
``snapshot_stride``, ``param_specs``, ``attention_paths``, ``stats_names`` and, on
both steps, ``return_choice`` and ``return_stats``.  No ``mixed_step``, no
``encode``, no LoRA, no int8, no mesh: refused at boot by name.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from production_stack_tpu.engine.config import PAGED_KINDS, ModelConfig
from production_stack_tpu.engine.models.sarvam_mla import (
    ROUTING_STATS, STATS_MAX, _dot, _result, _swiglu, held_experts, route,
)
from production_stack_tpu.engine.ops import attention as attn_ops
from production_stack_tpu.engine.ops.layers import rms_norm

Params = Dict
# Tokens a chunk of the chunkwise form.  Inside a chunk the decays are
# referred to the chunk's start (exp(G_t) and exp(-G_s), G the running sum of
# g): exact in float32 while a channel decays by less than e^-88 over CHUNK
# tokens, |g| < 5.5 a token, which the seeded gates (|g| < 1.7) keep with
# room; the (I + L)^-1 of a chunk is a product of log2(CHUNK) factors.
CHUNK = 16
# Snapshots of the state lie at multiples of this many tokens from a chunk's
# start (``kv/state_pool.py``): a multiple of CHUNK and of the 16-token block.
SNAPSHOT_STRIDE = 64
# Slots :func:`init_cache` makes where nobody says how many (the compare).
DEFAULT_STATE_SLOTS = 4


def stats_names(cfg: ModelConfig) -> tuple:
    return ROUTING_STATS


def snapshot_stride(cfg: ModelConfig) -> int:
    return SNAPSHOT_STRIDE


def layer_kind(cfg: ModelConfig, layer_idx: int) -> str:
    return cfg.layer_kind(layer_idx)


def _kinds(cfg: ModelConfig) -> List[str]:
    return [layer_kind(cfg, i) for i in range(cfg.num_layers)]


def _conv_width(cfg: ModelConfig) -> int:
    return 3 * cfg.linear_num_heads * cfg.linear_head_dim


def rows_pool_shape(slots: int, rows: int, width: int) -> tuple:
    """A pool of ``rows`` rows of ``width`` channels a slot (a convolution's
    last ``kernel - 1``): ``[slots, rows * width / lanes, lanes]``, a slot's
    rows one after another, the oldest first, in lines of ``lanes`` = 128
    channels (the widest power of two up to that which divides ``width``), so
    that a slot is whole tiles of the device's memory, as a page of keys is,
    and a gather or a scatter of slots moves those slots.  (``[slots, rows,
    width]`` with 3 rows is kept rows-outermost at a program's boundary and
    slots-outermost inside it: the whole pool is relaid, in and out, by every
    layer of every dispatch.)"""
    lanes = math.gcd(width, 128)
    return slots, rows * width // lanes, lanes


def cache_bytes_per_token(cfg: ModelConfig) -> int:
    """Bytes of cache one position takes on the device: K and V of the
    layers that keep pages (``config.PAGED_KINDS``) alone; another layer's
    state does not grow."""
    return (2 * cfg.num_kv_heads * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize
            * sum(kind in PAGED_KINDS for kind in _kinds(cfg)))


def state_bytes_per_slot(cfg: ModelConfig) -> int:
    """Bytes one sequence's slot takes over all ``kda`` layers: the float32
    state and the convolutions' rows."""
    H, D = cfg.linear_num_heads, cfg.linear_head_dim
    return _kinds(cfg).count("kda") * (
        H * D * D * 4 + (cfg.linear_conv_kernel - 1) * _conv_width(cfg)
        * jnp.dtype(cfg.dtype).itemsize)


def init_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
               sharding=None, state_slots: Optional[int] = None):
    """One tree: a layer's ``(K, V)`` pages ``[num_blocks, block_size, kv
    heads, head_dim]`` or its ``(state [slots, heads, D, D] float32, conv
    :func:`rows_pool_shape` of kernel - 1 rows of 3 heads D)`` slots."""
    slots = state_slots or DEFAULT_STATE_SLOTS
    H, D = cfg.linear_num_heads, cfg.linear_head_dim
    dtype = jnp.dtype(cfg.dtype)

    def zeros(shape, dt):
        return jax.jit(lambda: jnp.zeros(shape, dt), out_shardings=sharding)()

    page = (num_blocks, block_size, cfg.num_kv_heads, cfg.head_dim)
    return [
        (zeros(page, dtype), zeros(page, dtype)) if kind == "gqa" else
        (zeros((slots, H, D, D), jnp.float32),
         zeros(rows_pool_shape(slots, cfg.linear_conv_kernel - 1,
                               _conv_width(cfg)), dtype))
        for kind in _kinds(cfg)]


def default_slot(cfg: ModelConfig, first_block_id, kv_caches):
    """The slot of a row nobody named one for: the first block id of its
    table modulo the slots there are."""
    stateful = next(i for i, kind in enumerate(_kinds(cfg))
                    if kind not in PAGED_KINDS)
    return first_block_id % kv_caches[stateful][0].shape[0]


def _shapes(cfg: ModelConfig, layer_idx: int) -> Dict[str, tuple]:
    h = cfg.hidden_size
    E, I = cfg.num_experts, cfg.moe_intermediate_size
    S = cfg.num_shared_experts * I
    shapes = {
        "input_layernorm": (h,), "post_attention_layernorm": (h,),
        "router": (h, cfg.router_experts),
        "router_bias": (cfg.router_experts,),
        "experts_gate": (E, h, I), "experts_up": (E, h, I),
        "experts_down": (E, I, h),
        "shared_gate": (h, S), "shared_up": (h, S), "shared_down": (S, h),
    }
    if layer_kind(cfg, layer_idx) == "gqa":
        q, kv = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
        shapes.update({"q_proj": (h, q), "k_proj": (h, kv), "v_proj": (h, kv),
                       "o_proj": (q, h)})
        if cfg.use_gqa_gate:
            shapes["gate_proj"] = (h, q)
    else:
        H, D, r = cfg.linear_num_heads, cfg.linear_head_dim, cfg.linear_gate_rank
        shapes.update({
            "qkv_proj": (h, 3 * H * D), "conv": (cfg.linear_conv_kernel, 3 * H * D),
            "f_a": (h, r), "f_b": (r, H * D), "dt_bias": (H * D,),
            "A_log": (H,), "beta_proj": (h, H),
            "g_a": (h, r), "g_b": (r, H * D),
            "o_norm": (D,), "o_proj": (H * D, h),
        })
    return shapes


_NORMS = ("input_layernorm", "post_attention_layernorm", "o_norm")


def param_specs(cfg: ModelConfig) -> Dict:
    """Every tensor whole on every device (the engine refuses a mesh)."""
    return {"embed_tokens": P(), "norm": P(), "lm_head": P(), "layers": [
        {name: P() for name in _shapes(cfg, i)}
        for i in range(cfg.num_layers)]}


def init_params(cfg: ModelConfig, key: jax.Array, shardings=None) -> Params:
    """Seeded random weights, each tensor made on the device by a jitted
    initialiser, as ``models/sarvam_mla.py`` makes its own.  Dense matrices
    0.02; norm scales 1; router logits of unit variance, selection bias 0.1;
    a convolution's taps ``kernel^-1/2`` (the output keeps its input's
    spread); ``A_log = log U(1, 16)`` a head and ``dt_bias`` the inverse
    softplus of ``exp U(log 0.001, log 0.1)`` a channel, both float32 (the
    published initialisation of the family's layer): a channel forgets
    between a thousandth and 1.6 nats a token."""
    dtype = jnp.dtype(cfg.dtype)
    makers = {}

    def draw(kind, key, shape, sharding, scale=0.02, as_dtype=dtype):
        maker = (kind, shape, sharding, scale, as_dtype)
        if maker not in makers:
            def make(k):
                k = jax.random.wrap_key_data(
                    jnp.tile(jax.random.key_data(k), 2), impl="rbg")
                if kind == "normal":
                    out = jax.random.normal(k, shape, jnp.float32) * scale
                elif kind == "A_log":
                    out = jnp.log(jax.random.uniform(
                        k, shape, jnp.float32, 1.0, 16.0))
                else:   # dt_bias
                    dt = jnp.exp(jax.random.uniform(
                        k, shape, jnp.float32, math.log(1e-3), math.log(0.1)))
                    out = dt + jnp.log(-jnp.expm1(-dt))
                return out.astype(as_dtype)
            makers[maker] = jax.jit(make, out_shardings=sharding)
        return makers[maker](key)

    def ones(shape, sharding):
        return jax.jit(lambda: jnp.ones(shape, dtype),
                       out_shardings=sharding)()

    top = shardings or {}
    keys = jax.random.split(key, cfg.num_layers + 2)
    params: Params = {
        "embed_tokens": draw("normal", keys[0],
                             (cfg.vocab_size, cfg.hidden_size),
                             top.get("embed_tokens")),
        "lm_head": draw("normal", keys[1], (cfg.hidden_size, cfg.vocab_size),
                        top.get("lm_head")),
        "norm": ones((cfg.hidden_size,), top.get("norm")),
        "layers": [],
    }
    for i in range(cfg.num_layers):
        sh = shardings["layers"][i] if shardings else {}
        shapes = _shapes(cfg, i)
        layer = {}
        for name, k in zip(sorted(shapes),
                           jax.random.split(keys[i + 2], len(shapes))):
            shape, s = shapes[name], sh.get(name)
            if name in _NORMS:
                layer[name] = ones(shape, s)
            elif name == "router_bias":
                layer[name] = draw("normal", k, shape, s, 0.1, jnp.float32)
            elif name == "router":
                layer[name] = draw("normal", k, shape, s,
                                   cfg.hidden_size ** -0.5)
            elif name == "conv":
                layer[name] = draw("normal", k, shape, s, shape[0] ** -0.5)
            elif name in ("A_log", "dt_bias"):
                layer[name] = draw(name, k, shape, s, 0.0, jnp.float32)
            else:
                layer[name] = draw("normal", k, shape, s)
        params["layers"].append(layer)
    return params


def quantize_params(params: Params, cfg: ModelConfig) -> Params:
    if cfg.quantization is not None:
        raise ValueError(
            f"{__name__} has no {cfg.quantization} weights (bf16 throughout)")
    return params


# -- the gated delta rule ----------------------------------------------------


def _pallas_serves() -> bool:
    """A real TPU, and the A/B switch not set."""
    return (not attn_ops.pallas_disabled()
            and jax.default_backend() == "tpu")


def use_pallas_kda(cfg: ModelConfig) -> bool:
    """Trace-time dispatch check for both kernels of ``ops/pallas/kda.py``:
    a state of whole 128-lane tiles."""
    return cfg.linear_head_dim % 128 == 0 and _pallas_serves()


def attention_paths(cfg: ModelConfig):
    """(decode, prefill) for the engine's boot line: the ``gqa`` layers'
    kernels and the ``kda`` layers'."""
    decode = "pallas" if attn_ops.use_pallas_decode(
        cfg.num_kv_heads, cfg.head_dim) else "xla-gather"
    prefill = "pallas-flash" if attn_ops.use_pallas_prefill(
        cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, 256) else "xla-dense"
    kda = use_pallas_kda(cfg)
    return (f"{decode}+{'pallas-kda' if kda else 'xla-kda'}",
            f"{prefill}+{'pallas-kda-chunk' if kda else 'xla-kda-chunk'}")


def _hi(a, b, dims):
    return jnp.einsum(dims, a, b, precision=jax.lax.Precision.HIGHEST,
                      preferred_element_type=jnp.float32)


def kda_chunk_plain(q, k, v, g, beta, s0, snapshot_len=None, chunk=CHUNK):
    """The chunkwise form in plain ``jax.numpy``.  ``q, k, g`` [T, H, Dk] and
    ``v`` [T, H, Dv] float32 (``q`` scaled, ``g`` the log decay <= 0; ``g``
    [T, H], a decay a head, is spread over the head's key channels), ``beta``
    [T, H], ``s0`` [H, Dk, Dv] -> (o [T, H, Dv], the state after T tokens, the
    state after ``snapshot_len`` tokens, a multiple of ``chunk`` below T, or
    None).

    Within a chunk, with ``G_t`` the running sum of ``g`` from its start,
    ``S_t = Diag(e^{G_t}) S_0 + sum_{s<=t} Diag(e^{G_t-G_s}) k_s u_s^T`` where
    the pseudo-values solve ``(I + Diag(beta) A) U = Diag(beta) (V - (K e^G)
    S_0)``, ``A[t, s] = (k_t e^{G_t}) . (k_s e^{-G_s})`` strictly below the
    diagonal; the inverse of ``I + L`` (``L`` nilpotent) is ``(I - L)(I +
    L^2)(I + L^4) ...``; ``O = (Q e^G) S_0 + tril(B) U`` with ``B`` as ``A``
    from ``q``; ``S_C = Diag(e^{G_C}) S_0 + (K e^{G_C - G})^T U``."""
    T, H, D = q.shape
    if g.ndim == 2:
        g = jnp.broadcast_to(g[..., None], k.shape)
    C = chunk
    n = T // C
    to = lambda a: a.reshape(n, C, H, -1).transpose(0, 2, 1, 3)  # [n,H,C,.]
    qs, ks, vs, gs = to(q), to(k), to(v), to(g)
    bs = beta.reshape(n, C, H).transpose(0, 2, 1)[..., None]      # [n,H,C,1]
    tri = jnp.tril(jnp.ones((C, C), bool), -1)
    eye = jnp.eye(C, dtype=jnp.float32)

    def step(carry, xs):
        S, snap = carry
        i, qc, kc, vc, gc, bc = xs
        if snapshot_len is not None:
            snap = jnp.where(i * C == snapshot_len, S, snap)
        G = jnp.cumsum(gc, axis=1)
        eG = jnp.exp(G)
        kt, kn, qt = kc * eG, kc * jnp.exp(-G), qc * eG
        A = jnp.where(tri, _hi(kt, kn, "htd,hsd->hts"), 0.0)
        B = jnp.where(tri | (eye > 0), _hi(qt, kn, "htd,hsd->hts"), 0.0)
        X = -bc * A
        Tm, Pw = eye + X, X
        m = 2
        while m < C:
            Pw = _hi(Pw, Pw, "hts,hsr->htr")
            Tm = Tm + _hi(Tm, Pw, "hts,hsr->htr")
            m *= 2
        rhs = bc * (vc - _hi(kt, S, "htd,hde->hte"))
        U = _hi(Tm, rhs, "hts,hse->hte")
        o = _hi(qt, S, "htd,hde->hte") + _hi(B, U, "hts,hse->hte")
        last = G[:, -1:, :]                                       # [H,1,D]
        S = (jnp.swapaxes(jnp.exp(last), 1, 2) * S
             + _hi(kc * jnp.exp(last - G), U, "htd,hte->hde"))
        return (S, snap), o

    (S, snap), o = jax.lax.scan(
        step, (s0, s0), (jnp.arange(n), qs, ks, vs, gs, bs))
    o = o.transpose(0, 2, 1, 3).reshape(T, H, v.shape[-1])
    return o, S, (snap if snapshot_len is not None else None)


def kda_step_plain(q, k, v, g, beta, S):
    """One token a row: ``q, k`` [R, H, Dk] and ``v`` [R, H, Dv] float32, ``g``
    [R, H, Dk] or (a decay a head) [R, H], ``beta`` [R, H], ``S`` [R, H, Dk,
    Dv] -> (o [R, H, Dv], the new states)."""
    if g.ndim == 2:
        g = g[..., None]
    S1 = jnp.exp(g)[..., None] * S
    u = beta[..., None] * (v - jnp.sum(k[..., None] * S1, axis=-2))
    S2 = S1 + k[..., None] * u[..., None, :]
    return jnp.sum(q[..., None] * S2, axis=-2), S2


def _l2(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _kda_inputs(layer, cfg, x, mixed, live):
    """From the normed input ``x`` [T, h] and the convolved, activated stream
    ``mixed`` [T, 3 H D] float32: (q, k, v, g [T, H, D], beta [T, H]) float32;
    where ``live`` is False, ``beta`` 0 and ``g`` 0: the identity."""
    T = x.shape[0]
    H, D = cfg.linear_num_heads, cfg.linear_head_dim
    q, k, v = (mixed[:, i * H * D:(i + 1) * H * D].reshape(T, H, D)
               for i in range(3))
    q, k = _l2(q) * D ** -0.5, _l2(k)
    f = _dot(_dot(x, layer["f_a"]).astype(x.dtype), layer["f_b"])
    g = -jnp.exp(layer["A_log"])[None, :, None] * jax.nn.softplus(
        f + layer["dt_bias"]).reshape(T, H, D)
    beta = jax.nn.sigmoid(_dot(x, layer["beta_proj"]))
    if cfg.kda_allow_neg_eigval:
        beta = 2.0 * beta
    g = jnp.where(live[:, None, None], g, 0.0)
    beta = jnp.where(live[:, None], beta, 0.0)
    return q, k, v, g, beta


def _kda_out(layer, cfg, x, o):
    """(RMSNorm_head(o) * sigmoid(x W_ga W_gb)) -> [T, H D] in x's dtype."""
    T = x.shape[0]
    gate = jax.nn.sigmoid(
        _dot(_dot(x, layer["g_a"]).astype(x.dtype), layer["g_b"]))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + cfg.rms_norm_eps)
    o = o * layer["o_norm"].astype(jnp.float32)
    return (o.reshape(T, -1) * gate).astype(x.dtype)


def _convolve(layer, window):
    """``window`` [..., kernel, W] (the oldest row first) -> SiLU of the
    depthwise convolution's newest output [..., W], float32."""
    w = layer["conv"].astype(jnp.float32)
    return jax.nn.silu(jnp.sum(window.astype(jnp.float32) * w, axis=-2))


def _kda_prefill(layer, cfg, cache, x, live, valid_len, slots):
    """A chunk through one ``kda`` layer: (the heads' output [T, H D], the new
    ``(state, conv)``)."""
    state, conv = cache
    slot, start, snap_slot, snap_len = slots
    T, K = x.shape[0], cfg.linear_conv_kernel
    fresh = start < 0
    s0 = jnp.where(fresh, 0.0, state[jnp.maximum(start, 0)])
    c0 = jnp.where(fresh, 0, conv[jnp.maximum(start, 0)]).reshape(K - 1, -1)
    u = _dot(x, layer["qkv_proj"]).astype(x.dtype)
    full = jnp.concatenate([c0, u], axis=0)                # [K - 1 + T, W]
    mixed = jax.nn.silu(sum(
        full[j:j + T].astype(jnp.float32)
        * layer["conv"][j].astype(jnp.float32) for j in range(K)))
    q, k, v, g, beta = _kda_inputs(layer, cfg, x, mixed, live)
    with jax.named_scope("kda_prefill"):
        if use_pallas_kda(cfg):
            from production_stack_tpu.engine.ops.pallas.kda import (
                kda_prefill_pallas,
            )

            o, s1, snap = kda_prefill_pallas(
                q, k, v, g, beta, s0,
                None if snap_slot is None else snap_len)
        else:
            o, s1, snap = kda_chunk_plain(
                q, k, v, g, beta, s0,
                None if snap_slot is None else snap_len)
    rows = lambda at: jax.lax.dynamic_slice_in_dim(
        full, at, K - 1, axis=0).reshape(conv.shape[1:])
    if snap_slot is not None:
        state = state.at[snap_slot].set(snap)
        conv = conv.at[snap_slot].set(rows(snap_len))
    state = state.at[slot].set(s1)
    conv = conv.at[slot].set(rows(valid_len))
    return _kda_out(layer, cfg, x, o), (state, conv)


def _kda_decode(layer, cfg, cache, x, live, slots):
    """One token a row through one ``kda`` layer."""
    state, conv = cache
    u = _dot(x, layer["qkv_proj"]).astype(x.dtype)
    R, W = u.shape
    window = jnp.concatenate(
        [conv[slots].reshape(R, -1, W), u[:, None]], axis=1)
    q, k, v, g, beta = _kda_inputs(
        layer, cfg, x, _convolve(layer, window), live)
    with jax.named_scope("kda_decode"):
        if use_pallas_kda(cfg):
            from production_stack_tpu.engine.ops.pallas.kda import (
                kda_decode_pallas,
            )

            o, state = kda_decode_pallas(q, k, v, g, beta, state, slots)
        else:
            o, rows = kda_step_plain(q, k, v, g, beta, state[slots])
            state = state.at[slots].set(rows)
    conv = conv.at[slots].set(jnp.where(
        live[:, None, None], window[:, 1:], window[:, :-1]).reshape(
            R, *conv.shape[1:]))
    return _kda_out(layer, cfg, x, o), (state, conv)


# -- softmax attention without positions -------------------------------------


def _gqa_project(layer, cfg, x):
    T = x.shape[0]
    q = _dot(x, layer["q_proj"]).astype(x.dtype).reshape(
        T, cfg.num_heads, cfg.head_dim)
    k = _dot(x, layer["k_proj"]).astype(x.dtype).reshape(
        T, cfg.num_kv_heads, cfg.head_dim)
    v = _dot(x, layer["v_proj"]).astype(x.dtype).reshape(
        T, cfg.num_kv_heads, cfg.head_dim)
    return q, k, v


def _gqa_out(layer, cfg, x, out):
    out = out.reshape(x.shape[0], -1)
    if cfg.use_gqa_gate:
        out = (out.astype(jnp.float32) * jax.nn.sigmoid(
            _dot(x, layer["gate_proj"]))).astype(x.dtype)
    return out


def _gqa_prefill(layer, cfg, cache, h, cached_len, prefix_block_ids,
                 new_block_ids, valid_len, project=_gqa_project, out=_gqa_out):
    """A chunk through one layer that keeps pages: (what W_o reads, the new
    pages).  ``project`` and ``out`` are this module's; another module hands
    its own (``models/laguna.py``: rotary by layer kind, a gate a head)."""
    q, k, v = project(layer, cfg, h)
    attended = attn_ops.prefill_attention(
        q, k, v, *cache, prefix_block_ids, cached_len, valid_len,
        scale=cfg.head_dim ** -0.5)
    return out(layer, cfg, h, attended), attn_ops.write_prefill_kv(
        *cache, k, v, new_block_ids)


def _gqa_decode(layer, cfg, cache, h, block_tables, ctx_lens, slot_block_ids,
                slot_offsets, project=_gqa_project, out=_gqa_out):
    """One token a row through one layer that keeps pages."""
    q, k, v = project(layer, cfg, h)
    cache = attn_ops.append_decode_kv(
        *cache, k, v, slot_block_ids, slot_offsets)
    attended = attn_ops.decode_attention(
        q, *cache, block_tables, ctx_lens, scale=cfg.head_dim ** -0.5)
    return out(layer, cfg, h, attended), cache


# -- the layers --------------------------------------------------------------


def _ffn(layer, cfg, x, live):
    with jax.named_scope("routed_experts"):
        who, g = route(layer, cfg, x)
        routed, stats = held_experts(layer, cfg, x, who, g, live)
    shared = _swiglu(x, layer["shared_gate"], layer["shared_up"],
                     layer["shared_down"])
    return (shared + routed).astype(x.dtype), who, stats


def _blocks(params, cfg, kv_caches, x, live, mix, ffn=_ffn):
    """Both steps' layers: ``mix(kind, layer, cache, normed h) -> (what W_o
    reads [T, .], the layer's new cache)`` is the step's own; ``ffn(layer,
    cfg, normed h, live) -> (y, choice, counts)`` the module's
    (``models/jamba.py`` hands a dense one)."""
    caches, choice, stats = [], [], []
    for i, (layer, cache) in enumerate(zip(params["layers"], kv_caches)):
        h = rms_norm(x, layer["input_layernorm"], cfg.rms_norm_eps)
        out, new = mix(layer_kind(cfg, i), layer, cache, h)
        caches.append(new)
        x = x + _dot(out, layer["o_proj"]).astype(x.dtype)
        h = rms_norm(x, layer["post_attention_layernorm"], cfg.rms_norm_eps)
        y, who, counted = ffn(layer, cfg, h, live)
        choice.append(who)
        stats.append(counted)
        x = x + y
    return x, caches, choice, stats


def prefill(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,            # [T] int32 (padded to a bucket)
    cached_len: jax.Array,        # scalar int32: positions already cached
    prefix_block_ids: jax.Array,  # [P] int32 (0-padded)
    new_block_ids: jax.Array,     # [T // block_size] int32 (null-padded)
    valid_len: jax.Array,         # scalar int32: true number of new tokens
    kv_caches,
    mesh: Optional[Mesh] = None,
    sp_mode: str = "ring",
    prompt_targets: Optional[jax.Array] = None,
    prompt_topk: int = 0,
    return_choice: bool = False,
    return_stats: bool = False,
    state_slot: Optional[jax.Array] = None,     # the sequence's live slot
    state_from: Optional[jax.Array] = None,     # slot to start from; < 0: zeros
    snapshot_slot: Optional[jax.Array] = None,  # slot that keeps a snapshot
    snapshot_len: Optional[jax.Array] = None,   # ... after this many tokens
):
    """One sequence's prefill chunk: (last valid token's logits [V], new
    caches), then as ``models/sarvam_mla.py: prefill``.  The chunk's ``kda``
    layers start from slot ``state_from`` (zeros where negative), leave their
    state after ``valid_len`` tokens in ``state_slot`` and, where a
    ``snapshot_slot`` is handed, their state after ``snapshot_len`` tokens (a
    multiple of :data:`CHUNK` below ``valid_len``) there.  Without the slot
    arguments, :func:`default_slot`, from zeros where ``cached_len`` is 0, no
    snapshot."""
    if prompt_targets is not None:
        raise ValueError(f"{__name__}: prompt logprobs (echo) are not offered")
    T = tokens.shape[0]
    live = jnp.arange(T) < valid_len
    if state_slot is None:
        state_slot = default_slot(
            cfg, jnp.where(cached_len > 0, prefix_block_ids[0],
                           new_block_ids[0]), kv_caches)
    if state_from is None:
        state_from = jnp.where(cached_len > 0, state_slot, -1)
    slots = (state_slot, state_from, snapshot_slot, snapshot_len)

    def mix(kind, layer, cache, h):
        if kind == "kda":
            return _kda_prefill(layer, cfg, cache, h, live, valid_len, slots)
        return _gqa_prefill(layer, cfg, cache, h, cached_len,
                            prefix_block_ids, new_block_ids, valid_len)

    x, caches, *counted = _blocks(
        params, cfg, kv_caches, params["embed_tokens"][tokens], live, mix)
    x = rms_norm(x, params["norm"], cfg.rms_norm_eps)
    logits = _dot(x[jnp.maximum(valid_len - 1, 0)], params["lm_head"])
    return _result(logits, caches, *counted, [], return_choice, return_stats)


def decode(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,          # [S] int32, one token a row (padded batch)
    positions: jax.Array,       # [S] int32 (no layer reads it: no rotary)
    block_tables: jax.Array,    # [S, Bmax] int32
    ctx_lens: jax.Array,        # [S] int32 context length incl. the new token
    slot_block_ids: jax.Array,  # [S] int32 block receiving the new token
    slot_offsets: jax.Array,    # [S] int32 offset within that block
    kv_caches,
    mesh: Optional[Mesh] = None,
    return_choice: bool = False,
    return_stats: bool = False,
    state_slots: Optional[jax.Array] = None,   # [S] int32 live slots
):
    """Batched single-token decode: (logits [S, V], new caches), then as
    :func:`prefill`.  A row whose write is parked on the null block 0 is not
    live: routed nowhere, and the identity on its slot."""
    live = slot_block_ids != 0
    if state_slots is None:
        state_slots = default_slot(cfg, block_tables[:, 0], kv_caches)

    def mix(kind, layer, cache, h):
        if kind == "kda":
            return _kda_decode(layer, cfg, cache, h, live, state_slots)
        return _gqa_decode(layer, cfg, cache, h, block_tables, ctx_lens,
                           slot_block_ids, slot_offsets)

    x, caches, *counted = _blocks(
        params, cfg, kv_caches, params["embed_tokens"][tokens], live, mix)
    logits = _dot(rms_norm(x, params["norm"], cfg.rms_norm_eps),
                  params["lm_head"])
    return _result(logits, caches, *counted, [], return_choice, return_stats)
