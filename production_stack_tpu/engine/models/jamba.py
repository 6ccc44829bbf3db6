"""Selective state-space (Mamba-1) layers beside multi-query softmax attention
without position encoding, over a dense gated MLP (functional JAX): the
``jamba`` architecture.

Pre-norm blocks, RMSNorm: ``r = x + Mix(norm1(x))``, ``y = r + MLP(norm2(r))``,
a final RMSNorm, the embedding transposed as the head.  ``cfg.layer_kinds``
names each layer's mix, one period of it tiled over ``cfg.num_layers``
(``attn_layer_period`` / ``attn_layer_offset`` of the source); every layer's
MLP is ``models/llama.py``'s SwiGLU.

**``"gqa"``**: ``models/solar_kda.py``'s softmax layer with no position
encoding and its gate off (``cfg.use_gqa_gate`` False), imported: K and V in
pages, the two dense kernels.

**``"mamba"``: the selective state-space mixer** with an RMSNorm on the step
size, ``B`` and ``C``.  With ``Di = mamba_expand x hidden_size`` channels,
``N = mamba_d_state`` states a channel, ``K = mamba_d_conv``, ``R =
mamba_dt_rank``, per token:

    ``[u_t, z_t] = x_t W_in``
    ``c_t = SiLU(sum_{j<K} w_conv[j] . u_{t-K+1+j} + b_conv)``
    ``[d_t, B_t, C_t] = c_t W_x``, each RMS-normed with a learned scale
    ``dt_t = softplus(d_t W_dt + b_dt)``;  ``A = -exp(A_log)``
    ``h_t = exp(dt_t (x) A) . h_{t-1} + (dt_t . c_t) (x) B_t``
    ``y_t = (h_t^T C_t + D . c_t) . SiLU(z_t)``;  out ``= y_t W_out``

``h`` ``[N, Di]`` float32 (the states on the sublanes, the channels on the
lanes; ``A_log`` is kept the same way round).  **Such a layer keeps no keys**:
a sequence owns one *slot* of ``(h [N, Di] float32, the last K - 1 rows of
u)``, whatever its length.  Projections take the activations' dtype and
accumulate float32, rounded after each; ``dt``, ``exp``, the softplus, the
three inner norms, ``A_log``, ``D`` and the state are float32.

**One cache tree, two kinds of state**, addressed as ``models/solar_kda.py``'s
(the registry's state-pool contract): pages by block ids; slots by the
keywords ``state_slot`` / ``state_from`` / ``snapshot_slot`` / ``snapshot_len``
of :func:`prefill` and ``state_slots`` of :func:`decode`, or, where a caller
hands none, by ``solar_kda.default_slot``.  A padded slot of a chunk and a dead
row of a decode batch are the identity on the state (``dt`` 0) and do not
shift the convolution's rows.

**Two kernels, one plain form.**  Prefill scans a chunk from a slot's state
(``ops/pallas/ssm.py: ssm_prefill_pallas`` on a TPU; :func:`ssm_scan_plain`,
token by token, elsewhere); decode takes one step in place
(``ssm_decode_pallas``; the same plain step).

Offers the engine (``models/registry.py``): ``init_params``,
``quantize_params`` (identity), ``prefill``, ``decode``, ``init_cache``,
``cache_bytes_per_token`` (the ``gqa`` layers' alone), ``state_bytes_per_slot``,
``snapshot_stride``, ``param_specs``, ``attention_paths``, ``stats_names`` /
``STATS_MAX`` and ``return_stats`` on both steps: the largest ``|h|`` the
dispatch left in a slot and its largest ``dt``, x 1000.  No ``mixed_step``, no
``encode``, no LoRA, no int8, no mesh: refused at boot by name.
"""

from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from production_stack_tpu.engine.config import ModelConfig
from production_stack_tpu.engine.models import llama
# cache_bytes_per_token is the engine's to ask: the ``gqa`` layers' K and V.
from production_stack_tpu.engine.models.solar_kda import (  # noqa: F401
    _blocks, _dot, _gqa_decode, _gqa_prefill, _kinds, _pallas_serves,
    cache_bytes_per_token, default_slot, layer_kind, rows_pool_shape,
)
from production_stack_tpu.engine.ops import attention as attn_ops
from production_stack_tpu.engine.ops.layers import rms_norm

Params = Dict
# Snapshots of the state lie at multiples of this many tokens from a chunk's
# start (``kv/state_pool.py``): a multiple of the kernel's step and of the
# 16-token block.
SNAPSHOT_STRIDE = 64
# Slots :func:`init_cache` makes where nobody says how many (the compare).
DEFAULT_STATE_SLOTS = 4
# ``return_stats``: the largest |h| a slot was left with and the largest step
# size of a live token, over the dispatch's state-space layers, x 1000; both
# fold by a maximum (over steps and dispatches in the engine).
SSM_STATS = ("ssm_state_absmax_e3", "ssm_dt_max_e3")
STATS_MAX = SSM_STATS


def stats_names(cfg: ModelConfig) -> tuple:
    return SSM_STATS


def snapshot_stride(cfg: ModelConfig) -> int:
    return SNAPSHOT_STRIDE


def _inner(cfg: ModelConfig) -> int:
    return cfg.mamba_expand * cfg.hidden_size


def state_bytes_per_slot(cfg: ModelConfig) -> int:
    """Bytes one sequence's slot takes over all ``mamba`` layers: the float32
    state and the convolution's rows."""
    return _kinds(cfg).count("mamba") * _inner(cfg) * (
        cfg.mamba_d_state * 4
        + (cfg.mamba_d_conv - 1) * jnp.dtype(cfg.dtype).itemsize)


def init_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
               sharding=None, state_slots: Optional[int] = None):
    """One tree: a layer's ``(K, V)`` pages ``[num_blocks, block_size, kv
    heads, head_dim]`` or its ``(state [slots, N, Di] float32, conv
    ``solar_kda.rows_pool_shape`` of K - 1 rows of Di)`` slots."""
    slots = state_slots or DEFAULT_STATE_SLOTS
    dtype = jnp.dtype(cfg.dtype)

    def zeros(shape, dt):
        return jax.jit(lambda: jnp.zeros(shape, dt), out_shardings=sharding)()

    page = (num_blocks, block_size, cfg.num_kv_heads, cfg.head_dim)
    return [
        (zeros(page, dtype), zeros(page, dtype)) if kind == "gqa" else
        (zeros((slots, cfg.mamba_d_state, _inner(cfg)), jnp.float32),
         zeros(rows_pool_shape(slots, cfg.mamba_d_conv - 1, _inner(cfg)),
               dtype))
        for kind in _kinds(cfg)]


def _shapes(cfg: ModelConfig, layer_idx: int) -> Dict[str, tuple]:
    h, I = cfg.hidden_size, cfg.intermediate_size
    shapes = {
        "input_layernorm": (h,), "post_attention_layernorm": (h,),
        "gate_proj": (h, I), "up_proj": (h, I), "down_proj": (I, h),
    }
    if layer_kind(cfg, layer_idx) == "gqa":
        q, kv = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
        shapes.update({"q_proj": (h, q), "k_proj": (h, kv), "v_proj": (h, kv),
                       "o_proj": (q, h)})
        return shapes
    if cfg.mamba_proj_bias:
        raise ValueError(f"{__name__}: mamba_proj_bias is not offered")
    Di, N, R = _inner(cfg), cfg.mamba_d_state, cfg.mamba_dt_rank
    shapes.update({
        "in_proj": (h, 2 * Di), "conv": (cfg.mamba_d_conv, Di),
        "x_proj": (Di, R + 2 * N), "dt_norm": (R,), "b_norm": (N,),
        "c_norm": (N,), "dt_proj": (R, Di), "dt_bias": (Di,),
        "A_log": (N, Di), "D": (Di,), "o_proj": (Di, h),
    })
    if cfg.mamba_conv_bias:
        shapes["conv_bias"] = (Di,)
    return shapes


_ONES = ("input_layernorm", "post_attention_layernorm", "dt_norm", "b_norm",
         "c_norm")
_FLOAT32 = ("dt_bias", "A_log", "D")


def param_specs(cfg: ModelConfig) -> Dict:
    """Every tensor whole on every device (the engine refuses a mesh); the
    head is the embedding."""
    return {"embed_tokens": P(), "norm": P(), "layers": [
        {name: P() for name in _shapes(cfg, i)}
        for i in range(cfg.num_layers)]}


def init_params(cfg: ModelConfig, key: jax.Array, shardings=None) -> Params:
    """Seeded random weights, each tensor made on the device by a jitted
    initialiser.  Dense matrices 0.02; norm scales 1; the convolution's taps
    ``K^-1/2`` (the output keeps its input's spread), its bias 0.1; ``A_log =
    log(1..N)`` a channel, ``D`` 1 and ``dt_bias`` the inverse softplus of
    ``exp U(log 0.001, log 0.1)``, all three float32 (the published
    initialisation of the layer): a state forgets between a thousandth and
    1.6 nats a token."""
    if not cfg.tie_word_embeddings:
        raise ValueError(f"{__name__}: an untied head is not offered")
    dtype = jnp.dtype(cfg.dtype)
    makers = {}

    def draw(kind, key, shape, sharding, scale=0.02):
        as_dtype = jnp.float32 if kind != "normal" else dtype
        maker = (kind, shape, sharding, scale)
        if maker not in makers:
            def make(k):
                k = jax.random.wrap_key_data(
                    jnp.tile(jax.random.key_data(k), 2), impl="rbg")
                if kind == "normal":
                    out = jax.random.normal(k, shape, jnp.float32) * scale
                elif kind == "A_log":
                    out = jnp.broadcast_to(jnp.log(jnp.arange(
                        1.0, shape[0] + 1.0))[:, None], shape)
                elif kind == "D":
                    out = jnp.ones(shape, jnp.float32)
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
    keys = jax.random.split(key, cfg.num_layers + 1)
    params: Params = {
        "embed_tokens": draw("normal", keys[0],
                             (cfg.vocab_size, cfg.hidden_size),
                             top.get("embed_tokens")),
        "norm": ones((cfg.hidden_size,), top.get("norm")),
        "layers": [],
    }
    for i in range(cfg.num_layers):
        sh = shardings["layers"][i] if shardings else {}
        shapes = _shapes(cfg, i)
        layer = {}
        for name, k in zip(sorted(shapes),
                           jax.random.split(keys[i + 1], len(shapes))):
            shape, s = shapes[name], sh.get(name)
            if name in _ONES:
                layer[name] = ones(shape, s)
            elif name in _FLOAT32:
                layer[name] = draw(name, k, shape, s)
            elif name == "conv":
                layer[name] = draw("normal", k, shape, s, shape[0] ** -0.5)
            elif name == "conv_bias":
                layer[name] = draw("normal", k, shape, s, 0.1)
            else:
                layer[name] = draw("normal", k, shape, s)
        params["layers"].append(layer)
    return params


def quantize_params(params: Params, cfg: ModelConfig) -> Params:
    if cfg.quantization is not None:
        raise ValueError(
            f"{__name__} has no {cfg.quantization} weights (bf16 throughout)")
    return params


# -- the selective state-space mixer -----------------------------------------


def use_pallas_ssm(cfg: ModelConfig) -> bool:
    """Trace-time dispatch check for both kernels of ``ops/pallas/ssm.py``:
    whole 128-lane tiles of channels, whole sublane tiles of states."""
    return (_inner(cfg) % 128 == 0 and cfg.mamba_d_state % 8 == 0
            and _pallas_serves())


def attention_paths(cfg: ModelConfig):
    """(decode, prefill) for the engine's boot line: the ``gqa`` layers'
    kernels and the ``mamba`` layers'."""
    decode = "pallas" if attn_ops.use_pallas_decode(
        cfg.num_kv_heads, cfg.head_dim) else "xla-gather"
    prefill = "pallas-flash" if attn_ops.use_pallas_prefill(
        cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, 256) else "xla-dense"
    ssm = "pallas" if use_pallas_ssm(cfg) else "xla"
    return f"{decode}+{ssm}-ssm", f"{prefill}+{ssm}-ssm-scan"


def ssm_step_plain(c, dt, z, B, C, A_log, skip, h):
    """One token a row: ``c, dt, z`` [R, Di] float32, ``B, C`` [R, N],
    ``A_log`` [N, Di], ``skip`` [Di], ``h`` [R, N, Di] -> (y [R, Di], the new
    states)."""
    h = (jnp.exp(dt[:, None, :] * -jnp.exp(A_log)) * h
         + (dt * c)[:, None, :] * B[:, :, None])
    y = jnp.sum(h * C[:, :, None], axis=1) + skip * c
    return y * jax.nn.silu(z), h


def ssm_scan_plain(c, dt, z, B, C, A_log, skip, s0, snapshot_len=None):
    """The recurrence token by token in plain ``jax.numpy``: ``c, dt, z``
    [T, Di] float32, ``B, C`` [T, N], ``s0`` [N, Di] -> (y [T, Di], the state
    after T tokens, the state after ``snapshot_len`` tokens or None)."""
    def step(carry, xs):
        h, snap = carry
        i, *token = xs
        if snapshot_len is not None:
            snap = jnp.where(i == snapshot_len, h, snap)
        y, h = ssm_step_plain(*(a[None] for a in token), A_log, skip, h[None])
        return (h[0], snap), y[0]

    (h, snap), y = jax.lax.scan(
        step, (s0, s0), (jnp.arange(c.shape[0]), c, dt, z, B, C))
    return y, h, (snap if snapshot_len is not None else None)


def _selective(layer, cfg, c, live):
    """From the convolved, activated stream ``c`` [T, Di] (the activations'
    dtype): (dt [T, Di] float32, 0 where ``live`` is False; B, C [T, N]
    float32)."""
    R, N = cfg.mamba_dt_rank, cfg.mamba_d_state
    dbc = _dot(c, layer["x_proj"]).astype(c.dtype)
    d, B, C = (rms_norm(dbc[:, lo:hi], layer[name], cfg.rms_norm_eps)
               for lo, hi, name in ((0, R, "dt_norm"), (R, R + N, "b_norm"),
                                    (R + N, R + 2 * N, "c_norm")))
    dt = jax.nn.softplus(_dot(d, layer["dt_proj"]) + layer["dt_bias"])
    f32 = lambda a: a.astype(jnp.float32)
    return jnp.where(live[:, None], dt, 0.0), f32(B), f32(C)


def _convolved(layer, cfg, taps):
    """``taps``: the ``K`` shifted views of the stream, the oldest first,
    each [..., Di] -> SiLU(conv + bias) in the stream's dtype."""
    w = layer["conv"].astype(jnp.float32)
    out = sum(t.astype(jnp.float32) * w[j] for j, t in enumerate(taps))
    if cfg.mamba_conv_bias:
        out = out + layer["conv_bias"].astype(jnp.float32)
    return jax.nn.silu(out).astype(taps[0].dtype)


def _ssm_stats(h_absmax, dt):
    """[2] int32: the largest |h| and the largest dt, x 1000."""
    both = jnp.stack([jnp.max(h_absmax), jnp.max(dt)]) * 1e3
    return jnp.minimum(both, 2.0 ** 31 - 128).astype(jnp.int32)


def _mamba_prefill(layer, cfg, cache, x, live, valid_len, slots):
    """A chunk through one ``mamba`` layer: (what W_out reads [T, Di], the new
    ``(state, conv)``, the layer's counters)."""
    state, conv = cache
    slot, start, snap_slot, snap_len = slots
    T, K, Di = x.shape[0], cfg.mamba_d_conv, _inner(cfg)
    fresh = start < 0
    s0 = jnp.where(fresh, 0.0, state[jnp.maximum(start, 0)])
    c0 = jnp.where(fresh, 0, conv[jnp.maximum(start, 0)]).reshape(K - 1, Di)
    uz = _dot(x, layer["in_proj"]).astype(x.dtype)
    u, z = uz[:, :Di], uz[:, Di:]
    full = jnp.concatenate([c0, u], axis=0)                # [K - 1 + T, Di]
    c = _convolved(layer, cfg, [full[j:j + T] for j in range(K)])
    dt, B, C = _selective(layer, cfg, c, live)
    scan = ssm_scan_plain
    if use_pallas_ssm(cfg):
        from production_stack_tpu.engine.ops.pallas.ssm import (
            ssm_prefill_pallas as scan,
        )
    with jax.named_scope("ssm_prefill"):
        y, s1, snap = scan(
            c.astype(jnp.float32), dt, z.astype(jnp.float32), B, C,
            layer["A_log"], layer["D"], s0,
            None if snap_slot is None else snap_len)
    rows = lambda at: jax.lax.dynamic_slice_in_dim(
        full, at, K - 1, axis=0).reshape(conv.shape[1:])
    if snap_slot is not None:
        state = state.at[snap_slot].set(snap)
        conv = conv.at[snap_slot].set(rows(snap_len))
    state = state.at[slot].set(s1)
    conv = conv.at[slot].set(rows(valid_len))
    return y.astype(x.dtype), (state, conv), _ssm_stats(jnp.abs(s1), dt)


def _mamba_decode(layer, cfg, cache, x, live, slots):
    """One token a row through one ``mamba`` layer."""
    state, conv = cache
    Di = _inner(cfg)
    uz = _dot(x, layer["in_proj"]).astype(x.dtype)
    u, z = uz[:, :Di], uz[:, Di:]
    R = u.shape[0]
    window = jnp.concatenate(
        [conv[slots].reshape(R, -1, Di), u[:, None]], axis=1)
    c = _convolved(layer, cfg, [window[:, j] for j in range(window.shape[1])])
    dt, B, C = _selective(layer, cfg, c, live)
    args = (c.astype(jnp.float32), dt, z.astype(jnp.float32), B, C,
            layer["A_log"], layer["D"])
    with jax.named_scope("ssm_decode"):
        if use_pallas_ssm(cfg):
            from production_stack_tpu.engine.ops.pallas.ssm import (
                ssm_decode_pallas,
            )

            y, absmax, state = ssm_decode_pallas(*args, state, slots)
        else:
            y, rows = ssm_step_plain(*args, state[slots])
            state = state.at[slots].set(rows)
            absmax = jnp.max(jnp.abs(rows), axis=1)
    conv = conv.at[slots].set(jnp.where(
        live[:, None, None], window[:, 1:], window[:, :-1]).reshape(
            R, *conv.shape[1:]))
    stats = _ssm_stats(jnp.where(live[:, None], absmax, 0.0), dt)
    return y.astype(x.dtype), (state, conv), stats


# -- the two steps -----------------------------------------------------------


def _mlp(layer, cfg, x, live):
    return llama._mlp(layer, x, None, None, None, cfg), None, None


def _result(logits, caches, stats, return_stats):
    if not return_stats:
        return logits, caches
    return logits, caches, jnp.max(jnp.stack(stats), axis=0)


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
    return_stats: bool = False,
    state_slot: Optional[jax.Array] = None,     # the sequence's live slot
    state_from: Optional[jax.Array] = None,     # slot to start from; < 0: zeros
    snapshot_slot: Optional[jax.Array] = None,  # slot that keeps a snapshot
    snapshot_len: Optional[jax.Array] = None,   # ... after this many tokens
):
    """One sequence's prefill chunk: (last valid token's logits [V], new
    caches) and, with ``return_stats``, the chunk's counters (int32,
    :func:`stats_names`).  The slots as ``models/solar_kda.py: prefill``."""
    if prompt_targets is not None:
        raise ValueError(f"{__name__}: prompt logprobs (echo) are not offered")
    live = jnp.arange(tokens.shape[0]) < valid_len
    if state_slot is None:
        state_slot = default_slot(
            cfg, jnp.where(cached_len > 0, prefix_block_ids[0],
                           new_block_ids[0]), kv_caches)
    if state_from is None:
        state_from = jnp.where(cached_len > 0, state_slot, -1)
    slots = (state_slot, state_from, snapshot_slot, snapshot_len)
    stats = []

    def mix(kind, layer, cache, h):
        if kind == "gqa":
            return _gqa_prefill(layer, cfg, cache, h, cached_len,
                                prefix_block_ids, new_block_ids, valid_len)
        *out, counted = _mamba_prefill(
            layer, cfg, cache, h, live, valid_len, slots)
        stats.append(counted)
        return out

    x, caches, *_ = _blocks(
        params, cfg, kv_caches, params["embed_tokens"][tokens], live, mix,
        _mlp)
    x = rms_norm(x, params["norm"], cfg.rms_norm_eps)
    logits = llama._lm_head(params, cfg, x[jnp.maximum(valid_len - 1, 0)])
    return _result(logits, caches, stats, return_stats)


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
    return_stats: bool = False,
    state_slots: Optional[jax.Array] = None,   # [S] int32 live slots
):
    """Batched single-token decode: (logits [S, V], new caches), then as
    :func:`prefill`.  A row whose write is parked on the null block 0 is not
    live: the identity on its slot."""
    live = slot_block_ids != 0
    if state_slots is None:
        state_slots = default_slot(cfg, block_tables[:, 0], kv_caches)
    stats = []

    def mix(kind, layer, cache, h):
        if kind == "gqa":
            return _gqa_decode(layer, cfg, cache, h, block_tables, ctx_lens,
                               slot_block_ids, slot_offsets)
        *out, counted = _mamba_decode(layer, cfg, cache, h, live, state_slots)
        stats.append(counted)
        return out

    x, caches, *_ = _blocks(
        params, cfg, kv_caches, params["embed_tokens"][tokens], live, mix,
        _mlp)
    logits = llama._lm_head(
        params, cfg, rms_norm(x, params["norm"], cfg.rms_norm_eps))
    return _result(logits, caches, stats, return_stats)
