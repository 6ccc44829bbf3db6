"""The gated delta rule with a decay a head beside multi-head softmax attention
without position encoding, over a dense gated MLP, in blocks that norm what a
sub-layer returns (functional JAX): the ``olmo_hybrid`` architecture.

**The block, both kinds** (the Olmo 2/3 family's reordered norm; the source
has no key for it: assumed): ``h = x + RMSNorm(Mix(x))``, ``y = h +
RMSNorm(MLP(h))``, ``MLP(v) = (SiLU(v W_gate) . v W_up) W_down`` (``models/
llama.py``'s), no biases; a final RMSNorm and an untied head.
``cfg.layer_kinds`` names each layer's mix, one period tiled over
``cfg.num_layers`` (``layer_types`` of the source: three ``"gdn"`` then one
``"full"``).

**``"full"``: multi-head softmax attention.**  ``q, k, v = x W_q, x W_k, x
W_v``, ``cfg.num_kv_heads`` key heads for ``cfg.num_heads`` query heads (30 for
30 at the published size); an RMSNorm with a learned scale over the *whole
width* of ``q`` and of ``k`` before the split into heads (the family's
convention: assumed); scale ``head_dim^-1/2``, causal; NO rotary and no other
position encoding (``rope_parameters.rope_theta`` is null in the source: read
as ``cfg.use_rope`` False, the path ``solar_kda.py`` and ``jamba.py`` take; the
other reading, the family's base 500,000, is not served).  K and V lie in pages
and go through ``solar_kda``'s paged softmax path (the two dense kernels),
imported.  **A page holds** :func:`page_heads` **key heads**: the device tiles
a bf16 array's last two dimensions by (16, 128), so ``[N, 16, 30, 128]`` is kept
as ``[N, 16, 32, 128]`` whatever the program says, and Mosaic refuses a DMA of
30 of a tile's 32 rows; the pool is therefore made with 32 heads (two that no
query reads: their keys and values are zeros, their scores are under the
kernels' own-head mask), which costs the bytes the device's layout took
anyway: 16,384 B a position where the arithmetic needs 15,360, 6.7 % of the
pool and of every decode read.  Eight heads or fewer (one sublane tile or a
divisor of it) are kept as they are.

**``"gdn"``: the gated delta rule with a decay a head** (Gated DeltaNet, arXiv
2412.06464).  ``u = x [W_q ; W_k ; W_v]`` (``H x Dk``, ``H x Dk``, ``H x Dv``
columns: one product, the three projections side by side); ``q, k, v =
SiLU(conv(u))``, causal, depthwise over the last ``cfg.linear_conv_kernel``
positions, no bias; ``q`` and ``k`` L2-normalised a head (eps 1e-6), ``q``
scaled by ``Dk^-1/2``; ``g_t = -exp(A_log) softplus(x W_a + dt_bias)``, **one
number a head**; ``beta_t = 2 sigmoid(x W_b)`` a head (the 2 where
``cfg.kda_allow_neg_eigval``);

    ``S_t = (I - beta_t k_t k_t^T) e^{g_t} S_{t-1} + beta_t k_t v_t^T``,
    ``o_t = S_t^T q_t``

``S`` ``[Dk, Dv]`` a head (96 x 192), float32; out ``= (RMSNorm_head(o_t) .
SiLU(x W_g)) W_o``, ``W_g`` at full rank, the norm over a head's ``Dv`` with a
learned scale.  It is ``solar_kda.py``'s recurrence with ``Diag(exp g)`` a
multiple of the identity, a state that is not square, a SiLU gate where that
one has a sigmoid, and full-rank ``W_a`` / ``W_g``.  **Such a layer keeps no
keys**: a sequence owns one *slot* of ``(S [heads, Dk, Dv] float32, the last
kernel - 1 rows of u)``.

**The pool's layout and what pads.**  The state pool is ``[slots, H, Dk, Dv]``
float32, the orientation both kernels take and return (no transpose, no
relayout, the pool aliased through the decode kernel).  The device tiles the
last two dimensions by (8, 128): 96 key channels are twelve whole sublane
tiles, 192 value channels pad to 256 lanes, so a slot holds 2,949,120 B a
layer where the arithmetic needs 2,211,840 (a third more in the pool, 0.13 GB
of 59 slots, and on every decode read, 0.06 GB of a 7.4 GB step).  ``[Dv, Dk]``
pads 96 to 128 lanes, the same third.  The layout that pads nothing -- the 30
heads' value channels side by side, ``[Dk, H x Dv]`` = 45 whole lane tiles --
puts a head's columns at lane offsets of 192, which no block of the kernels'
grids can address (a block's last dimension is a multiple of 128 or the whole
array) and which a pair of heads a block would have to select apart lane by
lane; not taken for under 1 % of the step.  The convolution's rows lie in
``solar_kda.rows_pool_shape`` (11,520 channels: 90 whole lines of 128).

Slots are addressed as ``models/solar_kda.py``'s (the registry's state-pool
contract): ``state_slot`` / ``state_from`` / ``snapshot_slot`` /
``snapshot_len`` of :func:`prefill`, ``state_slots`` of :func:`decode``, or
``solar_kda.default_slot``.  A padded slot of a chunk and a dead row of a
decode batch are the identity on the state (``beta`` 0, ``g`` 0) and do not
shift the convolution rows.

**Kernels and plain forms.**  Prefill: ``ops/pallas/kda.py:
gdn_prefill_pallas`` on a TPU (the chunkwise form under a decay a head: a pair
of tokens' decay is one number, no ``k e^{-G}`` a channel exists),
``solar_kda.kda_chunk_plain`` elsewhere.  Decode: ``kda_decode_pallas``, the
call ``solar_kda.py`` makes, with the decay as one number down a head's column
(``solar_kda.kda_step_plain`` elsewhere).

Offers the engine (``models/registry.py``): ``init_params``,
``quantize_params`` (identity), ``prefill``, ``decode``, ``init_cache``,
``cache_bytes_per_token`` (the ``full`` layers' pages as the device keeps
them), ``state_bytes_per_slot``, ``snapshot_stride``, ``param_specs``,
``attention_paths``, ``layer_form``, ``stats_names`` / ``STATS_MAX`` and
``return_stats`` on both steps: the largest ``|S|`` the dispatch left in a slot
and its largest ``beta``, x 1000 (with ``beta`` up to 2 a state can grow where
a decay a channel damped it).  No ``mixed_step``, no ``encode``, no LoRA, no
int8, no mesh: refused at boot by name.  Nothing routes: no ``return_choice``.
"""

from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from production_stack_tpu.engine.config import PAGED_KINDS, ModelConfig
from production_stack_tpu.engine.models import llama
from production_stack_tpu.engine.models.solar_kda import (
    _convolve, _dot, _gqa_decode, _gqa_prefill, _kinds, _l2, _pallas_serves,
    default_slot, kda_chunk_plain, kda_step_plain, layer_kind,
    rows_pool_shape,
)
from production_stack_tpu.engine.ops import attention as attn_ops
from production_stack_tpu.engine.ops.layers import rms_norm

Params = Dict
# Snapshots of the state lie at multiples of this many tokens from a chunk's
# start (``kv/state_pool.py``): a multiple of the kernels' chunk and of the
# 16-token block.
SNAPSHOT_STRIDE = 64
# Slots :func:`init_cache` makes where nobody says how many (the compare).
DEFAULT_STATE_SLOTS = 4
# ``return_stats``: the largest |S| a slot was left with and the largest beta
# of a live token, over the dispatch's delta-rule layers, x 1000; both fold by
# a maximum (over steps and dispatches in the engine).
GDN_STATS = ("gdn_state_absmax_e3", "gdn_beta_max_e3")
STATS_MAX = GDN_STATS
# Rows of a bf16 tile of the device's memory: key heads a page is kept in
# whole tiles of (:func:`page_heads`).
HEAD_TILE = 16


def stats_names(cfg: ModelConfig) -> tuple:
    return GDN_STATS


def snapshot_stride(cfg: ModelConfig) -> int:
    return SNAPSHOT_STRIDE


def page_heads(cfg: ModelConfig) -> int:
    """Key heads a page of a ``full`` layer holds: ``cfg.num_kv_heads`` up to
    a sublane tile's eight, else rounded up to whole bf16 tiles of 16 rows
    (30 -> 32), which is what the device keeps the array in anyway."""
    K = cfg.num_kv_heads
    return K if K <= 8 else -(-K // HEAD_TILE) * HEAD_TILE


def _widths(cfg: ModelConfig):
    """(heads, key channels, value channels) of a delta-rule layer."""
    return (cfg.linear_num_heads, cfg.linear_head_dim,
            cfg.linear_value_head_dim or cfg.linear_head_dim)


def _conv_width(cfg: ModelConfig) -> int:
    H, Dk, Dv = _widths(cfg)
    return H * (2 * Dk + Dv)


def cache_bytes_per_token(cfg: ModelConfig) -> int:
    """Bytes of cache one position takes on the device: K and V of the
    layers that keep pages (``config.PAGED_KINDS``), :func:`page_heads` heads
    a page; a delta-rule layer's state does not grow."""
    return (2 * page_heads(cfg) * cfg.head_dim * jnp.dtype(cfg.dtype).itemsize
            * sum(kind in PAGED_KINDS for kind in _kinds(cfg)))


def state_bytes_per_slot(cfg: ModelConfig) -> int:
    """Bytes one sequence's slot takes on the device over all ``gdn``
    layers: the float32 state, its value channels in whole 128-lane tiles
    (192 -> 256), and the convolutions' rows."""
    H, Dk, Dv = _widths(cfg)
    return _kinds(cfg).count("gdn") * (
        H * Dk * -(-Dv // 128) * 128 * 4
        + (cfg.linear_conv_kernel - 1) * _conv_width(cfg)
        * jnp.dtype(cfg.dtype).itemsize)


def init_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
               sharding=None, state_slots: Optional[int] = None):
    """One tree: a ``full`` layer's ``(K, V)`` pages ``[num_blocks,
    block_size, page_heads, head_dim]`` or a ``gdn`` layer's ``(state [slots,
    H, Dk, Dv] float32, conv ``solar_kda.rows_pool_shape`` of kernel - 1 rows
    of H (2 Dk + Dv))`` slots."""
    slots = state_slots or DEFAULT_STATE_SLOTS
    dtype = jnp.dtype(cfg.dtype)

    def zeros(shape, dt):
        return jax.jit(lambda: jnp.zeros(shape, dt), out_shardings=sharding)()

    page = (num_blocks, block_size, page_heads(cfg), cfg.head_dim)
    return [
        (zeros(page, dtype), zeros(page, dtype)) if kind in PAGED_KINDS else
        (zeros((slots, *_widths(cfg)), jnp.float32),
         zeros(rows_pool_shape(slots, cfg.linear_conv_kernel - 1,
                               _conv_width(cfg)), dtype))
        for kind in _kinds(cfg)]


def _shapes(cfg: ModelConfig, layer_idx: int) -> Dict[str, tuple]:
    h, I = cfg.hidden_size, cfg.intermediate_size
    shapes = {
        "post_attention_layernorm": (h,), "post_feedforward_layernorm": (h,),
        "gate_proj": (h, I), "up_proj": (h, I), "down_proj": (I, h),
    }
    if layer_kind(cfg, layer_idx) in PAGED_KINDS:
        q, kv = cfg.num_heads * cfg.head_dim, cfg.num_kv_heads * cfg.head_dim
        shapes.update({"q_proj": (h, q), "k_proj": (h, kv), "v_proj": (h, kv),
                       "q_norm": (q,), "k_norm": (kv,), "o_proj": (q, h)})
        return shapes
    if cfg.linear_gate_rank:
        raise ValueError(f"{__name__}: a low-rank decay or gate "
                         f"(linear_gate_rank) is models/solar_kda.py's")
    H, Dk, Dv = _widths(cfg)
    shapes.update({
        "qkv_proj": (h, _conv_width(cfg)),
        "conv": (cfg.linear_conv_kernel, _conv_width(cfg)),
        "a_proj": (h, H), "dt_bias": (H,), "A_log": (H,), "b_proj": (h, H),
        "g_proj": (h, H * Dv), "o_norm": (Dv,), "o_proj": (H * Dv, h),
    })
    return shapes


_ONES = ("post_attention_layernorm", "post_feedforward_layernorm", "q_norm",
         "k_norm", "o_norm")
_FLOAT32 = ("A_log", "dt_bias")


def param_specs(cfg: ModelConfig) -> Dict:
    """Every tensor whole on every device (the engine refuses a mesh)."""
    return {"embed_tokens": P(), "norm": P(), "lm_head": P(), "layers": [
        {name: P() for name in _shapes(cfg, i)}
        for i in range(cfg.num_layers)]}


def init_params(cfg: ModelConfig, key: jax.Array, shardings=None) -> Params:
    """Seeded random weights, each tensor made on the device by a jitted
    initialiser.  Dense matrices 0.02; norm scales 1; a convolution's taps
    ``kernel^-1/2`` (the output keeps its input's spread); ``A_log = log U(1,
    16)`` and ``dt_bias`` the inverse softplus of ``exp U(log 0.001, log
    0.1)``, a head each, float32 (the published initialisation of the layer,
    as ``solar_kda.py`` draws its own): a head forgets between a thousandth and
    1.6 nats a token."""
    if cfg.tie_word_embeddings:
        raise ValueError(f"{__name__}: a tied head is not offered")
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
            if name in _ONES:
                layer[name] = ones(shape, s)
            elif name in _FLOAT32:
                layer[name] = draw(name, k, shape, s)
            elif name == "conv":
                layer[name] = draw("normal", k, shape, s, shape[0] ** -0.5)
            else:
                layer[name] = draw("normal", k, shape, s)
        params["layers"].append(layer)
    return params


def quantize_params(params: Params, cfg: ModelConfig) -> Params:
    if cfg.quantization is not None:
        raise ValueError(
            f"{__name__} has no {cfg.quantization} weights (bf16 throughout)")
    return params


# -- the gated delta rule with a decay a head --------------------------------


def use_pallas_gdn(cfg: ModelConfig) -> bool:
    """Trace-time dispatch check for the two kernels of ``ops/pallas/kda.py``
    this module calls: key channels in whole sublane tiles, value channels in
    half lane tiles at the least (96 x 192 serves; the tiny preset's 8 x 16
    takes the plain forms)."""
    _, Dk, Dv = _widths(cfg)
    return Dk % 8 == 0 and Dv % 64 == 0 and _pallas_serves()


def attention_paths(cfg: ModelConfig):
    """(decode, prefill) for the engine's boot line: the ``full`` layers'
    kernels and the ``gdn`` layers'."""
    K = page_heads(cfg)
    decode = "pallas" if attn_ops.use_pallas_decode(
        K, cfg.head_dim) else "xla-gather"
    prefill = "pallas-flash" if attn_ops.use_pallas_prefill(
        K, K, cfg.head_dim, 256) else "xla-dense"
    gdn = "pallas" if use_pallas_gdn(cfg) else "xla"
    return (f"{decode}[full {cfg.num_heads}q/{cfg.num_kv_heads}kv, pages of "
            f"{K}]+{gdn}-kda",
            f"{prefill}+{gdn}-gdn-chunk")


def layer_form(cfg: ModelConfig) -> str:
    """The engine's boot line ``Layer: ...``."""
    H, Dk, Dv = _widths(cfg)
    kinds = _kinds(cfg)
    return (f"{kinds.count('gdn')} gated delta-rule layers ({H} heads, state "
            f"{Dk} x {Dv} float32, a decay a head) + "
            f"{sum(k in PAGED_KINDS for k in kinds)} softmax layers "
            f"({cfg.num_heads} heads over {cfg.num_kv_heads} key heads of "
            f"{cfg.head_dim}, a page keeps {page_heads(cfg)}, whole-width QK "
            f"norm, no position encoding); the norm after each sub-layer; "
            f"dense SwiGLU {cfg.intermediate_size}; {len(kinds)} cache "
            f"arrays")


def _gdn_inputs(layer, cfg, x, mixed, live):
    """From the block's input ``x`` [T, h] and the convolved, activated stream
    ``mixed`` [T, H (2 Dk + Dv)] float32: (q, k [T, H, Dk], v [T, H, Dv], g,
    beta [T, H]) float32; where ``live`` is False, ``beta`` 0 and ``g`` 0: the
    identity."""
    T = x.shape[0]
    H, Dk, Dv = _widths(cfg)
    q = mixed[:, :H * Dk].reshape(T, H, Dk)
    k = mixed[:, H * Dk:2 * H * Dk].reshape(T, H, Dk)
    v = mixed[:, 2 * H * Dk:].reshape(T, H, Dv)
    q, k = _l2(q) * Dk ** -0.5, _l2(k)
    g = -jnp.exp(layer["A_log"]) * jax.nn.softplus(
        _dot(x, layer["a_proj"]) + layer["dt_bias"])
    beta = jax.nn.sigmoid(_dot(x, layer["b_proj"]))
    if cfg.kda_allow_neg_eigval:
        beta = 2.0 * beta
    live = live[:, None]
    return q, k, v, jnp.where(live, g, 0.0), jnp.where(live, beta, 0.0)


def _gdn_out(layer, cfg, x, o):
    """(RMSNorm_head(o) . SiLU(x W_g)) -> [T, H Dv] in x's dtype."""
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + cfg.rms_norm_eps)
    o = o * layer["o_norm"].astype(jnp.float32)
    gate = jax.nn.silu(_dot(x, layer["g_proj"]))
    return (o.reshape(x.shape[0], -1) * gate).astype(x.dtype)


def _gdn_stats(absmax, beta):
    """[2] int32: the largest |S| and the largest beta, x 1000."""
    both = jnp.stack([jnp.max(absmax), jnp.max(beta)]) * 1e3
    return jnp.minimum(both, 2.0 ** 31 - 128).astype(jnp.int32)


def _gdn_prefill(layer, cfg, cache, x, live, valid_len, slots):
    """A chunk through one ``gdn`` layer: (what W_o reads [T, H Dv], the new
    ``(state, conv)``, the layer's counters)."""
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
    q, k, v, g, beta = _gdn_inputs(layer, cfg, x, mixed, live)
    chunked = kda_chunk_plain
    if use_pallas_gdn(cfg):
        from production_stack_tpu.engine.ops.pallas.kda import (
            gdn_prefill_pallas as chunked,
        )
    with jax.named_scope("gdn_prefill"):
        o, s1, snap = chunked(q, k, v, g, beta, s0,
                              None if snap_slot is None else snap_len)
    rows = lambda at: jax.lax.dynamic_slice_in_dim(
        full, at, K - 1, axis=0).reshape(conv.shape[1:])
    if snap_slot is not None:
        state = state.at[snap_slot].set(snap)
        conv = conv.at[snap_slot].set(rows(snap_len))
    state = state.at[slot].set(s1)
    conv = conv.at[slot].set(rows(valid_len))
    return (_gdn_out(layer, cfg, x, o), (state, conv),
            _gdn_stats(jnp.abs(s1), beta))


def _gdn_decode(layer, cfg, cache, x, live, slots):
    """One token a row through one ``gdn`` layer."""
    state, conv = cache
    u = _dot(x, layer["qkv_proj"]).astype(x.dtype)
    R, W = u.shape
    window = jnp.concatenate(
        [conv[slots].reshape(R, -1, W), u[:, None]], axis=1)
    q, k, v, g, beta = _gdn_inputs(
        layer, cfg, x, _convolve(layer, window), live)
    with jax.named_scope("gdn_decode"):
        if use_pallas_gdn(cfg):
            from production_stack_tpu.engine.ops.pallas.kda import (
                kda_decode_pallas,
            )

            o, absmax, state = kda_decode_pallas(
                q, k, v, g, beta, state, slots, absmax=True)
        else:
            o, rows = kda_step_plain(q, k, v, g, beta, state[slots])
            state = state.at[slots].set(rows)
            absmax = jnp.max(jnp.abs(rows), axis=-2)
    conv = conv.at[slots].set(jnp.where(
        live[:, None, None], window[:, 1:], window[:, :-1]).reshape(
            R, *conv.shape[1:]))
    stats = _gdn_stats(jnp.where(live[:, None, None], absmax, 0.0), beta)
    return _gdn_out(layer, cfg, x, o), (state, conv), stats


# -- multi-head softmax attention, the norm over the whole width -------------


def _full_project(layer, cfg, x):
    """x [T, h] -> q [T, page heads, D], k, v [T, page heads, D]: the QK norm
    over the whole width, then the heads, then zeros up to a page's heads."""
    T = x.shape[0]
    q = rms_norm(_dot(x, layer["q_proj"]).astype(x.dtype), layer["q_norm"],
                 cfg.rms_norm_eps)
    k = rms_norm(_dot(x, layer["k_proj"]).astype(x.dtype), layer["k_norm"],
                 cfg.rms_norm_eps)
    v = _dot(x, layer["v_proj"]).astype(x.dtype)
    more = page_heads(cfg) - cfg.num_kv_heads

    def heads(a):
        a = a.reshape(T, -1, cfg.head_dim)
        return jnp.pad(a, ((0, 0), (0, more), (0, 0))) if more else a

    return heads(q), heads(k), heads(v)


def _full_out(layer, cfg, x, out):
    return out[:, :cfg.num_heads].reshape(x.shape[0], -1)


# -- the two steps -----------------------------------------------------------


def _blocks(params, cfg, kv_caches, x, mix):
    """Both steps' layers: ``mix(kind, layer, cache, x) -> (what W_o reads
    [T, .], the layer's new cache)`` is the step's own and reads the stream as
    it is; the norms come after each sub-layer, inside its residual branch."""
    caches = []
    for i, (layer, cache) in enumerate(zip(params["layers"], kv_caches)):
        out, new = mix(layer_kind(cfg, i), layer, cache, x)
        caches.append(new)
        x = x + rms_norm(_dot(out, layer["o_proj"]).astype(x.dtype),
                         layer["post_attention_layernorm"], cfg.rms_norm_eps)
        x = x + rms_norm(llama._mlp(layer, x, None, None, None, cfg),
                         layer["post_feedforward_layernorm"],
                         cfg.rms_norm_eps)
    return x, caches


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

    def mix(kind, layer, cache, x):
        if kind in PAGED_KINDS:
            return _gqa_prefill(layer, cfg, cache, x, cached_len,
                                prefix_block_ids, new_block_ids, valid_len,
                                project=_full_project, out=_full_out)
        *out, counted = _gdn_prefill(
            layer, cfg, cache, x, live, valid_len, slots)
        stats.append(counted)
        return out

    x, caches = _blocks(
        params, cfg, kv_caches, params["embed_tokens"][tokens], mix)
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

    def mix(kind, layer, cache, x):
        if kind in PAGED_KINDS:
            return _gqa_decode(layer, cfg, cache, x, block_tables, ctx_lens,
                               slot_block_ids, slot_offsets,
                               project=_full_project, out=_full_out)
        *out, counted = _gdn_decode(layer, cfg, cache, x, live, state_slots)
        stats.append(counted)
        return out

    x, caches = _blocks(
        params, cfg, kv_caches, params["embed_tokens"][tokens], mix)
    logits = llama._lm_head(
        params, cfg, rms_norm(x, params["norm"], cfg.rms_norm_eps))
    return _result(logits, caches, stats, return_stats)
