"""Window and full softmax attention in one model, head counts and rotary
forms by layer kind, a gate a head, over routed experts held by share behind
a leading dense layer (functional JAX): the ``laguna`` architecture.

Pre-norm blocks, RMSNorm, no biases, no norm on queries or keys: ``r = x +
Attn(norm1(x))``, ``y = r + FFN(norm2(r))``, a final RMSNorm, an untied head.
``cfg.layer_kinds`` names each layer's attention (a period of it tiled over
``cfg.num_layers``) and ``cfg.attention_specs[kind]`` says what that kind is
(``config.AttentionSpec``): its query heads ``H`` over the model's
``num_kv_heads`` key/value heads of ``head_dim``, its window, how much of a
head rotates, the rotary base and YaRN.  For a layer of kind ``k``:

    ``q = x W_q`` [H_k, D], ``k = x W_k``, ``v = x W_v`` [K, D]
    the first ``partial_rotary_factor x D`` dimensions of q and k rotate
    (rotate-half pairing, ``sarvam_mla.yarn_inv_freq``'s frequencies, cos and
    sin times ``attention_factor``), the rest pass through
    scores ``q . k / sqrt(D)``, causal; a ``window`` of W: position p sees
    keys ``p - W + 1 .. p``
    head h's output times ``sigmoid(x W_g)_h`` (``cfg.use_head_gate``), then W_o

**``"full"``** keeps K and V in pages of the block pool and goes through
``models/solar_kda.py``'s softmax path (the two dense kernels) with this
module's projection and gate.

**``"window"`` keeps a window's keys and no more**: a sequence owns one *slot*
of the state pool, a layer's rotated keys and values of its last W positions,
position p at row ``p mod W`` (the rolling buffer), ``[slots, W, K, D]`` twice:
a slot is whole tiles and is read and written where it lies.  Decode writes
the new key at ``p mod W`` and attends over the slot's ``min(ctx, W)`` live
rows -- the order of keys does not matter to a softmax once they are rotated
-- by reading the slot as ``W / 16`` pool-adjacent pages of 16 with a table
made on the device, through the paged decode kernel under a name of its own
(:data:`WINDOW_DECODE_KERNEL`).  A prefill chunk reads the buffer's up to W
earlier positions in order as its cached prefix and its own keys under the
window mask (the flash kernel skips tiles outside the window), then leaves in
the slot the last W positions as of the chunk's end and, in the snapshot slot,
those as of ``snapshot_len`` tokens into the chunk.  A padded slot of a chunk
and a dead row of a decode batch are the identity on the buffer.

Slots are addressed as ``models/solar_kda.py``'s (the registry's state-pool
contract): the keywords ``state_slot`` / ``state_from`` / ``snapshot_slot`` /
``snapshot_len`` of :func:`prefill` and ``state_slots`` of :func:`decode`, or,
where a caller hands none, ``solar_kda.default_slot``.

**FFN**: layers below ``cfg.first_k_dense_replace`` a dense SwiGLU of
``intermediate_size``; every other layer ``shared(x) + sum_chosen g_i E_i(x)``
with ``s = sigmoid(x W_r)`` over the router's published width, the
``num_experts_per_tok`` largest chosen, ``g = routed_scaling_factor s / sum
s``: ``models/sarvam_mla.py``'s ``route`` with no selection bias and its
``held_experts``, imported, with its counters.

Offers the engine (``models/registry.py``): ``init_params``,
``quantize_params`` (identity), ``prefill``, ``decode``, ``init_cache``,
``cache_bytes_per_token`` (the ``full`` layers' alone), ``state_bytes_per_slot``,
``snapshot_stride``, ``param_specs``, ``attention_paths``, ``stats_names`` and,
on both steps, ``return_choice`` and ``return_stats``.  No ``mixed_step``, no
``encode``, no LoRA, no int8, no mesh: refused at boot by name.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from production_stack_tpu.engine.config import (
    PAGED_KINDS, AttentionSpec, ModelConfig,
)
from production_stack_tpu.engine.models.sarvam_mla import (  # noqa: F401
    ROUTING_STATS, STATS_MAX, _is_routed, _result, _swiglu, held_experts,
    route, yarn_inv_freq,
)
# cache_bytes_per_token is the engine's to ask: the ``full`` layers' K and V.
from production_stack_tpu.engine.models.solar_kda import (  # noqa: F401
    _blocks, _dot, _gqa_decode, _gqa_prefill, _kinds, cache_bytes_per_token,
    default_slot, layer_kind,
)
from production_stack_tpu.engine.ops import attention as attn_ops
from production_stack_tpu.engine.ops.layers import apply_rope, rms_norm

Params = Dict
# Snapshots of a window layer's buffer lie at multiples of this many tokens
# from a chunk's start (``kv/state_pool.py``): a multiple of the 16-token
# block.
SNAPSHOT_STRIDE = 64
# Slots :func:`init_cache` makes where nobody says how many (the compare).
DEFAULT_STATE_SLOTS = 4
# Rows a page when a slot's buffer is read as pages by the decode kernel (the
# block pool's block; a window that is no multiple of it: their gcd).
PAGE = 16
# What the device trace calls the window layers' decode read.
WINDOW_DECODE_KERNEL = "window_decode_attention_pallas"


def stats_names(cfg: ModelConfig) -> tuple:
    return ROUTING_STATS


def snapshot_stride(cfg: ModelConfig) -> int:
    return SNAPSHOT_STRIDE


def _spec(cfg: ModelConfig, kind: str) -> AttentionSpec:
    return cfg.attention_specs[kind]


def state_bytes_per_slot(cfg: ModelConfig) -> int:
    """Bytes one sequence's slot takes over all ``window`` layers: the keys
    and the values of a window's positions."""
    return sum(
        2 * _spec(cfg, kind).window * cfg.num_kv_heads * cfg.head_dim
        * jnp.dtype(cfg.dtype).itemsize
        for kind in _kinds(cfg) if kind not in PAGED_KINDS)


def init_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
               sharding=None, state_slots: Optional[int] = None):
    """One tree, a ``(K, V)`` pair a layer: pages ``[num_blocks, block_size,
    kv heads, head_dim]`` for a ``full`` layer, rolling buffers ``[slots,
    window, kv heads, head_dim]`` for a ``window`` layer."""
    slots = state_slots or DEFAULT_STATE_SLOTS
    dtype = jnp.dtype(cfg.dtype)

    def pair(*lead):
        zeros = jax.jit(
            lambda: jnp.zeros((*lead, cfg.num_kv_heads, cfg.head_dim), dtype),
            out_shardings=sharding)
        return zeros(), zeros()

    return [pair(num_blocks, block_size) if kind in PAGED_KINDS
            else pair(slots, _spec(cfg, kind).window)
            for kind in _kinds(cfg)]


def _shapes(cfg: ModelConfig, layer_idx: int) -> Dict[str, tuple]:
    h, D = cfg.hidden_size, cfg.head_dim
    H = _spec(cfg, layer_kind(cfg, layer_idx)).num_heads
    shapes = {
        "input_layernorm": (h,), "post_attention_layernorm": (h,),
        "q_proj": (h, H * D), "k_proj": (h, cfg.num_kv_heads * D),
        "v_proj": (h, cfg.num_kv_heads * D), "o_proj": (H * D, h),
    }
    if cfg.use_head_gate:
        shapes["g_proj"] = (h, H)
    if _is_routed(cfg, layer_idx):
        E, I = cfg.num_experts, cfg.moe_intermediate_size
        S = cfg.num_shared_experts * I
        shapes.update({
            "router": (h, cfg.router_experts),
            "experts_gate": (E, h, I), "experts_up": (E, h, I),
            "experts_down": (E, I, h),
            "shared_gate": (h, S), "shared_up": (h, S), "shared_down": (S, h),
        })
    else:
        I = cfg.intermediate_size
        shapes.update({"gate_proj": (h, I), "up_proj": (h, I),
                       "down_proj": (I, h)})
    return shapes


_NORMS = ("input_layernorm", "post_attention_layernorm")


def param_specs(cfg: ModelConfig) -> Dict:
    """Every tensor whole on every device (the engine refuses a mesh)."""
    return {"embed_tokens": P(), "norm": P(), "lm_head": P(), "layers": [
        {name: P() for name in _shapes(cfg, i)}
        for i in range(cfg.num_layers)]}


def init_params(cfg: ModelConfig, key: jax.Array, shardings=None) -> Params:
    """Seeded random weights, each tensor made on the device by a jitted
    initialiser, as ``models/solar_kda.py`` makes its own: dense matrices
    0.02, norm scales 1, router logits of unit variance."""
    dtype = jnp.dtype(cfg.dtype)
    makers = {}

    def normal(key, shape, sharding, scale=0.02):
        maker = (shape, sharding, scale)
        if maker not in makers:
            def make(k):
                k = jax.random.wrap_key_data(
                    jnp.tile(jax.random.key_data(k), 2), impl="rbg")
                return (jax.random.normal(k, shape, jnp.float32)
                        * scale).astype(dtype)
            makers[maker] = jax.jit(make, out_shardings=sharding)
        return makers[maker](key)

    def ones(shape, sharding):
        return jax.jit(lambda: jnp.ones(shape, dtype),
                       out_shardings=sharding)()

    top = shardings or {}
    keys = jax.random.split(key, cfg.num_layers + 2)
    params: Params = {
        "embed_tokens": normal(keys[0], (cfg.vocab_size, cfg.hidden_size),
                               top.get("embed_tokens")),
        "lm_head": normal(keys[1], (cfg.hidden_size, cfg.vocab_size),
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
            elif name == "router":
                layer[name] = normal(k, shape, s, cfg.hidden_size ** -0.5)
            else:
                layer[name] = normal(k, shape, s)
        params["layers"].append(layer)
    return params


def quantize_params(params: Params, cfg: ModelConfig) -> Params:
    if cfg.quantization is not None:
        raise ValueError(
            f"{__name__} has no {cfg.quantization} weights (bf16 throughout)")
    return params


def attention_paths(cfg: ModelConfig):
    """(decode, prefill) for the engine's boot line: both kinds of layer with
    their query heads over the key heads, the window, and where each kind's
    keys lie."""
    K, D = cfg.num_kv_heads, cfg.head_dim
    decode = "pallas" if attn_ops.use_pallas_decode(K, D) else "xla-gather"
    parts = {"decode": [], "prefill": []}
    for kind, spec in cfg.attention_specs.items():
        flash = attn_ops.use_pallas_prefill(spec.num_heads, K, D, 256)
        heads = f"{kind} {spec.num_heads}q/{K}kv"
        where = ("pages of the block pool" if kind in PAGED_KINDS else
                 f"window {spec.window} in slots of the state pool")
        parts["decode"].append(f"{decode}[{heads}, {where}]")
        parts["prefill"].append(
            f"{'pallas-flash' if flash else 'xla-dense'}[{heads}]")
    return "+".join(parts["decode"]), "+".join(parts["prefill"])


# -- attention: what both kinds share ----------------------------------------


def rope_tables(cfg: ModelConfig, spec: AttentionSpec, positions: jax.Array):
    """cos, sin [..., rotated dimensions] for one kind of layer: the
    frequencies duplicated across both halves (rotate-half), times YaRN's
    ``attention_factor`` where the kind has a scaling."""
    dim = int(cfg.head_dim * spec.partial_rotary_factor)
    freqs = positions.astype(jnp.float32)[..., None] * yarn_inv_freq(
        dim, spec.rope_theta, spec.rope_scaling)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    amp = (spec.rope_scaling or {}).get("attention_factor", 1.0)
    return jnp.cos(emb) * amp, jnp.sin(emb) * amp


def _rotate(x, cos, sin):
    """The first ``cos.shape[-1]`` dimensions of each head of ``x``
    [..., heads, D] rotated, the rest passed through."""
    dim = cos.shape[-1]
    if dim == x.shape[-1]:
        return apply_rope(x, cos, sin)
    return jnp.concatenate(
        [apply_rope(x[..., :dim], cos, sin), x[..., dim:]], axis=-1)


def _project(spec: AttentionSpec, positions, layer, cfg, x):
    """x [T, h] -> (q [T, H, D], k, v [T, K, D]), q and k rotated as the
    layer's kind says (``positions`` [T])."""
    T, D = x.shape[0], cfg.head_dim
    cos, sin = rope_tables(cfg, spec, positions)
    heads = lambda w, n: _dot(x, layer[w]).astype(x.dtype).reshape(T, n, D)
    return (_rotate(heads("q_proj", spec.num_heads), cos, sin),
            _rotate(heads("k_proj", cfg.num_kv_heads), cos, sin),
            heads("v_proj", cfg.num_kv_heads))


def _gated(layer, cfg, x, out):
    """The heads' output [T, H, D] -> what W_o reads [T, H D], each head
    times its gate."""
    if cfg.use_head_gate:
        gate = jax.nn.sigmoid(_dot(x, layer["g_proj"]))          # [T, H]
        out = (out.astype(jnp.float32) * gate[..., None]).astype(x.dtype)
    return out.reshape(x.shape[0], -1)


# -- a window layer's rolling buffer -----------------------------------------


def _ring_after(old, new, cached_len, tokens):
    """The buffer ``[W, K, D]`` once ``tokens`` tokens of a chunk whose first
    lies at position ``cached_len`` are in: row r holds the newest position
    below ``cached_len + tokens`` that is r modulo W, from the chunk's own
    ``new`` [T, K, D] where the chunk holds it, else what ``old`` had."""
    W = old.shape[0]
    last = cached_len + tokens - 1
    newest = last - (last - jnp.arange(W)) % W
    mine = newest >= cached_len
    rows = new[jnp.clip(newest - cached_len, 0, new.shape[0] - 1)]
    return jnp.where(mine[:, None, None], rows.astype(old.dtype), old)


def _window_prefill(layer, cfg, spec, cache, x, cached_len, valid_len, slots):
    """A chunk through one ``window`` layer: (what W_o reads, the new
    buffers)."""
    slot, start, snap_slot, snap_len = slots
    W = spec.window
    q, k, v = _project(
        spec, cached_len + jnp.arange(x.shape[0]), layer, cfg, x)
    fresh = start < 0
    # What the slot the chunk starts from holds, taken out whole before
    # anything is written: the writes below then go into the pools in place.
    old = jax.lax.optimization_barrier([
        jnp.where(fresh, 0, jax.lax.dynamic_index_in_dim(
            buf, jnp.maximum(start, 0), keepdims=False)) for buf in cache])
    # The up to W positions before the chunk, the oldest first: the chunk's
    # cached prefix, its positions counted from the oldest kept (a mask reads
    # differences of positions alone, and the keys are rotated already).
    have = jnp.minimum(cached_len, W)
    order = (cached_len - have + jnp.arange(W)) % W
    # They go in as a pool of one W-token page with the table [0].
    out = attn_ops.prefill_attention(
        q, k, v, old[0][order][None], old[1][order][None],
        jnp.zeros((1,), jnp.int32), have, valid_len,
        scale=cfg.head_dim ** -0.5, sliding_window=W)
    put = jax.lax.dynamic_update_index_in_dim
    buffers = []
    for buf, was, new in zip(cache, old, (k, v)):
        if snap_slot is not None:
            buf = put(buf, _ring_after(was, new, cached_len, snap_len),
                      snap_slot, 0)
        buffers.append(
            put(buf, _ring_after(was, new, cached_len, valid_len), slot, 0))
    return _gated(layer, cfg, x, out), tuple(buffers)


def _window_decode(layer, cfg, spec, cache, x, positions, ctx_lens, live,
                   slots):
    """One token a row through one ``window`` layer: the new key at ``p mod
    W`` of the row's slot, then the slot's live rows, read as pages."""
    W, K, D = spec.window, cfg.num_kv_heads, cfg.head_dim
    q, k, v = _project(spec, positions, layer, cfg, x)
    rows = positions % W
    page = math.gcd(W, PAGE)
    per = W // page
    as_pages = lambda buf: buf.reshape(-1, page, K, D)
    # A dead row writes back what is there: the identity on its slot.
    k, v = (jnp.where(live[:, None, None], new, buf[slots, rows])
            for new, buf in zip((k, v), cache))
    pages = attn_ops.append_decode_kv(
        *(as_pages(buf) for buf in cache), k, v,
        slots * per + rows // page, rows % page)
    tables = slots[:, None] * per + jnp.arange(per, dtype=slots.dtype)
    seen = jnp.minimum(ctx_lens, W)
    if attn_ops.use_pallas_decode(K, D):
        from production_stack_tpu.engine.ops.pallas.paged_attention import (
            paged_decode_attention_pallas,
        )

        out = paged_decode_attention_pallas(
            q, *pages, tables, seen, scale=D ** -0.5,
            name=WINDOW_DECODE_KERNEL)
    else:
        with jax.named_scope("window_decode_attention"):
            out = attn_ops.paged_decode_attention(
                q, *pages, tables, seen, scale=D ** -0.5)
    return _gated(layer, cfg, x, out), tuple(
        p.reshape(buf.shape) for p, buf in zip(pages, cache))


# -- the two steps -----------------------------------------------------------


def _ffn(layer, cfg, x, live):
    """(FFN(x) [T, h], the layer's choice [T, k] or None, its counts or
    None): dense where the layer holds no router."""
    if "router" not in layer:
        return _swiglu(x, layer["gate_proj"], layer["up_proj"],
                       layer["down_proj"]).astype(x.dtype), None, None
    with jax.named_scope("routed_experts"):
        who, g = route(layer, cfg, x)     # no bias: the scores choose
        routed, stats = held_experts(layer, cfg, x, who, g, live)
    shared = _swiglu(x, layer["shared_gate"], layer["shared_up"],
                     layer["shared_down"])
    return (shared + routed).astype(x.dtype), who, stats


def _results(logits, caches, choice, stats, return_choice, return_stats):
    routed = lambda per_layer: [a for a in per_layer if a is not None]
    return _result(logits, caches, routed(choice), routed(stats), [],
                   return_choice, return_stats)


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
    caches), then as ``models/sarvam_mla.py: prefill``.  The slots as
    ``models/solar_kda.py: prefill``."""
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
    positions = cached_len + jnp.arange(T)

    def mix(kind, layer, cache, h):
        spec = _spec(cfg, kind)
        if kind not in PAGED_KINDS:
            return _window_prefill(
                layer, cfg, spec, cache, h, cached_len, valid_len, slots)
        return _gqa_prefill(
            layer, cfg, cache, h, cached_len, prefix_block_ids, new_block_ids,
            valid_len, functools.partial(_project, spec, positions), _gated)

    x, caches, *counted = _blocks(
        params, cfg, kv_caches, params["embed_tokens"][tokens], live, mix,
        _ffn)
    x = rms_norm(x, params["norm"], cfg.rms_norm_eps)
    logits = _dot(x[jnp.maximum(valid_len - 1, 0)], params["lm_head"])
    return _results(logits, caches, *counted, return_choice, return_stats)


def decode(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,          # [S] int32, one token a row (padded batch)
    positions: jax.Array,       # [S] int32 position of each token
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
        spec = _spec(cfg, kind)
        if kind not in PAGED_KINDS:
            return _window_decode(layer, cfg, spec, cache, h, positions,
                                  ctx_lens, live, state_slots)
        return _gqa_decode(
            layer, cfg, cache, h, block_tables, ctx_lens, slot_block_ids,
            slot_offsets, functools.partial(_project, spec, positions), _gated)

    x, caches, *counted = _blocks(
        params, cfg, kv_caches, params["embed_tokens"][tokens], live, mix,
        _ffn)
    logits = _dot(rms_norm(x, params["norm"], cfg.rms_norm_eps),
                  params["lm_head"])
    return _results(logits, caches, *counted, return_choice, return_stats)
