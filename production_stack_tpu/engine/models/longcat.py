"""A layer of two latent attentions and two dense FFNs with one routed FFN
across them (shortcut-connected), behind a softmax router some of whose
outputs are identity experts, held by share (functional JAX): the ``longcat``
architecture (LongCat-Flash; arXiv:2509.01322).

``x`` the residual, RMSNorm with a learned scale, no biases; one layer:

    ``a = x + Attn_0(norm_a0(x))``
    ``u = norm_f0(a)``
    ``m = MoE(u)``                      the shortcut: used only at the end
    ``b = a + FFN_0(u)``                dense SwiGLU of ``intermediate_size``
    ``c = b + Attn_1(norm_a1(b))``
    ``y = c + FFN_1(norm_f1(c)) + m``

then a final RMSNorm and an untied head.  Nothing stands between ``m`` and the
sum that takes it: XLA is free to run the grouped products beside ``FFN_0`` and
``Attn_1``; whether it does is the trace's to say.

**Attn** (both alike, weights and a cache array each) is
``models/sarvam_mla.py``'s latent attention with the low-rank query path,
imported: ``q = W_qb RMSNorm(W_qa h)`` times ``(hidden_size / q_lora_rank)^1/2``
(``cfg.mla_scale_q_lora``), the normed latent times ``(hidden_size /
kv_lora_rank)^1/2`` (``cfg.mla_scale_kv_lora``; the cache keeps the scaled
latent), no norm a head, plain rotary frequencies; the absorbed decode and
prefill, the expanded form, both Pallas kernels and the 640-lane cache layout
are that module's, unchanged.  The cache is ``cfg.cache_layers`` = 2 x
``num_layers`` arrays, layer ``l``'s at ``2 l`` and ``2 l + 1``.

**MoE**: ``p = softmax(W_r u)`` in float32 over ``cfg.router_width`` =
``router_experts`` + ``zero_expert_num`` outputs; the ``num_experts_per_tok``
largest of ``p + b`` are chosen (``b`` selects and never weighs); ``g_i =
routed_scaling_factor x p_i``, not renormalised (``sarvam_mla.route`` with
``cfg.router_scoring`` "softmax" and ``cfg.norm_topk_prob`` False).  Ids below
``router_experts`` are SwiGLU experts of ``moe_intermediate_size``; the ids
from there on return their input: ``MoE(u) = sum_{i real} g_i E_i(u) +
(sum_{i identity} g_i) u``.  **Held by share**: this chip holds experts ``0 ..
cfg.num_experts - 1``, routes over the whole width and computes its own
experts' part through ``sarvam_mla.held_experts``; what the absent experts
would add is left out, and the identity term, which every chip of the
deployment computes alike, is computed here whole and counted once
(:func:`moe`).  No shared expert, no leading dense layer.

Offers the engine (``models/registry.py``): ``init_params``,
``quantize_params`` (identity), ``prefill``, ``decode``, ``init_cache``,
``cache_bytes_per_token`` (two arrays a layer), ``param_specs``,
``attention_paths``, ``prefill_attn_tiles``, ``layer_form``, ``stats_names``
(``sarvam_mla.ROUTING_STATS`` and ``moe_zero_assigned``) and, on both steps,
``return_choice`` (ids over the router's whole width) and ``return_stats``.
No ``mixed_step``, no ``encode``, no LoRA, no int8, no mesh, no prompt
logprobs: refused by name.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from production_stack_tpu.engine.config import ModelConfig
from production_stack_tpu.engine.models.sarvam_mla import (  # noqa: F401
    ROUTING_STATS, STATS_MAX, _dot, _swiglu, attention_paths,
    cache_bytes_per_token, decode_attention, held_experts, init_cache,
    prefill_attention, prefill_attn_tiles, quantize_params, route,
)
from production_stack_tpu.engine.ops.layers import rms_norm

Params = Dict
# What ``return_stats`` counts beside ``sarvam_mla.ROUTING_STATS``: picks of
# live rows that named an identity expert, over routed layers (and, by the
# window program, over decode steps).
ZERO_STATS = ("moe_zero_assigned",)
_NORMS = ("input_layernorm", "post_attention_layernorm", "kv_a_layernorm",
          "q_a_layernorm")


def stats_names(cfg: ModelConfig) -> tuple:
    return ROUTING_STATS + ZERO_STATS


def layer_form(cfg: ModelConfig) -> str:
    """What a layer is made of, for the engine's boot line."""
    n = cfg.attn_per_layer
    return (f"{n} latent attentions + {n} dense FFN + 1 routed FFN "
            f"(shortcut), router {cfg.router_width} = {cfg.router_experts} + "
            f"{cfg.zero_expert_num} identity, {cfg.num_experts} held; "
            f"{cfg.cache_layers} cache arrays ({n} a layer)")


def _shapes(cfg: ModelConfig) -> Dict:
    """One layer's tree of shapes: ``attn`` and ``ffn`` a list, an entry an
    attention and the dense FFN that follows it."""
    h, H = cfg.hidden_size, cfg.num_heads
    qd = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    attn = {
        "input_layernorm": (h,),
        "q_a_proj": (h, cfg.q_lora_rank),
        "q_a_layernorm": (cfg.q_lora_rank,),
        "q_b_proj": (cfg.q_lora_rank, H * qd),
        "kv_a_proj": (h, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
        "kv_a_layernorm": (cfg.kv_lora_rank,),
        "kv_b_proj": (cfg.kv_lora_rank,
                      H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "o_proj": (H * cfg.v_head_dim, h),
    }
    I, E, M = cfg.intermediate_size, cfg.num_experts, cfg.moe_intermediate_size
    ffn = {"post_attention_layernorm": (h,), "gate_proj": (h, I),
           "up_proj": (h, I), "down_proj": (I, h)}
    return {
        "attn": [dict(attn) for _ in range(cfg.attn_per_layer)],
        "ffn": [dict(ffn) for _ in range(cfg.attn_per_layer)],
        "router": (h, cfg.router_width),
        "router_bias": (cfg.router_width,),
        "experts_gate": (E, h, M), "experts_up": (E, h, M),
        "experts_down": (E, M, h),
    }


_is_shape = lambda x: isinstance(x, tuple)


def param_specs(cfg: ModelConfig) -> Dict:
    """Every tensor whole on every device (the engine refuses a mesh)."""
    layer = jax.tree_util.tree_map(lambda _s: P(), _shapes(cfg),
                                   is_leaf=_is_shape)
    return {"embed_tokens": P(), "norm": P(), "lm_head": P(),
            "layers": [layer for _ in range(cfg.num_layers)]}


def init_params(cfg: ModelConfig, key: jax.Array, shardings=None) -> Params:
    """Seeded random weights, each tensor made on the device by a jitted
    initialiser, as ``models/sarvam_mla.py`` makes its own: dense matrices
    0.02, norm scales 1, router logits of unit variance.  The selection bias
    is drawn with a standard deviation of half a mean score (1 / (2 x the
    router's width): small against the scores' spread, so that it moves
    near-ties and no more, and the picks fall on the identities by their
    share of the width."""
    dtype = jnp.dtype(cfg.dtype)
    makers = {}

    def normal(key, shape, sharding, std=0.02, as_dtype=dtype):
        maker = (shape, sharding, std, as_dtype)
        if maker not in makers:
            def make(k):
                k = jax.random.wrap_key_data(
                    jnp.tile(jax.random.key_data(k), 2), impl="rbg")
                return (jax.random.normal(k, shape, jnp.float32)
                        * std).astype(as_dtype)
            makers[maker] = jax.jit(make, out_shardings=sharding)
        return makers[maker](key)

    def ones(shape, sharding):
        return jax.jit(lambda: jnp.ones(shape, dtype),
                       out_shardings=sharding)()

    def tensor(path, shape, key, sharding):
        name = path[-1].key
        if name in _NORMS:
            return ones(shape, sharding)
        if name == "router_bias":
            return normal(key, shape, sharding, 0.5 / cfg.router_width,
                          jnp.float32)
        if name == "router":
            return normal(key, shape, sharding, cfg.hidden_size ** -0.5)
        return normal(key, shape, sharding)

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
    with_paths, tree = jax.tree_util.tree_flatten_with_path(
        _shapes(cfg), is_leaf=_is_shape)
    for i in range(cfg.num_layers):
        sh = (tree.flatten_up_to(shardings["layers"][i]) if shardings
              else [None] * len(with_paths))
        layer_keys = jax.random.split(keys[i + 2], len(with_paths))
        params["layers"].append(jax.tree_util.tree_unflatten(tree, [
            tensor(path, shape, k, s)
            for (path, shape), k, s in zip(with_paths, layer_keys, sh)]))
    return params


# -- the routed FFN ----------------------------------------------------------


def moe(layer: Params, cfg: ModelConfig, x, live):
    """``MoE(x)`` [T, h] float32 as this chip computes it, the layer's choice
    [T, k] (ids over the router's whole width) and its counts (int32,
    :func:`stats_names`).  The held experts' part is ``held_experts``'s (an
    id it does not hold, an identity's among them, adds nothing there); the
    identity term is every chosen identity's share of the input itself, the
    same on every chip of the deployment and computed here whole."""
    who, g = route(layer, cfg, x)
    routed, stats = held_experts(layer, cfg, x, who, g, live)
    zero = who >= cfg.router_experts
    share = jnp.sum(jnp.where(zero, g, 0.0), axis=-1)            # [T]
    out = routed + share[:, None] * x.astype(jnp.float32)
    counted = jnp.sum(zero & live[:, None]).astype(jnp.int32)
    return out, who, jnp.concatenate([stats, counted[None]])


# -- the two steps -----------------------------------------------------------


def _blocks(params: Params, cfg: ModelConfig, kv_caches, x, live, attention):
    """The layers of both steps: embeddings ``x`` [T, d] -> (what the final
    norm reads, the new caches, each layer's choice, its counts).
    ``attention`` is ``sarvam_mla.prefill_attention``'s or
    ``decode_attention``'s."""
    T, eps = x.shape[0], cfg.rms_norm_eps
    caches, choice, stats = [], [], []

    def attend(sub, cache, h):
        out, new = attention(
            sub, cache, rms_norm(h, sub["input_layernorm"], eps))
        caches.append(new)
        return _dot(out.reshape(T, -1), sub["o_proj"]).astype(h.dtype)

    def dense(sub, h):
        return _swiglu(h, sub["gate_proj"], sub["up_proj"], sub["down_proj"])

    n = cfg.attn_per_layer
    for i, layer in enumerate(params["layers"]):
        mine = kv_caches[n * i:n * (i + 1)]
        shortcut = None
        for j, (attn, ffn, cache) in enumerate(
                zip(layer["attn"], layer["ffn"], mine)):
            x = x + attend(attn, cache, x)
            u = rms_norm(x, ffn["post_attention_layernorm"], eps)
            if j == 0:
                # The routed FFN reads what the first dense FFN reads and
                # lands after the last one.
                with jax.named_scope("routed_experts"):
                    shortcut, who, counted = moe(layer, cfg, u, live)
                choice.append(who)
                stats.append(counted)
            y = dense(ffn, u)
            if j == n - 1:
                y = y + shortcut
            x = x + y.astype(x.dtype)
    return x, caches, choice, stats


def _result(logits, caches, choice, stats, return_choice, return_stats):
    out = (logits, caches)
    if return_choice:
        out += (jnp.stack(choice),)
    if return_stats:
        # Counts add over the layers; the fullest expert is a maximum.
        stats = jnp.stack(stats)
        folds_by_max = jnp.array(
            [name in STATS_MAX for name in ROUTING_STATS + ZERO_STATS])
        out += (jnp.where(folds_by_max, stats.max(0), stats.sum(0)),)
    return out


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
):
    """One sequence's prefill chunk: (last valid token's logits [V], new
    caches), then as ``models/sarvam_mla.py: prefill``."""
    if prompt_targets is not None:
        raise ValueError(f"{__name__}: prompt logprobs (echo) are not offered")
    T = tokens.shape[0]
    attention = prefill_attention(
        cfg, T, cached_len, prefix_block_ids, new_block_ids, valid_len)
    live = jnp.arange(T) < valid_len
    x, caches, *counted = _blocks(
        params, cfg, kv_caches, params["embed_tokens"][tokens], live,
        attention)
    x = rms_norm(x, params["norm"], cfg.rms_norm_eps)
    logits = _dot(x[jnp.maximum(valid_len - 1, 0)], params["lm_head"])
    return _result(logits, caches, *counted, return_choice, return_stats)


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
):
    """Batched single-token decode: (logits [S, V], new caches), then as
    :func:`prefill`.  A row whose write is parked on the null block 0 is not
    live: routed nowhere and not counted."""
    attention = decode_attention(
        cfg, positions, block_tables, ctx_lens, slot_block_ids, slot_offsets)
    live = slot_block_ids != 0
    x, caches, *counted = _blocks(
        params, cfg, kv_caches, params["embed_tokens"][tokens], live,
        attention)
    logits = _dot(rms_norm(x, params["norm"], cfg.rms_norm_eps),
                  params["lm_head"])
    return _result(logits, caches, *counted, return_choice, return_stats)
