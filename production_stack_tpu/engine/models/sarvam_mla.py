"""Latent attention (MLA) over routed experts held by share (functional JAX,
paged latent cache): the ``sarvam_mla`` architecture.

Pre-norm blocks, RMSNorm: ``h = x + Attn(norm1(x))``, ``y = h + FFN(norm2(h))``.
The first ``cfg.first_k_dense_replace`` layers' FFN is a SwiGLU of
``cfg.intermediate_size``; the others are routed.  Two families are served,
told apart by the configuration alone: ``sarvam_mla`` (one residual stream, a
full query projection, a norm a query head, experts held by share) and
``xing4_0`` (``cfg.hc_mult`` residual streams, a low-rank query path, every
expert held).  One latent attention, one cache, one router and one grouped
dispatch serve both.

**The residual path.**  With ``cfg.hc_mult`` 0 the plain sum above, taken in
Python at trace time: no operation of what follows is in such a program.
Else a token carries ``n = cfg.hc_mult`` streams ``X [n, d]`` (the embedding
copied into each; summed before the final norm) and every sub-layer ``F`` owns
a mapping (:func:`_mhc`): ``[p, q, r] = RMSNorm(vec X) W``, ``H_pre =
sigmoid(a_pre p + b_pre)``, ``H_post = 2 sigmoid(a_post q + b_post)``,
``H_res`` = ``exp`` of ``a_res mat(r) + b_res`` clamped to
+-``cfg.hc_res_clamp``, made doubly stochastic by ``cfg.hc_sinkhorn_iters``
row-then-column normalisations; ``y = F(norm(H_pre X))``, ``X' = H_res X +
H_post^T y``.  The mapping is float32 throughout (its weights too); the
streams keep the activations' dtype.

**Latent attention.**  ``q = W_q x`` per head (with ``cfg.q_lora_rank``:
``q = W_qb RMSNorm(W_qa x)``, a latent of that rank with a learned scale),
split into a part without
position (``qk_nope_head_dim``) and a rotary part (``qk_rope_head_dim``);
``[c ; r] = W_kva x``, ``c`` the latent (``kv_lora_rank``), ``r`` one rotary
key shared by all heads.  With ``cfg.use_qk_norm`` an RMSNorm with a learned
scale runs over each query head's whole width before its rotary part is
rotated, and over ``r`` before rotation; ``c`` is always normed
(``kv_a_layernorm``).  Rotation is rotate-half (the two halves of the rotary
width pair up), frequencies by ``deepseek_yarn`` (:func:`yarn_inv_freq`).
**The cache holds ``[c ; r]``: one array a layer,
``kv_lora_rank + qk_rope_head_dim`` wide, no V** (:func:`init_cache`).
``[k_nope ; v] = W_kvb c`` per head; ``score = (q_nope.k_nope + q_rope.r) *
q_head_dim^-1/2 * m^2`` with ``m = 0.1 * mscale_all_dim * ln(factor) + 1``.

Two forms, one result.  **Absorbed**: ``W_kvb`` goes into the query and the
output (``q~ = W_UK^T q_nope``, ``score = [q~ ; q_rope] . [c ; r]``,
``o = W_UV sum p c``) and the latent pages are read as they lie, key and value
at once.  **Expanded**: cached and new latents become per-head K and V
(``W_kvb c``), a tile of keys at a time under a running softmax; no ``[chunk,
context, heads]`` score array is ever built.  What runs where:

- :func:`decode` is absorbed everywhere (:func:`_absorbed_attention`): on a
  TPU by the Pallas kernel ``latent_decode_attention_pallas`` of
  ``ops/pallas/latent_attention.py``, each live page copied HBM -> VMEM once;
  elsewhere by an XLA walk, a tile of blocks at a time (:func:`_latent_walk`).
- :func:`prefill` (:func:`_prefill_attention`) is absorbed on a TPU, one call
  of ``latent_prefill_attention_pallas`` a layer: the cached prefix's pages
  read through the block table, the chunk's own rows causally after them, a
  stage's scores and probabilities kept in VMEM, a tile of slots past
  ``valid_len`` skipped and written as zeros (PR 57).  Elsewhere -- the CPU,
  a cache row that is not whole 128-lane tiles, heads that do not fill a
  sublane tile, the A/B switch -- it is expanded in XLA
  (:func:`_expanded_attention`): the statement the kernel is held to
  (``tests/test_pallas_latent_prefill.py``) and the baseline it was measured
  against (``tools/latent_prefill_microbench.py``).

**Routed FFN.**  ``s = sigmoid(W_r x)`` in float32 over the router's
published width ``cfg.router_experts``; the ``num_experts_per_tok`` largest of
``s + b`` are chosen (``b``, a bias per expert, selects and never weighs);
``g_i = routed_scaling_factor * s_i / sum_chosen s``; ``FFN(x) = shared(x) +
sum_chosen g_i E_i(x)``.  **Held by share:** this chip holds experts
``0 .. cfg.num_experts - 1`` of the router's ``cfg.router_experts``.  ``g`` is
normalised over all chosen experts, the chip adds up those it holds, and what
the absent ones would have added is left out; no code stands in for the
other chips.  The dispatch is grouped: the (row, expert) pairs that land here
are sorted by expert and run as one ``jax.lax.ragged_dot`` a projection, so a
row costs the experts it chose here and a decode step streams only the
experts its rows touch.

``models/longcat.py`` imports the latent attention (through
:func:`prefill_attention` / :func:`decode_attention`, once an attention of its
two a layer), the cache, :func:`route` and :func:`held_experts`, and sets what
is decided here in Python at trace time for its family alone:
``cfg.mla_scale_q_lora`` / ``cfg.mla_scale_kv_lora`` (:func:`_project`),
``cfg.router_scoring`` / ``cfg.norm_topk_prob`` (:func:`route`) and
``cfg.cache_layers`` arrays (:func:`init_cache`).

What a module must offer the engine, and what it may, is in
``models/registry.py``.  This one offers ``init_params``, ``quantize_params``
(identity: bf16 throughout), ``prefill``, ``decode``, ``init_cache``,
``cache_bytes_per_token``, ``param_specs``, ``attention_paths``,
``residual_path``, ``stats_names`` and, on both steps, ``return_choice`` and
``return_stats``.  It has no ``mixed_step``, no
``encode``, no LoRA, no int8, no tensor parallelism: the engine refuses those
at boot by name.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from production_stack_tpu.engine.config import ModelConfig
from production_stack_tpu.engine.ops.layers import apply_rope, rms_norm

Params = Dict
LatentCaches = List[jax.Array]   # a layer: [num_blocks, block_size, width]

# What a dispatch's routing is counted as, in the order of the int32 vector
# ``return_stats`` gives (obs/flight_recorder.py: WindowRecord has a field of
# each name): (row, expert) pairs chosen by live rows over every routed layer;
# those that fell on experts held here; held experts with at least one row,
# summed over routed layers (and, by the window program, over decode steps);
# the fullest held expert's rows.
ROUTING_STATS = (
    "moe_assigned", "moe_assigned_here", "experts_touched", "expert_rows_max",
)
# What the residual path appends to that vector where ``cfg.hc_mult`` is set,
# over live rows and every mapping of the dispatch: entries of ``R`` the clamp
# changed; entries seen; the largest |row sum - 1| of an ``H_res`` after its
# last normalisation, x 1e6 (its columns sum to 1 by construction).
RESIDUAL_STATS = ("mhc_clamped", "mhc_entries", "mhc_err_e6")
# Of all those, the ones that fold by a maximum (over layers here, over steps
# and dispatches in the engine); every other adds.
STATS_MAX = ("expert_rows_max", "mhc_err_e6")
KEY_TILE = 512      # keys a tile of the expanded (prefill) attention
SCORE_ROWS = 32768  # heads x chunk slots of one tile's scores held at once


def stats_names(cfg: ModelConfig) -> tuple:
    """The names of ``return_stats``' vector for this configuration."""
    return ROUTING_STATS + (RESIDUAL_STATS if cfg.hc_mult else ())


def cache_width(cfg: ModelConfig) -> int:
    """What a position keeps: the latent and the rotary key."""
    return cfg.kv_lora_rank + cfg.qk_rope_head_dim


def cache_lanes(cfg: ModelConfig) -> int:
    """The cache array's last axis: :func:`cache_width` rounded up to the
    TPU's 128-lane tile (576 -> 640; the tail stays zero).  A minor axis
    that is no multiple of 128 makes the TPU lay the array out with another
    axis minor, and XLA then re-lays ALL of it around every scatter and
    gather (two copies of the whole pool a layer a step); padded, a position
    takes on the device what the tiled 576 would have taken anyway."""
    return -(-cache_width(cfg) // 128) * 128


def cache_bytes_per_token(cfg: ModelConfig) -> int:
    """Bytes of cache one position takes on the device over every cache
    array (one an attention: ``cfg.cache_layers``)."""
    return cache_lanes(cfg) * jnp.dtype(cfg.dtype).itemsize * cfg.cache_layers


def init_cache(cfg: ModelConfig, num_blocks: int, block_size: int,
               sharding=None) -> LatentCaches:
    """One array an attention (a layer, unless ``cfg.attn_per_layer`` says
    more: then a layer's arrays lie one after another), ``[c ; r]`` and the
    pad lanes wide.  A latent has no head axis to split, so ``sharding`` can
    only replicate it."""
    zeros = jax.jit(
        lambda: jnp.zeros((num_blocks, block_size, cache_lanes(cfg)),
                          jnp.dtype(cfg.dtype)),
        out_shardings=sharding)
    return [zeros() for _ in range(cfg.cache_layers)]


def _is_routed(cfg: ModelConfig, layer_idx: int) -> bool:
    return layer_idx >= cfg.first_k_dense_replace


def _shapes(cfg: ModelConfig, layer_idx: int) -> Dict[str, tuple]:
    h, H = cfg.hidden_size, cfg.num_heads
    qd = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    shapes = {
        "input_layernorm": (h,),
        "post_attention_layernorm": (h,),
        "kv_a_proj": (h, cache_width(cfg)),
        "kv_a_layernorm": (cfg.kv_lora_rank,),
        "kv_b_proj": (cfg.kv_lora_rank,
                      H * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
        "o_proj": (H * cfg.v_head_dim, h),
    }
    if cfg.q_lora_rank:
        shapes.update({"q_a_proj": (h, cfg.q_lora_rank),
                       "q_a_layernorm": (cfg.q_lora_rank,),
                       "q_b_proj": (cfg.q_lora_rank, H * qd)})
    else:
        shapes["q_proj"] = (h, H * qd)
    if cfg.use_qk_norm:
        shapes["q_norm"] = (qd,)
        shapes["k_rope_norm"] = (cfg.qk_rope_head_dim,)
    n = cfg.hc_mult
    for sub in ("attn", "ffn") if n else ():
        # [p, q, r] = x W: 2n + n^2 columns; a scale each of the three
        # groups; a bias a column.
        shapes.update({f"hc_{sub}_w": (n * h, 2 * n + n * n),
                       f"hc_{sub}_alpha": (3,),
                       f"hc_{sub}_bias": (2 * n + n * n,)})
    if _is_routed(cfg, layer_idx):
        E, I = cfg.num_experts, cfg.moe_intermediate_size
        S = cfg.num_shared_experts * I
        shapes.update({
            "router": (h, cfg.router_experts),
            "router_bias": (cfg.router_experts,),
            "experts_gate": (E, h, I), "experts_up": (E, h, I),
            "experts_down": (E, I, h),
            "shared_gate": (h, S), "shared_up": (h, S), "shared_down": (S, h),
        })
    else:
        I = cfg.intermediate_size
        shapes.update({"gate_proj": (h, I), "up_proj": (h, I),
                       "down_proj": (I, h)})
    return shapes


_NORMS = ("input_layernorm", "post_attention_layernorm", "kv_a_layernorm",
          "q_a_layernorm", "q_norm", "k_rope_norm")
# What the mappings' R is drawn to: a_res x (r of unit variance) + b_res
# spreads over several units, so that exp(R) spans orders of magnitude and
# twenty normalisations differ from three (tests/test_xing_mhc.py).
HC_RES_SPREAD = 3.0


def param_specs(cfg: ModelConfig) -> Dict:
    """Every tensor whole on every device: the engine refuses a mesh of more
    than one device for this module (expert parallelism under ``shard_map``
    is ROADMAP's, not here)."""
    return {"embed_tokens": P(), "norm": P(), "lm_head": P(), "layers": [
        {name: P() for name in _shapes(cfg, i)}
        for i in range(cfg.num_layers)]}


def init_params(cfg: ModelConfig, key: jax.Array, shardings=None) -> Params:
    """Seeded random weights, each tensor made on the device by a jitted
    initialiser (in its sharding where one is given): the float32 draw of
    one expert stack is the largest thing that ever exists beside the
    weights.  Norm scales are 1; the router's logits have unit variance at
    any width and the selection bias is drawn too (0.1, under half a
    score's spread), so that a bias misused as a weight shows.  A mapping of
    the residual path is float32: ``W`` drawn so that ``[p, q, r]`` have unit
    variance, its biases with 0.5, its scales 1, 1 and HC_RES_SPREAD."""
    dtype = jnp.dtype(cfg.dtype)
    makers = {}

    def dense(key, shape, sharding, std=0.02, as_dtype=dtype):
        maker = (shape, sharding, std, as_dtype)
        if maker not in makers:
            # The hardware's own bit generator (rbg): threefry draws the
            # 5.5 B weights of the served share in a minute, this in seconds.
            makers[maker] = jax.jit(
                lambda k: (jax.random.normal(
                    jax.random.wrap_key_data(
                        jnp.tile(jax.random.key_data(k), 2), impl="rbg"),
                    shape, jnp.float32) * std).astype(as_dtype),
                out_shardings=sharding)
        return makers[maker](key)

    def ones(shape, sharding):
        return jax.jit(lambda: jnp.ones(shape, dtype),
                       out_shardings=sharding)()

    top = shardings or {}
    keys = jax.random.split(key, cfg.num_layers + 2)
    params: Params = {
        "embed_tokens": dense(keys[0], (cfg.vocab_size, cfg.hidden_size),
                              top.get("embed_tokens")),
        "lm_head": dense(keys[1], (cfg.hidden_size, cfg.vocab_size),
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
            if name in _NORMS:
                layer[name] = ones(shapes[name], sh.get(name))
            elif name == "router_bias":
                layer[name] = dense(k, shapes[name], sh.get(name), 0.1,
                                    jnp.float32)
            elif name == "router":
                # Scores that spread at any width: logits of unit variance.
                layer[name] = dense(k, shapes[name], sh.get(name),
                                    cfg.hidden_size ** -0.5)
            elif name.startswith("hc_") and name.endswith("_alpha"):
                layer[name] = jax.device_put(
                    jnp.array([1.0, 1.0, HC_RES_SPREAD], jnp.float32),
                    sh.get(name))
            elif name.startswith("hc_"):
                std = (0.5 if name.endswith("_bias")
                       else shapes[name][0] ** -0.5)
                layer[name] = dense(k, shapes[name], sh.get(name), std,
                                    jnp.float32)
            else:
                layer[name] = dense(k, shapes[name], sh.get(name))
        params["layers"].append(layer)
    return params


def quantize_params(params: Params, cfg: ModelConfig) -> Params:
    """bf16 throughout: int8 experts are ROADMAP's, and the engine refuses
    ``--quantization`` for this module at boot."""
    if cfg.quantization is not None:
        raise ValueError(
            f"{__name__} has no {cfg.quantization} weights (bf16 throughout)")
    return params


# -- rotary positions --------------------------------------------------------


def yarn_inv_freq(dim: int, theta: float, scaling: Optional[dict]):
    """Inverse frequencies [dim / 2] of ``deepseek_yarn``: dimensions that
    turn more than ``beta_fast`` times over the original context keep their
    frequency, those that turn fewer than ``beta_slow`` times are divided by
    ``factor``, a linear ramp between."""
    exponents = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    plain = 1.0 / theta**exponents
    if not scaling:
        return plain
    kind = scaling.get("type", scaling.get("rope_type"))
    if kind != "deepseek_yarn":
        raise ValueError(f"rope_scaling type {kind!r}: this module knows "
                         f"deepseek_yarn alone")
    orig = scaling["original_max_position_embeddings"]

    def turns_to_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(turns_to_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(turns_to_dim(scaling["beta_slow"])), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0, 1)
    return plain / scaling["factor"] * ramp + plain * (1 - ramp)


def _mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _rope_tables(cfg: ModelConfig, positions: jax.Array):
    """cos, sin [..., rope width], scaled by mscale / mscale_all_dim (1 where
    the two agree, as published)."""
    freqs = positions.astype(jnp.float32)[..., None] * yarn_inv_freq(
        cfg.qk_rope_head_dim, cfg.rope_theta, cfg.rope_scaling)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    s = cfg.rope_scaling or {}
    amp = _mscale(s.get("factor", 1), s.get("mscale", 1)) / _mscale(
        s.get("factor", 1), s.get("mscale_all_dim", 0))
    return jnp.cos(emb) * amp, jnp.sin(emb) * amp


def softmax_scale(cfg: ModelConfig) -> float:
    s = cfg.rope_scaling or {}
    m = _mscale(s.get("factor", 1), s.get("mscale_all_dim", 0))
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


# -- attention ---------------------------------------------------------------


def _dot(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def _project(layer: Params, cfg: ModelConfig, x: jax.Array, cos, sin):
    """x [T, h] -> (q_nope [T, H, nope], q_rope [T, H, rope] rotated, the
    cache's rows [T, lanes]: the normed latent, the rotated key, zeros).
    ``cfg.mla_scale_q_lora`` / ``cfg.mla_scale_kv_lora`` (``models/
    longcat.py``'s family) scale the query and the normed latent by the
    square root of hidden size over the rank each came through."""
    T, H = x.shape[0], cfg.num_heads
    nope, eps = cfg.qk_nope_head_dim, cfg.rms_norm_eps
    if cfg.q_lora_rank:
        c_q = rms_norm(_dot(x, layer["q_a_proj"]).astype(x.dtype),
                       layer["q_a_layernorm"], eps)
        q = _dot(c_q, layer["q_b_proj"])
    else:
        q = _dot(x, layer["q_proj"])
    if cfg.mla_scale_q_lora:     # both parts of every head alike
        q = q * (cfg.hidden_size / cfg.q_lora_rank) ** 0.5
    q = q.astype(x.dtype).reshape(T, H, -1)
    kv = _dot(x, layer["kv_a_proj"]).astype(x.dtype)
    c = rms_norm(kv[:, :cfg.kv_lora_rank], layer["kv_a_layernorm"], eps)
    if cfg.mla_scale_kv_lora:    # the cache keeps the scaled latent
        c = (c.astype(jnp.float32)
             * (cfg.hidden_size / cfg.kv_lora_rank) ** 0.5).astype(x.dtype)
    r = kv[:, cfg.kv_lora_rank:]
    if cfg.use_qk_norm:
        q = rms_norm(q, layer["q_norm"], eps)
        r = rms_norm(r, layer["k_rope_norm"], eps)
    q_rope = apply_rope(q[..., nope:], cos, sin)
    r = apply_rope(r[:, None, :], cos, sin)[:, 0]
    pad = jnp.zeros((T, cache_lanes(cfg) - cache_width(cfg)), x.dtype)
    return q[..., :nope], q_rope, jnp.concatenate([c, r, pad], axis=-1)


def _kv_b(layer: Params, cfg: ModelConfig):
    """W_kvb as [latent, H, nope + v]."""
    return layer["kv_b_proj"].reshape(
        cfg.kv_lora_rank, cfg.num_heads, cfg.qk_nope_head_dim + cfg.v_head_dim)


def _rows_of(cache, block_ids):
    """The positions of the blocks ``block_ids`` [..., n] as rows
    [..., n * block_size, width], gathered row by row from the cache seen
    as [positions, width]."""
    bs = cache.shape[1]
    rows = (block_ids[..., None] * bs + jnp.arange(bs)).reshape(
        *block_ids.shape[:-1], -1)
    return cache.reshape(-1, cache.shape[-1])[rows]


def _online(state, scores, values):
    """One tile further under a running softmax.  ``scores`` [..., q, k]
    float32 with -inf where masked, ``values`` what ``...qk,kd`` sums;
    ``state`` = (running max [..., q], sum [..., q], accumulator [..., q, d])."""
    m, l, acc = state
    m_new = jnp.maximum(m, scores.max(-1))
    # A row with nothing live yet keeps m = -inf: shift by 0 there.
    shift = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.exp(scores - shift[..., None])
    fade = jnp.exp(m - shift)
    return m_new, l * fade + p.sum(-1), acc * fade[..., None] + values(p)


def _expanded_attention(layer, cfg, q_nope, q_rope, rows, cache,
                        prefix_block_ids, cached_len, valid_len):
    """Prefill: queries [T, H, .] of one chunk over its cached prefix (the
    latent pages named by ``prefix_block_ids``, ``cached_len`` positions of
    them live) and over the chunk's own ``rows`` [T, lanes], causally.  Each
    tile of keys is expanded to per-head K and V (``W_kvb c``) and folded
    into a running softmax, a group of heads at a time where the chunk is
    long: [heads of a group, T, KEY_TILE] with heads x T <= SCORE_ROWS is
    the largest score array (64 MB), whatever the context."""
    T, H = q_nope.shape[0], cfg.num_heads
    L, nope, rope = cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    bs, scale = cache.shape[1], softmax_scale(cfg)
    q_pos = cached_len + jnp.arange(T)
    # The cached prefix, gathered whole (one sequence's latents are small:
    # 42 MB at 32k positions) and read a tile at a time, as many tiles as
    # hold a live position.
    pad = -(prefix_block_ids.shape[0] * bs) % KEY_TILE
    prefix = jnp.pad(_rows_of(cache, prefix_block_ids), ((0, pad), (0, 0)))
    own = min(KEY_TILE, T)

    def heads(group):
        """``group`` = (q_nope, q_rope, W_kvb) of some heads [., G, .]."""
        qn, qr, w = group
        G = qn.shape[1]

        def fold(state, tile, live):
            """``tile`` [K, lanes] latent rows, ``live`` [T, K] bool."""
            kv = jnp.einsum(
                "kl,lhd->khd", tile[:, :L], w,
                preferred_element_type=jnp.float32).astype(tile.dtype)
            scores = (
                jnp.einsum("thd,khd->htk", qn, kv[..., :nope],
                           preferred_element_type=jnp.float32)
                + jnp.einsum("thd,kd->htk", qr, tile[:, L:L + rope],
                             preferred_element_type=jnp.float32)) * scale
            scores = jnp.where(live[None], scores, -jnp.inf)
            return _online(state, scores, lambda p: jnp.einsum(
                "htk,khd->htd", p.astype(tile.dtype), kv[..., nope:],
                preferred_element_type=jnp.float32))

        def prefix_tile(i, state):
            tile = jax.lax.dynamic_slice(
                prefix, (i * KEY_TILE, 0), (KEY_TILE, prefix.shape[1]))
            k_pos = i * KEY_TILE + jnp.arange(KEY_TILE)
            return fold(state, tile, jnp.broadcast_to(
                k_pos[None] < cached_len, (T, KEY_TILE)))

        state = (jnp.full((G, T), -jnp.inf), jnp.zeros((G, T)),
                 jnp.zeros((G, T, cfg.v_head_dim)))
        state = jax.lax.fori_loop(
            0, (cached_len + KEY_TILE - 1) // KEY_TILE, prefix_tile, state)
        # The chunk itself, causally; slots past valid_len are padding.
        for lo in range(0, T, own):
            j = lo + jnp.arange(own)
            live = (cached_len + j[None] <= q_pos[:, None]) & (
                j[None] < valid_len)
            state = fold(state, rows[lo:lo + own], live)
        _m, l, acc = state
        out = acc / jnp.maximum(l, 1e-30)[..., None]   # a padded slot: l = 0
        return out.astype(qn.dtype)                    # [G, T, v]

    w = _kv_b(layer, cfg)
    G = max(math.gcd(H, max(SCORE_ROWS // T, 1)), 1)
    if G == H:
        return heads((q_nope, q_rope, w)).transpose(1, 0, 2)
    split = lambda a: jnp.moveaxis(          # [., H, .] -> [H / G, ., G, .]
        a.reshape(a.shape[0], H // G, G, a.shape[2]), 1, 0)
    out = jax.lax.map(heads, (split(q_nope), split(q_rope), split(w)))
    return out.reshape(H, T, -1).transpose(1, 0, 2)    # [T, H, v]


def _prefill_attention(layer, cfg, q_nope, q_rope, rows, cache,
                       prefix_block_ids, cached_len, valid_len):
    """A prefill chunk's attention [T, H, v].  On a TPU absorbed, as the
    decode's is, between the same two weight einsums: one Pallas kernel
    reads the prefix's pages through the block table and the chunk's own
    rows, keeps a stage's scores in VMEM and skips the tiles of slots past
    ``valid_len`` (:func:`use_pallas_latent_prefill`).  Elsewhere
    :func:`_expanded_attention`, the statement the kernel is held to."""
    lanes = cache.shape[-1]
    if not use_pallas_latent_prefill(lanes, cfg.num_heads):
        with jax.named_scope("latent_attention_expanded"):
            return _expanded_attention(
                layer, cfg, q_nope, q_rope, rows, cache, prefix_block_ids,
                cached_len, valid_len)
    from production_stack_tpu.engine.ops.pallas.latent_attention import (
        latent_prefill_attention_pallas,
    )

    w = _kv_b(layer, cfg)
    with jax.named_scope("latent_attention_absorbed_prefill"):
        latent = latent_prefill_attention_pallas(
            _into_latent(w, cfg, q_nope, q_rope, lanes),
            rows.astype(cache.dtype), cache, prefix_block_ids, cached_len,
            valid_len, latent_rank=cfg.kv_lora_rank,
            scale=softmax_scale(cfg))
        return _out_of_latent(w, cfg, latent)


PAGE_TILE = 128     # blocks a tile of the XLA walk over the latent pages


def _pallas_serves() -> bool:
    """A real TPU, and the A/B switch not set."""
    from production_stack_tpu.engine.ops.attention import pallas_disabled

    return not pallas_disabled() and jax.default_backend() == "tpu"


def use_pallas_sinkhorn() -> bool:
    """Trace-time dispatch check for the residual path's normalisation
    kernel (``ops/pallas/mhc_sinkhorn.py``)."""
    return _pallas_serves()


def use_pallas_latent_decode(lanes: int) -> bool:
    """Trace-time dispatch check for the paged latent decode kernel
    (``ops/pallas/latent_attention.py``), as ``ops/attention.py:
    use_pallas_decode`` decides for the dense models: a real TPU, a cache
    row of whole 128-lane tiles (a DMA'd row has to be), and the A/B
    switch not set.  Everything else walks the pages in XLA."""
    return lanes % 128 == 0 and _pallas_serves()


def use_pallas_latent_prefill(lanes: int, heads: int) -> bool:
    """Trace-time dispatch check for the latent prefill kernel of the same
    file: what :func:`use_pallas_latent_decode` asks, and heads that fill
    whole sublane tiles (a query tile ``[slots, heads, lanes]`` is read as
    ``[slots x heads, lanes]`` where it lies)."""
    return heads % 16 == 0 and use_pallas_latent_decode(lanes)


def attention_paths(cfg: ModelConfig):
    """(decode, prefill): which path each step's attention takes in this
    process, for the engine's boot line."""
    lanes = cache_lanes(cfg)
    return ("pallas-latent" if use_pallas_latent_decode(lanes)
            else "xla-absorbed-latent",
            "pallas-latent" if use_pallas_latent_prefill(lanes, cfg.num_heads)
            else "xla-expanded-latent")


def prefill_attn_tiles(cfg: ModelConfig, bucket_len: int, prefix_blocks: int,
                       block_size: int, cached_len: int, num_new_tokens: int):
    """((query tile, key stage) pairs a layer's prefill attention computes,
    pairs in its grid) for one chunk, by the latent prefill kernel's own
    rule: what the engine's ``kv_tiles_live`` / ``kv_tiles_grid`` count for
    this module (``core/engine.py: _count_kv_tiles``)."""
    from production_stack_tpu.engine.ops.pallas.latent_attention import (
        count_tiles,
    )

    return count_tiles(
        bucket_len, cached_len, num_new_tokens, num_heads=cfg.num_heads,
        prefix_blocks=prefix_blocks, block_size=block_size)


def _latent_walk(q_lat, cache, block_tables, ctx_lens, latent_rank, scale):
    """``q_lat`` [S, H, lanes] over each row's latent pages as they lie, in
    XLA: softmax of ``q_lat . page * scale`` over the row's ``ctx_lens``
    positions, weighing the pages' first ``latent_rank`` lanes ->
    [S, H, latent_rank] float32.  The pages are read a tile of PAGE_TILE
    blocks a row at a time, for as long as the longest row is live
    (``tools/latent_decode_microbench.py``: a sixth of the time of gathering
    every row's positions to the block table's full width,
    PERF.md section 5)."""
    S, H, lanes = q_lat.shape
    bs = cache.shape[1]
    blocks = min(PAGE_TILE, block_tables.shape[1])
    tile = blocks * bs
    tables = jnp.pad(block_tables,
                     ((0, 0), (0, -block_tables.shape[1] % blocks)))

    def page_tile(i, state):
        ids = jax.lax.dynamic_slice(tables, (0, i * blocks), (S, blocks))
        pages = cache[ids].reshape(S, tile, lanes)
        k_pos = i * tile + jnp.arange(tile)
        scores = jnp.einsum("shw,skw->shk", q_lat, pages,
                            preferred_element_type=jnp.float32) * scale
        scores = jnp.where((k_pos[None] < ctx_lens[:, None])[:, None],
                           scores, -jnp.inf)
        return _online(state, scores, lambda p: jnp.einsum(
            "shk,skl->shl", p.astype(pages.dtype), pages[..., :latent_rank],
            preferred_element_type=jnp.float32))

    state = (jnp.full((S, H), -jnp.inf), jnp.zeros((S, H)),
             jnp.zeros((S, H, latent_rank)))
    _m, l, acc = jax.lax.fori_loop(
        0, (jnp.max(ctx_lens) + tile - 1) // tile, page_tile, state)
    return acc / jnp.maximum(l, 1e-30)[..., None]


def _into_latent(w, cfg, q_nope, q_rope, lanes):
    """Queries [S, H, .] as the cache's rows are laid out: ``[q~ ; q_rope ;
    0]`` [S, H, lanes], ``q~ = W_UK^T q_nope``; ``w`` is :func:`_kv_b`'s."""
    S, H = q_nope.shape[:2]
    return jnp.concatenate([
        jnp.einsum("shd,lhd->shl", q_nope, w[..., :cfg.qk_nope_head_dim],
                   preferred_element_type=jnp.float32).astype(q_nope.dtype),
        q_rope,
        jnp.zeros((S, H, lanes - cache_width(cfg)), q_rope.dtype)],
        axis=-1)


def _out_of_latent(w, cfg, latent):
    """``W_UV`` lifts the weighed latents [S, H, latent] -> [S, H, v]."""
    out = jnp.einsum("shl,lhd->shd", latent, w[..., cfg.qk_nope_head_dim:],
                     preferred_element_type=jnp.float32)
    return out.astype(latent.dtype)


def _absorbed_attention(layer, cfg, q_nope, q_rope, cache, block_tables,
                        ctx_lens):
    """Decode: one query a row [S, H, .] over that row's latent pages as they
    lie.  ``W_kvb`` is absorbed: the query goes into the latent space
    (``q~``), scores are ``[q~ ; q_rope] . [c ; r]``, the probabilities weigh
    the latents themselves and ``W_UV`` lifts the sum; the scores are
    [S, H, positions of a tile]: there is no chunk axis to multiply them.
    Between the two weight einsums the pages are read by the Pallas kernel
    on a TPU (each live page HBM -> VMEM once, key and value at once) and by
    :func:`_latent_walk` elsewhere (:func:`use_pallas_latent_decode`): the
    same operand dtypes and float32 statistics on both."""
    L, lanes = cfg.kv_lora_rank, cache.shape[-1]
    w = _kv_b(layer, cfg)
    q_lat = _into_latent(w, cfg, q_nope, q_rope, lanes)
    scale = softmax_scale(cfg)
    if use_pallas_latent_decode(lanes):
        from production_stack_tpu.engine.ops.pallas.latent_attention import (
            latent_decode_attention_pallas,
        )

        latent = latent_decode_attention_pallas(
            q_lat, cache, block_tables, ctx_lens, latent_rank=L, scale=scale)
    else:
        latent = _latent_walk(
            q_lat, cache, block_tables, ctx_lens, L, scale
        ).astype(q_nope.dtype)
    return _out_of_latent(w, cfg, latent)                    # [S, H, v]


# -- the feed-forward halves -------------------------------------------------


def _swiglu(x, gate, up, down):
    act = (jax.nn.silu(_dot(x, gate)) * _dot(x, up)).astype(x.dtype)
    return _dot(act, down)


def route(layer: Params, cfg: ModelConfig, x: jax.Array):
    """x [T, h] -> (chosen experts [T, k] int32 over the router's whole
    width, their shares g [T, k] float32).  A layer without a
    ``router_bias`` (``models/laguna.py``) chooses by the scores alone.
    ``cfg.router_scoring`` "softmax" scores by a softmax over that width and
    ``cfg.norm_topk_prob`` False leaves the chosen scores as they are
    (``models/longcat.py``)."""
    logits = _dot(x, layer["router"])
    s = (jax.nn.softmax(logits, axis=-1) if cfg.router_scoring == "softmax"
         else jax.nn.sigmoid(logits))
    _best, who = jax.lax.top_k(s + layer.get("router_bias", 0.0),
                               cfg.num_experts_per_tok)
    chosen = jnp.take_along_axis(s, who, axis=-1)
    g = cfg.routed_scaling_factor * chosen
    if cfg.norm_topk_prob:
        g = g / chosen.sum(-1, keepdims=True)
    return who.astype(jnp.int32), g


def held_experts(layer: Params, cfg: ModelConfig, x, who, g, live):
    """The held experts' part of ``sum_chosen g_i E_i(x)`` [T, h] float32,
    and how the rows fell ([4] int32, ROUTING_STATS).  The (row, expert)
    pairs of ``live`` rows that name a held expert are sorted by expert and
    run as three grouped matmuls; every other pair is left out."""
    T, k = who.shape
    E = cfg.num_experts
    here = (who < E) & live[:, None]
    expert = jnp.where(here, who, E).reshape(-1)       # E: not here
    order = jnp.argsort(expert)                        # stable
    sizes = jnp.zeros((E + 1,), jnp.int32).at[expert].add(1)[:E]
    xs = x[order // k]                                 # [T * k, h]
    # Each grouped projection rounds to the activations' dtype as every
    # other projection of the engine does (its float32 form of a 2,048-slot
    # chunk's 16,384 pairs is 0.8 GB of temporaries).
    def grouped(rows, stack):
        return jax.lax.ragged_dot(rows, stack, sizes,
                                  preferred_element_type=x.dtype)

    gate, up = grouped(xs, layer["experts_gate"]), grouped(xs, layer["experts_up"])
    act = (jax.nn.silu(gate.astype(jnp.float32)) * up).astype(x.dtype)
    down = grouped(act, layer["experts_down"])
    # Back in (row, choice) order; a pair that is not here reads rows past
    # the last group, which are not the grouped matmul's to define.
    back = down[jnp.argsort(order)].reshape(T, k, -1)
    out = jnp.sum(jnp.where(here[..., None], back, 0).astype(jnp.float32)
                  * g[..., None], axis=1)
    stats = jnp.stack([live.sum() * k, here.sum(), (sizes > 0).sum(),
                       sizes.max()]).astype(jnp.int32)
    return out, stats


def _ffn(layer, cfg, layer_idx, x, live):
    """(FFN(x) [T, h], the layer's choice [T, k] or None, its stats or
    None)."""
    if not _is_routed(cfg, layer_idx):
        return _swiglu(x, layer["gate_proj"], layer["up_proj"],
                       layer["down_proj"]).astype(x.dtype), None, None
    with jax.named_scope("routed_experts"):
        who, g = route(layer, cfg, x)
        routed, stats = held_experts(layer, cfg, x, who, g, live)
    shared = _swiglu(x, layer["shared_gate"], layer["shared_up"],
                     layer["shared_down"])
    return (shared + routed).astype(x.dtype), who, stats


# -- the residual path -------------------------------------------------------


def residual_path(cfg: ModelConfig):
    """None for the plain residual, else (streams, normalisations, which
    path normalises in this process), for the engine's boot line."""
    if not cfg.hc_mult:
        return None
    return (cfg.hc_mult, cfg.hc_sinkhorn_iters,
            "pallas" if use_pallas_sinkhorn() else "xla")


def _sinkhorn(M, iters: int, eps: float):
    """``M`` [n, n, T] positive, the tokens on the last (lane) axis ->
    doubly stochastic a token: rows then columns, ``iters`` times, unrolled.
    The plain form, which serves off the TPU; on it the same arithmetic is
    one kernel (:func:`use_pallas_sinkhorn`)."""
    for _ in range(iters):
        M = M / (M.sum(1, keepdims=True) + eps)
        M = M / (M.sum(0, keepdims=True) + eps)
    return M


def _mhc(layer: Params, cfg: ModelConfig, sub: str, X, live):
    """One sub-layer's mapping, from the streams it is about to read: ``X``
    [T, n, d] -> (H_pre [T, n], H_post [T, n], H_res [n, n, T], its counts
    [3] int32, RESIDUAL_STATS, over ``live`` rows), float32 all through."""
    T, n, _d = X.shape
    x = X.reshape(T, -1).astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                          + cfg.rms_norm_eps)
    z = jnp.dot(x, layer[f"hc_{sub}_w"], precision=jax.lax.Precision.HIGHEST)
    alpha, bias = layer[f"hc_{sub}_alpha"], layer[f"hc_{sub}_bias"]
    h_pre = jax.nn.sigmoid(alpha[0] * z[:, :n] + bias[:n])
    h_post = 2.0 * jax.nn.sigmoid(alpha[1] * z[:, n:2 * n] + bias[n:2 * n])
    raw = (alpha[2] * z[:, 2 * n:] + bias[2 * n:]).T.reshape(n, n, T)
    R = jnp.clip(raw, -cfg.hc_res_clamp, cfg.hc_res_clamp)
    if use_pallas_sinkhorn():
        from production_stack_tpu.engine.ops.pallas.mhc_sinkhorn import (
            mhc_sinkhorn_pallas,
        )

        M = mhc_sinkhorn_pallas(
            jnp.exp(R), iters=cfg.hc_sinkhorn_iters, eps=cfg.hc_eps)
    else:
        M = _sinkhorn(jnp.exp(R), cfg.hc_sinkhorn_iters, cfg.hc_eps)
    err = jnp.max(jnp.where(live, jnp.abs(M.sum(1) - 1.0), 0.0))
    counted = jnp.stack([
        jnp.sum((R != raw) & live), live.sum() * n * n,
        jnp.minimum(err * 1e6, 2.0**30)]).astype(jnp.int32)
    return h_pre, h_post, M, counted


def _sub_layer(layer: Params, cfg: ModelConfig, sub: str, x, live, F):
    """One sub-layer on the residual path: ``x`` [T, d] (plain) or [T, n, d]
    (streams), ``F`` its function of what it reads [T, d] -> [T, d].  Returns
    (the path after it, the mapping's counts or None)."""
    if not cfg.hc_mult:
        return x + F(x), None
    n = cfg.hc_mult
    with jax.named_scope("mhc"):
        h_pre, h_post, h_res, counted = _mhc(layer, cfg, sub, x, live)
        xf = x.astype(jnp.float32)
        h = sum(h_pre[:, j, None] * xf[:, j] for j in range(n)).astype(x.dtype)
    y = F(h)
    with jax.named_scope("mhc"):
        # Four streams: sums of products, no [4, 4] matmul a token.
        yf = y.astype(jnp.float32)
        out = jnp.stack([
            sum(h_res[i, j][:, None] * xf[:, j] for j in range(n))
            + h_post[:, i, None] * yf for i in range(n)], axis=1)
    return out.astype(x.dtype), counted


def _streams_in(cfg: ModelConfig, x):
    """The embedding, copied into every stream."""
    if not cfg.hc_mult:
        return x
    return jnp.broadcast_to(x[:, None], (x.shape[0], cfg.hc_mult, x.shape[1]))


def _streams_out(cfg: ModelConfig, x):
    """The streams, summed (float32) before the final norm."""
    if not cfg.hc_mult:
        return x
    return jnp.sum(x.astype(jnp.float32), axis=1).astype(x.dtype)


def _blocks(params: Params, cfg: ModelConfig, kv_caches, x, live, attention):
    """The layers of both steps: embeddings ``x`` [T, d] -> (what the final
    norm reads [T, d], the new caches, each routed layer's choice, its counts,
    each mapping's counts).  ``attention(layer, cache, normed h) -> (the
    heads' outputs [T, H, v], the layer's new cache)`` is the step's own."""
    T = x.shape[0]
    x = _streams_in(cfg, x)
    caches, choice, stats, residual = [], [], [], []
    for i, (layer, cache) in enumerate(zip(params["layers"], kv_caches)):
        def attend(h):
            h = rms_norm(h, layer["input_layernorm"], cfg.rms_norm_eps)
            out, new = attention(layer, cache, h)
            caches.append(new)
            return _dot(out.reshape(T, -1), layer["o_proj"]).astype(h.dtype)

        def feed(h):
            h = rms_norm(h, layer["post_attention_layernorm"],
                         cfg.rms_norm_eps)
            y, who, counted = _ffn(layer, cfg, i, h, live)
            if who is not None:
                choice.append(who)
                stats.append(counted)
            return y

        for sub, F in (("attn", attend), ("ffn", feed)):
            x, counted = _sub_layer(layer, cfg, sub, x, live, F)
            if counted is not None:
                residual.append(counted)
    return _streams_out(cfg, x), caches, choice, stats, residual


def _sum_stats(stats, residual):
    """Routed layers' [4] vectors and the mappings' [3] -> one: counts add,
    the fullest expert and the worst row sum are maxima (STATS_MAX)."""
    stats = jnp.stack(stats)
    out = [stats[:, :3].sum(0), stats[:, 3:].max(0)]
    if residual:
        residual = jnp.stack(residual)
        out += [residual[:, :2].sum(0), residual[:, 2:].max(0)]
    return jnp.concatenate(out)


def _result(logits, caches, choice, stats, residual, return_choice,
            return_stats):
    out = (logits, caches)
    if return_choice:
        out += (jnp.stack(choice),)
    if return_stats:
        out += (_sum_stats(stats, residual),)
    return out


# -- the two steps -----------------------------------------------------------


def prefill_attention(cfg, T, cached_len, prefix_block_ids, new_block_ids,
                      valid_len):
    """One chunk's ``attention(layer, cache, normed h) -> (the heads' outputs
    [T, H, v], the layer's new cache)``: project, attend the cached prefix
    and the chunk's own rows, write the rows into ``new_block_ids``.  A
    module whose layer holds several attentions (``models/longcat.py``)
    calls it once an attention, each with its own weights and array."""
    cos, sin = _rope_tables(cfg, cached_len + jnp.arange(T))

    def attention(layer, cache, h):
        q_nope, q_rope, rows = _project(layer, cfg, h, cos, sin)
        out = _prefill_attention(
            layer, cfg, q_nope, q_rope, rows, cache, prefix_block_ids,
            cached_len, valid_len)
        bs = cache.shape[1]
        return out, cache.at[new_block_ids].set(
            rows.reshape(T // bs, bs, -1).astype(cache.dtype))

    return attention


def decode_attention(cfg, positions, block_tables, ctx_lens, slot_block_ids,
                     slot_offsets):
    """A decode batch's ``attention(layer, cache, normed h)``, as
    :func:`prefill_attention`'s: the new row written where its slot says,
    then the absorbed walk over each row's pages."""
    cos, sin = _rope_tables(cfg, positions)

    def attention(layer, cache, h):
        q_nope, q_rope, rows = _project(layer, cfg, h, cos, sin)
        # Write, then attend: ctx_lens counts the new token.
        bs = cache.shape[1]
        cache = cache.reshape(-1, cache.shape[-1]).at[
            slot_block_ids * bs + slot_offsets].set(
                rows.astype(cache.dtype)).reshape(cache.shape)
        with jax.named_scope("latent_attention_absorbed"):
            out = _absorbed_attention(
                layer, cfg, q_nope, q_rope, cache, block_tables, ctx_lens)
        return out, cache

    return attention


def prefill(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,            # [T] int32 (padded to a bucket)
    cached_len: jax.Array,        # scalar int32: positions already cached
    prefix_block_ids: jax.Array,  # [P] int32 (0-padded)
    new_block_ids: jax.Array,     # [T // block_size] int32 (null-padded)
    valid_len: jax.Array,         # scalar int32: true number of new tokens
    kv_caches: LatentCaches,
    mesh: Optional[Mesh] = None,
    sp_mode: str = "ring",        # the engine's; one device has no ring
    prompt_targets: Optional[jax.Array] = None,  # [T] int32 next-token ids
    prompt_topk: int = 0,         # static: alternatives a prompt position
    return_choice: bool = False,
    return_stats: bool = False,
):
    """One sequence's prefill chunk: (last valid token's logits [V], new
    caches), then with ``return_choice`` the experts every slot chose
    (int32 [routed layers, T, k], ids over the router's width), then with
    ``return_stats`` the chunk's counts (int32, :func:`stats_names`) over its
    valid slots.  With ``prompt_targets`` the third result is
    ``models/llama.py: prefill``'s (target_logprob [T], top_ids [T, k],
    top_logps [T, k]), the head swept in row chunks."""
    T = tokens.shape[0]
    attention = prefill_attention(
        cfg, T, cached_len, prefix_block_ids, new_block_ids, valid_len)
    live = jnp.arange(T) < valid_len
    x, caches, *counted = _blocks(
        params, cfg, kv_caches, params["embed_tokens"][tokens], live,
        attention)
    x = rms_norm(x, params["norm"], cfg.rms_norm_eps)
    logits = _dot(x[jnp.maximum(valid_len - 1, 0)], params["lm_head"])
    out = _result(logits, caches, *counted, return_choice, return_stats)
    if prompt_targets is None:
        return out
    C, k = math.gcd(T, 128), max(prompt_topk, 1)

    def head_chunk(args):
        rows, targets = args
        lsm = jax.nn.log_softmax(_dot(rows, params["lm_head"]), axis=-1)
        top_lp, top_id = jax.lax.top_k(lsm, k)
        return (jnp.take_along_axis(lsm, targets[:, None], axis=-1)[:, 0],
                top_id.astype(jnp.int32), top_lp)

    tlp, top_ids, top_lps = jax.lax.map(head_chunk, (
        x.reshape(T // C, C, -1), prompt_targets.reshape(T // C, C)))
    return out[:2] + ((tlp.reshape(T), top_ids.reshape(T, k),
                       top_lps.reshape(T, k)),) + out[2:]


def decode(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,          # [S] int32, one token a row (padded batch)
    positions: jax.Array,       # [S] int32 position of each token
    block_tables: jax.Array,    # [S, Bmax] int32
    ctx_lens: jax.Array,        # [S] int32 context length incl. the new token
    slot_block_ids: jax.Array,  # [S] int32 block receiving the new token
    slot_offsets: jax.Array,    # [S] int32 offset within that block
    kv_caches: LatentCaches,
    mesh: Optional[Mesh] = None,
    return_choice: bool = False,
    return_stats: bool = False,
):
    """Batched single-token decode: (logits [S, V], new caches), then as
    :func:`prefill`.  A row whose write is parked on the null block 0 (a
    padding row, a row the window froze) is not live: it is routed nowhere,
    touches no expert and is not counted."""
    attention = decode_attention(
        cfg, positions, block_tables, ctx_lens, slot_block_ids, slot_offsets)
    live = slot_block_ids != 0
    x, caches, *counted = _blocks(
        params, cfg, kv_caches, params["embed_tokens"][tokens], live,
        attention)
    logits = _dot(rms_norm(x, params["norm"], cfg.rms_norm_eps),
                  params["lm_head"])
    return _result(logits, caches, *counted, return_choice, return_stats)
