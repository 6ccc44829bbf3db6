"""Llama-family model (functional JAX, paged-KV attention).

Weight layout matches HF ``LlamaForCausalLM`` modulo transposition (we store
[in, out] so the forward is ``x @ W``); loaders in weights.py map HF
safetensors names directly.  Correctness is pinned against the HF torch
implementation in tests/test_llama_vs_hf.py.

Covers the whole RMSNorm+RoPE+gated-MLP decoder family via ModelConfig
switches: Llama 3.x (GQA, rope_theta, tied embeddings), Mistral
(sliding_window), Qwen2 (QKV biases), Mixtral (sparse MoE, _moe_mlp), and
Gemma (zero-centered norms, tanh GeGLU, sqrt(h) embedding scale, decoupled
head_dim/MQA) — each pinned against its HF torch implementation in
tests/test_llama_vs_hf.py.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from production_stack_tpu.engine.config import ModelConfig
from production_stack_tpu.engine.ops import attention as attn_ops
from production_stack_tpu.engine.ops.layers import (
    apply_rope,
    rms_norm,
    rope_cos_sin,
    swiglu,
)
from production_stack_tpu.engine.parallel.mesh import AXES

Params = Dict
KVCaches = List[Tuple[jax.Array, jax.Array]]


def _sp_size(mesh: Optional[Mesh]) -> int:
    return mesh.shape[AXES.SP] if mesh is not None else 1


def _constrain(x: jax.Array, mesh: Optional[Mesh], spec: P) -> jax.Array:
    """Pin an activation's sharding (no-op off-mesh)."""
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def param_dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def quantize_weight(w: jax.Array) -> Params:
    """Per-out-channel symmetric int8 form of one [in, out] projection."""
    w32 = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=0)
    s = jnp.where(amax > 0, amax / 127.0, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(w32 / s), -127, 127).astype(jnp.int8)
    return {"q": q, "s": s}


def place_weight(w, sharding=None):
    """One host or device tensor into its final sharding.  A {"q", "s"}
    sharding pair (parallel/shardings.py under cfg.quantization) asks for
    the int8 form: the tensor is placed sharded first and quantized there."""
    if sharding is None:
        return jnp.asarray(w)
    if isinstance(sharding, dict):
        return jax.jit(quantize_weight, out_shardings=sharding)(
            jax.device_put(w, sharding["q"])
        )
    return jax.device_put(w, sharding)


def init_params(cfg: ModelConfig, key: jax.Array, shardings=None) -> Params:
    """Random init with HF-compatible tree structure.

    With ``shardings`` (parallel/shardings.py param_shardings) every
    tensor is created by a jitted initializer directly in its final
    sharding and, where that is a {"q", "s"} pair (cfg.quantization),
    quantized there: neither the unsharded nor the unquantized model ever
    sits on one device (int8 mistral-7b fits a 16 GB chip; its bf16 form
    does not).  Without it: plain cfg.dtype arrays on the default device.
    """
    dtype = param_dtype(cfg)
    h, hd = cfg.hidden_size, cfg.head_dim
    H, K, I = cfg.num_heads, cfg.num_kv_heads, cfg.intermediate_size
    makers = {}  # (shape, sharding) -> jitted initializer

    def dense(key, shape, sharding=None):
        quantized = isinstance(sharding, dict)
        maker_key = (shape, tuple(sharding.values()) if quantized else sharding)
        if maker_key not in makers:

            def make(key):
                w = jax.random.normal(key, shape, jnp.float32) * 0.02
                w = w.astype(dtype)
                return quantize_weight(w) if quantized else w

            makers[maker_key] = jax.jit(make, out_shardings=sharding)
        return makers[maker_key](key)

    def const(fill, shape, sharding=None):
        return place_weight(jnp.full(shape, fill, dtype), sharding)

    top = shardings or {}
    keys = jax.random.split(key, cfg.num_layers + 3)
    params: Params = {
        "embed_tokens": dense(
            keys[0], (cfg.vocab_size, h), top.get("embed_tokens")
        ),
        "norm": const(1, (h,), top.get("norm")),
        "layers": [],
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = dense(
            keys[1], (h, cfg.vocab_size), top.get("lm_head")
        )
    for i in range(cfg.num_layers):
        lk = jax.random.split(keys[i + 3], 8)
        sh = shardings["layers"][i] if shardings else {}
        layer = {
            "input_layernorm": const(1, (h,), sh.get("input_layernorm")),
            "post_attention_layernorm": const(
                1, (h,), sh.get("post_attention_layernorm")
            ),
            "q_proj": dense(lk[0], (h, H * hd), sh.get("q_proj")),
            "k_proj": dense(lk[1], (h, K * hd), sh.get("k_proj")),
            "v_proj": dense(lk[2], (h, K * hd), sh.get("v_proj")),
            "o_proj": dense(lk[3], (H * hd, h), sh.get("o_proj")),
        }
        if cfg.num_experts:
            E = cfg.num_experts
            layer["gate"] = dense(lk[7], (h, E), sh.get("gate"))
            layer["experts_gate"] = dense(
                lk[4], (E, h, I), sh.get("experts_gate")
            )
            layer["experts_up"] = dense(lk[5], (E, h, I), sh.get("experts_up"))
            layer["experts_down"] = dense(
                lk[6], (E, I, h), sh.get("experts_down")
            )
        else:
            layer["gate_proj"] = dense(lk[4], (h, I), sh.get("gate_proj"))
            layer["up_proj"] = dense(lk[5], (h, I), sh.get("up_proj"))
            layer["down_proj"] = dense(lk[6], (I, h), sh.get("down_proj"))
        if cfg.attention_bias:
            # Qwen2-style QKV biases (o_proj stays bias-free there).
            layer["q_bias"] = const(0, (H * hd,), sh.get("q_bias"))
            layer["k_bias"] = const(0, (K * hd,), sh.get("k_bias"))
            layer["v_bias"] = const(0, (K * hd,), sh.get("v_bias"))
        params["layers"].append(layer)
    return params


def _norm(x: jax.Array, weight: jax.Array, cfg: ModelConfig) -> jax.Array:
    return rms_norm(x, weight, cfg.rms_norm_eps, cfg.rms_norm_offset)


def _act(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    if cfg.hidden_act == "gelu_tanh":  # gemma
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.silu(x)


def _embed(params: Params, cfg: ModelConfig, tokens: jax.Array) -> jax.Array:
    x = params["embed_tokens"][tokens]
    if cfg.scale_embeddings:  # gemma: sqrt(h) in the input dtype
        x = x * jnp.asarray(cfg.hidden_size**0.5, x.dtype)
    return x


def _dot(x: jax.Array, w) -> jax.Array:
    """Projection matmul, fp32 accumulation.  ``w`` is either a plain
    [in, out] array or an int8 weight-only pair {"q": int8 [in, out],
    "s": f32 [out]} (quantize_params).  For the quantized form the convert
    fuses into the MXU operand read — int8 is what streams from HBM — and
    the per-out-channel scale applies to the small output:
    x @ (q * s) == (x @ q) * s."""
    if isinstance(w, dict):
        y = jnp.dot(x, w["q"].astype(x.dtype), preferred_element_type=jnp.float32)
        return y * w["s"]
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


_QUANT_TARGETS = (
    "q_proj", "k_proj", "v_proj", "o_proj",
    "gate_proj", "up_proj", "down_proj",
)


def quantize_params(params: Params, cfg: ModelConfig) -> Params:
    """Per-out-channel symmetric int8 quantization of the projection
    weights (and lm_head).  Embeddings, norms, biases, and MoE expert
    stacks keep the model dtype — the dense projections are where decode's
    weight traffic is."""
    if cfg.quantization is None:
        return params

    out = dict(params)
    out["layers"] = []
    for layer in params["layers"]:
        new = dict(layer)
        for name in _QUANT_TARGETS:
            if name in layer:
                new[name] = quantize_weight(layer[name])
        out["layers"].append(new)
    if "lm_head" in params:
        out["lm_head"] = quantize_weight(params["lm_head"])
    return out


def _maybe_lora(y, x, lora_layer, proj, adapter_idx, lora_scale):
    """Add the LoRA delta for ``proj`` when adapters are live (lora.py)."""
    if lora_layer is None:
        return y
    from production_stack_tpu.engine.lora import lora_delta

    A, B = lora_layer[proj]
    return y + lora_delta(x, A, B, adapter_idx, lora_scale)


def _project_qkv(layer: Params, x: jax.Array, cfg: ModelConfig,
                 lora_layer=None, adapter_idx=None, lora_scale=None):
    """x: [T, h] -> q [T, H, D], k/v [T, K, D]."""
    T = x.shape[0]
    q = _dot(x, layer["q_proj"])
    k = _dot(x, layer["k_proj"])
    v = _dot(x, layer["v_proj"])
    q = _maybe_lora(q, x, lora_layer, "q_proj", adapter_idx, lora_scale)
    k = _maybe_lora(k, x, lora_layer, "k_proj", adapter_idx, lora_scale)
    v = _maybe_lora(v, x, lora_layer, "v_proj", adapter_idx, lora_scale)
    if cfg.attention_bias:
        q = q + layer["q_bias"].astype(jnp.float32)
        k = k + layer["k_bias"].astype(jnp.float32)
        v = v + layer["v_bias"].astype(jnp.float32)
    q = q.astype(x.dtype).reshape(T, cfg.num_heads, cfg.head_dim)
    k = k.astype(x.dtype).reshape(T, cfg.num_kv_heads, cfg.head_dim)
    v = v.astype(x.dtype).reshape(T, cfg.num_kv_heads, cfg.head_dim)
    return q, k, v


def _o_proj(layer: Params, out: jax.Array, lora_layer, adapter_idx, lora_scale):
    y = _dot(out, layer["o_proj"])
    return _maybe_lora(y, out, lora_layer, "o_proj", adapter_idx, lora_scale)


def _moe_mlp(layer: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Mixtral-style sparse MoE block: full-softmax router, top-k
    renormalized weights, SwiGLU experts.

    TPU-first layout: expert weights are STACKED ``[E, ...]`` arrays
    sharded over the tp mesh axis (parallel/shardings.py) — each device
    runs its E/tp experts over all tokens and GSPMD reduces the weighted
    sum.  Every token mathematically visits every (local) expert with its
    routing weight (zero outside the top-k): static shapes, no
    capacity-overflow token dropping, no host-side sorting.  The
    megablocks-style block-sparse dispatch kernel is the optimization
    path once profiling justifies it; this formulation is the correctness
    and sharding reference.
    """
    E, k = cfg.num_experts, cfg.num_experts_per_tok
    router_logits = jnp.dot(
        x, layer["gate"], preferred_element_type=jnp.float32
    )  # [T, E]
    probs = jax.nn.softmax(router_logits, axis=-1)
    top_vals, top_idx = jax.lax.top_k(probs, k)  # [T, k]
    top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)
    # Dense routing-weight matrix [T, E]: top-k weights, zero elsewhere.
    weights = jnp.sum(
        jax.nn.one_hot(top_idx, E, dtype=jnp.float32) * top_vals[..., None],
        axis=1,
    )

    gate = jnp.einsum(
        "th,ehi->tei", x, layer["experts_gate"],
        preferred_element_type=jnp.float32,
    )
    up = jnp.einsum(
        "th,ehi->tei", x, layer["experts_up"],
        preferred_element_type=jnp.float32,
    )
    activated = (_act(gate, cfg) * up).astype(x.dtype)
    down = jnp.einsum(
        "tei,eih->teh", activated, layer["experts_down"],
        preferred_element_type=jnp.float32,
    )  # [T, E, h]
    out = jnp.einsum("te,teh->th", weights, down)
    return out.astype(x.dtype)


def _mlp(layer: Params, x: jax.Array, lora_layer, adapter_idx, lora_scale,
         cfg: ModelConfig):
    """Gated MLP with optional LoRA on gate/up/down (matches ops/layers.py
    swiglu exactly when lora_layer is None); dispatches to the sparse MoE
    block for mixtral-style configs (LoRA then applies to attention only)."""
    if cfg.num_experts:
        return _moe_mlp(layer, x, cfg)
    if lora_layer is None and not isinstance(layer["gate_proj"], dict):
        return swiglu(
            x, layer["gate_proj"], layer["up_proj"], layer["down_proj"],
            act=cfg.hidden_act,
        )
    gate = _dot(x, layer["gate_proj"])
    up = _dot(x, layer["up_proj"])
    gate = _maybe_lora(gate, x, lora_layer, "gate_proj", adapter_idx, lora_scale)
    up = _maybe_lora(up, x, lora_layer, "up_proj", adapter_idx, lora_scale)
    activated = (_act(gate, cfg) * up).astype(x.dtype)
    down = _dot(activated, layer["down_proj"])
    down = _maybe_lora(
        down, activated, lora_layer, "down_proj", adapter_idx, lora_scale
    )
    return down.astype(x.dtype)


def _lm_head(params: Params, cfg: ModelConfig, hidden: jax.Array) -> jax.Array:
    """hidden [..., h] -> logits [..., V] in fp32."""
    if cfg.tie_word_embeddings:
        return jnp.dot(
            hidden, params["embed_tokens"].T, preferred_element_type=jnp.float32
        )
    return _dot(hidden, params["lm_head"])


def prefill(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,  # [T] int32 (padded to a bucket)
    cached_len: jax.Array,  # scalar int32: prefix tokens already in cache
    prefix_block_ids: jax.Array,  # [P] int32 (0-padded)
    new_block_ids: jax.Array,  # [T // block_size] int32 (null-padded)
    valid_len: jax.Array,  # scalar int32: true number of new tokens
    kv_caches: KVCaches,
    mesh: Optional[Mesh] = None,  # SPMD mesh; sp>1 -> ring/ulysses attention
    lora: Optional[Dict] = None,  # LoRA slot arrays (lora.py); None = off
    adapter_idx: Optional[jax.Array] = None,  # scalar slot for this seq
    sp_mode: str = "ring",  # sequence-parallel strategy when sp>1
    prompt_targets: Optional[jax.Array] = None,  # [T] int32 next-token ids
    prompt_topk: int = 0,  # static: top-k alternatives per prompt position
) -> Tuple[jax.Array, KVCaches]:
    """One sequence's prefill.  Returns (last-token logits [V], new caches);
    with ``prompt_targets`` set, returns (logits, caches, (target_logprob
    [T], top_ids [T, k], top_logps [T, k])) — the per-position
    next-token logprobs the OpenAI ``echo`` + ``logprobs`` surface needs
    (lm-eval-harness loglikelihood scoring).  The lm_head sweep runs in
    row chunks so the full [T, V] logits are never materialized.

    Under a mesh, the token axis is sharded over ``sp`` (every projection /
    MLP matmul computes on T/sp rows per device) and attention runs the
    ring (parallel/ring_attention.py) so no device ever materializes the
    full [T, T] score matrix; head/channel dims are sharded over ``tp``
    (GSPMD inserts the psum after o_proj / down_proj)."""
    T = tokens.shape[0]
    scale = cfg.head_dim**-0.5
    use_ring = _sp_size(mesh) > 1
    positions = cached_len + jnp.arange(T)
    cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta,
                            cfg.rope_scaling)

    x = _embed(params, cfg, tokens)  # [T, h]
    x = _constrain(x, mesh, P(AXES.SP, None))
    lora_scale = lora["scale"] if lora is not None else None
    new_caches: KVCaches = []
    for li, (layer, (k_cache, v_cache)) in enumerate(
        zip(params["layers"], kv_caches)
    ):
        lora_layer = lora["layers"][li] if lora is not None else None
        residual = x
        x_n = _norm(x, layer["input_layernorm"], cfg)
        q, k, v = _project_qkv(
            layer, x_n, cfg, lora_layer, adapter_idx, lora_scale
        )
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        if use_ring:
            k_prefix, v_prefix = attn_ops.gather_prefix_kv(
                k_cache, v_cache, prefix_block_ids, dtype=k.dtype
            )
            if sp_mode == "ulysses":
                from production_stack_tpu.engine.parallel.ulysses import (
                    ulysses_prefill_with_prefix,
                )

                sp_attention = partial(
                    ulysses_prefill_with_prefix,
                    sliding_window=cfg.sliding_window,
                )
            else:
                # The ring does not implement sliding windows;
                # validate_sp_mode rejects windowed models under ring sp>1
                # rather than silently widening the receptive field.
                from production_stack_tpu.engine.parallel.ring_attention import (
                    ring_prefill_with_prefix as sp_attention,
                )

            out = shard_map(
                partial(
                    sp_attention, axis_name=AXES.SP, scale=scale
                ),
                mesh=mesh,
                in_specs=(
                    P(AXES.SP, AXES.TP, None),  # q [T, H, D]
                    P(AXES.SP, AXES.TP, None),  # k [T, K, D]
                    P(AXES.SP, AXES.TP, None),  # v
                    P(AXES.SP, AXES.TP, None),  # k_prefix (ring-sharded too)
                    P(AXES.SP, AXES.TP, None),  # v_prefix
                    P(),  # cached_len
                    P(),  # valid_len
                ),
                out_specs=P(AXES.SP, AXES.TP, None),
                check_vma=False,
            )(q, k, v, k_prefix, v_prefix, cached_len, valid_len)
        else:
            out = attn_ops.prefill_attention(
                q, k, v, k_cache, v_cache, prefix_block_ids,
                cached_len, valid_len,
                scale=scale, sliding_window=cfg.sliding_window, mesh=mesh,
            )
        k_cache, v_cache = attn_ops.write_prefill_kv(
            k_cache, v_cache, k, v, new_block_ids
        )
        new_caches.append((k_cache, v_cache))
        out = out.reshape(T, cfg.num_heads * cfg.head_dim)
        x = residual + _o_proj(
            layer, out, lora_layer, adapter_idx, lora_scale
        ).astype(x.dtype)
        residual = x
        x_n = _norm(x, layer["post_attention_layernorm"], cfg)
        x = residual + _mlp(layer, x_n, lora_layer, adapter_idx, lora_scale, cfg)

    x = _norm(x, params["norm"], cfg)
    last = x[jnp.maximum(valid_len - 1, 0)]  # [h]
    logits = _lm_head(params, cfg, last)
    if prompt_targets is None:
        return logits, new_caches

    # Chunked lm_head sweep: [C, V] at a time (T=2048, V=128k fp32 would
    # be ~1 GB if materialized whole).  C must divide T (buckets are
    # free-form CLI ints, e.g. 192).
    C = math.gcd(T, 128)
    k = max(prompt_topk, 1)
    rows = x.reshape(T // C, C, cfg.hidden_size)
    tgts = prompt_targets.reshape(T // C, C)

    def head_chunk(args):
        r, t = args
        lg = _lm_head(params, cfg, r)  # [C, V] fp32
        lsm = jax.nn.log_softmax(lg, axis=-1)
        tlp = jnp.take_along_axis(lsm, t[:, None], axis=-1)[:, 0]
        top_lp, top_id = jax.lax.top_k(lsm, k)
        return tlp, top_id.astype(jnp.int32), top_lp

    tlp, top_ids, top_lps = jax.lax.map(head_chunk, (rows, tgts))
    plp = (
        tlp.reshape(T),
        top_ids.reshape(T, k),
        top_lps.reshape(T, k),
    )
    return logits, new_caches, plp


def encode(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,  # [T] int32 (padded to a bucket)
    valid_len: jax.Array,  # scalar int32
    mesh: Optional[Mesh] = None,  # routes attention off Pallas under tp/sp
) -> jax.Array:
    """Embedding forward: causal self-attention over the prompt, returning
    the mean of the final-layer hidden states over valid tokens,
    L2-normalized — the /v1/embeddings path.  No KV bookkeeping: the
    sequence is processed once and discarded, so attention runs with an
    empty prefix and the per-layer K/V stay in registers/VMEM."""
    T = tokens.shape[0]
    scale = cfg.head_dim**-0.5
    positions = jnp.arange(T)
    cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta,
                            cfg.rope_scaling)
    no_prefix = jnp.zeros((0,), jnp.int32)  # and no cache behind it

    x = _embed(params, cfg, tokens)  # [T, h]
    for layer in params["layers"]:
        residual = x
        x_n = _norm(x, layer["input_layernorm"], cfg)
        q, k, v = _project_qkv(layer, x_n, cfg)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        out = attn_ops.prefill_attention(
            q, k, v, None, None, no_prefix, jnp.int32(0), valid_len,
            scale=scale, sliding_window=cfg.sliding_window, mesh=mesh,
        )
        out = out.reshape(T, cfg.num_heads * cfg.head_dim)
        x = residual + _o_proj(layer, out, None, None, None).astype(x.dtype)
        residual = x
        x_n = _norm(x, layer["post_attention_layernorm"], cfg)
        x = residual + _mlp(layer, x_n, None, None, None, cfg)

    x = _norm(x, params["norm"], cfg).astype(jnp.float32)
    mask = (jnp.arange(T) < valid_len)[:, None]
    pooled = jnp.sum(x * mask, axis=0) / jnp.maximum(valid_len, 1)
    return pooled / jnp.maximum(jnp.linalg.norm(pooled), 1e-9)


def encode_batch(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,  # [B, T] int32 (B and T both padded to buckets)
    valid_lens: jax.Array,  # [B] int32 (0 for padding rows)
    mesh: Optional[Mesh] = None,
) -> jax.Array:
    """Batched embedding forward: B independent ``encode`` passes fused
    into one dispatch — the encode lane's [B, T]-bucketed executable.
    Unsharded we vmap the single-text encode (one wide kernel); under a
    tp/sp mesh the shard_map'd attention inside ``encode`` is not
    vmappable, so rows run under ``jax.lax.map`` instead (still one
    dispatch, B sequential shard_map bodies).  Returns [B, hidden]
    L2-normalized float32 vectors; padding rows (valid_len 0) produce
    garbage vectors the caller drops."""
    if mesh is None:
        return jax.vmap(
            lambda t, v: encode(params, cfg, t, v, mesh=None)
        )(tokens, valid_lens)
    return jax.lax.map(
        lambda tv: encode(params, cfg, tv[0], tv[1], mesh=mesh),
        (tokens, valid_lens),
    )


def mixed_step(
    params: Params,
    cfg: ModelConfig,
    dec_tokens: jax.Array,  # [S] int32, one token per decoding sequence
    dec_positions: jax.Array,  # [S] int32 (=ctx_len-1)
    dec_block_tables: jax.Array,  # [S, Bmax] int32
    dec_ctx_lens: jax.Array,  # [S] int32 incl. the new token
    dec_slot_block_ids: jax.Array,  # [S] int32 block receiving the token
    dec_slot_offsets: jax.Array,  # [S] int32 offset within that block
    pf_tokens: jax.Array,  # [T] int32 prefill chunk (padded to a bucket)
    pf_cached_len: jax.Array,  # scalar int32: prefix tokens already cached
    pf_prefix_block_ids: jax.Array,  # [P] int32 (0-padded)
    pf_new_block_ids: jax.Array,  # [T // block_size] int32 (null-padded)
    pf_valid_len: jax.Array,  # scalar int32: true number of chunk tokens
    kv_caches: KVCaches,
    mesh: Optional[Mesh] = None,  # tp-only mesh (engine gates dp/sp to 1)
    lora: Optional[Dict] = None,
    adapter_idx: Optional[jax.Array] = None,  # [S+T] row-aligned slots
) -> Tuple[jax.Array, KVCaches]:
    """Fused mixed step: S decoding sequences' next tokens AND one
    sequence's prefill chunk in a single forward over the packed
    ``[S + T]`` token batch.  Returns (logits [S+1, V], new caches):
    rows 0..S-1 are the decode batch, row S is the chunk's last valid
    token (only meaningful on a final chunk).

    The win is shared weight streaming: every projection/MLP matmul runs
    once over S+T rows, so the decode batch — which would otherwise sit
    idle for a whole prefill bucket when a prompt arrives — pays zero
    extra HBM weight traffic for riding along.  Attention splits by
    segment: decode rows use paged attention over their block tables
    exactly like :func:`decode`; the chunk runs flash/dense prefill
    attention against its accumulated prefix blocks exactly like
    :func:`prefill`.  The two segments touch disjoint KV slots (decode
    appends land in each sequence's own tail block; the chunk writes its
    freshly allocated blocks and reads its ref-counted prefix), so the
    within-layer update order is immaterial.

    lm_head runs on S+1 rows only — the full [T, V] chunk logits are
    never materialized (mid-prompt rows have no consumer)."""
    S = dec_tokens.shape[0]
    T = pf_tokens.shape[0]
    scale = cfg.head_dim**-0.5
    positions = jnp.concatenate(
        [dec_positions, pf_cached_len + jnp.arange(T, dtype=jnp.int32)]
    )
    cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta,
                            cfg.rope_scaling)

    x = _embed(params, cfg, jnp.concatenate([dec_tokens, pf_tokens]))
    lora_scale = lora["scale"] if lora is not None else None
    new_caches: KVCaches = []
    for li, (layer, (k_cache, v_cache)) in enumerate(
        zip(params["layers"], kv_caches)
    ):
        lora_layer = lora["layers"][li] if lora is not None else None
        residual = x
        x_n = _norm(x, layer["input_layernorm"], cfg)
        q, k, v = _project_qkv(
            layer, x_n, cfg, lora_layer, adapter_idx, lora_scale
        )
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        # Decode segment: write-then-attend, like decode().
        k_cache, v_cache = attn_ops.append_decode_kv(
            k_cache, v_cache, k[:S], v[:S],
            dec_slot_block_ids, dec_slot_offsets,
        )
        out_dec = attn_ops.decode_attention(
            q[:S], k_cache, v_cache, dec_block_tables, dec_ctx_lens,
            scale=scale, sliding_window=cfg.sliding_window, mesh=mesh,
        )
        # Prefill segment: attend over prefix + chunk, then scatter the
        # chunk's KV into its new blocks.
        out_pf = attn_ops.prefill_attention(
            q[S:], k[S:], v[S:], k_cache, v_cache, pf_prefix_block_ids,
            pf_cached_len, pf_valid_len,
            scale=scale, sliding_window=cfg.sliding_window, mesh=mesh,
        )
        k_cache, v_cache = attn_ops.write_prefill_kv(
            k_cache, v_cache, k[S:], v[S:], pf_new_block_ids
        )
        new_caches.append((k_cache, v_cache))
        out = jnp.concatenate([out_dec, out_pf]).reshape(
            S + T, cfg.num_heads * cfg.head_dim
        )
        x = residual + _o_proj(
            layer, out, lora_layer, adapter_idx, lora_scale
        ).astype(x.dtype)
        residual = x
        x_n = _norm(x, layer["post_attention_layernorm"], cfg)
        x = residual + _mlp(layer, x_n, lora_layer, adapter_idx, lora_scale, cfg)

    x = _norm(x, params["norm"], cfg)
    tail = x[S + jnp.maximum(pf_valid_len - 1, 0)]  # chunk's last valid row
    head_rows = jnp.concatenate([x[:S], tail[None, :]], axis=0)  # [S+1, h]
    return _lm_head(params, cfg, head_rows), new_caches


def decode(
    params: Params,
    cfg: ModelConfig,
    tokens: jax.Array,  # [S] int32, one token per sequence (padded batch)
    positions: jax.Array,  # [S] int32 position of each token (=ctx_len-1)
    block_tables: jax.Array,  # [S, Bmax] int32
    ctx_lens: jax.Array,  # [S] int32 context length incl. the new token
    slot_block_ids: jax.Array,  # [S] int32 block receiving the new token
    slot_offsets: jax.Array,  # [S] int32 offset within that block
    kv_caches: KVCaches,
    mesh: Optional[Mesh] = None,  # SPMD mesh; batch sharded over dp
    lora: Optional[Dict] = None,  # LoRA slot arrays (lora.py); None = off
    adapter_idx: Optional[jax.Array] = None,  # [S] slot per sequence
) -> Tuple[jax.Array, KVCaches]:
    """Batched single-token decode.  Returns (logits [S, V], new caches).

    Under a mesh the batch axis is sharded over ``dp`` (each dp group
    decodes S/dp sequences) and heads over ``tp``; the paged KV pool is
    replicated across dp so any sequence can land on any dp group."""
    S = tokens.shape[0]
    scale = cfg.head_dim**-0.5
    cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta,
                            cfg.rope_scaling)

    x = _embed(params, cfg, tokens)  # [S, h]
    x = _constrain(x, mesh, P(AXES.DP, None))
    lora_scale = lora["scale"] if lora is not None else None
    new_caches: KVCaches = []
    for li, (layer, (k_cache, v_cache)) in enumerate(
        zip(params["layers"], kv_caches)
    ):
        lora_layer = lora["layers"][li] if lora is not None else None
        residual = x
        x_n = _norm(x, layer["input_layernorm"], cfg)
        q, k, v = _project_qkv(
            layer, x_n, cfg, lora_layer, adapter_idx, lora_scale
        )
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        # The new token's KV must be visible to its own attention: write
        # first, then attend (ctx_lens already includes the new token).
        k_cache, v_cache = attn_ops.append_decode_kv(
            k_cache, v_cache, k, v, slot_block_ids, slot_offsets
        )
        out = attn_ops.decode_attention(
            q, k_cache, v_cache, block_tables, ctx_lens,
            scale=scale, sliding_window=cfg.sliding_window, mesh=mesh,
        )
        new_caches.append((k_cache, v_cache))
        out = out.reshape(S, cfg.num_heads * cfg.head_dim)
        x = residual + _o_proj(
            layer, out, lora_layer, adapter_idx, lora_scale
        ).astype(x.dtype)
        residual = x
        x_n = _norm(x, layer["post_attention_layernorm"], cfg)
        x = residual + _mlp(layer, x_n, lora_layer, adapter_idx, lora_scale, cfg)

    x = _norm(x, params["norm"], cfg)
    return _lm_head(params, cfg, x), new_caches
