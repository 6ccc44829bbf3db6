"""Checkpoint loading: HF safetensors -> our functional param trees.

Zero-egress friendly: with no checkpoint path, models get a deterministic
random init from the seed — throughput benchmarking and scale testing need
correct shapes, not trained weights.  A path that was given and cannot be
loaded is an error: the server never answers from weights nobody asked for.
"""

from __future__ import annotations

import json
import logging
import os
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from production_stack_tpu.engine.config import ModelConfig

logger = logging.getLogger(__name__)


def load_params(
    cfg: ModelConfig,
    weights_path: Optional[str],
    *,
    seed: int = 0,
    shardings=None,
):
    """HF-layout safetensors from ``weights_path``, or random init from
    ``seed`` when no path is given.  ``shardings`` (parallel/shardings.py
    param_shardings) places, and under cfg.quantization quantizes, each
    tensor directly in its final sharding (models/llama.py place_weight)."""
    from production_stack_tpu.engine.models import get_model, llama

    model = get_model(cfg.name)
    if weights_path:
        if not os.path.isdir(weights_path):
            raise FileNotFoundError(
                f"weights path {weights_path!r} is not a directory"
            )
        if model is not llama:
            raise ValueError(
                f"no checkpoint loader for {model.__name__}: "
                f"{cfg.name!r} serves seeded random weights only"
            )
        return load_hf_safetensors(cfg, weights_path, shardings)
    return model.init_params(cfg, jax.random.PRNGKey(seed), shardings)


def _open_safetensors(weights_path: str) -> Dict[str, np.ndarray]:
    """Read all tensors from one or more *.safetensors shards."""
    from safetensors import safe_open  # ships with transformers

    tensors: Dict[str, np.ndarray] = {}
    index_file = os.path.join(weights_path, "model.safetensors.index.json")
    if os.path.exists(index_file):
        with open(index_file) as f:
            index = json.load(f)
        shards = sorted(set(index["weight_map"].values()))
    else:
        shards = sorted(
            f for f in os.listdir(weights_path) if f.endswith(".safetensors")
        )
    for shard in shards:
        with safe_open(os.path.join(weights_path, shard), framework="np") as f:
            for name in f.keys():
                tensors[name] = f.get_tensor(name)
    return tensors


def load_hf_safetensors(cfg: ModelConfig, weights_path: str, shardings=None):
    """Map HF LlamaForCausalLM tensor names into our layout.

    torch Linear stores [out, in]; we store [in, out], hence the transposes
    (see models/llama.py docstring).  Tensors stay on the host until
    ``place_weight`` puts each one in its final sharding.
    """
    from production_stack_tpu.engine.models.llama import place_weight

    sd = _open_safetensors(weights_path)
    dtype = jnp.dtype(cfg.dtype)

    def take(name: str, transpose: bool = False) -> np.ndarray:
        arr = sd[name]
        if transpose:
            arr = arr.T
        return np.asarray(arr).astype(dtype)

    params = {
        "embed_tokens": take("model.embed_tokens.weight"),
        "norm": take("model.norm.weight"),
        "layers": [],
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = take("lm_head.weight", transpose=True)
    for i in range(cfg.num_layers):
        p = f"model.layers.{i}."
        layer = {
            "input_layernorm": take(p + "input_layernorm.weight"),
            "post_attention_layernorm": take(
                p + "post_attention_layernorm.weight"
            ),
            "q_proj": take(p + "self_attn.q_proj.weight", transpose=True),
            "k_proj": take(p + "self_attn.k_proj.weight", transpose=True),
            "v_proj": take(p + "self_attn.v_proj.weight", transpose=True),
            "o_proj": take(p + "self_attn.o_proj.weight", transpose=True),
        }
        if cfg.num_experts:
            # Mixtral: block_sparse_moe.gate + per-expert w1/w3/w2
            # (gate/up/down), stacked into [E, ...] arrays.
            moe = p + "block_sparse_moe."
            layer["gate"] = take(moe + "gate.weight", transpose=True)
            layer["experts_gate"] = np.stack([
                take(moe + f"experts.{e}.w1.weight", transpose=True)
                for e in range(cfg.num_experts)
            ])
            layer["experts_up"] = np.stack([
                take(moe + f"experts.{e}.w3.weight", transpose=True)
                for e in range(cfg.num_experts)
            ])
            layer["experts_down"] = np.stack([
                take(moe + f"experts.{e}.w2.weight", transpose=True)
                for e in range(cfg.num_experts)
            ])
        else:
            layer["gate_proj"] = take(p + "mlp.gate_proj.weight", transpose=True)
            layer["up_proj"] = take(p + "mlp.up_proj.weight", transpose=True)
            layer["down_proj"] = take(p + "mlp.down_proj.weight", transpose=True)
        if cfg.attention_bias:
            # Qwen2-style QKV biases (HF Qwen2Attention has bias=True on
            # q/k/v projections only).
            layer["q_bias"] = take(p + "self_attn.q_proj.bias")
            layer["k_bias"] = take(p + "self_attn.k_proj.bias")
            layer["v_bias"] = take(p + "self_attn.v_proj.bias")
        params["layers"].append(layer)
    logger.info("Loaded %d tensors from %s", len(sd), weights_path)
    if shardings is None:
        return jax.tree_util.tree_map(place_weight, params)
    # tree_map flattens ``shardings`` only as deep as ``params``: a
    # quantized leaf's {"q", "s"} pair reaches place_weight whole.
    return jax.tree_util.tree_map(place_weight, params, shardings)
