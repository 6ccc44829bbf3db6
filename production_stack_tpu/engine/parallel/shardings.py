"""GSPMD sharding rules for params, KV caches and activations.

Megatron-style tensor parallelism expressed as PartitionSpecs — XLA inserts
the ICI collectives (one psum after o_proj, one after down_proj per layer):

  q/k/v_proj  [h, heads*hd]  -> shard output dim over tp (head-parallel)
  o_proj      [heads*hd, h]  -> shard input dim over tp (psum after)
  gate/up     [h, I]         -> shard I over tp
  down        [I, h]         -> shard I over tp (psum after)
  embed       [V, h]         -> shard V over tp (logits all-gathered)
  KV cache    [N, bs, K, D]  -> shard K (kv heads) over tp
  decode batch [S, ...]      -> shard S over dp

Requires num_heads % tp == 0 and num_kv_heads % tp == 0 (GQA: tp beyond
num_kv_heads would duplicate KV — rejected rather than silently replicated).
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from production_stack_tpu.engine.config import ModelConfig
from production_stack_tpu.engine.parallel.mesh import AXES

TP = AXES.TP
DP = AXES.DP


def validate_tp(cfg: ModelConfig, tp_size: int) -> None:
    if cfg.num_heads % tp_size:
        raise ValueError(f"num_heads={cfg.num_heads} not divisible by tp={tp_size}")
    if cfg.num_kv_heads % tp_size:
        raise ValueError(
            f"num_kv_heads={cfg.num_kv_heads} not divisible by tp={tp_size}"
        )
    if cfg.num_experts:
        if cfg.num_experts % tp_size:
            raise ValueError(
                f"num_experts={cfg.num_experts} not divisible by tp={tp_size} "
                "(MoE experts shard over the tp axis)"
            )
    elif cfg.intermediate_size % tp_size:
        raise ValueError(
            f"intermediate_size={cfg.intermediate_size} not divisible by tp={tp_size}"
        )


def validate_sp_mode(cfg: ModelConfig, par) -> None:
    """Ulysses redistributes heads across sp: every device's local kv-head
    count (after tp) must split evenly (parallel/ulysses.py)."""
    if par.sequence_parallel_mode not in ("ring", "ulysses"):
        raise ValueError(
            f"Unknown sequence_parallel_mode {par.sequence_parallel_mode!r} "
            "(ring|ulysses)"
        )
    sp, tp = par.sequence_parallel, par.tensor_parallel
    if par.sequence_parallel_mode == "ulysses" and sp > 1:
        local_kv = cfg.num_kv_heads // tp
        if local_kv % sp:
            raise ValueError(
                f"ulysses needs (num_kv_heads/tp)={local_kv} divisible by "
                f"sp={sp}; use sequence_parallel_mode='ring' instead"
            )
    if (
        par.sequence_parallel_mode == "ring"
        and sp > 1
        and cfg.sliding_window is not None
    ):
        # The ring rotation has no window support; silently computing full
        # attention would be wrong for windowed models (e.g. mistral).
        raise ValueError(
            f"sliding_window={cfg.sliding_window} is not supported with "
            "sequence_parallel_mode='ring'; use 'ulysses' (requires "
            "(num_kv_heads/tp) % sp == 0) or sp=1"
        )


def _maybe_quant(spec: P, cfg) -> object:
    """Quantized projections are {"q": int8 [in, out], "s": f32 [out]}
    (models/llama.py quantize_params): the int8 block keeps the weight's
    spec, the scale follows the OUT (last) axis partitioning."""
    if cfg.quantization is None:
        return spec
    return {"q": spec, "s": P(spec[1] if len(spec) >= 2 else None)}


def _layer_specs(cfg) -> Dict[str, P]:
    specs = {
        "input_layernorm": P(),
        "post_attention_layernorm": P(),
        "q_proj": _maybe_quant(P(None, TP), cfg),
        "k_proj": _maybe_quant(P(None, TP), cfg),
        "v_proj": _maybe_quant(P(None, TP), cfg),
        "o_proj": _maybe_quant(P(TP, None), cfg),
    }
    if cfg.num_experts:
        # MoE: experts shard over the tp axis (expert parallelism); the
        # router gate is replicated.  GSPMD reduces the weighted expert
        # sum across tp (models/llama.py _moe_mlp).
        specs["gate"] = P()
        specs["experts_gate"] = P(TP, None, None)
        specs["experts_up"] = P(TP, None, None)
        specs["experts_down"] = P(TP, None, None)
    else:
        specs["gate_proj"] = _maybe_quant(P(None, TP), cfg)
        specs["up_proj"] = _maybe_quant(P(None, TP), cfg)
        specs["down_proj"] = _maybe_quant(P(TP, None), cfg)
    if cfg.attention_bias:
        # Biases follow their projection's output (head) dim.
        specs["q_bias"] = P(TP)
        specs["k_bias"] = P(TP)
        specs["v_bias"] = P(TP)
    return specs


def param_specs(cfg: ModelConfig) -> Dict:
    """PartitionSpec tree matching the param tree from models/llama.py, or
    the tree of a module that brings its own (``param_specs``)."""
    from production_stack_tpu.engine.models import get_model

    model = get_model(cfg.name)
    if hasattr(model, "param_specs"):
        return model.param_specs(cfg)
    specs: Dict = {
        "embed_tokens": P(TP, None),
        "norm": P(),
        "layers": [_layer_specs(cfg) for _ in range(cfg.num_layers)],
    }
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = _maybe_quant(P(None, TP), cfg)
    return specs


def param_shardings(cfg: ModelConfig, mesh: Mesh) -> Dict:
    import jax

    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec),
        param_specs(cfg),
        is_leaf=lambda x: isinstance(x, P),
    )


def kv_cache_spec() -> P:
    # [num_blocks, block_size, num_kv_heads, head_dim]: shard kv heads.
    return P(None, None, TP, None)


def kv_scale_sharding(mesh: Mesh) -> NamedSharding:
    # int8 KV scale planes [num_blocks, block_size, num_kv_heads]: the
    # head axis shards over tp exactly like the data (kv/quant.py).
    return NamedSharding(mesh, P(None, None, TP))


def kv_cache_shardings(cfg: ModelConfig, mesh: Mesh) -> List[Tuple]:
    sharding = NamedSharding(mesh, kv_cache_spec())
    return [(sharding, sharding) for _ in range(cfg.num_layers)]


def decode_batch_spec() -> P:
    return P(DP)  # shard sequences over data-parallel axis
