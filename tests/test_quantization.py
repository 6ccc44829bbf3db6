"""Int8 weight-only quantization (ModelConfig.quantization).

Per-out-channel symmetric scales on the projection matmuls; decode is
HBM-bound so int8 halves the weight bytes streamed per step.  Quality gate:
quantized logits must track bf16/f32 logits closely, and the engine must
serve end-to-end (including under a tp mesh, where the scale vectors shard
with their projection's out axis).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    ParallelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.core.engine import LLMEngine
from production_stack_tpu.engine.core.sequence import SamplingParams
from production_stack_tpu.engine.models import llama


def test_quantize_params_structure_and_reconstruction():
    cfg = ModelConfig(dtype="float32")
    params = llama.init_params(cfg, jax.random.PRNGKey(0))
    qparams = llama.quantize_params(params, ModelConfig(
        dtype="float32", quantization="int8"))
    layer, qlayer = params["layers"][0], qparams["layers"][0]
    assert set(qlayer["q_proj"]) == {"q", "s"}
    assert qlayer["q_proj"]["q"].dtype == jnp.int8
    assert qlayer["q_proj"]["s"].shape == (layer["q_proj"].shape[1],)
    # Norms/embeddings untouched.
    assert qlayer["input_layernorm"].dtype == jnp.float32
    assert qparams["embed_tokens"].dtype == jnp.float32
    # Dequantized reconstruction within one quantization step per channel.
    recon = qlayer["q_proj"]["q"].astype(jnp.float32) * qlayer["q_proj"]["s"]
    err = jnp.max(jnp.abs(recon - layer["q_proj"]))
    assert float(err) <= float(jnp.max(qlayer["q_proj"]["s"])) + 1e-7


def test_quantized_logits_track_full_precision():
    cfg = ModelConfig(dtype="float32")
    params = llama.init_params(cfg, jax.random.PRNGKey(1))
    qcfg = ModelConfig(dtype="float32", quantization="int8")
    qparams = llama.quantize_params(params, qcfg)

    T = 16
    tokens = jnp.asarray(np.random.RandomState(0).randint(4, 200, T), jnp.int32)
    kv = [
        (jnp.zeros((8, 4, cfg.num_kv_heads, cfg.head_dim), jnp.float32),) * 2
        for _ in range(cfg.num_layers)
    ]
    kwargs = dict(
        tokens=tokens,
        cached_len=jnp.int32(0),
        prefix_block_ids=jnp.zeros((4,), jnp.int32),
        new_block_ids=jnp.asarray([1, 2, 3, 4], jnp.int32),
        valid_len=jnp.int32(T),
    )
    ref, _ = llama.prefill(params, cfg, kv_caches=[tuple(c) for c in kv], **kwargs)
    got, _ = llama.prefill(qparams, qcfg, kv_caches=[tuple(c) for c in kv], **kwargs)
    ref, got = np.asarray(ref), np.asarray(got)
    # Cosine similarity of the next-token logit rows stays high.
    cos = np.sum(ref * got) / (np.linalg.norm(ref) * np.linalg.norm(got))
    assert cos > 0.999
    # Greedy argmax agrees on the final (sampled) position.
    assert int(ref[-1].argmax()) == int(got[-1].argmax())


def _engine(quantization=None, parallel=None):
    return LLMEngine(EngineConfig(
        model=ModelConfig(dtype="float32", quantization=quantization),
        cache=CacheConfig(block_size=4, num_blocks=64),
        scheduler=SchedulerConfig(
            max_num_seqs=2, prefill_buckets=(16, 32, 64), max_model_len=128
        ),
        parallel=parallel or ParallelConfig(),
    ))


def _drain(engine, prompt="quantization smoke test", max_tokens=8):
    engine.add_request("q1", prompt=prompt,
                       sampling_params=SamplingParams(max_tokens=max_tokens))
    tokens = []
    steps = 0
    while engine.has_unfinished():
        steps += 1
        assert steps < 200
        for out in engine.step():
            tokens.append(out.new_token_id)
    return tokens


def test_engine_serves_quantized_end_to_end():
    tokens = _drain(_engine(quantization="int8"))
    assert len(tokens) == 8


def test_quantized_under_tensor_parallel_mesh():
    if jax.device_count() < 2:
        pytest.skip("needs multi-device mesh")
    tokens_tp = _drain(_engine(
        quantization="int8",
        parallel=ParallelConfig(tensor_parallel=2),
    ))
    assert len(tokens_tp) == 8


def test_embed_works_quantized():
    engine = _engine(quantization="int8")
    vec = engine.embed(engine.tokenizer.encode("quantized embedding"))
    np.testing.assert_allclose(np.linalg.norm(vec), 1.0, rtol=1e-5)


def test_unknown_quantization_rejected():
    with pytest.raises(ValueError, match="quantization"):
        ModelConfig(quantization="fp4")


# -- parameters made, quantized and placed in their final sharding ----------
#
# Bring-up on the chip: the whole bf16 model used to be built on the default
# device, quantized there and only then put on the mesh, which cannot fit a
# 7B model on one 16 GB chip and piles a tp=4 model on device 0.  Now each
# tensor is created (or read), quantized and placed by itself.


def _tp2(quantization=None, dtype="float32"):
    from production_stack_tpu.engine.parallel import shardings as sh
    from production_stack_tpu.engine.parallel.mesh import build_mesh

    cfg = ModelConfig(dtype=dtype, quantization=quantization)
    mesh = build_mesh(ParallelConfig(tensor_parallel=2))
    return cfg, sh.param_shardings(cfg, mesh)


def _assert_trees_equal(got, want):
    """Bit for bit, but for the int8 form: a scale computed inside one
    jitted program and one computed op by op differ by a float32 ulp
    (amax / 127 fuses differently), which can move a weight that sits on
    a rounding boundary by one int8 step."""
    got_leaves, got_def = jax.tree_util.tree_flatten(got)
    want_leaves, want_def = jax.tree_util.tree_flatten(want)
    assert got_def == want_def
    quantized = any(x.dtype == jnp.int8 for x in want_leaves)
    for g, w in zip(got_leaves, want_leaves):
        assert g.dtype == w.dtype and g.shape == w.shape
        g, w = np.asarray(g), np.asarray(w)
        if not quantized:
            np.testing.assert_array_equal(g, w)
        elif w.dtype == np.int8:
            assert np.abs(g.astype(np.int32) - w.astype(np.int32)).max() <= 1
        else:
            np.testing.assert_allclose(g, w, rtol=1e-6, atol=0)


@pytest.mark.parametrize("quantization", [None, "int8"])
def test_init_params_in_final_sharding_matches_whole_model_path(quantization):
    cfg, shardings = _tp2(quantization)
    want = llama.quantize_params(
        llama.init_params(cfg, jax.random.PRNGKey(3)), cfg
    )
    got = llama.init_params(cfg, jax.random.PRNGKey(3), shardings)
    _assert_trees_equal(got, want)
    # Every leaf landed where the sharding tree says; a projection spans
    # both devices of the tp axis.
    for x, want_sharding in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(shardings)
    ):
        assert x.sharding.is_equivalent_to(want_sharding, x.ndim), x.shape
    q_proj = jax.tree_util.tree_leaves(got["layers"][0]["q_proj"])[0]
    assert len(q_proj.sharding.device_set) == 2


def _save_hf_checkpoint(params, path):
    from safetensors.numpy import save_file

    tensors = {
        "model.embed_tokens.weight": params["embed_tokens"],
        "model.norm.weight": params["norm"],
        "lm_head.weight": params["lm_head"].T,
    }
    names = {
        "q_proj": "self_attn.q_proj", "k_proj": "self_attn.k_proj",
        "v_proj": "self_attn.v_proj", "o_proj": "self_attn.o_proj",
        "gate_proj": "mlp.gate_proj", "up_proj": "mlp.up_proj",
        "down_proj": "mlp.down_proj",
    }
    for i, layer in enumerate(params["layers"]):
        p = f"model.layers.{i}."
        tensors[p + "input_layernorm.weight"] = layer["input_layernorm"]
        tensors[p + "post_attention_layernorm.weight"] = layer[
            "post_attention_layernorm"
        ]
        for ours, theirs in names.items():
            tensors[p + theirs + ".weight"] = layer[ours].T  # torch [out, in]
    save_file(
        {k: np.ascontiguousarray(np.asarray(v)) for k, v in tensors.items()},
        str(path / "model.safetensors"),
    )


@pytest.mark.parametrize("quantization", [None, "int8"])
def test_load_params_reads_quantizes_and_places_a_checkpoint(
    tmp_path, quantization
):
    from production_stack_tpu.engine.models.weights import load_params

    cfg, shardings = _tp2(quantization)
    params = llama.init_params(cfg, jax.random.PRNGKey(5))
    _save_hf_checkpoint(params, tmp_path)
    want = llama.quantize_params(params, cfg)
    _assert_trees_equal(load_params(cfg, str(tmp_path), shardings=shardings), want)
    _assert_trees_equal(load_params(cfg, str(tmp_path)), params)


def test_load_params_raises_on_a_path_it_cannot_load(tmp_path):
    """A --weights-path that fails to load used to log and serve random
    weights.  It raises: nobody asked for those."""
    from production_stack_tpu.engine.models.weights import load_params

    cfg = ModelConfig(dtype="float32")
    with pytest.raises(FileNotFoundError):
        load_params(cfg, str(tmp_path / "missing"))
    with pytest.raises(KeyError):  # a directory with no tensors in it
        load_params(cfg, str(tmp_path))
    # No path at all is the seeded random init, as before.
    _assert_trees_equal(
        load_params(cfg, None, seed=7),
        llama.init_params(cfg, jax.random.PRNGKey(7)),
    )
