"""Multi-device SPMD tests on the virtual 8-CPU mesh (conftest.py forces
``--xla_force_host_platform_device_count=8``).

Covers every file in engine/parallel/: mesh construction, sharding specs
applied through a real engine, ring attention vs the dense reference, and
full engine generation parity across (dp, tp, sp) layouts.  The same tp
path on four real chips: ``python chip_smoke.py --chips 4``.
"""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    ModelConfig,
    ParallelConfig,
    SchedulerConfig,
)
from production_stack_tpu.engine.core.engine import LLMEngine
from production_stack_tpu.engine.core.sequence import SamplingParams
from production_stack_tpu.engine.ops import attention as attn_ops
from production_stack_tpu.engine.parallel.mesh import AXES, build_mesh
from production_stack_tpu.engine.parallel.ring_attention import (
    ring_prefill_with_prefix,
    ring_self_attention,
)

requires_8_devices = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs the 8-device virtual CPU mesh"
)


def sp_mesh(sp: int, dp: int = 1, tp: int = 1):
    return build_mesh(
        ParallelConfig(data_parallel=dp, tensor_parallel=tp, sequence_parallel=sp)
    )


# -- ring attention vs dense reference --------------------------------------


def dense_causal(q, k, v, scale):
    """Naive causal GQA attention (fp32 softmax), the ground truth."""
    T, H, D = q.shape
    K = k.shape[1]
    G = H // K
    qg = q.reshape(T, K, G, D)
    scores = jnp.einsum("tkgd,skd->kgts", qg, k).astype(jnp.float32) * scale
    mask = jnp.tril(jnp.ones((T, T), bool))
    scores = jnp.where(mask[None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("kgts,skd->tkgd", probs.astype(v.dtype), v)
    return out.reshape(T, H, D)


@requires_8_devices
@pytest.mark.parametrize("sp", [2, 4, 8])
def test_ring_self_attention_matches_dense(sp):
    T, H, K, D = 64, 4, 2, 8
    key = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (T, H, D), jnp.float32)
    k = jax.random.normal(kk, (T, K, D), jnp.float32)
    v = jax.random.normal(kv, (T, K, D), jnp.float32)
    scale = D**-0.5

    mesh = sp_mesh(sp)
    ring = shard_map(
        partial(ring_self_attention, axis_name=AXES.SP, scale=scale),
        mesh=mesh,
        in_specs=(P(AXES.SP), P(AXES.SP), P(AXES.SP)),
        out_specs=P(AXES.SP),
        check_vma=False,
    )
    got = jax.jit(ring)(q, k, v)
    want = dense_causal(q, k, v, scale)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=2e-5)


@requires_8_devices
def test_ring_self_attention_respects_valid_len():
    """Padded tail queries/keys must not contaminate valid positions."""
    T, H, K, D = 32, 4, 2, 8
    valid = 21
    key = jax.random.PRNGKey(1)
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (T, H, D), jnp.float32)
    k = jax.random.normal(kk, (T, K, D), jnp.float32)
    v = jax.random.normal(kv, (T, K, D), jnp.float32)
    scale = D**-0.5

    mesh = sp_mesh(4)
    ring = shard_map(
        partial(
            ring_self_attention,
            axis_name=AXES.SP,
            scale=scale,
            valid_len=jnp.int32(valid),
        ),
        mesh=mesh,
        in_specs=(P(AXES.SP), P(AXES.SP), P(AXES.SP)),
        out_specs=P(AXES.SP),
        check_vma=False,
    )
    got = np.asarray(jax.jit(ring)(q, k, v))
    want = np.asarray(dense_causal(q[:valid], k[:valid], v[:valid], scale))
    np.testing.assert_allclose(got[:valid], want, rtol=2e-5, atol=2e-5)


@requires_8_devices
@pytest.mark.parametrize("cached_len,valid_len", [(0, 32), (8, 24), (12, 17)])
def test_ring_prefill_with_prefix_matches_gather_path(cached_len, valid_len):
    """The sp>1 prefill attention must agree with ops/attention.py's
    single-device gather path for every (prefix, padding) combination."""
    T, H, K, D, C_max = 32, 4, 2, 8, 16
    key = jax.random.PRNGKey(2)
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (T, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (T, K, D), jnp.float32)
    v = jax.random.normal(ks[2], (T, K, D), jnp.float32)
    k_pre = jax.random.normal(ks[3], (C_max, K, D), jnp.float32)
    v_pre = jax.random.normal(ks[4], (C_max, K, D), jnp.float32)
    scale = D**-0.5
    cl = jnp.int32(cached_len)
    vl = jnp.int32(valid_len)

    mesh = sp_mesh(8)
    ring = shard_map(
        partial(ring_prefill_with_prefix, axis_name=AXES.SP, scale=scale),
        mesh=mesh,
        in_specs=(
            P(AXES.SP), P(AXES.SP), P(AXES.SP),
            P(AXES.SP), P(AXES.SP),  # prefix K/V ride the ring too
            P(), P(),
        ),
        out_specs=P(AXES.SP),
        check_vma=False,
    )
    got = np.asarray(jax.jit(ring)(q, k, v, k_pre, v_pre, cl, vl))
    want = np.asarray(
        attn_ops.dense_prefill_attention(q, k, v, k_pre, v_pre, cl, vl, scale=scale)
    )
    np.testing.assert_allclose(
        got[:valid_len], want[:valid_len], rtol=2e-5, atol=2e-5
    )


# -- engine generation parity across mesh layouts ---------------------------


def mesh_engine(dp=1, tp=1, sp=1, **overrides) -> LLMEngine:
    cfg = EngineConfig(
        model=ModelConfig(dtype="float32"),  # f32: parity unaffected by
        # collective reduction order (bf16 could flip a near-tie argmax)
        cache=CacheConfig(block_size=4, num_blocks=128),
        parallel=ParallelConfig(
            data_parallel=dp, tensor_parallel=tp, sequence_parallel=sp
        ),
        scheduler=SchedulerConfig(
            max_num_seqs=overrides.pop("max_num_seqs", 4),
            prefill_buckets=(16, 32, 64, 128),
            max_model_len=256,
        ),
    )
    return LLMEngine(cfg)


def generate_all(engine, prompts, max_tokens=6):
    for i, p in enumerate(prompts):
        engine.add_request(
            f"r{i}", prompt=p, sampling_params=SamplingParams(max_tokens=max_tokens)
        )
    outputs = {}
    for _ in range(500):
        if not engine.has_unfinished():
            break
        for out in engine.step():
            outputs.setdefault(out.seq_id, []).append(out.new_token_id)
    assert not engine.has_unfinished()
    return outputs


PROMPTS = [
    "the quick brown fox jumps over the lazy dog",
    "sequence parallel ring attention on a tpu mesh",
    "short",
]


@requires_8_devices
@pytest.mark.parametrize(
    "dp,tp,sp",
    [(1, 2, 1), (2, 1, 1), (1, 1, 2), (1, 2, 4), (2, 2, 2)],
)
def test_engine_generation_parity_across_meshes(dp, tp, sp):
    """Greedy generation must be identical on every mesh layout — tensor,
    data and sequence parallelism change the schedule, not the math."""
    want = generate_all(mesh_engine(), PROMPTS)
    got = generate_all(mesh_engine(dp=dp, tp=tp, sp=sp), PROMPTS)
    assert got == want


@requires_8_devices
def test_engine_prefix_cache_with_sp():
    """Prefix-cache hits must survive the ring path (prefix chunk merge)."""
    engine = mesh_engine(sp=2)
    prompt = "shared system prompt " * 4
    first = generate_all(engine, [prompt], max_tokens=5)["r0"]
    engine.add_request(
        "again", prompt=prompt, sampling_params=SamplingParams(max_tokens=5)
    )
    outputs = {}
    for _ in range(200):
        if not engine.has_unfinished():
            break
        for out in engine.step():
            outputs.setdefault(out.seq_id, []).append(out.new_token_id)
    assert engine.block_pool.prefix_hit_rate > 0.0
    assert outputs["again"] == first


def test_tp_validation_rejects_indivisible_heads():
    with pytest.raises(ValueError):
        mesh_engine(tp=3)  # num_kv_heads=2 not divisible


def test_dp_validation_rejects_indivisible_batch():
    with pytest.raises(ValueError):
        mesh_engine(dp=2, max_num_seqs=3)


@requires_8_devices
def test_engine_generation_parity_with_attention_bias_tp():
    """Qwen2-style QKV biases under tensor parallelism: the P(TP) bias
    shardings (parallel/shardings.py _layer_specs) must compile and keep
    greedy parity with the single-device engine."""
    def biased_engine(dp=1, tp=1, sp=1):
        cfg = EngineConfig(
            model=ModelConfig(dtype="float32", attention_bias=True),
            cache=CacheConfig(block_size=4, num_blocks=128),
            parallel=ParallelConfig(
                data_parallel=dp, tensor_parallel=tp, sequence_parallel=sp
            ),
            scheduler=SchedulerConfig(
                max_num_seqs=4,
                prefill_buckets=(16, 32, 64, 128),
                max_model_len=256,
            ),
        )
        return LLMEngine(cfg)

    want = generate_all(biased_engine(), PROMPTS[:2])
    got = generate_all(biased_engine(tp=2, sp=2), PROMPTS[:2])
    assert got == want


# -- Ulysses (all-to-all) sequence parallelism ------------------------------


@requires_8_devices
@pytest.mark.parametrize("cached_len,valid_len", [(0, 32), (8, 24), (12, 17)])
def test_ulysses_prefill_with_prefix_matches_gather_path(cached_len, valid_len):
    """The all-to-all SP strategy must agree with the single-device path
    for every (prefix, padding) combination — same contract as the ring."""
    from production_stack_tpu.engine.parallel.ulysses import (
        ulysses_prefill_with_prefix,
    )

    T, H, K, D, C_max = 32, 8, 2, 8, 16
    key = jax.random.PRNGKey(5)
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (T, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (T, K, D), jnp.float32)
    v = jax.random.normal(ks[2], (T, K, D), jnp.float32)
    k_pre = jax.random.normal(ks[3], (C_max, K, D), jnp.float32)
    v_pre = jax.random.normal(ks[4], (C_max, K, D), jnp.float32)
    scale = D**-0.5
    cl = jnp.int32(cached_len)
    vl = jnp.int32(valid_len)

    mesh = sp_mesh(2)  # K=2 kv heads: sp=2 is the divisibility limit
    ulysses = shard_map(
        partial(ulysses_prefill_with_prefix, axis_name=AXES.SP, scale=scale),
        mesh=mesh,
        in_specs=(
            P(AXES.SP), P(AXES.SP), P(AXES.SP),
            P(AXES.SP), P(AXES.SP),
            P(), P(),
        ),
        out_specs=P(AXES.SP),
        check_vma=False,
    )
    got = np.asarray(jax.jit(ulysses)(q, k, v, k_pre, v_pre, cl, vl))
    want = np.asarray(
        attn_ops.dense_prefill_attention(q, k, v, k_pre, v_pre, cl, vl, scale=scale)
    )
    np.testing.assert_allclose(
        got[:valid_len], want[:valid_len], rtol=2e-5, atol=2e-5
    )


@requires_8_devices
def test_engine_generation_parity_ulysses_mode():
    """Full-engine greedy parity with sequence_parallel_mode='ulysses'
    (dp=2 x sp=2 needs (K/tp)=2 % sp==0)."""
    def ulysses_engine(dp=1, tp=1, sp=1):
        cfg = EngineConfig(
            model=ModelConfig(dtype="float32"),
            cache=CacheConfig(block_size=4, num_blocks=128),
            parallel=ParallelConfig(
                data_parallel=dp, tensor_parallel=tp, sequence_parallel=sp,
                sequence_parallel_mode="ulysses",
            ),
            scheduler=SchedulerConfig(
                max_num_seqs=4, prefill_buckets=(16, 32, 64, 128),
                max_model_len=256,
            ),
        )
        return LLMEngine(cfg)

    want = generate_all(mesh_engine(), PROMPTS)
    got = generate_all(ulysses_engine(dp=2, sp=2), PROMPTS)
    assert got == want


def test_ulysses_mode_validation():
    """kv-heads indivisible by sp must fail loudly at engine construction."""
    from production_stack_tpu.engine.parallel.shardings import validate_sp_mode

    cfg = ModelConfig()  # K=2
    with pytest.raises(ValueError, match="divisible by sp"):
        validate_sp_mode(cfg, ParallelConfig(
            sequence_parallel=4, sequence_parallel_mode="ulysses"
        ))
    with pytest.raises(ValueError, match="Unknown sequence_parallel_mode"):
        validate_sp_mode(cfg, ParallelConfig(sequence_parallel_mode="bogus"))
    # ring never restricts kv heads.
    validate_sp_mode(cfg, ParallelConfig(sequence_parallel=8))


def test_ring_rejects_sliding_window():
    """Windowed models must not silently widen under ring sp>1."""
    from production_stack_tpu.engine.parallel.shardings import validate_sp_mode

    cfg = ModelConfig(sliding_window=64)
    with pytest.raises(ValueError, match="sliding_window"):
        validate_sp_mode(cfg, ParallelConfig(sequence_parallel=2))
    # Ulysses carries the window through; sp=1 ring is fine too.
    validate_sp_mode(cfg, ParallelConfig(
        sequence_parallel=2, sequence_parallel_mode="ulysses"
    ))
    validate_sp_mode(cfg, ParallelConfig(sequence_parallel=1))


@requires_8_devices
def test_ulysses_sliding_window_matches_dense():
    from production_stack_tpu.engine.parallel.ulysses import (
        ulysses_prefill_with_prefix,
    )

    T, H, K, D = 32, 4, 2, 8
    key = jax.random.PRNGKey(9)
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (T, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (T, K, D), jnp.float32)
    v = jax.random.normal(ks[2], (T, K, D), jnp.float32)
    k_pre = jnp.zeros((4, K, D), jnp.float32)
    v_pre = jnp.zeros((4, K, D), jnp.float32)
    scale = D**-0.5
    window = 12

    mesh = sp_mesh(2)
    fn = shard_map(
        partial(ulysses_prefill_with_prefix, axis_name=AXES.SP, scale=scale,
                sliding_window=window),
        mesh=mesh,
        in_specs=(P(AXES.SP),) * 5 + (P(), P()),
        out_specs=P(AXES.SP),
        check_vma=False,
    )
    got = np.asarray(jax.jit(fn)(q, k, v, k_pre, v_pre, jnp.int32(0), jnp.int32(T)))
    want = np.asarray(attn_ops.dense_prefill_attention(
        q, k, v, k_pre, v_pre, jnp.int32(0), jnp.int32(T),
        scale=scale, sliding_window=window,
    ))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


# -- MoE (mixtral-style) expert parallelism ---------------------------------


def moe_engine(dp=1, tp=1, sp=1):
    cfg = EngineConfig(
        model=ModelConfig(dtype="float32", num_experts=4,
                          num_experts_per_tok=2, intermediate_size=64),
        cache=CacheConfig(block_size=4, num_blocks=128),
        parallel=ParallelConfig(
            data_parallel=dp, tensor_parallel=tp, sequence_parallel=sp
        ),
        scheduler=SchedulerConfig(
            max_num_seqs=4, prefill_buckets=(16, 32, 64, 128),
            max_model_len=256,
        ),
    )
    return LLMEngine(cfg)


def test_moe_engine_generates():
    outputs = generate_all(moe_engine(), PROMPTS[:2])
    assert all(len(v) == 6 for v in outputs.values())


@requires_8_devices
@pytest.mark.parametrize("dp,tp,sp", [(1, 2, 1), (2, 2, 2), (2, 2, 1)])
def test_moe_engine_parity_with_expert_parallelism(dp, tp, sp):
    """Experts shard over tp (P(TP) on the stacked expert axis): greedy
    outputs must match the single-device MoE engine on every layout."""
    want = generate_all(moe_engine(), PROMPTS[:2])
    got = generate_all(moe_engine(dp=dp, tp=tp, sp=sp), PROMPTS[:2])
    assert got == want


def test_moe_tp_divisibility_validated():
    from production_stack_tpu.engine.parallel.shardings import validate_tp

    cfg = ModelConfig(num_experts=3)  # heads/kv pass tp=2; experts don't
    with pytest.raises(ValueError, match="num_experts"):
        validate_tp(cfg, 2)
    validate_tp(ModelConfig(num_experts=4), 2)  # experts divisible
