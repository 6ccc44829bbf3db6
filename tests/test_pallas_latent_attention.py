"""Pallas paged latent (MLA) kernels vs the XLA paths they replace on a TPU,
which stay the CPU paths: the decode kernel against
``models/sarvam_mla.py: _latent_walk``, the prefill kernel against
``_expanded_attention``.

Runs the kernel in Pallas interpret mode on the CPU, on the tiny preset's
shapes: 4 heads, a cache row of 48 values (latent 32, rotary key 16) padded
to 128 lanes, blocks of 16.  The compiled kernel is compiled for a described
v5e at the served widths by ``tests/test_chip_compile.py`` and runs on the
chip under the benchmark's compare.
"""

import ast
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from production_stack_tpu.engine.config import PRESETS
from production_stack_tpu.engine.models import sarvam_mla
from production_stack_tpu.engine.ops.pallas import latent_attention as la

from test_sarvam_mla import _case, _close, _decode, _hp, _prefill, ref

H, LANES, WIDTH, RANK, BS = 4, 128, 48, 32, 16
SCALE = 0.3
# Reading a stage that was never waited for shows as NaN (buffers start as
# NaN and a DMA lands at its wait) instead of as the right numbers the plain
# interpreter's copy-at-start leaves there.
TPU_INTERPRET = pltpu.InterpretParams()


def _paged_case(seed, ctx_lens, dtype=jnp.float32, max_blocks=64,
                num_blocks=256):
    """Queries and rows as the module makes them: content in the first
    WIDTH lanes, zeros in the pad."""
    rng = np.random.default_rng(seed)
    S = len(ctx_lens)
    content = np.arange(LANES) < WIDTH
    q = jnp.asarray(rng.standard_normal((S, H, LANES)) * content, dtype)
    cache = jnp.asarray(
        rng.standard_normal((num_blocks, BS, LANES)) * content, dtype)
    tables = np.zeros((S, max_blocks), np.int32)   # the tail: null block 0
    next_free = 1
    for s, ctx in enumerate(ctx_lens):
        nb = -(-ctx // BS)
        tables[s, :nb] = np.arange(next_free, next_free + nb)
        next_free += nb
    assert next_free <= num_blocks
    return (q, cache, jnp.asarray(tables),
            jnp.asarray(ctx_lens, jnp.int32))


def _walk(q, cache, tables, ctx):
    return np.asarray(sarvam_mla._latent_walk(
        q, cache, tables, ctx, RANK, SCALE), np.float32)


def _kernel(q, cache, tables, ctx, chunk_blocks, interpret=True):
    return np.asarray(la.latent_decode_attention_pallas(
        q, cache, tables, ctx, latent_rank=RANK, scale=SCALE,
        chunk_blocks=chunk_blocks, interpret=interpret), np.float32)


def _contexts(stage):
    """Context lengths a row, by the positions of a stage."""
    return {
        "one": [1, 1, 1, 1],
        # One short of, exactly at and one past a stage, and two stages.
        "stage-edges": [stage - 1, stage, stage + 1, 2 * stage],
        "very-different": [3 * stage + 5, 2, stage, 7],
        # Padding rows (ctx 0) before, between and after live rows: the
        # next row's first stage is fetched under this row's last one.
        "padding-between": [0, 2 * stage + 1, 0, stage + 3],
        "padding-last": [stage + 9, 0, 0, 0],
        "all-padding": [0, 0, 0, 0],
    }


@pytest.mark.parametrize("interpret", [True, TPU_INTERPRET],
                         ids=["interpret", "tpu-interpret"])
@pytest.mark.parametrize("chunk_blocks", [1, 2, 16])
@pytest.mark.parametrize("which", sorted(_contexts(1)))
def test_latent_kernel_matches_the_xla_walk(which, chunk_blocks, interpret):
    ctx_lens = _contexts(chunk_blocks * BS)[which]
    q, cache, tables, ctx = _paged_case(0, ctx_lens)
    got = _kernel(q, cache, tables, ctx, chunk_blocks, interpret)
    want = _walk(q, cache, tables, ctx)
    live = np.asarray(ctx) > 0
    # test_pallas_decode_matches_gather's tolerance.
    np.testing.assert_allclose(got[live], want[live], rtol=2e-5, atol=2e-5)
    # A padding row reads zeros, not what the buffers held.
    assert not got[~live].any()


@pytest.mark.parametrize("ctx_lens", [[1, 16, 17, 33], [0, 300, 0, 129],
                                      [257, 0, 0, 1]])
def test_latent_kernel_bf16_cache_matches_the_xla_walk(ctx_lens):
    """bf16 pages go to the MXU as they are stored, queries and
    probabilities rounded to bf16 on both paths, statistics fp32 on both.
    They differ in where ``p`` is rounded (``exp(s - running max)`` a stage
    of 64 positions here, a tile of 2,048 there) and each rounds its
    output to bf16: one ulp of that output, 2^-8 relative; the tolerance is
    twice that, as ``test_pallas_decode_bf16_cache_matches_gather`` has it."""
    q, cache, tables, ctx = _paged_case(2, ctx_lens, dtype=jnp.bfloat16)
    got = la.latent_decode_attention_pallas(
        q, cache, tables, ctx, latent_rank=RANK, scale=SCALE, chunk_blocks=4,
        interpret=True)
    assert got.dtype == jnp.bfloat16
    want = np.asarray(sarvam_mla._latent_walk(
        q, cache, tables, ctx, RANK, SCALE).astype(jnp.bfloat16), np.float32)
    live = np.asarray(ctx) > 0
    np.testing.assert_allclose(np.asarray(got, np.float32)[live], want[live],
                               rtol=2**-7, atol=2**-7)
    assert np.all(np.isfinite(np.asarray(got, np.float32)))


def test_a_rows_output_does_not_depend_on_its_batch():
    """The same row alone, first, last, and behind a padding row: the same
    numbers to the bit, whichever ring slot its stages fall into."""
    q, cache, tables, ctx = _paged_case(3, [70, 33, 129, 200])
    whole = _kernel(q, cache, tables, ctx, 2, TPU_INTERPRET)
    for rows in ([2], [2, 0], [1, 2], [3, 2, 1, 0]):
        got = _kernel(q[jnp.array(rows)], cache, tables[jnp.array(rows)],
                      ctx[jnp.array(rows)], 2, TPU_INTERPRET)
        np.testing.assert_array_equal(got, whole[rows])
    padded = _kernel(q[jnp.array([0, 2])], cache, tables[jnp.array([0, 2])],
                     jnp.asarray([0, 129], jnp.int32), 2, TPU_INTERPRET)
    np.testing.assert_array_equal(padded[1], whole[2])


def test_a_block_id_outside_the_pool_is_clipped_into_it():
    """The copies' bounds checks are off: an id past the pool reads the
    pool's last block and nothing else."""
    q, cache, tables, ctx = _paged_case(4, [40], num_blocks=8, max_blocks=4)
    beyond = tables.at[0, 1].set(1000)
    inside = tables.at[0, 1].set(7)
    np.testing.assert_array_equal(
        _kernel(q, cache, beyond, ctx, 2), _kernel(q, cache, inside, ctx, 2))


@pytest.fixture
def fresh_traces():
    """A planted fault changes what the kernel traces to, not its
    arguments: drop every cached trace before it and after it."""
    jax.clear_caches()
    yield
    jax.clear_caches()


WAIT = la._wait_stage


def _never_the_second_buffer(cache_hbm, buf, sems, slot, **how):
    if slot != 1:
        return WAIT(cache_hbm, buf, sems, slot, **how)


@pytest.mark.parametrize("attr, fault", [
    # Values from the lanes behind the latent: the rotary key and the pad.
    ("_values", lambda tile, rank: tile[:, -rank:]),
    # The mask off by one: the position after the context is attended.
    ("_live", lambda pos, ctx: pos <= ctx),
    ("_wait_stage", _never_the_second_buffer),
])
def test_a_planted_fault_in_the_kernel_fails(monkeypatch, fresh_traces,
                                             attr, fault):
    q, cache, tables, ctx = _paged_case(5, [70, 33, 0, 100])
    want = _walk(q, cache, tables, ctx)
    live = np.asarray(ctx) > 0
    sound = _kernel(q, cache, tables, ctx, 2, TPU_INTERPRET)
    np.testing.assert_allclose(sound[live], want[live], rtol=2e-5, atol=2e-5)
    jax.clear_caches()
    monkeypatch.setattr(la, attr, fault)
    got = _kernel(q, cache, tables, ctx, 2, TPU_INTERPRET)
    assert not np.allclose(got[live], want[live], rtol=1e-2, atol=1e-2)


KERNEL = la.latent_decode_attention_pallas


@pytest.fixture
def kernel_on_the_path(monkeypatch):
    """``sarvam_mla.decode`` as a TPU serves it, the kernel in interpret
    mode: the selection says yes and the call is interpreted."""
    called = []

    def interpreted(*args, **kwargs):
        called.append(args[0].shape)
        return KERNEL(*args, **dict(kwargs, interpret=True))

    monkeypatch.setattr(sarvam_mla, "use_pallas_latent_decode",
                        lambda lanes: lanes % 128 == 0)
    monkeypatch.setattr(la, "latent_decode_attention_pallas", interpreted)
    return called


@pytest.mark.parametrize("seed", [0, 1])
def test_decode_through_the_kernel_matches_the_reference(
        kernel_on_the_path, seed):
    """``test_prefill_in_two_chunks_then_decode_matches_the_reference`` with
    the kernel between the two weight einsums of every layer's decode."""
    with jax.default_matmul_precision("highest"):
        cfg, params, tokens, blocks, cache = _case(seed)
        want = ref.forward(params, _hp(cfg), jnp.asarray(tokens))
        _, cache = _prefill(cfg, params, cache, tokens, 0, 64, 64, blocks)
        _, cache = _prefill(cfg, params, cache, tokens, 64, 36, 64, blocks)
        for pos in range(100, 104):
            logits, cache = _decode(
                cfg, params, cache, tokens[pos], pos, blocks)
            _close(logits[0], want[pos])
    # Two rows (one padding), 4 heads, 128 lanes; 3 layers x 4 steps.
    assert kernel_on_the_path == [(2, 4, 128)] * 12


def test_the_kernel_serves_on_a_tpu_alone(monkeypatch):
    """Selection by what the code observes: a TPU backend, whole 128-lane
    rows, the A/B switch not set.  No flag of its own."""
    cfg = PRESETS["sarvam-105b-ep4"]
    assert not sarvam_mla.use_pallas_latent_decode(640)   # the CPU
    assert sarvam_mla.attention_paths(cfg) == (
        "xla-absorbed-latent", "xla-expanded-latent")
    assert not sarvam_mla.use_pallas_latent_prefill(640, 64)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert sarvam_mla.use_pallas_latent_decode(640)
    assert sarvam_mla.use_pallas_latent_prefill(640, 64)
    # What the engine's boot line says (core/engine.py).
    assert sarvam_mla.attention_paths(cfg) == (
        "pallas-latent", "pallas-latent")
    assert sarvam_mla.attention_paths(PRESETS["xing4.0-29b-a4b-stage"]) == (
        "pallas-latent", "pallas-latent")
    # Four heads do not fill a sublane tile: the chunk stays on the walk.
    assert sarvam_mla.attention_paths(PRESETS["tiny-sarvam"]) == (
        "pallas-latent", "xla-expanded-latent")
    assert sarvam_mla.use_pallas_latent_decode(128)
    assert not sarvam_mla.use_pallas_latent_decode(576)
    assert not sarvam_mla.use_pallas_latent_prefill(576, 64)
    monkeypatch.setenv("PSTPU_DISABLE_PALLAS", "1")
    assert not sarvam_mla.use_pallas_latent_decode(640)
    assert not sarvam_mla.use_pallas_latent_prefill(640, 64)


def test_the_dense_model_does_not_import_the_kernel():
    """``mistral-7b-int8`` runs none of it: the module imports the kernel
    inside its decode path, not as it is imported itself, and nothing the
    dense model runs names it."""
    spec = importlib.util.find_spec(
        "production_stack_tpu.engine.models.sarvam_mla")
    with open(spec.origin) as f:
        top = ast.parse(f.read()).body
    imported = [ast.unparse(node) for node in top
                if isinstance(node, (ast.Import, ast.ImportFrom))]
    assert imported and not any("pallas" in line for line in imported)
    root = os.path.dirname(os.path.dirname(spec.origin))
    for rel in ("models/llama.py", "core/step_programs.py",
                "ops/attention.py", "ops/pallas/paged_attention.py"):
        with open(os.path.join(root, rel)) as f:
            assert "latent_attention" not in f.read(), rel
