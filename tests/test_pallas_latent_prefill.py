"""Pallas latent (MLA) prefill kernel vs the XLA walk it replaces on a TPU
(``models/sarvam_mla.py: _expanded_attention``), which stays the CPU path and
the statement the kernel is held to.

Runs the kernel in Pallas interpret mode on the CPU at the tiny preset's
widths (a cache row of 48 values in 128 lanes, blocks of 16) with stages of
two blocks and query tiles of a few slots, so that a 32-slot chunk has what
a served one has: several query tiles, some of them padded, a prefix of
several stages, a table that is permuted.  The compiled kernel is compiled for
a described v5e at the served widths by ``tests/test_chip_compile.py`` and
runs on the chip under the benchmark's compare.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import PRESETS
from production_stack_tpu.engine.models import sarvam_mla
from production_stack_tpu.engine.ops.pallas import latent_attention as la

from test_pallas_latent_attention import (
    BS, H, LANES, RANK, TPU_INTERPRET, WIDTH, _never_the_second_buffer,
    fresh_traces,
)
from test_sarvam_mla import _close, _prefill

STAGE = 2 * BS      # positions a prefix stage at ``chunk_blocks`` 2


@functools.lru_cache(maxsize=None)
def _layer_of(heads, dtype_name):
    """(the tiny preset at ``heads`` heads, one layer's weights, the walk
    jitted over them): made once a width, so that a case costs a call."""
    cfg = dataclasses.replace(
        PRESETS["tiny-sarvam"], num_heads=heads, num_layers=2,
        dtype=dtype_name)
    dtype = jnp.dtype(dtype_name)
    layer = jax.tree.map(
        lambda a: a.astype(dtype),
        sarvam_mla.init_params(cfg, jax.random.PRNGKey(0))["layers"][1])
    # Weights of 0.02 would leave every score near zero: a softmax that
    # weighs every key alike hides a wrong mask.
    layer["kv_b_proj"] = layer["kv_b_proj"] * 8
    walk = jax.jit(lambda *a: sarvam_mla._expanded_attention(layer, cfg, *a))
    return cfg, layer, walk


def _chunk_case(seed, T, cached, heads=H, dtype=jnp.float32, table="permuted",
                max_blocks=64, num_blocks=256):
    """A chunk's queries and own rows as ``_project`` makes them, a pool and
    the prefix's block table (``permuted`` over the pool, or ``repeated``:
    ascending, with one live id repeated past ``cached`` where the engine
    leaves the null block)."""
    cfg, _layer, _walk = _layer_of(heads, jnp.dtype(dtype).name)
    rng = np.random.default_rng(seed)
    content = np.arange(LANES) < WIDTH
    cache = jnp.asarray(
        rng.standard_normal((num_blocks, BS, LANES)) * content, dtype)
    nb = -(-cached // BS)
    ids = np.zeros(max_blocks, np.int32)
    if table == "permuted":
        ids[:nb] = rng.permutation(num_blocks - 1)[:nb] + 1
    else:
        ids[:nb] = np.arange(1, nb + 1)
        ids[nb:] = 1
    q_nope = jnp.asarray(
        rng.standard_normal((T, heads, cfg.qk_nope_head_dim)), dtype)
    q_rope = jnp.asarray(
        rng.standard_normal((T, heads, cfg.qk_rope_head_dim)), dtype)
    rows = jnp.asarray(rng.standard_normal((T, LANES)) * content, dtype)
    return q_nope, q_rope, rows, cache, jnp.asarray(ids)


def _both_paths(case, cached, valid, q_rows, interpret=True, **how):
    """(the kernel path's output, the walk's, slots a query tile): what
    ``_prefill_attention`` computes on a TPU and off it."""
    q_nope, q_rope, rows, cache, ids = case
    cfg, layer, walk = _layer_of(q_nope.shape[1], q_nope.dtype.name)
    lens = (jnp.int32(cached), jnp.int32(valid))
    want = walk(q_nope, q_rope, rows, cache, ids, *lens)
    w = sarvam_mla._kv_b(layer, cfg)
    latent = la.latent_prefill_attention_pallas(
        sarvam_mla._into_latent(w, cfg, q_nope, q_rope, LANES), rows, cache,
        ids, *lens, latent_rank=RANK, scale=sarvam_mla.softmax_scale(cfg),
        q_rows=q_rows, chunk_blocks=2, interpret=interpret, **how)
    got = sarvam_mla._out_of_latent(w, cfg, latent)
    Tq, _own = la.prefill_tiling(
        q_nope.shape[0], cfg.num_heads, q_rows,
        how.get("own_tile", la.OWN_TILE))
    return np.asarray(got, np.float32), np.asarray(want, np.float32), Tq


def _assert_the_chunk_matches(got, want, Tq, valid, tol=2e-5):
    live = -(-valid // Tq) * Tq
    assert np.all(np.isfinite(got))
    # A tile of slots past valid_len: exactly zero, nothing computed.
    assert not got[live:].any()
    # Every slot of a live tile, its padded ones too: they attend what the
    # walk's padded slots attend.
    np.testing.assert_allclose(got[:live], want[:live], rtol=tol, atol=tol)


CACHED = [0, 1, STAGE - 1, STAGE, STAGE + 1, 3 * STAGE + 5]


@pytest.mark.parametrize("valid", [32, 20, 9, 1], ids=lambda v: f"valid{v}")
@pytest.mark.parametrize("cached", CACHED, ids=lambda c: f"cached{c}")
def test_latent_prefill_kernel_matches_the_expanded_walk(cached, valid):
    """A 32-slot chunk in four query tiles of 8 slots x 4 heads over a
    permuted table: nothing cached, one position, a stage's edge and one
    either side, several stages; every slot valid, whole tiles padded (20:
    one and a half; 9, 1: nearly all), one slot."""
    with jax.default_matmul_precision("highest"):
        got, want, Tq = _both_paths(
            _chunk_case(cached, 32, cached), cached, valid, q_rows=32)
    assert Tq == 8
    _assert_the_chunk_matches(got, want, Tq, valid)


@pytest.mark.parametrize("cached", CACHED, ids=lambda c: f"cached{c}")
def test_no_stage_is_read_before_its_wait(cached):
    """The same under the TPU interpreter, whose buffers start as NaN and
    whose DMA lands at its wait: a stage read early shows."""
    with jax.default_matmul_precision("highest"):
        got, want, Tq = _both_paths(
            _chunk_case(cached, 32, cached), cached, 20, q_rows=32,
            interpret=TPU_INTERPRET)
    _assert_the_chunk_matches(got, want, Tq, 20)


@pytest.mark.parametrize("table", ["permuted", "repeated"])
@pytest.mark.parametrize("heads, T, q_rows, tiles", [
    (64, 32, 1024, 2),     # sarvam's grouping: 16 slots x 64 heads a tile
    (32, 64, 1024, 2),     # xing's: 32 slots x 32 heads
    (4, 8, 1024, 1),       # one query tile holds the chunk
    (4, 64, 16, 16),       # sixteen tiles of 4 slots
])
def test_latent_prefill_kernel_by_heads_and_tiles(heads, T, q_rows, tiles,
                                                  table):
    cached, valid = 2 * STAGE + 7, T - T // tiles - 1   # the last tile padded
    with jax.default_matmul_precision("highest"):
        got, want, Tq = _both_paths(
            _chunk_case(1, T, cached, heads=heads, table=table),
            cached, valid, q_rows)
    assert T // Tq == tiles
    _assert_the_chunk_matches(got, want, Tq, valid)


@pytest.mark.parametrize("cached, valid", [(0, 64), (50, 37), (50, 1),
                                           (STAGE, 48)])
def test_the_chunks_own_keys_in_several_stages(cached, valid):
    """A 2,048-slot chunk walks its own rows four stages of 512 deep; here
    64 slots in four own stages of 16, a query tile (4 or 16 slots) inside
    one of them, each reading up to its own causal frontier."""
    for q_rows in (16, 64):
        with jax.default_matmul_precision("highest"):
            got, want, Tq = _both_paths(
                _chunk_case(2, 64, cached), cached, valid, q_rows,
                own_tile=16)
        assert Tq == q_rows // H
        _assert_the_chunk_matches(got, want, Tq, valid)


def test_latent_prefill_kernel_bf16_matches_the_expanded_walk():
    """bf16 rows go to the MXU as they are stored on both paths, statistics
    fp32 on both; the walk expands a tile by ``W_kvb`` and rounds the
    expansion, the kernel takes the query into the latent space and rounds
    that, as the decode does: a few bf16 ulps of the largest output."""
    cached, valid = 3 * STAGE + 5, 20
    got, want, Tq = _both_paths(
        _chunk_case(3, 32, cached, dtype=jnp.bfloat16), cached, valid, 32)
    live = -(-valid // Tq) * Tq
    assert np.all(np.isfinite(got)) and not got[live:].any()
    assert np.abs(got[:live] - want[:live]).max() <= 2**-5 * np.abs(
        want[:live]).max()


@pytest.mark.parametrize("attr, fault", [
    ("_values", lambda tile, rank: tile[:, -rank:]),
    # Off by one: the position after the prefix, the slot after the valid.
    ("_live", lambda pos, end: pos <= end),
    ("_wait_stage", _never_the_second_buffer),
])
def test_a_planted_fault_in_the_prefill_kernel_fails(
        monkeypatch, fresh_traces, attr, fault):
    cached, valid = 3 * STAGE + 5, 20
    case = _chunk_case(4, 32, cached)
    with jax.default_matmul_precision("highest"):
        got, want, Tq = _both_paths(case, cached, valid, 32, TPU_INTERPRET)
        _assert_the_chunk_matches(got, want, Tq, valid)
        jax.clear_caches()
        monkeypatch.setattr(la, attr, fault)
        got, _, _ = _both_paths(case, cached, valid, 32, TPU_INTERPRET)
    assert not np.allclose(got[:valid], want[:valid], rtol=1e-2, atol=1e-2)


PREFILL_KERNEL = la.latent_prefill_attention_pallas


@pytest.mark.parametrize("preset", ["tiny-sarvam", "tiny-xing"])
def test_prefill_through_the_kernel_matches_the_xla_path(
        monkeypatch, preset):
    """A two-layer module, plain residual and four streams (``hc_mult``):
    two chunks, the second behind the first's pages with its last tile
    padded; the logits, and the rows each chunk wrote, by the kernel path
    against the XLA path."""
    cfg = dataclasses.replace(PRESETS[preset], dtype="float32", num_layers=2)
    assert bool(cfg.hc_mult) == (preset == "tiny-xing")
    params = sarvam_mla.init_params(cfg, jax.random.PRNGKey(0))
    tokens = np.random.default_rng(0).integers(
        1, cfg.vocab_size, 100).astype(np.int32)
    blocks = np.arange(1, 8, dtype=np.int32)

    def two_chunks():
        cache = sarvam_mla.init_cache(cfg, 64, BS)
        first, cache = _prefill(cfg, params, cache, tokens, 0, 64, 64, blocks)
        last, cache = _prefill(cfg, params, cache, tokens, 64, 36, 64, blocks)
        return first, last, cache

    with jax.default_matmul_precision("highest"):
        want = two_chunks()
        called = []
        kernel = lambda *a, **kw: (
            called.append(a[0].shape),
            PREFILL_KERNEL(*a, **dict(kw, q_rows=32, chunk_blocks=2,
                                      interpret=True)))[1]
        monkeypatch.setattr(sarvam_mla, "use_pallas_latent_prefill",
                            lambda lanes, heads: lanes % 128 == 0)
        monkeypatch.setattr(la, "latent_prefill_attention_pallas", kernel)
        got = two_chunks()
    assert called == [(64, 4, 128)] * 4        # 2 layers x 2 chunks
    _close(got[0], want[0])
    _close(got[1], want[1])
    for layer_got, layer_want in zip(got[2], want[2]):
        written = np.asarray(layer_want)[blocks]
        assert np.abs(written).max() > 0
        # Positions 100..111 of the last block are the chunk's padded slots:
        # finite on both paths, and nobody's to compare.
        _close(np.asarray(layer_got)[blocks].reshape(-1, LANES)[:100],
               written.reshape(-1, LANES)[:100])
        assert np.all(np.isfinite(np.asarray(layer_got)))


def _brute_force_tile_pairs(T, Tq, own, stage, prefix_positions, cached,
                            valid):
    """(pairs with a score that survives the mask for a valid slot of the
    query tile, pairs of the grid), every (slot, key) looked at."""
    t = np.arange(T)
    live = grid = 0
    for first in range(0, T, Tq):
        slots = t[first:first + Tq]
        slots = slots[slots < valid]
        for lo in range(0, prefix_positions, stage):
            grid += 1
            live += bool(len(slots)) and lo < cached
        for lo in range(0, T, own):
            grid += 1
            keys = np.arange(lo, lo + own)
            live += bool(((keys[None] <= slots[:, None])
                          & (keys[None] < valid)).any())
    return live, grid


@pytest.mark.parametrize("T, heads, q_rows, own_tile, chunk_blocks", [
    (256, 64, 1024, 512, 32),      # a round of sessions-20k
    (2048, 64, 1024, 512, 32),     # a history's chunk
    (2048, 32, 1024, 512, 32),
    (64, 4, 16, 16, 2),
    (32, 4, 1024, 512, 32),        # one tile, one own stage
])
def test_count_tiles_matches_brute_force(T, heads, q_rows, own_tile,
                                         chunk_blocks):
    prefix_blocks = 128
    Tq, own = la.prefill_tiling(T, heads, q_rows, own_tile)
    stage = min(chunk_blocks, prefix_blocks) * BS
    for cached in (0, 1, stage - 1, stage, stage + 1, 3 * stage + 5,
                   prefix_blocks * BS):
        for valid in sorted({0, 1, Tq - 1, Tq, Tq + 1, T // 2 + 3, T - 1, T}):
            assert la.count_tiles(
                T, cached, valid, num_heads=heads,
                prefix_blocks=prefix_blocks, block_size=BS, q_rows=q_rows,
                chunk_blocks=chunk_blocks, own_tile=own_tile,
            ) == _brute_force_tile_pairs(
                T, Tq, own, stage, prefix_blocks * BS, cached, valid,
            ), (cached, valid)


def test_a_round_of_sessions_20k_skips_a_third_and_the_dead_stages():
    """176 new tokens in a 256-slot program of 64 heads behind 24,000 cached
    positions of a 32,768-position table: 11 of 16 query tiles are live,
    each against 47 of 64 prefix stages and the one own stage."""
    assert la.count_tiles(
        256, 24000, 176, num_heads=64, prefix_blocks=2048, block_size=16,
    ) == (11 * (47 + 1), 16 * (64 + 1))


@pytest.mark.parametrize("preset, rule", [
    ("tiny-sarvam", "latent"), ("tiny-xing", "latent"),
    ("tiny-llama", "flash"), ("tiny-solar", "flash"),
    ("tiny-jamba", "flash"), ("tiny-laguna", "flash"),
])
def test_the_engine_counts_tiles_by_the_modules_own_rule(
        preset, rule, monkeypatch):
    """``LLMEngine._count_kv_tiles`` takes the latent prefill kernel's rule
    for a module that names it (``prefill_attn_tiles``) and the flash
    prefill kernel's for every other, a layer of each kind; the flash
    kernel's pages where it is the path taken, none where the dense form
    runs, as here off a TPU."""
    import types

    from production_stack_tpu.engine.config import config_from_preset
    from production_stack_tpu.engine.core.engine import LLMEngine
    from production_stack_tpu.engine.ops.pallas import flash_prefill as fp

    eng = LLMEngine(config_from_preset(preset, **{
        "cache.num_blocks": 64, "scheduler.max_num_seqs": 2,
        "scheduler.prefill_buckets": (32, 64),
        "scheduler.mixed_batch": False}))
    cfg = eng.config.model
    bmax = eng.config.scheduler.max_model_len // BS
    plan = types.SimpleNamespace(bucket_len=64, cached_len=48,
                                 num_new_tokens=20)
    if rule == "flash":
        assert eng._count_kv_tiles([plan], 64)[2] == 0
        # tiny models' heads are no 128 lanes: say the kernel serves.
        monkeypatch.setattr(
            LLMEngine, "_flash_prefill_serves", lambda self, kind, T: True)
    before = dict(eng.prefill_attn_tiles)
    got = eng._count_kv_tiles([plan], 64)
    if rule == "latent":
        want = la.count_tiles(64, 48, 20, num_heads=cfg.num_heads,
                              prefix_blocks=bmax, block_size=BS)
        assert hasattr(eng.model, "prefill_attn_tiles")
    else:
        assert not hasattr(eng.model, "prefill_attn_tiles")
        want = (0, 0, 0)
        for _label, window, layers, in_slots in eng._attn_kinds:
            # A window layer's buffer in a slot: a pool of one window page.
            n_live, n, pages = fp.count_kv_tiles(
                64, *((1, window) if in_slots else (bmax, BS)),
                min(48, window) if in_slots else 48, 20, window)
            want = (want[0] + n_live, want[1] + n, want[2] + pages * layers)
        assert got[2] > 0
    assert got == (*want, 0)[:3] and 0 < got[0] < got[1]
    assert eng.prefill_attn_tiles == {
        "live": before["live"] + got[0],
        "skipped": before["skipped"] + got[1] - got[0]}
    eng.close()
