"""Pallas paged-decode-attention kernel vs the pure-JAX gather reference.

Runs the kernel in Pallas interpret mode on the CPU test mesh; the same
compiled path is exercised on real TPU by tools/paged_decode_microbench.py
and by the engine on TPU backends (ops/attention.py:decode_attention dispatch).
"""

import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from production_stack_tpu.engine.ops.attention import paged_decode_attention
from production_stack_tpu.engine.ops.pallas.paged_attention import (
    paged_decode_attention_pallas,
)


def _random_paged_case(
    seed, S, H, K, D, bs, num_blocks, max_blocks, ctx_lens, dtype=jnp.float32
):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((S, H, D)), dtype)
    k_cache = jnp.asarray(rng.standard_normal((num_blocks, bs, K, D)), dtype)
    v_cache = jnp.asarray(rng.standard_normal((num_blocks, bs, K, D)), dtype)
    tables = np.zeros((S, max_blocks), np.int32)
    next_free = 1  # block 0 is the null block
    for s, ctx in enumerate(ctx_lens):
        nb = -(-ctx // bs)
        tables[s, :nb] = np.arange(next_free, next_free + nb)
        next_free += nb
    assert next_free <= num_blocks
    return q, k_cache, v_cache, jnp.asarray(tables), jnp.asarray(ctx_lens, jnp.int32)


# Reading a stage that was never waited for shows as NaN (buffers start
# as NaN and a DMA lands at its wait) instead of as the right numbers the
# plain interpreter's copy-at-start leaves there.
TPU_INTERPRET = pltpu.InterpretParams()


def _live(ctx):
    # Padded slots: kernel emits zeros, gather emits garbage-but-finite;
    # compare only live rows.
    return np.asarray(ctx) > 0


@pytest.mark.parametrize(
    "ctx_lens",
    [
        [1, 16, 17, 33],  # block-boundary edges
        [64, 3, 0, 0],  # padded slots (ctx 0) must not poison anything
        [40, 40, 40, 40],
        # Several stages a row (a stage is 4 blocks = 64 tokens here), with
        # the next row's first stage fetched under this row's last one:
        # padded rows between and before live ones, a padded last row, a
        # single live row, none at all.
        [0, 300, 0, 129],
        [257, 0, 0, 1],
        [300, 65, 128, 0],
        [0, 0, 200, 0],
        [0, 0, 0, 0],
    ],
)
@pytest.mark.parametrize("interpret", [True, TPU_INTERPRET],
                         ids=["interpret", "tpu-interpret"])
def test_pallas_decode_matches_gather(ctx_lens, interpret):
    S, H, K, D, bs = 4, 8, 2, 64, 16
    q, k_cache, v_cache, tables, ctx = _random_paged_case(
        0, S, H, K, D, bs, num_blocks=64, max_blocks=24, ctx_lens=ctx_lens
    )
    scale = D**-0.5
    want = paged_decode_attention(
        q, k_cache, v_cache, tables, ctx, scale=scale
    )
    got = paged_decode_attention_pallas(
        q, k_cache, v_cache, tables, ctx, scale=scale, chunk_blocks=4,
        interpret=interpret,
    )
    live = _live(ctx)
    np.testing.assert_allclose(
        np.asarray(got)[live], np.asarray(want)[live], rtol=2e-5, atol=2e-5
    )
    assert np.all(np.isfinite(np.asarray(got)))


@pytest.mark.parametrize(
    "ctx_lens,window",
    [
        ([1, 16, 17, 33], None),
        ([0, 300, 0, 129], None),
        ([257, 0, 0, 1], 100),
        ([300, 65, 128, 0], 70),
    ],
)
def test_pallas_decode_bf16_cache_matches_gather(ctx_lens, window):
    """A bf16 cache goes to the MXU as it is stored, and so do the queries
    and the probabilities (both paths round ``p`` to bf16 before the
    second dot); scores and accumulators are fp32 on both.  What differs
    is where ``p`` is rounded -- the gather path rounds ``exp(s - max) /
    sum`` over the whole context, the kernel ``exp(s - running max)`` a
    stage at a time and divides at the end -- and then each rounds its
    output to bf16, so they agree to an ulp of that output: 2^-8 relative.
    The tolerance is twice that, 2^-7 relative plus 2^-7 absolute (outputs
    are 0.1-3 here; six seeds read at most half of it), an order under
    what a wrong mask or a stale stage reads (0.1-1)."""
    S, H, K, D, bs = 4, 8, 2, 64, 16
    q, k_cache, v_cache, tables, ctx = _random_paged_case(
        2, S, H, K, D, bs, num_blocks=64, max_blocks=24, ctx_lens=ctx_lens,
        dtype=jnp.bfloat16,
    )
    scale = D**-0.5
    want = paged_decode_attention(
        q, k_cache, v_cache, tables, ctx, scale=scale, sliding_window=window
    )
    got = paged_decode_attention_pallas(
        q, k_cache, v_cache, tables, ctx, scale=scale, sliding_window=window,
        chunk_blocks=4, interpret=True,
    )
    assert got.dtype == jnp.bfloat16
    live = _live(ctx)
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live], np.asarray(want, np.float32)[live],
        rtol=2**-7, atol=2**-7,
    )
    assert np.all(np.isfinite(np.asarray(got, np.float32)))


@pytest.mark.parametrize(
    "ctx_lens,window,chunk_blocks",
    [
        ([50, 23], 16, 16),  # the window inside one stage
        # A window shorter than the context across more than two stages
        # (2 blocks = 16 tokens a stage): whole stages behind the window
        # are all mask, and the running max must recover from them.
        ([60, 23], 16, 2),
        ([64, 33], 20, 2),
        ([0, 61], 7, 2),
    ],
)
def test_pallas_decode_sliding_window(ctx_lens, window, chunk_blocks):
    S, H, K, D, bs = 2, 4, 2, 32, 8
    q, k_cache, v_cache, tables, ctx = _random_paged_case(
        1, S, H, K, D, bs, num_blocks=32, max_blocks=8, ctx_lens=ctx_lens
    )
    scale = D**-0.5
    want = paged_decode_attention(
        q, k_cache, v_cache, tables, ctx, scale=scale, sliding_window=window
    )
    got = paged_decode_attention_pallas(
        q, k_cache, v_cache, tables, ctx, scale=scale, sliding_window=window,
        chunk_blocks=chunk_blocks, interpret=True,
    )
    live = _live(ctx)
    np.testing.assert_allclose(
        np.asarray(got)[live], np.asarray(want)[live], rtol=2e-5, atol=2e-5
    )


def test_pallas_decode_gqa_head_mapping():
    """Head h=k*G+g must read kv head k: make kv heads wildly different."""
    S, H, K, D, bs = 1, 4, 2, 32, 8
    q = jnp.ones((S, H, D), jnp.float32)
    k_cache = jnp.zeros((8, bs, K, D), jnp.float32)
    v_cache = jnp.zeros((8, bs, K, D), jnp.float32)
    # kv head 0 values = 1.0, kv head 1 values = -1.0
    v_cache = v_cache.at[1, :, 0, :].set(1.0).at[1, :, 1, :].set(-1.0)
    tables = jnp.asarray([[1, 0]], jnp.int32)
    ctx = jnp.asarray([8], jnp.int32)
    out = paged_decode_attention_pallas(
        q, k_cache, v_cache, tables, ctx, scale=1.0, interpret=True
    )
    out = np.asarray(out)
    np.testing.assert_allclose(out[0, 0], 1.0, atol=1e-6)  # g heads of kv 0
    np.testing.assert_allclose(out[0, 1], 1.0, atol=1e-6)
    np.testing.assert_allclose(out[0, 2], -1.0, atol=1e-6)  # kv head 1
    np.testing.assert_allclose(out[0, 3], -1.0, atol=1e-6)


# -- flash prefill kernel ---------------------------------------------------

from production_stack_tpu.engine.ops.attention import prefill_attention
from production_stack_tpu.engine.ops.pallas import flash_prefill as fp
from production_stack_tpu.engine.ops.pallas.flash_prefill import (
    flash_prefill_attention,
)


def _prefill_case(seed, T, H, K, D, C, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((T, H, D)), dtype)
    k_new = jnp.asarray(rng.standard_normal((T, K, D)), dtype)
    v_new = jnp.asarray(rng.standard_normal((T, K, D)), dtype)
    k_prefix = jnp.asarray(rng.standard_normal((C, K, D)), dtype)
    v_prefix = jnp.asarray(rng.standard_normal((C, K, D)), dtype)
    return q, k_new, v_new, k_prefix, v_prefix


@pytest.mark.parametrize(
    "T,H,K,D,C,cached,valid,window",
    [
        (64, 4, 2, 32, 0, 0, 64, None),      # no prefix, full tile
        (64, 4, 2, 32, 32, 20, 50, None),    # prefix hit + padded tail
        (128, 8, 8, 32, 0, 0, 128, None),    # MHA (G=1)
        (64, 6, 2, 32, 16, 16, 64, None),    # G=3 (llama-3.2-3b shape)
        (64, 4, 2, 32, 32, 32, 64, 24),      # sliding window
        (512, 4, 2, 32, 64, 48, 500, None),  # multi q-tile + multi kv-tile
        # The served pattern, small: the engine gathers max_model_len
        # prefix slots whatever cached_len is, and pads T to a bucket.
        (64, 4, 2, 32, 512, 0, 64, None),     # C >> T, nothing cached
        (64, 4, 2, 32, 256, 100, 64, None),   # cached_len inside a kv tile
        (64, 4, 2, 32, 256, 128, 64, None),   # cached_len on a tile edge
        (64, 4, 2, 32, 256, 256, 40, None),   # cached_len == C
        (128, 4, 2, 32, 128, 70, 10, None),   # valid_len < Tq: a dead q tile
        (128, 4, 2, 32, 256, 0, 128, None),   # valid_len == T, dead prefix
        (128, 4, 2, 32, 200, 150, 100, None),  # C not a multiple of kv_tile
        (64, 4, 2, 32, 256, 256, 64, 100),    # window cuts inside the prefix
        (128, 4, 2, 32, 320, 200, 128, 150),  # window + dead prefix tiles
        (128, 4, 2, 32, 64, 64, 128, 40),     # window cuts inside new keys
        (64, 4, 2, 32, 128, 64, 0, None),     # valid_len 0: nothing live
    ],
)
def test_flash_prefill_matches_dense(T, H, K, D, C, cached, valid, window):
    q, k_new, v_new, k_prefix, v_prefix = _prefill_case(3, T, H, K, D, C)
    scale = D**-0.5
    cached_len = jnp.int32(cached)
    valid_len = jnp.int32(valid)
    want = prefill_attention(
        q, k_new, v_new, k_prefix, v_prefix, cached_len, valid_len,
        scale=scale, sliding_window=window,
    )
    got = flash_prefill_attention(
        q, k_new, v_new, k_prefix, v_prefix, cached_len, valid_len,
        scale=scale, sliding_window=window,
        q_tile=64, kv_tile=64, interpret=True,
    )
    # Rows past valid_len are padding garbage on both paths; compare live.
    live = np.arange(T) < valid
    np.testing.assert_allclose(
        np.asarray(got)[live], np.asarray(want)[live], rtol=2e-5, atol=2e-5
    )
    assert np.all(np.isfinite(np.asarray(got)))


def _brute_force_live_tiles(T, C, cached, valid, window, Tq, Tk):
    """[query tiles, kv tiles] bool: does any score of the tile survive
    the dense path's mask (ops/attention.py: prefill_attention) on a row
    below valid_len?  Rows past it are padding nobody reads."""
    key_pos = np.concatenate([np.arange(C), cached + np.arange(T)])
    key_valid = np.concatenate([np.arange(C) < cached, np.arange(T) < valid])
    q_pos = cached + np.arange(T)
    mask = (key_pos[None, :] <= q_pos[:, None]) & key_valid[None, :]
    if window is not None:
        mask &= key_pos[None, :] > q_pos[:, None] - window
    mask &= (np.arange(T) < valid)[:, None]
    nkv = -(-(C + T) // Tk)
    mask = np.pad(mask, [(0, 0), (0, nkv * Tk - (C + T))])
    return mask.reshape(T // Tq, Tq, nkv, Tk).any(axis=(1, 3))


@pytest.mark.parametrize(
    "T,C,q_tile,kv_tile",
    [
        (64, 0, 16, 32),      # the encode lane: no prefix
        (64, 128, 16, 32),    # C a multiple of the kv tile
        (64, 100, 16, 32),    # a tile straddles prefix and new keys
        (32, 96, 32, 48),     # one query tile
        (256, 8192, 128, 512),   # the served buckets behind the 8,192
        (2048, 8192, 128, 512),  # gathered prefix slots
    ],
)
def test_liveness_rule_matches_the_mask_tile_by_tile(T, C, q_tile, kv_tile):
    """The one rule behind the compute fence, the kv index map and the
    host's counter, against brute force over (cached_len, valid_len,
    window); and the index map fetches each live tile once and nothing
    else."""
    Tq, Tk, NQ, NKV = fp._tiling(T, C, q_tile, kv_tile)
    i, j = np.arange(NQ)[:, None], np.arange(NKV)[None, :]
    small = C + T <= 1024
    for cached in sorted({0, 1, C // 3, Tk, C - 1, C} & set(range(C + 1))):
        for valid in sorted({0, 1, Tq - 1, Tq, T // 2 + 3, T}):
            for window in (None, 1, Tq + 5, C // 2 + 7, 4096):
                if window == 4096 and small:
                    continue
                kw = dict(Tq=Tq, Tk=Tk, C=C, sliding_window=window, xp=np)
                what = f"cached={cached} valid={valid} window={window}"
                want = _brute_force_live_tiles(
                    T, C, cached, valid, window, Tq, Tk)
                got = fp._tile_is_live(
                    j, fp.live_kv_tiles(i, cached, valid, **kw))
                np.testing.assert_array_equal(got, want, err_msg=what)
                assert fp.count_kv_tiles(
                    T, C, cached, valid, window,
                    q_tile=q_tile, kv_tile=kv_tile,
                ) == (want.sum(), NQ * NKV), what
                # A live step holds its own tile, a dead step of a live
                # query tile one of that query tile's live tiles, a dead
                # query tile whatever the step before it held: walking
                # the grid in order fetches no more blocks than are live.
                idx = fp.kv_block_index(i, j, cached, valid, **kw)
                np.testing.assert_array_equal(
                    idx[want], np.broadcast_to(j, idx.shape)[want], what)
                assert idx.min() >= 0 and idx.max() < NKV, what
                q_live = want.any(axis=1)
                assert np.take_along_axis(want, idx, 1)[q_live].all(), what
                flat = idx.ravel()
                moved = np.flatnonzero(flat[1:] != flat[:-1]) + 1
                assert q_live[moved // NKV].all(), what
                assert len(moved) + 1 <= max(want.sum(), 1), what


def test_flight_record_counts_the_tiles_the_kernel_visits():
    """``kv_tiles_live`` / ``kv_tiles_grid`` on a prefill's flight record
    are the rule's count for that plan, at the kernel's own tile sizes,
    and /metrics' counter is their sum."""
    from production_stack_tpu.engine.config import config_from_preset
    from production_stack_tpu.engine.core.engine import LLMEngine
    from production_stack_tpu.engine.core.sequence import SamplingParams

    config = config_from_preset(
        "tiny-llama",
        **{"cache.num_blocks": 64, "scheduler.max_num_seqs": 2,
           "scheduler.prefill_buckets": (16, 32),
           "scheduler.mixed_batch": False},
    )
    eng = LLMEngine(config)
    shared = list(range(3, 35))  # two whole 16-token blocks
    for rid, ids in (("a", shared + [40, 41, 42]), ("b", shared + [50])):
        eng.add_request(rid, prompt_token_ids=ids,
                        sampling_params=SamplingParams(
                            max_tokens=2, ignore_eos=True))
        while eng.has_unfinished():
            eng.step()
    C = config.scheduler.max_model_len
    window = config.model.sliding_window
    prefills = [w for w in eng.obs.windows_payload()["windows"]
                if w.get("bucket_tokens")]
    assert {w["cached_tokens"] for w in prefills} >= {0, 32}
    live = grid = 0
    for w in prefills:
        T = w["bucket_tokens"]
        Tq, Tk, NQ, NKV = fp._tiling(T, C, fp.Q_TILE, fp.KV_TILE)
        want = _brute_force_live_tiles(
            T, C, w["cached_tokens"], w["new_tokens"], window, Tq, Tk)
        assert (w["kv_tiles_live"], w["kv_tiles_grid"]) == (
            want.sum(), NQ * NKV)
        assert 0 < w["kv_tiles_live"] < w["kv_tiles_grid"]
        live += w["kv_tiles_live"]
        grid += w["kv_tiles_grid"]
    assert eng.stats()["prefill_attn_tiles"] == {
        "live": live, "skipped": grid - live}


def test_flash_prefill_causality():
    """Future tokens must not leak: perturbing token t+1 cannot change
    output row t."""
    T, H, K, D = 64, 4, 2, 32
    q, k_new, v_new, k_prefix, v_prefix = _prefill_case(5, T, H, K, D, 0)
    scale = D**-0.5
    base = flash_prefill_attention(
        q, k_new, v_new, k_prefix, v_prefix, jnp.int32(0), jnp.int32(T),
        scale=scale, q_tile=32, kv_tile=32, interpret=True,
    )
    k_mut = k_new.at[40].add(100.0)
    v_mut = v_new.at[40].add(100.0)
    mut = flash_prefill_attention(
        q, k_mut, v_mut, k_prefix, v_prefix, jnp.int32(0), jnp.int32(T),
        scale=scale, q_tile=32, kv_tile=32, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(mut)[:40], np.asarray(base)[:40], rtol=1e-6, atol=1e-6
    )
    assert not np.allclose(np.asarray(mut)[40:], np.asarray(base)[40:])
