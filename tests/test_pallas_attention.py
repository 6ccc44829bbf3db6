"""Pallas paged-decode-attention kernel vs the pure-JAX gather reference.

Runs the kernel in Pallas interpret mode on the CPU test mesh; the same
compiled path is exercised on real TPU by tools/paged_decode_microbench.py
and by the engine on TPU backends (ops/attention.py:decode_attention dispatch).
"""

import jax
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
# 1: every page a DMA of its own, a stage of 4 blocks as the cases were
# written for; None: what the page's 8 kB give (4 pages a DMA, a stage of
# 16 blocks), the tables being ascending neighbours.
@pytest.mark.parametrize("group_blocks", [1, None], ids=["single", "grouped"])
def test_pallas_decode_matches_gather(ctx_lens, interpret, group_blocks):
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
        group_blocks=group_blocks, interpret=interpret,
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
    # Every page a DMA of its own: the stages are the cases' (a 2 kB page
    # would otherwise go 16 to a DMA and the whole table be one stage).
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
        chunk_blocks=chunk_blocks, group_blocks=1, interpret=True,
    )
    live = _live(ctx)
    np.testing.assert_allclose(
        np.asarray(got)[live], np.asarray(want)[live], rtol=2e-5, atol=2e-5
    )
    # And with the groups the page gives: one stage, the same numbers.
    grouped = paged_decode_attention_pallas(
        q, k_cache, v_cache, tables, ctx, scale=scale, sliding_window=window,
        chunk_blocks=chunk_blocks, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(grouped)[live], np.asarray(want)[live],
        rtol=2e-5, atol=2e-5,
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


# -- groups of pages in one DMA (one key head: a 4 kB page) -------------------

from production_stack_tpu.engine.ops.pallas.paged_attention import (
    blocks_per_descriptor,
    whole_groups,
)

# jamba2-3b's attention layers: 20 query heads over ONE key head of 128,
# 16-token pages of 4 kB, eight to a descriptor.  Small stages (2 groups =
# 16 blocks = 256 positions) so that rows span several.
MQ = dict(H=20, K=1, D=128, bs=16)
MQ_R, MQ_CHUNK = 8, 2
MQ_POOL = 1200


def _runs(rng, nb, lo, hi, free):
    """``nb`` table entries as runs of ascending neighbours, each run's
    length drawn from [lo, hi] and its place among the ``free`` ids."""
    out = []
    while len(out) < nb:
        n = min(int(rng.integers(lo, hi + 1)), nb - len(out))
        while True:
            b = int(rng.integers(1, MQ_POOL - n + 1))
            if all(i in free for i in range(b, b + n)):
                break
        free.difference_update(range(b, b + n))
        out.extend(range(b, b + n))
    return out


def _layout_tables(layout):
    """(tables [S, Bmax], ctx [S]): one way a row's blocks can lie in the
    pool.  A stage is 16 table entries, a group 8."""
    rng = np.random.default_rng(7)
    bs, N = MQ["bs"], MQ_POOL
    blocks = lambda tokens: -(-tokens // bs)
    ctx = [700, 0, 333, 1000]                   # 44, 0, 21, 63 blocks
    if layout == "adjacent":
        # Ascending neighbours, as the pool hands them out.
        ctx = [700, 512, 333, 1000]
        rows = [range(1, 45), range(50, 82), range(100, 121),
                range(200, 263)]
    elif layout == "padded_row":
        # The same with a padded row between live ones.
        rows = [range(1, 45), [], range(100, 121), range(200, 263)]
    elif layout == "descending":
        rows = [range(44, 0, -1), [], range(120, 99, -1), range(262, 199, -1)]
    elif layout == "permuted":
        ids = rng.permutation(np.arange(1, N)).tolist()
        rows = [ids[:44], [], ids[44:65], ids[65:128]]
    elif layout == "mixed":
        ctx = [6000, 0, 4100]                   # runs of 1-200 blocks
        free = set(range(1, N))
        rows = [_runs(rng, blocks(c), 1, 200, free) for c in ctx]
    elif layout == "offset":
        # A 62-block prefix every row shares, then the row's own run: the
        # group of entries 56..63 straddles the two.
        ctx = [70 * bs + 5, 0, 62 * bs + 9, 100 * bs]
        shared = list(range(900, 962))
        rows = [shared + list(range(1, 10)), [],
                shared + [300], shared + list(range(400, 438))]
    elif layout == "cross_stage":
        # Ten blocks from anywhere, then one run over entries 10..: it
        # starts inside group 1 and crosses the stage boundary at entry 16.
        ctx = [30 * bs, 0, 0, 40 * bs - 7]
        head = rng.permutation(np.arange(600, 700)).tolist()
        rows = [head[:10] + list(range(50, 70)), [], [],
                head[10:20] + list(range(150, 180))]
    elif layout == "ctx_in_group":
        # The table is allocated to the end of the group the context ends
        # in: the merged copy brings pages past the context, which the mask
        # drops.  Contexts end on a group's last block, its first, inside.
        ctx = [21 * bs + 3, 24 * bs, 16 * bs + 1, 9]
        rows = [range(1, 25), range(100, 124), range(200, 224),
                range(300, 308)]
    elif layout == "pool_end":
        # The pool's last region as one group (ids N-8 .. N-1), and a run
        # that ends on the last block in the middle of a group (its group
        # would need id N to be whole).
        ctx = [24 * bs - 5, 0, 20 * bs, 0]
        rows = [list(range(1, 17)) + list(range(N - 8, N)), [],
                list(range(500, 516)) + list(range(N - 4, N)), []]
    else:
        raise ValueError(layout)
    bmax = -(-max(blocks(c) for c in ctx) // 16) * 16 + 16
    tables = np.zeros((len(ctx), bmax), np.int32)
    for s, ids in enumerate(rows):
        ids = list(ids)
        assert len(ids) >= blocks(ctx[s]), (layout, s)
        tables[s, :len(ids)] = ids
    return tables, np.asarray(ctx, np.int32)


@pytest.mark.parametrize(
    "layout",
    ["adjacent", "descending", "permuted", "mixed", "offset", "cross_stage",
     "ctx_in_group", "padded_row", "pool_end"],
)
def test_grouped_walk_is_the_single_page_walk_bit_for_bit(layout):
    """However a row's blocks lie in the pool, the walk that fetches whole
    groups in one DMA gives the bits of the walk that fetches every page
    alone (``group_blocks=1``, the same stage), and both agree with the
    gather path."""
    H, K, D, bs = MQ["H"], MQ["K"], MQ["D"], MQ["bs"]
    tables, ctx = _layout_tables(layout)
    assert tables.max() < MQ_POOL
    rng = np.random.default_rng(11)
    S = len(ctx)
    q = jnp.asarray(rng.standard_normal((S, H, D)), jnp.bfloat16)
    k_cache = jnp.asarray(
        rng.standard_normal((MQ_POOL, bs, K, D)), jnp.bfloat16)
    v_cache = jnp.asarray(
        rng.standard_normal((MQ_POOL, bs, K, D)), jnp.bfloat16)
    args = (q, k_cache, v_cache, jnp.asarray(tables), jnp.asarray(ctx))
    scale = D ** -0.5
    assert blocks_per_descriptor(bs * K * D * 2) == MQ_R
    whole = np.asarray(whole_groups(tables, MQ_R, xp=np))
    live = np.arange(whole.shape[1]) < -(-ctx // (bs * MQ_R))[:, None]
    share = (whole & live).sum() / max(live.sum(), 1)
    # Each layout takes the path it is here for.
    if layout in ("adjacent", "padded_row", "ctx_in_group"):
        assert share > 0.8
    elif layout in ("descending", "permuted"):
        assert share == 0
    else:
        assert 0 < share < 1

    got = paged_decode_attention_pallas(
        *args, scale=scale, chunk_blocks=MQ_CHUNK, interpret=TPU_INTERPRET)
    single = paged_decode_attention_pallas(
        *args, scale=scale, chunk_blocks=MQ_CHUNK * MQ_R, group_blocks=1,
        interpret=TPU_INTERPRET)
    np.testing.assert_array_equal(
        np.asarray(got, np.float32), np.asarray(single, np.float32))
    want = paged_decode_attention(*args, scale=scale)
    rows = _live(ctx)
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[rows], np.asarray(want, np.float32)[rows],
        rtol=2**-7, atol=2**-7,
    )
    assert np.all(np.isfinite(np.asarray(got, np.float32)))


def test_whole_groups_is_the_rule_entry_by_entry():
    rng = np.random.default_rng(3)
    R = 4
    tables = rng.integers(0, 40, (6, 24)).astype(np.int32)
    tables[0, 4:8] = [9, 10, 11, 12]          # whole
    tables[1, 0:4] = [0, 1, 2, 3]             # starts at the null block
    tables[2, 8:12] = [5, 6, 7, 0]            # its last entry not allocated
    tables[3, 12:16] = [20, 21, 23, 24]       # a gap
    tables[4, 16:20] = [33, 32, 31, 30]       # descending
    want = np.zeros((6, 6), bool)
    for s in range(6):
        for g in range(6):
            t = tables[s, g * R:(g + 1) * R]
            want[s, g] = all(t[i] == t[0] + i for i in range(R)) and t[-1] != 0
    assert want[0, 1] and want[1, 0] and not want[2, 2]
    assert not want[3, 3] and not want[4, 4]
    np.testing.assert_array_equal(whole_groups(tables, R, xp=np), want)
    np.testing.assert_array_equal(
        np.asarray(whole_groups(jnp.asarray(tables), R)), want)


@pytest.mark.parametrize(
    "bs,K,D,itemsize,quantized,want",
    [
        (16, 1, 128, 2, False, 8),    # jamba2-3b: 4 kB a page
        (16, 2, 128, 2, False, 4),    # mistral-7b under tp=4: 8 kB
        (16, 8, 128, 2, False, 1),    # mistral-7b, solar: 32 kB
        (16, 8, 128, 4, False, 1),    # a page over 32 kB
        (16, 1, 128, 1, True, 1),     # an int8 (data, scale) cache
    ],
)
def test_blocks_per_descriptor(bs, K, D, itemsize, quantized, want):
    assert blocks_per_descriptor(bs * K * D * itemsize, quantized) == want


def test_a_32kb_page_lowers_to_the_single_page_walk():
    """At eight key heads a page is a descriptor of its own: the kernel
    takes the block table and the contexts as its only scalar operands (no
    flags are computed or passed) and a stage is 16 blocks -- the program
    mistral-7b and solar ran before groups existed."""
    import jax

    H, K, D, bs = 32, 8, 128, 16
    cache = jax.ShapeDtypeStruct((64, bs, K, D), jnp.bfloat16)

    def call(K):
        cache = jax.ShapeDtypeStruct((64, bs, K, D), jnp.bfloat16)
        return jax.make_jaxpr(
            lambda q, k, v, bt, cl: paged_decode_attention_pallas(
                q, k, v, bt, cl, scale=D ** -0.5, interpret=True)
        )(jax.ShapeDtypeStruct((4, H, D), jnp.bfloat16), cache, cache,
          jax.ShapeDtypeStruct((4, 64), jnp.int32),
          jax.ShapeDtypeStruct((4,), jnp.int32))

    def pallas_eqn(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                return eqn, jaxpr
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found = pallas_eqn(sub)
                if found:
                    return found

    eqn, holder = pallas_eqn(call(8).jaxpr)
    assert len(eqn.invars) == 5                 # tables, contexts, q, k, v
    assert [e.primitive.name for e in holder.eqns] == ["pallas_call"]
    k_buf = eqn.params["grid_mapping"].scratch_avals[0] if hasattr(
        eqn.params["grid_mapping"], "scratch_avals") else None
    if k_buf is not None:
        assert k_buf.shape[:2] == (2, 16)
    grouped, _ = pallas_eqn(call(1).jaxpr)
    assert len(grouped.invars) == 7             # + groups' and stages' flags


def test_the_engine_counts_groups_by_the_kernels_rule():
    """``kv_groups`` / ``kv_groups_coalesced`` on a decode window's flight
    record and /metrics' two counters: the groups the rows' tables hold and
    those that ``whole_groups`` calls one region, counted from the batch's
    numpy tables where a descriptor carries more than a page -- and zero
    where it carries one (every CPU engine: the kernel does not serve)."""
    from production_stack_tpu.engine.config import config_from_preset
    from production_stack_tpu.engine.core.engine import LLMEngine
    from production_stack_tpu.engine.core.sequence import SamplingParams

    config = config_from_preset(
        "tiny-llama",
        **{"cache.num_blocks": 64, "scheduler.max_num_seqs": 2,
           "scheduler.prefill_buckets": (16, 32, 64)},
    )
    eng = LLMEngine(config)
    assert eng._kv_group_blocks == 1 and eng.block_pool.run == 1
    assert eng.stats()["paged_decode_groups"] == {"total": 0, "coalesced": 0}
    # As a TPU engine of a model with a small page would be set: 2 pages a
    # descriptor (the pool's hand-out is already ascending).
    eng._kv_group_blocks = 2

    def run(rid, n):
        eng.add_request(rid, prompt_token_ids=list(range(3, 3 + n)),
                        sampling_params=SamplingParams(
                            max_tokens=3, ignore_eos=True))
        while eng.has_unfinished():
            eng.step()

    run("a", 40)        # 3 blocks: one whole group and a half
    records = [w for w in eng.obs.windows_payload()["windows"]
               if w.get("kv_groups")]
    assert records
    for w in records:
        assert 0 < w["kv_groups_coalesced"] <= w["kv_groups"]
        assert w["kv_groups"] == -(-w["kv_tokens"] // (16 * 2))
    got = eng.stats()["paged_decode_groups"]
    assert got["total"] >= sum(w["kv_groups"] for w in records)
    assert 0 < got["coalesced"] < got["total"]

    # The rule itself, on a batch by hand: row 0 two whole groups and a
    # half one, row 1 a broken group, row 2 padding.
    tables = np.zeros((3, 8), np.int32)
    tables[0, :5] = [4, 5, 6, 7, 9]
    tables[1, :2] = [11, 10]
    before = dict(eng.paged_decode_groups)
    eng._count_kv_groups(tables, np.asarray([16 * 4 + 3, 20, 0], np.int32))
    assert eng._last_kv_groups == (3 + 1, 2)
    assert eng.paged_decode_groups == {
        "total": before["total"] + 4, "coalesced": before["coalesced"] + 2}


# -- flash prefill kernel ---------------------------------------------------

from production_stack_tpu.engine.ops.attention import (
    dense_prefill_attention,
    gather_prefix_kv,
)
from production_stack_tpu.engine.ops.pallas import flash_prefill as fp
from production_stack_tpu.engine.ops.pallas.flash_prefill import (
    flash_prefill_attention,
)


def _prefill_case(seed, T, H, K, D, P, bs=16, dtype=jnp.float32):
    """Queries, the chunk's keys and values, K and V pools of ``2 P + 1``
    pages of ``bs`` positions, and a table of ``P`` of them in no order
    (never the null block 0, which holds NaNs here: nothing may read it)."""
    rng = np.random.default_rng(seed)
    N = 2 * P + 1
    q = jnp.asarray(rng.standard_normal((T, H, D)), dtype)
    k_new = jnp.asarray(rng.standard_normal((T, K, D)), dtype)
    v_new = jnp.asarray(rng.standard_normal((T, K, D)), dtype)
    k_pool = rng.standard_normal((N, bs, K, D))
    v_pool = rng.standard_normal((N, bs, K, D))
    k_pool[0] = v_pool[0] = np.nan
    ids = jnp.asarray(rng.permutation(N - 1)[:P] + 1, jnp.int32)
    return (q, k_new, v_new, jnp.asarray(k_pool, dtype),
            jnp.asarray(v_pool, dtype), ids)


def _dense(q, k_new, v_new, k_pool, v_pool, ids, cached, valid, **kw):
    """ops/attention.py's dense statement over the gathered table."""
    k_prefix, v_prefix = gather_prefix_kv(k_pool, v_pool, ids)
    return dense_prefill_attention(
        q, k_new, v_new, k_prefix, v_prefix, jnp.int32(cached),
        jnp.int32(valid), **kw)


# bs 16 and kv tiles of 64: a prefix tile is four pages.
@pytest.mark.parametrize(
    "T,H,K,D,P,cached,valid,window",
    [
        (64, 4, 2, 32, 0, 0, 64, None),      # an empty table (the encode lane)
        (64, 4, 2, 32, 2, 20, 50, None),     # prefix hit + padded tail
        (128, 8, 8, 32, 0, 0, 128, None),    # MHA (G=1)
        (64, 6, 2, 32, 1, 16, 64, None),     # G=3 (llama-3.2-3b shape)
        (64, 4, 2, 32, 2, 32, 64, 24),       # sliding window
        (512, 4, 2, 32, 4, 48, 500, None),   # multi q-tile + multi kv-tile
        # The served pattern, small: the engine hands max_model_len of
        # block ids whatever cached_len is, and pads T to a bucket.
        (64, 4, 2, 32, 32, 0, 64, None),      # P*bs >> T, nothing cached
        (64, 4, 2, 32, 16, 100, 64, None),    # cached_len inside a kv tile
        (64, 4, 2, 32, 16, 128, 64, None),    # cached_len on a tile edge
        (64, 4, 2, 32, 16, 256, 40, None),    # cached_len == P*bs
        (128, 4, 2, 32, 8, 70, 10, None),     # valid_len < Tq: a dead q tile
        (128, 4, 2, 32, 16, 0, 128, None),    # valid_len == T, dead prefix
        (128, 4, 2, 32, 13, 150, 100, None),  # P no multiple of a tile's pages
        (64, 4, 2, 32, 16, 256, 64, 100),     # window cuts inside the prefix
        (128, 4, 2, 32, 20, 200, 128, 150),   # window + dead prefix tiles
        (128, 4, 2, 32, 4, 64, 128, 40),      # window cuts inside new keys
        (64, 4, 2, 32, 8, 64, 0, None),       # valid_len 0: nothing live
        # cached_len: one position, a block less one, one tile, several
        # tiles with a ragged end.
        (64, 4, 2, 32, 16, 1, 64, None),
        (64, 4, 2, 32, 16, 15, 64, None),
        (64, 4, 2, 32, 16, 64, 50, None),
        (128, 4, 2, 32, 16, 203, 100, None),
        # a window longer than the prefix, and shorter than a block
        (128, 4, 2, 32, 16, 203, 100, 4096),
        (128, 4, 2, 32, 16, 203, 100, 7),
        # query heads a key head: 4, 6 (laguna), 20 over one (jamba)
        (64, 16, 4, 32, 8, 100, 60, None),
        (64, 12, 2, 32, 8, 100, 60, None),
        (64, 20, 1, 32, 8, 100, 60, None),
        (128, 20, 1, 32, 16, 250, 128, 90),
    ],
)
def test_flash_prefill_matches_dense(T, H, K, D, P, cached, valid, window):
    """The kernel walking the pool's pages through a table in no order,
    against the dense statement over the gathered copy."""
    case = _prefill_case(3, T, H, K, D, P)
    kw = dict(scale=D**-0.5, sliding_window=window)
    want = _dense(*case, cached, valid, **kw)
    got = flash_prefill_attention(
        *case, jnp.int32(cached), jnp.int32(valid), **kw,
        q_tile=64, kv_tile=64, interpret=True,
    )
    # Rows past valid_len are padding garbage on both paths; compare live.
    live = np.arange(T) < valid
    np.testing.assert_allclose(
        np.asarray(got)[live], np.asarray(want)[live], rtol=2e-5, atol=2e-5
    )
    assert np.all(np.isfinite(np.asarray(got)))


@pytest.mark.parametrize("cached,valid", [(512, 200), (300, 256), (0, 256)])
def test_flash_prefill_takes_a_pool_of_one_window_page(cached, valid):
    """``models/laguna.py``'s window layers: the slot's 512-row rolling
    buffer, oldest first, is a pool of one 512-token page with the table
    [0], under a window of as many positions."""
    T, H, K, D, W = 256, 8, 2, 32, 512
    rng = np.random.default_rng(11)
    q, k_new, v_new = (jnp.asarray(rng.standard_normal((T, h, D)), jnp.float32)
                       for h in (H, K, K))
    k_buf, v_buf = (jnp.asarray(rng.standard_normal((W, K, D)), jnp.float32)
                    for _ in range(2))
    kw = dict(scale=D**-0.5, sliding_window=W)
    want = dense_prefill_attention(
        q, k_new, v_new, k_buf, v_buf, jnp.int32(cached), jnp.int32(valid),
        **kw)
    got = flash_prefill_attention(
        q, k_new, v_new, k_buf[None], v_buf[None], jnp.zeros((1,), jnp.int32),
        jnp.int32(cached), jnp.int32(valid), **kw, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got)[:valid], np.asarray(want)[:valid], rtol=2e-5,
        atol=2e-5)
    assert fp._tiling(T, 1, W, fp.Q_TILE, fp.KV_TILE) == (128, 1, 256, 1, 1)


def test_prefill_attention_gathers_where_the_kernel_cannot_serve(monkeypatch):
    """``ops/attention.py: prefill_attention`` picks by what it sees at trace
    time: on a single TPU device the kernel, over the pools as they lie or,
    for a quantized (data, scale) cache, over the dequantized copy of the
    prefix as a pool of its own; the dense statement over the gathered
    prefix under a mesh and off a TPU -- and they agree."""
    import production_stack_tpu.engine.ops.attention as attn_ops
    from production_stack_tpu.engine.kv import quant as kv_quant

    T, H, K, D, P = 64, 4, 2, 128, 8
    q, k_new, v_new, k_pool, v_pool, ids = _prefill_case(7, T, H, K, D, P)
    # A served pool's null block holds finite rows (padded slots' writes).
    k_pool, v_pool = k_pool.at[0].set(0.0), v_pool.at[0].set(0.0)
    quantized = tuple(kv_quant.quantize_vectors(pool)
                      for pool in (k_pool, v_pool))
    args = (ids, jnp.int32(70), jnp.int32(50))
    kw = dict(scale=D**-0.5, sliding_window=40)
    calls = []

    def kernel(*a, **k):
        calls.append(a)
        return flash_prefill_attention(
            *a, **k, q_tile=32, kv_tile=32, interpret=True)

    def close(got, want):
        np.testing.assert_allclose(
            np.asarray(got)[:50], np.asarray(want)[:50], rtol=2e-5, atol=2e-5)

    monkeypatch.setattr(fp, "flash_prefill_attention", kernel)
    dense = attn_ops.prefill_attention(q, k_new, v_new, k_pool, v_pool, *args, **kw)
    dense_q = attn_ops.prefill_attention(q, k_new, v_new, *quantized, *args, **kw)
    assert not calls  # off a TPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    close(attn_ops.prefill_attention(
        q, k_new, v_new, k_pool, v_pool, *args, **kw), dense)
    assert len(calls) == 1 and calls[0][3] is k_pool  # the pool as it lies
    # The quantized cache's prefix: gathered and dequantized, its P * bs
    # positions one page of a pool of its own, in order.
    close(attn_ops.prefill_attention(
        q, k_new, v_new, *quantized, *args, **kw), dense_q)
    assert len(calls) == 2 and calls[1][3].shape == (1, P * 16, K, D)
    np.testing.assert_array_equal(calls[1][5], [0])
    # No cache behind an empty table (the encode lane).
    none = (None, None, ids[:0], jnp.int32(0), jnp.int32(50))
    close(attn_ops.prefill_attention(q, k_new, v_new, *none, **kw),
          dense_prefill_attention(
              q, k_new, v_new, k_new[:0], v_new[:0], *none[3:], **kw))
    assert len(calls) == 3
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]), ("tp",))
    attn_ops.prefill_attention(
        q, k_new, v_new, k_pool, v_pool, *args, **kw, mesh=mesh)
    assert len(calls) == 3


def _brute_force_live_tiles(T, P, bs, cached, valid, window, Tq, Tp, Tn):
    """[query tiles, grid steps] bool: does any score of the tile survive
    the dense path's mask (ops/attention.py: dense_prefill_attention) on a
    row below valid_len?  Rows past it are padding nobody reads.  The grid's
    steps: the table's positions in tiles of ``Tp``, then the chunk's keys in
    tiles of ``Tn``."""
    C = P * bs
    key_pos = np.concatenate([np.arange(C), cached + np.arange(T)])
    key_valid = np.concatenate([np.arange(C) < cached, np.arange(T) < valid])
    q_pos = cached + np.arange(T)
    mask = (key_pos[None, :] <= q_pos[:, None]) & key_valid[None, :]
    if window is not None:
        mask &= key_pos[None, :] > q_pos[:, None] - window
    mask &= (np.arange(T) < valid)[:, None]
    NP = -(-C // Tp) if C else 0
    prefix = np.pad(mask[:, :C], [(0, 0), (0, NP * Tp - C)])
    return np.concatenate([
        prefix.reshape(T // Tq, Tq, NP, Tp).any(axis=(1, 3)),
        mask[:, C:].reshape(T // Tq, Tq, T // Tn, Tn).any(axis=(1, 3)),
    ], axis=1)


@pytest.mark.parametrize(
    "T,P,bs,q_tile,kv_tile",
    [
        (64, 0, 16, 16, 32),      # the encode lane: no prefix
        (64, 8, 16, 16, 32),      # the table a whole number of tiles
        (64, 7, 16, 16, 32),      # the last prefix tile holds one page
        (32, 12, 8, 32, 48),      # one query tile
        (256, 512, 16, 128, 512),   # the served buckets behind the 8,192
        (2048, 512, 16, 128, 512),  # positions of a block table
        (64, 1, 24, 16, 32),      # a pool of one page (a window's buffer)
    ],
)
def test_liveness_rule_matches_the_mask_tile_by_tile(T, P, bs, q_tile, kv_tile):
    """The one rule behind the compute fence, the walk's lookahead, the new
    keys' index map and the host's counters, against brute force over
    (cached_len, valid_len, window); and the index map fetches each live
    new-key tile once and nothing else."""
    Tq, Cp, Tn, NP, NN = fp._tiling(T, P, bs, q_tile, kv_tile)
    Tp, NQ, C = Cp * bs, T // Tq, P * bs
    i, j = np.arange(NQ)[:, None], np.arange(NP + NN)[None, :]
    small = C + T <= 1024
    for cached in sorted({0, 1, bs - 1, C // 3, Tp, C - 1, C} & set(range(C + 1))):
        for valid in sorted({0, 1, Tq - 1, Tq, T // 2 + 3, T}):
            for window in (None, 1, Tq + 5, C // 2 + 7, 4096):
                if window == 4096 and small:
                    continue
                kw = dict(Tq=Tq, Tp=Tp, Tn=Tn, NP=NP, sliding_window=window,
                          xp=np)
                what = f"cached={cached} valid={valid} window={window}"
                want = _brute_force_live_tiles(
                    T, P, bs, cached, valid, window, Tq, Tp, Tn)
                got = fp._tile_is_live(
                    j, fp.live_kv_tiles(i, cached, valid, **kw))
                np.testing.assert_array_equal(got, want, err_msg=what)
                # kv_tiles_live, kv_tiles_grid and prefix_pages: every page
                # of every live prefix tile is fetched.
                assert fp.count_kv_tiles(
                    T, P, bs, cached, valid, window,
                    q_tile=q_tile, kv_tile=kv_tile,
                ) == (want.sum(), NQ * (NP + NN), want[:, :NP].sum() * Cp), what
                # A live new-key step holds its own tile, any other step of
                # a live query tile one of that query tile's live new-key
                # tiles, a dead query tile whatever the step before it held:
                # walking the grid in order fetches no more blocks than are
                # live.
                idx = fp.new_block_index(i, j, cached, valid, **kw)
                new = want[:, NP:]
                np.testing.assert_array_equal(
                    idx[:, NP:][new],
                    np.broadcast_to(j[:, NP:] - NP, new.shape)[new], what)
                assert idx.min() >= 0 and idx.max() < NN, what
                q_live = want.any(axis=1)
                assert np.take_along_axis(new, idx, 1)[q_live].all(), what
                flat = idx.ravel()
                moved = np.flatnonzero(flat[1:] != flat[:-1]) + 1
                assert q_live[moved // (NP + NN)].all(), what
                assert len(moved) + 1 <= max(new.sum(), 1), what


@pytest.mark.parametrize("preset, kind, bucket", [
    ("mistral-7b", "window", 256), ("mistral-7b", "window", 2048),
    ("jamba2-3b", "full", 256), ("laguna-xs.2-ep2", "full", 256),
    ("laguna-xs.2-ep2", "window", 2048),
])
def test_engine_counts_pages_where_the_kernel_is_the_path(
        monkeypatch, preset, kind, bucket):
    """``LLMEngine._flash_prefill_serves`` is ``prefill_attention``'s own
    selector, by the heads of the layer's kind: a single TPU device, and
    neither a mesh nor another backend."""
    import types

    from production_stack_tpu.engine.config import PRESETS
    from production_stack_tpu.engine.core.engine import LLMEngine

    boot = types.SimpleNamespace(
        config=types.SimpleNamespace(model=PRESETS[preset]),
        mesh=types.SimpleNamespace(size=1))
    assert not LLMEngine._flash_prefill_serves(boot, kind, bucket)  # a CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert LLMEngine._flash_prefill_serves(boot, kind, bucket)
    boot.mesh.size = 4
    assert not LLMEngine._flash_prefill_serves(boot, kind, bucket)


def test_flight_record_counts_the_tiles_the_kernel_visits(monkeypatch):
    """``kv_tiles_live`` / ``kv_tiles_grid`` / ``prefix_pages`` on a
    prefill's flight record are the rule's count for that plan, at the
    kernel's own tile sizes over the plan's block table, and /metrics'
    counter is the tiles' sum.  Pages are counted where the kernel is the
    path taken (steered here, as a test steers code that asks JAX for its
    backend), and none where the dense form ran."""
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

    def serve(rid, ids):
        eng.add_request(rid, prompt_token_ids=ids,
                        sampling_params=SamplingParams(
                            max_tokens=2, ignore_eos=True))
        while eng.has_unfinished():
            eng.step()

    served = lambda: [  # noqa: E731
        w for w in eng.obs.windows_payload()["windows"][::-1]  # oldest first
        if w.get("bucket_tokens")]
    serve("dense", shared + [60, 61])
    serve("dense-behind", shared + [62])
    assert {w["cached_tokens"] for w in served()} >= {0, 32}
    assert not any(w["prefix_pages"] for w in served())  # off a TPU
    dense = len(served())
    monkeypatch.setattr(
        LLMEngine, "_flash_prefill_serves", lambda self, kind, T: True)
    for rid, ids in (("a", shared[:16] + [40, 41, 42] + shared[16:]),
                     ("b", shared[:16] + [40, 41, 42] + shared[16:] + [50])):
        serve(rid, ids)
    bs = config.cache.block_size
    P = config.scheduler.max_model_len // bs
    window = config.model.sliding_window
    prefills = served()
    assert {w["cached_tokens"] for w in prefills[dense:]} >= {16, 32}
    live = grid = 0
    for n, w in enumerate(prefills):
        T = w["bucket_tokens"]
        Tq, Cp, Tn, NP, NN = fp._tiling(T, P, bs, fp.Q_TILE, fp.KV_TILE)
        want = _brute_force_live_tiles(
            T, P, bs, w["cached_tokens"], w["new_tokens"], window, Tq,
            Cp * bs, Tn)
        assert (w["kv_tiles_live"], w["kv_tiles_grid"]) == (
            want.sum(), (T // Tq) * (NP + NN))
        assert 0 < w["kv_tiles_live"] < w["kv_tiles_grid"]
        # Every page of a live prefix tile, in each of the model's layers.
        assert w["prefix_pages"] == (n >= dense) * (
            want[:, :NP].sum() * Cp * config.model.num_layers)
        assert (w["prefix_pages"] > 0) == (
            n >= dense and w["cached_tokens"] > 0)
        live += w["kv_tiles_live"]
        grid += w["kv_tiles_grid"]
    assert eng.stats()["prefill_attn_tiles"] == {
        "live": live, "skipped": grid - live}


def test_flash_prefill_causality():
    """Future tokens must not leak: perturbing token t+1 cannot change
    output row t."""
    T, H, K, D = 64, 4, 2, 32
    q, k_new, v_new, k_pool, v_pool, ids = _prefill_case(5, T, H, K, D, 0)
    scale = D**-0.5
    base = flash_prefill_attention(
        q, k_new, v_new, k_pool, v_pool, ids, jnp.int32(0), jnp.int32(T),
        scale=scale, q_tile=32, kv_tile=32, interpret=True,
    )
    k_mut = k_new.at[40].add(100.0)
    v_mut = v_new.at[40].add(100.0)
    mut = flash_prefill_attention(
        q, k_mut, v_mut, k_pool, v_pool, ids, jnp.int32(0), jnp.int32(T),
        scale=scale, q_tile=32, kv_tile=32, interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(mut)[:40], np.asarray(base)[:40], rtol=1e-6, atol=1e-6
    )
    assert not np.allclose(np.asarray(mut)[40:], np.asarray(base)[40:])
