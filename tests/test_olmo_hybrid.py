"""``models/olmo_hybrid.py``: gated delta-rule layers with a decay a head (a
state that is not square, a head count 16 does not divide) beside a multi-head
softmax layer whose pages keep more key heads than the model has, in blocks
that norm what a sub-layer returns; against ``bench/reference/olmo_hybrid.py``
(the recurrence token by token), through its own caches, through the engine
with both pools, and the kernels of ``ops/pallas/kda.py``, ``paged_attention.py``
and ``flash_prefill.py`` in interpret mode.  CPU, the ``tiny-olmo`` preset
(gdn, gdn, gdn, full), seeded weights."""

import asyncio
import dataclasses
import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import PRESETS, config_from_preset
from production_stack_tpu.engine.core.engine import LLMEngine
from production_stack_tpu.engine.core.sequence import SamplingParams
from production_stack_tpu.engine.models import (
    get_model, llama, olmo_hybrid as olmo, solar_kda,
)
from production_stack_tpu.engine.ops import attention as attn_ops
from production_stack_tpu.engine.ops.pallas import kda
from production_stack_tpu.engine.ops.pallas.flash_prefill import (
    flash_prefill_attention,
)
from production_stack_tpu.engine.ops.pallas.paged_attention import (
    paged_decode_attention_pallas,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS = 16   # tokens a cache block
STATE_LAYERS = (0, 1, 2)


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_olmo_hybrid",
        os.path.join(ROOT, "bench", "reference", "olmo_hybrid.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _cfg(**changes):
    return dataclasses.replace(
        PRESETS["tiny-olmo"], **{"dtype": "float32", **changes})


def _hp(cfg):
    """The reference's view of ``cfg``: the configuration file's keys."""
    return dict(
        hidden_size=cfg.hidden_size, num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        rms_norm_eps=cfg.rms_norm_eps,
        layer_types=["full_attention" if k == "full" else "linear_attention"
                     for k in solar_kda._kinds(cfg)],
        linear_num_key_heads=cfg.linear_num_heads,
        linear_num_value_heads=cfg.linear_num_heads,
        linear_key_head_dim=cfg.linear_head_dim,
        linear_value_head_dim=cfg.linear_value_head_dim,
        linear_conv_kernel_dim=cfg.linear_conv_kernel,
        linear_allow_neg_eigval=cfg.kda_allow_neg_eigval)


@functools.lru_cache(maxsize=None)
def _jitted(step, dtype, keywords):
    """``olmo.prefill`` / ``olmo.decode`` of the tiny preset, jitted once a
    set of keyword arguments (``return_stats`` is static; the slots trace)."""
    cfg = _cfg(dtype=dtype)
    static = {"return_stats": True} if "return_stats" in keywords else {}
    return jax.jit(lambda params, *args, **slots: getattr(olmo, step)(
        params, cfg, *args, **slots, **static))


def _call(step, cfg, params, *args, **more):
    slots = {k: v for k, v in more.items() if k != "return_stats"}
    return _jitted(step, cfg.dtype, tuple(sorted(more)))(
        params, *args, **slots)


def _prefill(cfg, params, cache, tokens, start, n, T, blocks, **more):
    """Chunk ``tokens[start:start + n]`` in a ``T``-slot program."""
    slots = np.zeros(T, np.int32)
    slots[:n] = tokens[start:start + n]
    prefix = np.zeros(64, np.int32)
    prefix[:start // BS] = blocks[:start // BS]
    new = np.zeros(T // BS, np.int32)
    held = -(-n // BS)
    new[:held] = blocks[start // BS:start // BS + held]
    return _call(
        "prefill", cfg, params, jnp.asarray(slots), jnp.int32(start),
        jnp.asarray(prefix), jnp.asarray(new), jnp.int32(n), cache, **more)


def _decode(cfg, params, cache, token, pos, blocks, **more):
    """One live row at ``pos`` beside one padding row."""
    tables = np.zeros((2, 64), np.int32)
    tables[0, :len(blocks)] = blocks
    return _call(
        "decode", cfg, params, jnp.asarray([token, 0]), jnp.asarray([pos, 0]),
        jnp.asarray(tables), jnp.asarray([pos + 1, 0]),
        jnp.asarray([blocks[pos // BS], 0]), jnp.asarray([pos % BS, 0]),
        cache, **more)


def _case(seed=0, n=150, slots=None, **changes):
    cfg = _cfg(**changes)
    params = olmo.init_params(cfg, jax.random.PRNGKey(seed))
    tokens = np.random.default_rng(seed).integers(
        1, cfg.vocab_size, n).astype(np.int32)
    blocks = np.arange(1, 1 + -(-n // BS), dtype=np.int32)
    return cfg, params, tokens, blocks, olmo.init_cache(
        cfg, 64, BS, state_slots=slots)


def _err(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want)
    return np.max(np.abs(got - want)) / np.max(np.abs(want))


def _close(got, want, tol=2e-5):
    assert _err(got, want) <= tol, _err(got, want)


def test_the_registry_serves_the_preset_and_the_shared_pieces_are_imported():
    assert get_model(PRESETS["olmo-hybrid-7b-stage"].name) is olmo
    assert get_model(PRESETS["tiny-olmo"].name) is olmo
    for name in ("_gqa_prefill", "_gqa_decode", "default_slot", "layer_kind",
                 "rows_pool_shape", "kda_chunk_plain", "kda_step_plain"):
        assert getattr(olmo, name) is getattr(solar_kda, name)
    assert olmo.llama is llama
    assert "return_choice" not in olmo.prefill.__code__.co_varnames


def test_the_served_preset_is_one_whole_period_at_the_published_widths():
    """The issue's counts: 88.7 M and 59.0 M in the two mixes, 126.8 M in an
    MLP, 15,360 B a position needed and 16,384 held (32 key heads for 30),
    2,211,840 B of state a layer needed and 2,949,120 held (256 lanes for
    192), 69,120 B of convolution rows."""
    cfg = PRESETS["olmo-hybrid-7b-stage"]
    assert solar_kda._kinds(cfg) == ["gdn", "gdn", "gdn", "full"]
    count = lambda i: sum(
        int(np.prod(s)) for s in olmo._shapes(cfg, i).values())
    mlp = 3 * 3840 * 11008
    assert count(0) - mlp - 2 * 3840 == 88_750_332
    assert count(3) - mlp - 2 * 3840 == 58_990_080
    total = sum(count(i) for i in range(4)) + 2 * 100352 * 3840 + 3840
    assert total == 1_603_227_636
    assert olmo.page_heads(cfg) == 32
    assert olmo.cache_bytes_per_token(cfg) == 2 * 32 * 128 * 2 == 16_384
    assert olmo.state_bytes_per_slot(cfg) == 3 * (
        30 * 96 * 256 * 4 + 3 * 11_520 * 2) == 3 * (2_949_120 + 69_120)
    shapes = jax.eval_shape(lambda: olmo.init_cache(cfg, 8, BS, state_slots=3))
    assert shapes[0][0].shape == (3, 30, 96, 192)
    assert shapes[0][0].dtype == jnp.float32
    assert shapes[0][1].shape == (3, 270, 128)
    assert shapes[3][0].shape == (8, BS, 32, 128)
    # Eight key heads or fewer are kept as they are.
    assert olmo.page_heads(dataclasses.replace(
        cfg, num_heads=32, num_kv_heads=8)) == 8
    assert olmo.page_heads(_cfg()) == 16


def test_prefill_in_two_chunks_then_decode_matches_the_reference(tol=2e-5):
    """The chunkwise form over a carried state and cached keys, the one-step
    decode through both caches, against one full forward token by token, at
    float32 activations (bfloat16 ones are the chip compare's, and the CPU
    rehearsal's in ``bench/tests/test_olmo.py``)."""
    cfg, params, tokens, blocks, cache = _case(0, n=132)
    want = np.asarray(ref.forward(params, _hp(cfg), jnp.asarray(tokens)))
    _, cache = _prefill(cfg, params, cache, tokens, 0, 64, 64, blocks)
    logits, cache, stats = _prefill(
        cfg, params, cache, tokens, 64, 56, 64, blocks, return_stats=True)
    _close(logits, want[119], tol)
    absmax, beta_max = np.asarray(stats) / 1e3
    assert 0.5 < beta_max <= 2.0 and 0.01 < absmax < 100.0
    for pos in range(120, 132):      # crosses a block boundary at 128
        logits, cache = _decode(cfg, params, cache, tokens[pos], pos, blocks)
        _close(logits[0], want[pos], tol)


def test_return_stats_leaves_the_logits_bit_equal_and_reads_the_state():
    cfg, params, tokens, blocks, cache = _case(1)
    plain, a = _prefill(cfg, params, cache, tokens, 0, 120, 128, blocks)
    counted, b, stats = _prefill(
        cfg, params, olmo.init_cache(cfg, 64, BS), tokens, 0, 120, 128,
        blocks, return_stats=True)
    np.testing.assert_array_equal(plain, counted)
    assert olmo.stats_names(cfg) == olmo.GDN_STATS == olmo.STATS_MAX
    slot = int(olmo.default_slot(cfg, blocks[0], b))
    largest = max(float(jnp.abs(b[i][0][slot]).max()) for i in STATE_LAYERS)
    assert int(stats[0]) == int(np.float32(largest) * np.float32(1e3))
    one, _ = _decode(cfg, params, a, tokens[120], 120, blocks)
    two, after, stats = _decode(cfg, params, b, tokens[120], 120, blocks,
                                return_stats=True)
    np.testing.assert_array_equal(one, two)
    largest = max(float(jnp.abs(after[i][0][slot]).max())
                  for i in STATE_LAYERS)
    assert int(stats[0]) == int(np.float32(largest) * np.float32(1e3))
    assert 0 < int(stats[1]) <= 2000


@pytest.mark.parametrize("boundary", [64, 192])
def test_a_run_resumed_from_a_snapshot_equals_the_uninterrupted_run(boundary):
    """The first prompt leaves a snapshot ``boundary`` tokens in; a second
    sequence with the same first ``boundary`` tokens starts from it, over the
    first one's pages, and equals its own uninterrupted prefill."""
    cfg, params, tokens, blocks, cache = _case(3, n=250, slots=6)
    assert boundary % olmo.snapshot_stride(cfg) == 0
    slot = lambda *v: {k: jnp.int32(x) for k, x in zip(
        ("state_slot", "state_from", "snapshot_slot", "snapshot_len"), v)}
    _, cache = _prefill(cfg, params, cache, tokens, 0, 250, 256, blocks,
                        **slot(1, -1, 4, boundary))
    other = tokens.copy()
    other[boundary:] = np.random.default_rng(9).integers(
        1, cfg.vocab_size, 250 - boundary)
    mine = np.concatenate([blocks[:boundary // BS], np.arange(
        30, 30 + len(blocks) - boundary // BS, dtype=np.int32)])
    resumed, cache = _prefill(cfg, params, cache, other, boundary,
                              250 - boundary, 256, mine, **slot(2, 4, 2, 0))
    whole, cache = _prefill(cfg, params, cache, other, 0, 250, 256,
                            np.arange(40, 56, dtype=np.int32),
                            **slot(3, -1, 3, 0))
    _close(resumed, whole, 1e-5)
    _close(resumed, ref.forward(params, _hp(cfg), jnp.asarray(other))[249])
    for i in STATE_LAYERS:
        _close(cache[i][0][2], cache[i][0][3], 1e-5)   # the two live states
        _close(cache[i][1][2], cache[i][1][3], 1e-5)   # and their conv rows


def test_padding_and_dead_rows_leave_state_and_conv_rows_bit_equal():
    cfg, params, tokens, blocks, cache = _case(4, n=100, slots=4)
    one = dict(state_slot=jnp.int32(1), state_from=jnp.int32(-1))
    _, cache = _prefill(cfg, params, cache, tokens, 0, 40, 64, blocks, **one)
    # The same 40 tokens in a program of 128 slots: 88 padded slots.
    _, padded = _prefill(cfg, params, olmo.init_cache(
        cfg, 64, BS, state_slots=4), tokens, 0, 40, 128, blocks, **one)
    for i in STATE_LAYERS:
        np.testing.assert_array_equal(cache[i][0][1], padded[i][0][1])
        np.testing.assert_array_equal(cache[i][1][1], padded[i][1][1])
    # A decode batch whose row is dead (its write parked on the null block).
    before = [(np.asarray(cache[i][0]), np.asarray(cache[i][1]))
              for i in STATE_LAYERS]
    tables = np.zeros((2, 64), np.int32)
    tables[0, :len(blocks)] = blocks
    _, after = _call(
        "decode", cfg, params, jnp.asarray([5, 0]), jnp.asarray([40, 0]),
        jnp.asarray(tables), jnp.asarray([41, 0]), jnp.asarray([0, 0]),
        jnp.asarray([8, 0]), cache, state_slots=jnp.asarray([1, 0]))
    for (s, c), i in zip(before, STATE_LAYERS):
        np.testing.assert_array_equal(s, after[i][0])
        np.testing.assert_array_equal(c, after[i][1])


def test_the_compares_default_addressing_equals_explicit_slots():
    """``bench/harness/compare.py`` hands the cache and nothing else: the
    slot is then the first block id of the row's table modulo the slots."""
    cfg, params, tokens, blocks, cache = _case(6, n=150)
    blocks = blocks + 4                      # first block 5: slot 5 % 4 = 1
    assert int(olmo.default_slot(cfg, blocks[0], cache)) == (
        5 % olmo.DEFAULT_STATE_SLOTS) == 1
    _, a = _prefill(cfg, params, cache, tokens, 0, 64, 64, blocks)
    la, a = _prefill(cfg, params, a, tokens, 64, 56, 64, blocks)
    da, a = _decode(cfg, params, a, tokens[120], 120, blocks)
    one = lambda start: dict(state_slot=jnp.int32(1),
                             state_from=jnp.int32(start))
    _, b = _prefill(cfg, params, olmo.init_cache(cfg, 64, BS), tokens,
                    0, 64, 64, blocks, **one(-1))
    lb, b = _prefill(cfg, params, b, tokens, 64, 56, 64, blocks, **one(1))
    db, b = _decode(cfg, params, b, tokens[120], 120, blocks,
                    state_slots=jnp.asarray([1, 0]))
    np.testing.assert_array_equal(la, lb)
    np.testing.assert_array_equal(da[0], db[0])


@pytest.mark.parametrize("attr, value, at_least", [
    ("FAULT", "beta_not_doubled", 1e-3), ("FAULT", "decay_a_channel", 1e-3),
    ("FAULT", "norm_before", 1e-3), ("STATE_DTYPE", jnp.bfloat16, 6e-5),
])
def test_a_planted_fault_fails(monkeypatch, attr, value, at_least):
    """The reference with one thing wrong (beta not doubled, the head's decay
    read a channel, the norm in front of a sub-layer) is no longer what the
    module computes; nor is one whose state is rounded to bfloat16 after every
    token, at float32 activations, against the 2e-5 every other test here
    holds."""
    cfg, params, tokens, good, want = _sound_case()
    assert _err(good, want) <= 2e-5
    monkeypatch.setattr(ref, attr, value)
    bad = ref.forward(params, _hp(cfg), jnp.asarray(tokens))[119]
    assert _err(good, bad) > at_least, value


@functools.lru_cache(maxsize=None)
def _sound_case():
    """One prefill of 120 tokens and the sound reference's row for it: what
    each planted fault is held against."""
    with jax.default_matmul_precision("highest"):
        cfg, params, tokens, blocks, cache = _case(2, n=120)
        good, _ = _prefill(cfg, params, cache, tokens, 0, 120, 128, blocks)
        want = ref.forward(params, _hp(cfg), jnp.asarray(tokens))[119]
    return cfg, params, tokens, good, want


def test_a_state_not_carried_over_a_chunk_boundary_fails():
    cfg, params, tokens, blocks, cache = _case(2, n=120)
    want = _sound_case()[4]
    _, cache = _prefill(cfg, params, cache, tokens, 0, 64, 64, blocks)
    fresh, _ = _prefill(cfg, params, cache, tokens, 64, 56, 64, blocks,
                        state_slot=jnp.int32(1), state_from=jnp.int32(-1))
    assert _err(fresh, want) > 1e-3


# -- the kernels, interpreted ------------------------------------------------


def _rule_inputs(T, H, Dk, Dv, scalar, seed=0, live=None):
    """Normalised keys and queries, a log decay a head (``scalar``) or a
    channel, beta up to 2; dead tokens are the identity."""
    r = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(r.standard_normal(s), jnp.float32)
    q, k, v = f(T, H, Dk), f(T, H, Dk), f(T, H, Dv)
    q, k = solar_kda._l2(q) * Dk ** -0.5, solar_kda._l2(k)
    g = -jnp.abs(f(T, H) if scalar else f(T, H, Dk)) * 0.8
    beta = 2 * jax.nn.sigmoid(f(T, H))
    if live is not None:
        g = jnp.where(live.reshape((T,) + (1,) * (g.ndim - 1)), g, 0.0)
        beta = jnp.where(live[:, None], beta, 0.0)
    return q, k, v, g, beta


def _token_by_token(q, k, v, g, beta, s0, until):
    S, out = s0[None], []
    for t in range(until):
        o, S = solar_kda.kda_step_plain(
            q[t:t + 1], k[t:t + 1], v[t:t + 1], g[t:t + 1], beta[t:t + 1], S)
        out.append(o[0])
    return jnp.stack(out), S[0]


SHAPES = [(96, 192), (128, 128)]


@pytest.mark.parametrize("snapshot_len", [None, 64])
@pytest.mark.parametrize("Dk, Dv", SHAPES, ids=["96x192", "128x128"])
def test_the_scalar_decay_prefill_kernel_is_the_recurrence(
        Dk, Dv, snapshot_len):
    """``gdn_prefill_pallas`` (a decay a head) against the recurrence token by
    token and against the plain chunkwise form, a head count 16 does not
    divide, the last 40 tokens padding."""
    T, H = 128, 6
    live = jnp.arange(T) < 88
    q, k, v, g, beta = _rule_inputs(T, H, Dk, Dv, True, live=live)
    s0 = jnp.asarray(np.random.default_rng(1).standard_normal((H, Dk, Dv)),
                     jnp.float32) * 0.1
    o, s1, snap = kda.gdn_prefill_pallas(
        q, k, v, g, beta, s0, snapshot_len, interpret=True)
    want, state = _token_by_token(q, k, v, g, beta, s0, 88)
    _close(o[:88], want, 1e-5)
    _close(s1, state, 1e-5)
    po, ps, psnap = solar_kda.kda_chunk_plain(
        q, k, v, g, beta, s0, snapshot_len)
    _close(o, po, 1e-5)
    _close(s1, ps, 1e-5)
    if snapshot_len is None:
        assert snap is None and psnap is None
    else:
        _close(snap, _token_by_token(q, k, v, g, beta, s0, snapshot_len)[1],
               1e-5)
        _close(snap, psnap, 1e-5)


def test_the_channel_decay_prefill_kernel_keeps_its_square_state():
    """``kda_prefill_pallas`` is solar's: a decay a channel over a square
    state; the other cases are refused by name, not computed wrongly."""
    q, k, v, g, beta = _rule_inputs(64, 4, 128, 128, False)
    s0 = jnp.zeros((4, 128, 128), jnp.float32)
    o, s1, _ = kda.kda_prefill_pallas(q, k, v, g, beta, s0, interpret=True)
    want, state = _token_by_token(q, k, v, g, beta, s0, 64)
    _close(o, want, 1e-5)
    _close(s1, state, 1e-5)
    q, k, v, g, beta = _rule_inputs(64, 4, 96, 192, False)
    with pytest.raises(ValueError, match="gdn_prefill_pallas"):
        kda.kda_prefill_pallas(q, k, v, g, beta,
                               jnp.zeros((4, 96, 192)), interpret=True)
    with pytest.raises(ValueError, match="gdn_prefill_pallas"):
        kda.kda_prefill_pallas(q, k, v, g[..., 0], beta,
                               jnp.zeros((4, 96, 192)), interpret=True)


@pytest.mark.parametrize("scalar", [True, False], ids=["a-head", "a-channel"])
@pytest.mark.parametrize("Dk, Dv", SHAPES, ids=["96x192", "128x128"])
def test_the_decode_kernel_is_one_step_in_place(Dk, Dv, scalar):
    """``kda_decode_pallas`` at 30 heads (blocks of 15: every head computed),
    both decays, both states: named slots advance one token, a dead row and
    the slots nobody names keep their bits."""
    R, H = 4, 30
    live = jnp.asarray([True, True, False, True])
    q, k, v, g, beta = _rule_inputs(R, H, Dk, Dv, scalar, seed=3, live=live)
    pool = jnp.asarray(np.random.default_rng(2).standard_normal(
        (6, H, Dk, Dv)), jnp.float32)
    slots = jnp.asarray([4, 1, 3, 2], jnp.int32)
    o, absmax, after = kda.kda_decode_pallas(
        q, k, v, g, beta, pool, slots, absmax=True, interpret=True)
    want, rows = solar_kda.kda_step_plain(q, k, v, g, beta, pool[slots])
    _close(o, want, 1e-5)
    _close(after[slots], rows, 1e-5)
    assert not np.array_equal(after[4][29], pool[4][29])   # the last head too
    for untouched in (0, 3, 5):
        np.testing.assert_array_equal(after[untouched], pool[untouched])
    _close(absmax, jnp.max(jnp.abs(rows), axis=-2), 1e-6)
    plain, again = kda.kda_decode_pallas(
        q, k, v, g, beta, pool, slots, interpret=True)
    np.testing.assert_array_equal(plain, o)
    np.testing.assert_array_equal(again, after)


@pytest.mark.parametrize("heads, block", [
    (64, 16), (30, 15), (6, 6), (4, 4), (1, 1), (32, 16), (24, 12)])
def test_a_head_block_divides_the_heads(heads, block):
    assert kda.head_block(heads) == block and heads % block == 0


@pytest.mark.parametrize("heads", [17, 19, 23, 31])
def test_a_head_count_no_block_divides_is_an_error_not_a_floor(heads):
    """Before: ``H // min(16, H)`` blocks, the heads past the last whole block
    dropped without a word (30 heads: one block of 16, 14 dropped)."""
    with pytest.raises(ValueError, match=f"divides {heads} heads"):
        kda.head_block(heads)
    q, k, v, g, beta = _rule_inputs(2, heads, 8, 16, True)
    with pytest.raises(ValueError, match="no block"):
        kda.kda_decode_pallas(
            q, k, v, g, beta, jnp.zeros((3, heads, 8, 16)),
            jnp.asarray([1, 2], jnp.int32), interpret=True)


# -- the dense kernels at 30 key heads for 30 query heads --------------------


def _pages(seed, N, K, D):
    ks = jax.random.split(jax.random.PRNGKey(seed), 2)
    return tuple(jax.random.normal(k, (N, BS, K, D), jnp.bfloat16) for k in ks)


@pytest.mark.parametrize("K", [30, 32], ids=["30", "32-of-which-2-null"])
def test_the_paged_kernel_at_thirty_key_heads_is_the_gather_path(K):
    """``paged_decode_attention_pallas`` at G = 1, interpreted, against the
    gather path: at 30 heads as they are, and as the module serves them, 32 a
    page with two heads of zeros, whose first 30 outputs are the 30-head
    result."""
    S, D, N, bmax = 3, 128, 25, 8
    kc, vc = _pages(0, N, 30, D)
    q = jax.random.normal(jax.random.PRNGKey(2), (S, 30, D), jnp.bfloat16)
    tables = jnp.asarray(np.random.default_rng(0).permutation(
        np.arange(1, N))[:S * bmax].reshape(S, bmax), jnp.int32)
    ctx = jnp.asarray([100, 0, 37], jnp.int32)
    want = attn_ops.paged_decode_attention(
        q, kc, vc, tables, ctx, scale=D ** -0.5)
    pad = lambda a, axis: jnp.pad(
        a, [(0, K - 30) if i == axis else (0, 0) for i in range(a.ndim)])
    got = paged_decode_attention_pallas(
        pad(q, 1), pad(kc, 2), pad(vc, 2), tables, ctx, scale=D ** -0.5,
        interpret=True)
    live = np.asarray(ctx) > 0
    _close(np.asarray(got[:, :30], np.float32)[live],
           np.asarray(want, np.float32)[live], 2e-2)
    assert not np.asarray(got[:, 30:], np.float32).any()


def test_the_flash_kernel_at_thirty_key_heads_is_the_dense_path():
    """``flash_prefill_attention`` at G = 1 (its first), interpreted, behind a
    cached prefix it walks through the block table."""
    T, D, N = 128, 128, 24
    kc, vc = _pages(1, N, 30, D)
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    q, k, v = (jax.random.normal(key, (T, 30, D), jnp.bfloat16) for key in ks)
    ids = jnp.asarray([5, 9, 2, 17, 0, 0, 0, 0], jnp.int32)
    cached, valid = jnp.int32(60), jnp.int32(100)
    got = flash_prefill_attention(
        q, k, v, kc, vc, ids, cached, valid, scale=D ** -0.5,
        sliding_window=None, interpret=True)
    want = attn_ops.dense_prefill_attention(
        q, k, v, *attn_ops.gather_prefix_kv(kc, vc, ids), cached, valid,
        scale=D ** -0.5)
    _close(np.asarray(got, np.float32)[:100],
           np.asarray(want, np.float32)[:100], 2e-2)


# -- a slot is read and written where it lies ---------------------------------


@pytest.fixture
def kernels_serve(monkeypatch):
    """The module's TPU branch on the CPU: both kernels, interpreted."""
    monkeypatch.setattr(olmo, "use_pallas_gdn", lambda cfg: True)
    for name in ("gdn_prefill_pallas", "kda_decode_pallas"):
        monkeypatch.setattr(kda, name, functools.partial(
            getattr(kda, name), interpret=True))


def _layer_case(T, seed=0, slots=6):
    """One ``gdn`` layer of the tiny preset, a pool whose every slot holds
    something, a chunk's input."""
    cfg = _cfg()
    layer = olmo.init_params(cfg, jax.random.PRNGKey(seed))["layers"][0]
    ks = jax.random.split(jax.random.PRNGKey(seed + 7), 3)
    pools = (jax.random.normal(ks[0], (slots, *olmo._widths(cfg))) * 0.1,
             jax.random.normal(ks[1], solar_kda.rows_pool_shape(
                 slots, 3, olmo._conv_width(cfg))))
    return cfg, layer, pools, jax.random.normal(ks[2], (T, cfg.hidden_size))


def _others_bit_equal(before, after, written):
    for was, now in zip(before, after):
        for slot in set(range(was.shape[0])) - set(written):
            np.testing.assert_array_equal(was[slot], now[slot])


@pytest.mark.parametrize("path, T, valid, start, slot, snap_slot, snap_len", [
    ("kernel", 256, 200, -1, 1, None, None),   # from zeros (the compare's)
    ("kernel", 256, 200, -1, 1, 4, 64),        # ... a snapshot mid-chunk
    ("plain", 256, 200, -1, 1, 4, 64),
    ("kernel", 256, 256, 1, 1, 1, 0),          # a second chunk: its own slot
    ("kernel", 512, 300, 4, 2, 0, 0),          # resumed from a snapshot
], ids=["zeros", "zeros-snapshot", "zeros-snapshot-plain", "own-slot",
        "resumed"])
def test_a_prefill_writes_the_slots_it_names_and_nothing_else(
        path, request, T, valid, start, slot, snap_slot, snap_len):
    if path == "kernel":
        request.getfixturevalue("kernels_serve")
    cfg, layer, pools, x = _layer_case(T)
    W = olmo._conv_width(cfg)
    live = jnp.arange(T) < valid
    named = tuple(None if v is None else jnp.int32(v)
                  for v in (slot, start, snap_slot, snap_len))
    out, after, _ = jax.jit(lambda pools, x: olmo._gdn_prefill(
        layer, cfg, pools, x, live, jnp.int32(valid), named))(pools, x)
    _others_bit_equal(pools, after, {slot} | ({snap_slot} - {None}))
    u = olmo._dot(x, layer["qkv_proj"]).astype(x.dtype)
    head = jnp.zeros((3, W)) if start < 0 else pools[1][start].reshape(3, W)
    full = jnp.concatenate([head, u])
    np.testing.assert_array_equal(
        after[1][slot].reshape(-1), full[valid:valid + 3].reshape(-1))
    s0 = jnp.zeros_like(pools[0][0]) if start < 0 else pools[0][start]

    def stepped(n):
        """The state ``n`` tokens in, token by token over the same stream."""
        if n == 0:
            return s0
        mixed = jax.nn.silu(sum(
            full[j:j + n] * layer["conv"][j] for j in range(4)))
        return _token_by_token(
            *olmo._gdn_inputs(layer, cfg, x[:n], mixed, live[:n]), s0, n)[1]

    _close(after[0][slot], stepped(valid), 1e-5)
    if snap_slot not in (None, slot):
        np.testing.assert_array_equal(
            after[1][snap_slot].reshape(-1),
            full[snap_len:snap_len + 3].reshape(-1))
        _close(after[0][snap_slot], stepped(snap_len), 1e-5)


@pytest.mark.parametrize("path", ["kernel", "plain"])
def test_a_decode_step_leaves_dead_rows_and_unnamed_slots_alone(
        path, request):
    """Rows 0 and 1 live on slots 4 and 2; two padding rows share the null
    slot and a dead row names slot 3: of both pools only slots 4 and 2 move,
    and their rows shift by one."""
    if path == "kernel":
        request.getfixturevalue("kernels_serve")
    cfg, layer, pools, x = _layer_case(5, seed=2)
    W = olmo._conv_width(cfg)
    slots = jnp.asarray([4, 2, 0, 0, 3], jnp.int32)
    live = jnp.asarray([True, True, False, False, False])
    out, after, stats = jax.jit(lambda pools, x: olmo._gdn_decode(
        layer, cfg, pools, x, live, slots))(pools, x)
    _others_bit_equal(pools, after, {4, 2})
    u = olmo._dot(x, layer["qkv_proj"]).astype(x.dtype)
    for row, slot in ((0, 4), (1, 2)):
        was, now = (a[1][slot].reshape(3, W) for a in (pools, after))
        np.testing.assert_array_equal(now[:2], was[1:])
        np.testing.assert_array_equal(now[2], u[row])
        assert not np.array_equal(after[0][slot], pools[0][slot])
    assert out.shape == (5, cfg.linear_num_heads * cfg.linear_value_head_dim)
    largest = max(float(jnp.abs(after[0][s]).max()) for s in (4, 2))
    assert int(stats[0]) == int(np.float32(largest) * np.float32(1e3))


# -- the engine, both pools -------------------------------------------------


def _engine_config(**overrides):
    return config_from_preset("tiny-olmo", **{
        "model.dtype": "float32", "scheduler.prefill_buckets": (64, 128),
        "scheduler.max_num_seqs": 4, "scheduler.mixed_batch": False,
        **overrides})


def test_the_engine_serves_it_end_to_end():
    """Allocation of pages and slots in one tree, a resumed admission, the
    K=8 window with the rows' slots, the counters, the reference's tokens."""
    eng = LLMEngine(_engine_config())
    cfg = eng.config.model
    pool = eng.state_pool
    assert (pool.live_slots, pool.snapshot_slots, pool.num_slots) == (
        6, 10, 17)
    assert eng.kv_caches[3][0].shape == (
        eng.block_pool.num_blocks, BS, 16, cfg.head_dim)
    for i in STATE_LAYERS:
        state, conv = eng.kv_caches[i]
        assert state.shape == (17, 6, 8, 16) and state.dtype == jnp.float32
        assert conv.shape == (17, 9, 64)
    assert eng._state_bytes() == 17 * olmo.state_bytes_per_slot(cfg)
    rng = np.random.default_rng(0)
    shared = rng.integers(1, 260, 200).tolist()
    prompts = [shared + rng.integers(1, 260, n).tolist() for n in (30, 100)]
    got = {}
    for i, prompt in enumerate(prompts):
        eng.add_request(f"r{i}", prompt_token_ids=prompt,
                        sampling_params=SamplingParams(
                            max_tokens=12, temperature=0.0, ignore_eos=True))
        while eng.has_unfinished():
            for out in eng.step():
                got.setdefault(out.seq_id, []).append(out.new_token_id)
    hp = _hp(cfg)
    for i, prompt in enumerate(prompts):
        assert len(got[f"r{i}"]) == 12
        want = np.asarray(ref.forward(
            eng.params, hp, jnp.asarray(prompt + got[f"r{i}"], jnp.int32)))
        for step, token in enumerate(got[f"r{i}"]):
            logits = want[len(prompt) - 1 + step]
            assert logits.max() - logits[token] <= 1e-4 * np.abs(logits).max()
    stats = eng.stats()
    # r0 (230 tokens: chunks 128 + 102) left a snapshot at 128 + 64 = 192;
    # r1 matches 12 blocks = 192 tokens of keys and resumes exactly there.
    assert stats["prefix_cache_hit_tokens"] == 192
    assert (stats["state_resumes"], stats["state_resume_misses"]) == (1, 0)
    assert stats["state_recomputed_tokens"] == 0
    windows = eng.obs.windows_payload()["windows"]
    decodes = [w for w in windows if w["rows"]]
    assert decodes and all("window_fn" in w["programs"] for w in decodes)
    assert all(w["state_rows"] == w["rows"] for w in decodes)
    # The full layer's positions alone are counted as keys read.
    assert all(w["kv_tokens"] > 0 and not w.get("kv_tokens_slots")
               for w in decodes)
    assert stats["attn_positions"]["full"] > 0
    assert stats["attn_positions"]["window"] == 0
    prefills = sorted((w for w in windows if not w["rows"]),
                      key=lambda w: w["dispatched_at"])
    assert [w["state_resumed"] for w in prefills] == [False, False, True]
    # The module's counters: on every record, folded by maximum, on stats().
    assert all(0 < w["gdn_beta_max_e3"] <= 2000 for w in windows)
    assert all(0 < w["gdn_state_absmax_e3"] < 100_000 for w in windows)
    assert stats["gdn_beta_max"] == max(
        w["gdn_beta_max_e3"] for w in windows) / 1e3
    assert stats["gdn_state_absmax"] == max(
        w["gdn_state_absmax_e3"] for w in windows) / 1e3
    assert stats["ssm_dt_max"] == 0.0


@pytest.mark.parametrize("what, overrides", [
    ("--quantization", {"model.quantization": "int8"}),
    ("--kv-cache-dtype int8", {"cache.kv_cache_dtype": "int8"}),
    ("LoRA", {"lora.max_loras": 2}),
    ("host KV offload", {"cache.host_offload_gb": 0.5}),
    ("remote KV store", {"cache.remote_kv_url": "kv://127.0.0.1:1"}),
    ("speculative", {"scheduler.speculative_ngram": 3}),
    ("mixed prefill", {"scheduler.mixed_batch": True}),
    ("more than one device|tp=2", {"parallel.tensor_parallel": 2}),
])
def test_what_the_module_lacks_is_refused_at_boot_by_name(what, overrides):
    with pytest.raises(ValueError, match=what):
        LLMEngine(_engine_config(**overrides))


def test_a_preempted_sequence_comes_back_to_the_same_tokens():
    """Three sequences over a block pool that cannot keep them all: the
    scheduler preempts, the state slot is dropped with the blocks, and every
    sequence still ends on the tokens it gets alone."""
    prompts = [np.random.default_rng(i).integers(1, 260, 90).tolist()
               for i in range(3)]
    sp = lambda: SamplingParams(max_tokens=32, temperature=0.0,
                                ignore_eos=True)

    def run(eng, which):
        got = {}
        for i in which:
            eng.add_request(f"r{i}", prompt_token_ids=prompts[i],
                            sampling_params=sp())
        while eng.has_unfinished():
            for out in eng.step():
                got.setdefault(out.seq_id, []).append(out.new_token_id)
        return got

    roomy, alone = LLMEngine(_engine_config()), {}
    for i in range(3):
        alone.update(run(roomy, [i]))
    assert roomy.stats()["num_preemptions"] == 0
    tight = LLMEngine(_engine_config(**{"cache.num_blocks": 22}))
    together = run(tight, range(3))
    assert together == alone
    assert tight.stats()["num_preemptions"] > 0


def test_two_rounds_through_the_async_engine_with_and_without_caching():
    """Two rounds of two sessions through ``AsyncEngine``: with prefix caching
    on, round two resumes from round one's snapshot; the tokens are those of
    caching off; the gauges are on ``/metrics``' source."""
    from production_stack_tpu.engine.server.async_engine import AsyncEngine

    rng = np.random.default_rng(1)
    system = rng.integers(1, 260, 70).tolist()
    history = [system + rng.integers(1, 260, 150).tolist() for _ in range(2)]
    more = [rng.integers(1, 260, 60).tolist() for _ in range(2)]

    async def serve(caching):
        engine = AsyncEngine(_engine_config(
            **{"cache.enable_prefix_caching": caching}))
        await engine.start()

        async def one(prompt):
            return [e.token_id async for e in engine.generate(
                prompt_token_ids=prompt, sampling_params=SamplingParams(
                    max_tokens=10, temperature=0.0, ignore_eos=True))]

        try:
            first = await asyncio.gather(*(one(h) for h in history))
            second = await asyncio.gather(*(
                one(h + m) for h, m in zip(history, more)))
            return first + second, engine.engine.stats()
        finally:
            await engine.close()

    on, stats_on = asyncio.run(serve(True))
    off, stats_off = asyncio.run(serve(False))
    assert on == off and all(len(tokens) == 10 for tokens in on)
    assert stats_on["state_resumes"] >= 2
    assert stats_on["state_slots_in_use"] == stats_on[
        "state_snapshots_taken"] > 0
    assert (stats_off["state_resumes"], stats_off["state_snapshots_taken"],
            stats_off["state_slots_in_use"]) == (0, 0, 0)
    assert 0 < stats_on["gdn_beta_max"] <= 2 and stats_on[
        "gdn_state_absmax"] > 0
