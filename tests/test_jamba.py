"""``models/jamba.py``: selective state-space (Mamba-1) layers beside a
multi-query softmax layer without position encoding, over a dense MLP and a
tied head; against ``bench/reference/jamba.py`` (the recurrence token by
token), through its own caches, through the engine with both pools, and the
two Pallas kernels in interpret mode.  CPU, the ``tiny-jamba`` preset
(mamba, gqa, mamba, mamba), seeded weights."""

import asyncio
import dataclasses
import functools
import hashlib
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
    get_model, jamba, llama, solar_kda,
)
from production_stack_tpu.engine.ops.pallas import ssm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS = 16   # tokens a cache block
STATE_LAYERS = (0, 2, 3)


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_jamba", os.path.join(ROOT, "bench", "reference", "jamba.py"))
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
        PRESETS["tiny-jamba"], **{"dtype": "float32", **changes})


def _hp(cfg):
    """The reference's view of ``cfg``: the configuration file's keys."""
    kinds = cfg.layer_kinds
    return dict(
        hidden_size=cfg.hidden_size, num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        rms_norm_eps=cfg.rms_norm_eps, attn_layer_period=len(kinds),
        attn_layer_offset=kinds.index("gqa"), mamba_d_conv=cfg.mamba_d_conv,
        mamba_d_state=cfg.mamba_d_state, mamba_dt_rank=cfg.mamba_dt_rank,
        mamba_expand=cfg.mamba_expand, mamba_conv_bias=cfg.mamba_conv_bias)


def _prefill(cfg, params, cache, tokens, start, n, T, blocks, **more):
    """Chunk ``tokens[start:start + n]`` in a ``T``-slot program."""
    slots = np.zeros(T, np.int32)
    slots[:n] = tokens[start:start + n]
    prefix = np.zeros(64, np.int32)
    prefix[:start // BS] = blocks[:start // BS]
    new = np.zeros(T // BS, np.int32)
    held = -(-n // BS)
    new[:held] = blocks[start // BS:start // BS + held]
    return jamba.prefill(
        params, cfg, jnp.asarray(slots), jnp.int32(start),
        jnp.asarray(prefix), jnp.asarray(new), jnp.int32(n), cache, **more)


def _decode(cfg, params, cache, token, pos, blocks, **more):
    """One live row at ``pos`` beside one padding row."""
    tables = np.zeros((2, 64), np.int32)
    tables[0, :len(blocks)] = blocks
    return jamba.decode(
        params, cfg, jnp.asarray([token, 0]), jnp.asarray([pos, 0]),
        jnp.asarray(tables), jnp.asarray([pos + 1, 0]),
        jnp.asarray([blocks[pos // BS], 0]), jnp.asarray([pos % BS, 0]),
        cache, **more)


def _case(seed=0, n=150, slots=None, **changes):
    cfg = _cfg(**changes)
    params = jamba.init_params(cfg, jax.random.PRNGKey(seed))
    tokens = np.random.default_rng(seed).integers(
        1, cfg.vocab_size, n).astype(np.int32)
    blocks = np.arange(1, 1 + -(-n // BS), dtype=np.int32)
    return cfg, params, tokens, blocks, jamba.init_cache(
        cfg, 64, BS, state_slots=slots)


def _err(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want)
    return np.max(np.abs(got - want)) / np.max(np.abs(want))


def _close(got, want, tol=2e-5):
    assert _err(got, want) <= tol


def test_the_registry_serves_the_preset_and_the_shared_pieces_are_imported():
    assert get_model(PRESETS["jamba2-3b"].name) is jamba
    assert get_model(PRESETS["tiny-jamba"].name) is jamba
    for name in ("_blocks", "_gqa_prefill", "_gqa_decode", "default_slot",
                 "layer_kind", "cache_bytes_per_token"):
        assert getattr(jamba, name) is getattr(solar_kda, name)
    assert jamba.llama is llama and jamba.rms_norm is solar_kda.rms_norm


def test_the_served_preset_is_the_whole_model():
    """The issue's counts: 26 mixers and layers 7 and 21 attention, 3.03 B
    parameters, 9,318,400 B a slot, 1,024 B a position."""
    cfg = PRESETS["jamba2-3b"]
    kinds = solar_kda._kinds(cfg)
    assert [i for i, k in enumerate(kinds) if k == "gqa"] == [7, 21]
    assert kinds.count("mamba") == 26 and len(kinds) == 28
    assert jamba.state_bytes_per_slot(cfg) == 26 * (
        5120 * 16 * 4 + 3 * 5120 * 2) == 9_318_400
    assert jamba.cache_bytes_per_token(cfg) == 1024
    count = lambda i: sum(
        int(np.prod(s)) for s in jamba._shapes(cfg, i).values())
    assert count(7) == 76_682_240 and count(0) == 104_161_472
    total = sum(count(i) for i in range(28)) + 65536 * 2560 + 2560
    assert 3.02e9 < total < 3.04e9 and total == 3_029_337_472
    # The compare's 14 layers and the served 28 read one tuple.
    half = dataclasses.replace(cfg, num_layers=14)
    assert solar_kda._kinds(half) == kinds[:14]


@pytest.mark.parametrize("dtype, tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
def test_prefill_in_two_chunks_then_decode_matches_the_reference(dtype, tol):
    """The scan over a carried state and cached keys, the one-step decode
    through both caches, against one full forward token by token."""
    cfg, params, tokens, blocks, cache = _case(0, n=132, dtype=dtype)
    want = np.asarray(ref.forward(params, _hp(cfg), jnp.asarray(tokens)))
    _, cache = _prefill(cfg, params, cache, tokens, 0, 64, 64, blocks)
    logits, cache, stats = _prefill(
        cfg, params, cache, tokens, 64, 56, 64, blocks, return_stats=True)
    _close(logits, want[119], tol)
    absmax, dt_max = np.asarray(stats) / 1e3
    assert 0.001 < dt_max < 1.0 and 0.01 < absmax < 100.0
    for pos in range(120, 132):      # crosses a block boundary at 128
        logits, cache = _decode(cfg, params, cache, tokens[pos], pos, blocks)
        _close(logits[0], want[pos], tol)


def test_return_stats_leaves_the_logits_bit_equal_and_reads_the_state():
    cfg, params, tokens, blocks, cache = _case(1)
    plain, a = _prefill(cfg, params, cache, tokens, 0, 120, 128, blocks)
    counted, b, stats = _prefill(
        cfg, params, jamba.init_cache(cfg, 64, BS), tokens, 0, 120, 128,
        blocks, return_stats=True)
    np.testing.assert_array_equal(plain, counted)
    assert jamba.stats_names(cfg) == jamba.SSM_STATS == jamba.STATS_MAX
    slot = int(jamba.default_slot(cfg, blocks[0], b))
    largest = max(float(jnp.abs(b[i][0][slot]).max()) for i in STATE_LAYERS)
    assert int(stats[0]) == int(np.float32(largest) * np.float32(1e3))
    one, _ = _decode(cfg, params, a, tokens[120], 120, blocks)
    two, after, stats = _decode(cfg, params, b, tokens[120], 120, blocks,
                                return_stats=True)
    np.testing.assert_array_equal(one, two)
    largest = max(float(jnp.abs(after[i][0][slot]).max())
                  for i in STATE_LAYERS)
    assert int(stats[0]) == int(np.float32(largest) * np.float32(1e3))


@pytest.mark.parametrize("boundary", [64, 128, 192])
def test_a_run_resumed_from_a_snapshot_equals_the_uninterrupted_run(boundary):
    """The first prompt leaves a snapshot ``boundary`` tokens in; a second
    sequence with the same first ``boundary`` tokens starts from it, over the
    first one's pages, and equals its own uninterrupted prefill."""
    cfg, params, tokens, blocks, cache = _case(3, n=250, slots=6)
    assert boundary % jamba.snapshot_stride(cfg) == 0
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
    _, padded = _prefill(cfg, params, jamba.init_cache(
        cfg, 64, BS, state_slots=4), tokens, 0, 40, 128, blocks, **one)
    for i in STATE_LAYERS:
        np.testing.assert_array_equal(cache[i][0][1], padded[i][0][1])
        np.testing.assert_array_equal(cache[i][1][1], padded[i][1][1])
    # A decode batch whose row is dead (its write parked on the null block).
    before = [(np.asarray(cache[i][0]), np.asarray(cache[i][1]))
              for i in STATE_LAYERS]
    tables = np.zeros((2, 64), np.int32)
    tables[0, :len(blocks)] = blocks
    _, after = jamba.decode(
        params, cfg, jnp.asarray([5, 0]), jnp.asarray([40, 0]),
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
    assert int(jamba.default_slot(cfg, blocks[0], cache)) == (
        5 % jamba.DEFAULT_STATE_SLOTS) == 1
    _, a = _prefill(cfg, params, cache, tokens, 0, 64, 64, blocks)
    la, a = _prefill(cfg, params, a, tokens, 64, 56, 64, blocks)
    da, a = _decode(cfg, params, a, tokens[120], 120, blocks)
    one = lambda start: dict(state_slot=jnp.int32(1),
                             state_from=jnp.int32(start))
    _, b = _prefill(cfg, params, jamba.init_cache(cfg, 64, BS), tokens,
                    0, 64, 64, blocks, **one(-1))
    lb, b = _prefill(cfg, params, b, tokens, 64, 56, 64, blocks, **one(1))
    db, b = _decode(cfg, params, b, tokens[120], 120, blocks,
                    state_slots=jnp.asarray([1, 0]))
    np.testing.assert_array_equal(la, lb)
    np.testing.assert_array_equal(da[0], db[0])


@pytest.mark.parametrize("attr, value, at_least", [
    ("FAULT", "no_inner_norm", 1e-3), ("FAULT", "no_dt_bias", 1e-3),
    ("FAULT", "conv_shifted", 1e-3), ("STATE_DTYPE", jnp.bfloat16, 6e-5),
])
def test_a_planted_fault_fails(monkeypatch, attr, value, at_least):
    """The reference with one thing wrong (an inner norm dropped, the step's
    bias dropped, the convolution shifted by one) is no longer what the module
    computes; nor is one whose state is rounded to bfloat16 after every token:
    at float32 activations that reads 1e-4 against the 2e-5 every other test
    here holds, three layers deep (a state's rounding is averaged over the
    tokens it remembers, so it is the smallest of the four)."""
    cfg, params, tokens, blocks, cache = _case(2, n=120)
    good, _ = _prefill(cfg, params, cache, tokens, 0, 120, 128, blocks)
    hp = _hp(cfg)
    assert _err(good, ref.forward(params, hp, jnp.asarray(tokens))[119]) <= 2e-5
    monkeypatch.setattr(ref, attr, value)
    bad = ref.forward(params, hp, jnp.asarray(tokens))[119]
    assert _err(good, bad) > at_least, value


def test_a_state_not_carried_over_a_chunk_boundary_fails():
    cfg, params, tokens, blocks, cache = _case(2, n=120)
    want = ref.forward(params, _hp(cfg), jnp.asarray(tokens))[119]
    _, cache = _prefill(cfg, params, cache, tokens, 0, 64, 64, blocks)
    fresh, _ = _prefill(cfg, params, cache, tokens, 64, 56, 64, blocks,
                        state_slot=jnp.int32(1), state_from=jnp.int32(-1))
    assert _err(fresh, want) > 1e-3


# -- the kernels, interpreted ------------------------------------------------


def _scan_inputs(T, Di, N=16, seed=0, live=None):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    c, z = (jax.random.normal(k, (T, Di)) for k in ks[:2])
    B, C = (jax.random.normal(k, (T, N)) for k in ks[2:4])
    dt = jnp.exp(jax.random.uniform(
        ks[4], (T, Di), minval=np.log(1e-3), maxval=np.log(0.3)))
    if live is not None:
        dt = jnp.where(jnp.arange(T)[:, None] < live, dt, 0.0)
    A_log = jnp.broadcast_to(jnp.log(jnp.arange(1.0, N + 1))[:, None], (N, Di))
    skip = jax.random.normal(ks[5], (Di,))
    s0 = jax.random.normal(ks[6], (N, Di))
    return c, dt, z, B, C, A_log, skip, s0


def _by_the_reference(c, dt, z, B, C, A_log, skip, s0):
    y, state = ref.selective_scan(c, dt, B, C, -jnp.exp(A_log), s0)
    return (y + skip * c) * jax.nn.silu(z), state


@pytest.mark.parametrize("snapshot_len", [None, 0, 64, 256, 448])
def test_the_prefill_kernel_is_the_recurrence(snapshot_len):
    """``ssm_prefill_pallas`` (interpreted), two channel blocks and two token
    tiles, the second all padding, against the reference's token-by-token
    scan; the snapshot against a shorter scan."""
    args = _scan_inputs(512, 1024, live=256)
    y, s1, snap = ssm.ssm_prefill_pallas(*args, snapshot_len, interpret=True)
    want_y, want_s = _by_the_reference(*args)
    _close(y, want_y, 1e-5)
    _close(s1, want_s, 1e-5)
    np.testing.assert_array_equal(       # the padding moved nothing
        s1, ssm.ssm_prefill_pallas(*(a[:256] if a.shape[0] == 512 else a
                                     for a in args), interpret=True)[1])
    if snapshot_len is None:
        assert snap is None
    else:
        n = snapshot_len
        head = [a[:n] if a.shape[0] == 512 else a for a in args]
        _close(snap, _by_the_reference(*head)[1] if n else args[-1], 1e-5)
    plain = jamba.ssm_scan_plain(*args, snapshot_len)
    _close(plain[0], want_y, 1e-5)
    _close(plain[1], want_s, 1e-5)


def test_the_decode_kernel_is_one_step_in_place():
    c, dt, z, B, C, A_log, skip, _ = _scan_inputs(4, 1024, seed=1)
    state = jax.random.normal(jax.random.PRNGKey(3), (6, 16, 1024))
    slots = jnp.asarray([4, 2, 0, 0], jnp.int32)
    dt = jnp.where(jnp.asarray([True, True, False, False])[:, None], dt, 0.0)
    y, absmax, after = ssm.ssm_decode_pallas(
        c, dt, z, B, C, A_log, skip, state, slots, interpret=True)
    want_y, want_rows = jamba.ssm_step_plain(
        c, dt, z, B, C, A_log, skip, state[slots])
    _close(y[:2], want_y[:2], 1e-6)
    _close(after[slots[:2]], want_rows[:2], 1e-6)
    _close(absmax[:2], jnp.abs(want_rows[:2]).max(1), 1e-6)
    for untouched in (0, 1, 3, 5):       # the null slot: dead rows, bit-equal
        np.testing.assert_array_equal(after[untouched], state[untouched])
    one = _by_the_reference(*(a[:1] for a in (c, dt, z, B, C)), A_log, skip,
                            state[4])
    _close(y[0], one[0][0], 1e-5)
    _close(after[4], one[1], 1e-5)


# -- a slot is read and written where it lies ---------------------------------


@pytest.fixture
def kernels_serve(monkeypatch):
    """The module's TPU branch on the CPU: both kernels, interpreted."""
    monkeypatch.setattr(jamba, "use_pallas_ssm", lambda cfg: True)
    for name in ("ssm_prefill_pallas", "ssm_decode_pallas"):
        monkeypatch.setattr(ssm, name, functools.partial(
            getattr(ssm, name), interpret=True))


def _layer_case(T, seed=0, slots=6):
    """One ``mamba`` layer of the tiny preset (128 channels of 16 states), a
    pool whose every slot holds something, a chunk's normed input."""
    cfg = _cfg()
    Di, N = jamba._inner(cfg), cfg.mamba_d_state
    layer = jamba.init_params(cfg, jax.random.PRNGKey(seed))["layers"][0]
    ks = jax.random.split(jax.random.PRNGKey(seed + 7), 3)
    pools = (jax.random.normal(ks[0], (slots, N, Di)),
             jax.random.normal(
                 ks[1], solar_kda.rows_pool_shape(slots, 3, Di)))
    return cfg, layer, pools, jax.random.normal(ks[2], (T, cfg.hidden_size))


def _others_bit_equal(before, after, written):
    for was, now in zip(before, after):
        for slot in set(range(was.shape[0])) - set(written):
            np.testing.assert_array_equal(was[slot], now[slot])


@pytest.mark.parametrize("path", ["kernel", "plain"])
@pytest.mark.parametrize("T, valid, start, slot, snap_slot, snap_len", [
    (256, 200, -1, 1, None, None),   # from zeros, no snapshot (the compare's)
    (256, 200, -1, 1, 4, 64),        # from zeros, a snapshot mid-chunk
    (256, 256, 1, 1, 1, 0),          # a second chunk: start == slot, and the
                                     # served "no snapshot": its own slot at 0
    (512, 300, 4, 2, 0, 0),          # resumed from a snapshot, the null slot
    (512, 512, 4, 2, 5, 448),        # ... a snapshot at the last boundary
], ids=["zeros", "zeros-snapshot", "own-slot", "resumed", "resumed-last"])
def test_a_prefill_writes_the_slots_it_names_and_nothing_else(
        path, request, T, valid, start, slot, snap_slot, snap_len):
    """``_mamba_prefill`` by either scan: the slots the chunk names hold the
    state and the stream's last three rows at their places; every other slot
    of both pools keeps its bits."""
    if path == "kernel":
        request.getfixturevalue("kernels_serve")
    cfg, layer, pools, x = _layer_case(T)
    Di = jamba._inner(cfg)
    live = jnp.arange(T) < valid
    named = tuple(None if v is None else jnp.int32(v)
                  for v in (slot, start, snap_slot, snap_len))
    out, after, _ = jax.jit(lambda pools, x: jamba._mamba_prefill(
        layer, cfg, pools, x, live, jnp.int32(valid), named))(pools, x)
    _others_bit_equal(pools, after, {slot} | ({snap_slot} - {None}))
    u = jamba._dot(x, layer["in_proj"]).astype(x.dtype)[:, :Di]
    head = jnp.zeros((3, Di)) if start < 0 else pools[1][start].reshape(3, Di)
    full = jnp.concatenate([head, u])
    np.testing.assert_array_equal(
        after[1][slot].reshape(-1), full[valid:valid + 3].reshape(-1))
    s0 = jnp.zeros_like(pools[0][0]) if start < 0 else pools[0][start]

    def scanned(n):
        """The state ``n`` tokens in, by the plain scan over the same
        stream."""
        if n == 0:
            return s0
        c = jamba._convolved(layer, cfg, [full[j:j + n] for j in range(4)])
        dt, B, C = jamba._selective(layer, cfg, c, live[:n])
        return jamba.ssm_scan_plain(
            c, dt, jnp.zeros_like(c), B, C, layer["A_log"], layer["D"], s0)[1]

    _close(after[0][slot], scanned(valid), 1e-5)
    if snap_slot not in (None, slot):
        np.testing.assert_array_equal(
            after[1][snap_slot].reshape(-1),
            full[snap_len:snap_len + 3].reshape(-1))
        _close(after[0][snap_slot], scanned(snap_len), 1e-5)


@pytest.mark.parametrize("path", ["kernel", "plain"])
def test_a_decode_step_leaves_dead_rows_and_unnamed_slots_alone(
        path, request):
    """Rows 0 and 1 live on slots 4 and 2; two padding rows share the null
    slot and a dead row names slot 3: of both pools only slots 4 and 2 move,
    and their rows shift by one."""
    if path == "kernel":
        request.getfixturevalue("kernels_serve")
    cfg, layer, pools, x = _layer_case(5, seed=2)
    Di = jamba._inner(cfg)
    slots = jnp.asarray([4, 2, 0, 0, 3], jnp.int32)
    live = jnp.asarray([True, True, False, False, False])
    out, after, _ = jax.jit(lambda pools, x: jamba._mamba_decode(
        layer, cfg, pools, x, live, slots))(pools, x)
    _others_bit_equal(pools, after, {4, 2})
    u = jamba._dot(x, layer["in_proj"]).astype(x.dtype)[:, :Di]
    for row, slot in ((0, 4), (1, 2)):
        was, now = (a[1][slot].reshape(3, Di) for a in (pools, after))
        np.testing.assert_array_equal(now[:2], was[1:])
        np.testing.assert_array_equal(now[2], u[row])
        assert not np.array_equal(after[0][slot], pools[0][slot])
    assert out.shape == (5, Di)


# -- what the shared files' other users lower to ------------------------------


def _lowered(preset):
    """sha256 of the lowered text of a preset's two steps at fixed shapes."""
    cfg = dataclasses.replace(PRESETS[preset])
    model = get_model(cfg.name)
    params = jax.eval_shape(
        lambda: model.init_params(cfg, jax.random.PRNGKey(0)))
    bs, nb, T, S, bmax = 16, 64, 32, 4, 8
    if hasattr(model, "init_cache"):
        kv = jax.eval_shape(lambda: model.init_cache(cfg, nb, bs, None))
    else:
        page = jax.ShapeDtypeStruct(
            (nb, bs, cfg.num_kv_heads, cfg.head_dim), jnp.dtype(cfg.dtype))
        kv = [(page, page) for _ in range(cfg.num_layers)]
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    more = {"return_stats": True} if hasattr(model, "stats_names") else {}
    texts = [
        jax.jit(lambda p, t, c, pre, new, v, kv: model.prefill(
            p, cfg, t, c, pre, new, v, kv, **more)).lower(
                params, i32(T), i32(), i32(bmax), i32(T // bs), i32(),
                kv).as_text(),
        jax.jit(lambda p, t, pos, bt, cl, sb, so, kv: model.decode(
            p, cfg, t, pos, bt, cl, sb, so, kv, **more)).lower(
                params, i32(S), i32(S), i32(S, bmax), i32(S), i32(S), i32(S),
                kv).as_text()]
    return [hashlib.sha256(t.encode()).hexdigest()[:16] for t in texts]


@pytest.mark.parametrize("preset, want", [
    ("tiny-llama", ["873b5204c91b164f", "0247206c6c71daca"]),
    ("tiny-solar", ["8199b97afa9c86f8", "50b476ee875f80f7"]),
])
def test_the_other_modules_programs_lower_as_before_this_module(preset, want):
    """``solar_kda.py``'s layer loop and softmax path were factored for
    ``jamba.py`` to import: ``tiny-solar``'s and ``tiny-llama``'s ``prefill``
    and ``decode`` lower to the text they had at the commit before (hashes
    taken there, same JAX), at the default precision as the engine jits
    them.  ``tiny-solar``'s were taken again when a slot of its convolution
    rows' pool became lines of 128 channels (PR 55; ``2afd96b51fcd3ec0``,
    ``529eae6b689206f5`` before): the module's own change."""
    with jax.default_matmul_precision(None):
        assert _lowered(preset) == want


# -- the engine, both pools -------------------------------------------------


def _engine_config(**overrides):
    return config_from_preset("tiny-jamba", **{
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
    assert eng.kv_caches[1][0].shape == (
        eng.block_pool.num_blocks, BS, 1, cfg.head_dim)
    for i in STATE_LAYERS:
        state, conv = eng.kv_caches[i]
        assert state.shape == (17, 16, 128) and state.dtype == jnp.float32
        assert conv.shape == (17, 3, 128)
    # Slots x bytes a slot come off what the K/V pool is sized from.
    assert eng._state_bytes() == 17 * jamba.state_bytes_per_slot(cfg)
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
    assert stats["moe_assignments"] == {"held": 0, "away": 0}
    windows = eng.obs.windows_payload()["windows"]
    decodes = [w for w in windows if w["rows"]]
    assert decodes and all("window_fn" in w["programs"] for w in decodes)
    assert all(w["state_rows"] == w["rows"] for w in decodes)
    prefills = sorted((w for w in windows if not w["rows"]),
                      key=lambda w: w["dispatched_at"])
    assert [w["state_resumed"] for w in prefills] == [False, False, True]
    # The module's counters: on every record, folded by maximum, on stats().
    assert all(0 < w["ssm_dt_max_e3"] < 1000 for w in windows)
    assert all(0 < w["ssm_state_absmax_e3"] < 100_000 for w in windows)
    assert stats["ssm_dt_max"] == max(
        w["ssm_dt_max_e3"] for w in windows) / 1e3
    assert stats["ssm_state_absmax"] == max(
        w["ssm_state_absmax_e3"] for w in windows) / 1e3


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
    assert 0 < stats_on["ssm_dt_max"] < 1 and stats_on["ssm_state_absmax"] > 0
