"""``models/laguna.py``: window and full softmax layers in one model, head
counts and rotary forms by layer kind, a gate a head, a window layer's keys in
a rolling buffer of the state pool, routed experts held by share behind a
dense lead; against ``bench/reference/laguna.py`` (no cache, the window as a
mask), through its own caches and through the engine with both pools.  CPU,
the ``tiny-laguna`` preset (full + dense, window x 3, full; a window of 24),
seeded weights, float32 activations unless said."""

import asyncio
import dataclasses
import hashlib
import importlib.util
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import (
    PAGED_KINDS, PRESETS, config_from_preset,
)
from production_stack_tpu.engine.core.engine import LLMEngine
from production_stack_tpu.engine.core.sequence import SamplingParams
from production_stack_tpu.engine.models import (
    get_model, laguna, sarvam_mla, solar_kda,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS = 16   # tokens a cache block
W = PRESETS["tiny-laguna"].attention_specs["window"].window   # 24
WINDOW_LAYERS = (1, 2, 3)
KIND = {"full": "full_attention", "window": "sliding_attention"}


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_laguna",
        os.path.join(ROOT, "bench", "reference", "laguna.py"))
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
        PRESETS["tiny-laguna"], **{"dtype": "float32", **changes})


def _hp(cfg):
    """The reference's view of ``cfg``: the configuration file's keys."""
    n = cfg.num_layers
    full, window = (cfg.attention_specs[k] for k in ("full", "window"))
    yarn = {k: v for k, v in full.rope_scaling.items() if k != "type"}
    return dict(
        hidden_size=cfg.hidden_size, num_key_value_heads=cfg.num_kv_heads,
        head_dim=cfg.head_dim, rms_norm_eps=cfg.rms_norm_eps,
        num_experts=cfg.num_experts,
        num_experts_per_tok=cfg.num_experts_per_tok,
        moe_routed_scaling_factor=cfg.routed_scaling_factor,
        sliding_window=window.window, gating=cfg.use_head_gate,
        layer_types=[KIND[cfg.layer_kind(i)] for i in range(n)],
        mlp_layer_types=["dense" if i < cfg.first_k_dense_replace
                         else "sparse" for i in range(n)],
        num_attention_heads_per_layer=[
            cfg.attention_specs[cfg.layer_kind(i)].num_heads
            for i in range(n)],
        rope_parameters={
            "full_attention": dict(
                rope_theta=full.rope_theta, rope_type="yarn",
                partial_rotary_factor=full.partial_rotary_factor, **yarn),
            "sliding_attention": dict(
                rope_theta=window.rope_theta, rope_type="default",
                partial_rotary_factor=window.partial_rotary_factor)})


_PROGRAMS = {}


def _program(step, cfg, more):
    """``step`` (the module's ``prefill`` or ``decode``) jitted once a
    configuration and a set of keywords: the flags closed over, the slots
    traced, as the engine hands them."""
    flags = {k: v for k, v in more.items() if isinstance(v, bool)}
    key = (step.__name__, repr(cfg), tuple(sorted(flags.items())),
           tuple(sorted(set(more) - set(flags))))
    if key not in _PROGRAMS:
        _PROGRAMS[key] = jax.jit(lambda params, *args, **slots: step(
            params, cfg, *args, **flags, **slots))
    return _PROGRAMS[key], {k: v for k, v in more.items() if k not in flags}


def _prefill(cfg, params, cache, tokens, start, n, T, blocks, **more):
    """Chunk ``tokens[start:start + n]`` in a ``T``-slot program."""
    slots = np.zeros(T, np.int32)
    slots[:n] = tokens[start:start + n]
    prefix = np.zeros(64, np.int32)
    prefix[:start // BS] = blocks[:start // BS]
    new = np.zeros(T // BS, np.int32)
    held = -(-n // BS)
    new[:held] = blocks[start // BS:start // BS + held]
    program, named = _program(laguna.prefill, cfg, more)
    return program(
        params, jnp.asarray(slots), jnp.int32(start), jnp.asarray(prefix),
        jnp.asarray(new), jnp.int32(n), cache, **named)


def _decode(cfg, params, cache, token, pos, blocks, **more):
    """One live row at ``pos`` beside one padding row."""
    tables = np.zeros((2, 64), np.int32)
    tables[0, :len(blocks)] = blocks
    program, named = _program(laguna.decode, cfg, more)
    return program(
        params, jnp.asarray([token, 0]), jnp.asarray([pos, 0]),
        jnp.asarray(tables), jnp.asarray([pos + 1, 0]),
        jnp.asarray([blocks[pos // BS], 0]), jnp.asarray([pos % BS, 0]),
        cache, **named)


def _case(seed=0, n=150, slots=None, **changes):
    cfg = _cfg(**changes)
    params = laguna.init_params(cfg, jax.random.PRNGKey(seed))
    tokens = np.random.default_rng(seed).integers(
        1, cfg.vocab_size, n).astype(np.int32)
    blocks = np.arange(1, 1 + -(-n // BS), dtype=np.int32)
    return cfg, params, tokens, blocks, laguna.init_cache(
        cfg, 64, BS, state_slots=slots)


def _err(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want)
    return np.max(np.abs(got - want)) / np.max(np.abs(want))


def _close(got, want, tol=2e-5):
    assert _err(got, want) <= tol


# -- what the module is made of ------------------------------------------------


def test_the_registry_serves_the_preset_and_the_shared_pieces_are_imported():
    assert get_model(PRESETS["laguna-xs.2-ep2"].name) is laguna
    assert get_model(PRESETS["tiny-laguna"].name) is laguna
    for name in ("_blocks", "_gqa_prefill", "_gqa_decode", "default_slot",
                 "layer_kind", "cache_bytes_per_token"):
        assert getattr(laguna, name) is getattr(solar_kda, name)
    for name in ("route", "held_experts", "yarn_inv_freq", "_swiglu"):
        assert getattr(laguna, name) is getattr(sarvam_mla, name)
    assert laguna.stats_names(_cfg()) == sarvam_mla.ROUTING_STATS


def test_the_served_preset_is_the_share_the_file_states():
    """The issue's counts: 2 full and 6 window layers, 8,192 B a position,
    12,582,912 B a slot, 3.386 B parameters = 6.77 GB."""
    cfg = PRESETS["laguna-xs.2-ep2"]
    assert (cfg.num_experts, cfg.router_experts) == (128, 256)
    assert (cfg.vocab_size, cfg.published_vocab_size) == (50176, 100352)
    kinds = solar_kda._kinds(cfg)
    assert kinds == ["full", "window", "window", "window"] * 2
    assert (cfg.layers_of("full"), cfg.layers_of("window")) == (2, 6)
    full, window = cfg.attention_specs["full"], cfg.attention_specs["window"]
    assert (full.num_heads, window.num_heads, window.window) == (48, 64, 512)
    assert full.window is None and full.partial_rotary_factor == 0.5
    assert laguna.cache_bytes_per_token(cfg) == 2 * 4096 == 8192
    assert laguna.state_bytes_per_slot(cfg) == (
        6 * 2 * 512 * 8 * 128 * 2) == 12_582_912
    count = lambda i: sum(
        int(np.prod(s)) for s in laguna._shapes(cfg, i).values())
    assert abs(count(0) / 1e6 - 79.7) < 0.1        # attention 29.4 + MLP 50.3
    assert abs(count(1) / 1e6 - 444.2) < 0.1       # a window layer
    assert abs(count(4) / 1e6 - 435.8) < 0.1       # the routed full layer
    total = sum(count(i) for i in range(8)) + 2 * 50176 * 2048 + 2048
    assert abs(total / 1e9 - 3.386) < 0.001
    shapes = jax.eval_shape(
        lambda: laguna.init_params(cfg, jax.random.PRNGKey(0)))
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(shapes))
    assert held == 2 * total and abs(held / 1e9 - 6.77) < 0.005
    # The compare's 5 layers and the served 8 read one tuple.
    five = dataclasses.replace(cfg, num_layers=5)
    assert solar_kda._kinds(five) == kinds[:5]
    cache = jax.eval_shape(lambda: laguna.init_cache(
        cfg, 100, BS, state_slots=59))
    assert [pair[0].shape for pair in cache] == [
        (100, 16, 8, 128) if k in PAGED_KINDS else (59, 512, 8, 128)
        for k in kinds]
    decode, prefill = laguna.attention_paths(cfg)
    for words in ("full 48q/8kv", "window 64q/8kv", "window 512",
                  "pages of the block pool", "slots of the state pool"):
        assert words in decode, words
    assert "48q/8kv" in prefill and "64q/8kv" in prefill


# -- against the reference -----------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1])
@pytest.mark.parametrize("chunks", [(64, 56), (128, 22), (16, 16, 100)],
                         ids=lambda c: "+".join(map(str, c)))
def test_prefill_in_chunks_then_decode_matches_the_reference(seed, chunks):
    """A prompt five windows long, split over chunks (a chunk shorter than
    the window, one that wraps the buffer several times), then decode steps
    that wrap it again, through both caches; against one full forward with the
    window as a mask.  Logits, not tokens (float32 activations: in bfloat16
    a near-tie of the router flips and the row reads another model, which
    the benchmark's compare follows and these cases need not)."""
    n, tol = sum(chunks), 2e-5
    assert n > 4 * W
    cfg, params, tokens, blocks, cache = _case(seed, n=n + 30)
    want = np.asarray(ref.forward(params, _hp(cfg), jnp.asarray(tokens)))
    start = 0
    for size in chunks:
        T = max(64, 1 << (size - 1).bit_length())
        logits, cache = _prefill(
            cfg, params, cache, tokens, start, size, T, blocks)
        start += size
        _close(logits, want[start - 1], tol)
    for pos in range(n, n + 30):     # crosses a block and wraps the buffer
        logits, cache = _decode(cfg, params, cache, tokens[pos], pos, blocks)
        _close(logits[0], want[pos], tol)


def test_a_prompt_inside_the_window_matches_too():
    cfg, params, tokens, blocks, cache = _case(1, n=W + 4)
    want = np.asarray(ref.forward(params, _hp(cfg), jnp.asarray(tokens)))
    logits, cache = _prefill(cfg, params, cache, tokens, 0, W - 6, 64, blocks)
    _close(logits, want[W - 7])
    for pos in range(W - 6, W + 4):  # the buffer fills, then wraps
        logits, cache = _decode(cfg, params, cache, tokens[pos], pos, blocks)
        _close(logits[0], want[pos])


@pytest.mark.parametrize("fault, at_least", [
    ("whole_context", 1e-3), ("no_gate", 1e-2), ("rotate_all", 1e-4)])
def test_a_planted_fault_fails(monkeypatch, fault, at_least):
    """The reference with one thing wrong (the window's mask left out, the
    gate left out, a full layer rotating all of a head) is no longer what the
    module computes."""
    cfg, params, tokens, blocks, cache = _case(2, n=120)
    good, _ = _prefill(cfg, params, cache, tokens, 0, 120, 128, blocks)
    hp = _hp(cfg)
    assert _err(good, ref.forward(params, hp, jnp.asarray(tokens))[119]) <= 2e-5
    monkeypatch.setattr(ref, "FAULT", fault)
    bad = ref.forward(params, hp, jnp.asarray(tokens))[119]
    assert _err(good, bad) > at_least, fault


def test_a_buffer_not_carried_over_a_chunk_boundary_fails():
    cfg, params, tokens, blocks, cache = _case(2, n=120)
    want = ref.forward(params, _hp(cfg), jnp.asarray(tokens))[119]
    _, cache = _prefill(cfg, params, cache, tokens, 0, 64, 64, blocks)
    fresh, _ = _prefill(cfg, params, cache, tokens, 64, 56, 64, blocks,
                        state_slot=jnp.int32(1), state_from=jnp.int32(-1))
    assert _err(fresh, want) > 1e-3


def test_return_choice_and_stats_leave_the_logits_bit_equal():
    """Operation by operation (``disable_jit``): the same arithmetic with and
    without the two extra results; jitted, the CPU backend fuses the counters
    into the products and the last bits move, which says nothing of the
    module (the benchmark's compare holds the jitted pair on the chip)."""
    with jax.disable_jit():
        cfg, params, tokens, blocks, cache = _case(5)
        plain, a = _prefill(cfg, params, cache, tokens, 0, 64, 64, blocks)
        logits, b, who, stats = _prefill(
            cfg, params, laguna.init_cache(cfg, 64, BS), tokens, 0, 64, 64,
            blocks, return_choice=True, return_stats=True)
        np.testing.assert_array_equal(plain, logits)
        routed = cfg.num_layers - cfg.first_k_dense_replace
        assert who.shape == (routed, 64, cfg.num_experts_per_tok)
        assert int(who.max()) < cfg.router_experts
        assert int(stats[0]) == 64 * routed * cfg.num_experts_per_tok
        one, _ = _decode(cfg, params, a, tokens[64], 64, blocks)
        two, _, who, stats = _decode(cfg, params, b, tokens[64], 64, blocks,
                                     return_choice=True, return_stats=True)
        np.testing.assert_array_equal(one, two)
        assert who.shape == (routed, 2, cfg.num_experts_per_tok)
        # One live row.
        assert int(stats[0]) == routed * cfg.num_experts_per_tok


# -- the rolling buffer ----------------------------------------------------------


def _window_layer(T, seed=0, slots=6):
    """One ``window`` layer of the tiny preset, pools whose every slot holds
    something, a chunk's normed input."""
    cfg = _cfg()
    spec = cfg.attention_specs["window"]
    layer = laguna.init_params(cfg, jax.random.PRNGKey(seed))["layers"][1]
    ks = jax.random.split(jax.random.PRNGKey(seed + 7), 3)
    shape = (slots, W, cfg.num_kv_heads, cfg.head_dim)
    pools = (jax.random.normal(ks[0], shape), jax.random.normal(ks[1], shape))
    return cfg, spec, layer, pools, jax.random.normal(
        ks[2], (T, cfg.hidden_size))


def _others_bit_equal(before, after, written):
    for was, now in zip(before, after):
        for slot in set(range(was.shape[0])) - set(written):
            np.testing.assert_array_equal(was[slot], now[slot])


def _rows_after(spec, layer, cfg, x, cached, n, old):
    """What the buffers must hold once ``n`` tokens of the chunk at
    ``cached`` are in: position p at row p mod W, by a plain loop."""
    _q, k, v = laguna._project(
        spec, cached + jnp.arange(x.shape[0]), layer, cfg, x)
    out = [np.array(o) for o in old]
    for t in range(n):
        for buf, new in zip(out, (k, v)):
            buf[(cached + t) % W] = np.asarray(new[t])
    return out


@pytest.mark.parametrize("T, valid, cached, start, slot, snap_slot, snap_len", [
    (64, 50, 0, -1, 1, None, None),    # from zeros, no snapshot (the compare's)
    (64, 50, 0, -1, 1, 4, 16),         # from zeros, a snapshot below a window
    (64, 64, 64, 1, 1, 1, 0),          # a second chunk: start == slot, and the
                                       # served "no snapshot": its own slot at 0
    (128, 100, 72, 4, 2, 0, 0),        # resumed from a snapshot, the null slot
    (128, 128, 200, 4, 2, 5, 64),      # ... a snapshot after a wrap
    (64, 7, 19, 3, 3, 5, 0),           # a chunk that ends inside the window
], ids=["zeros", "zeros-snapshot", "own-slot", "resumed", "resumed-wrapped",
        "short"])
def test_a_prefill_writes_the_slots_it_names_and_nothing_else(
        T, valid, cached, start, slot, snap_slot, snap_len):
    """``_window_prefill``: the slots the chunk names hold, row by row, the
    newest position that is the row modulo the window; every other slot of
    both pools keeps its bits."""
    cfg, spec, layer, pools, x = _window_layer(T)
    named = tuple(None if v is None else jnp.int32(v)
                  for v in (slot, start, snap_slot, snap_len))
    _out, after = jax.jit(lambda pools, x: laguna._window_prefill(
        layer, cfg, spec, pools, x, jnp.int32(cached), jnp.int32(valid),
        named))(pools, x)
    _others_bit_equal(pools, after, {slot} | ({snap_slot} - {None}))
    old = [np.zeros_like(p[0]) if start < 0 else np.asarray(p[start])
           for p in pools]
    # (The rows the chunk wrote are the jitted projection's: equal to a few
    # units in the last place; the rows it kept are the old bits.)
    for got, want in zip(after, _rows_after(
            spec, layer, cfg, x, cached, valid, old)):
        np.testing.assert_allclose(got[slot], want, rtol=1e-5, atol=1e-6)
    if snap_slot not in (None, slot):
        for got, want in zip(after, _rows_after(
                spec, layer, cfg, x, cached, snap_len, old)):
            np.testing.assert_allclose(
                got[snap_slot], want, rtol=1e-5, atol=1e-6)


def test_a_decode_step_leaves_dead_rows_and_unnamed_slots_alone():
    """Rows 0 and 1 live on slots 4 and 2; two padding rows share the null
    slot and a dead row names slot 3: of both pools only row ``p mod W`` of
    slots 4 and 2 moves."""
    cfg, spec, layer, pools, x = _window_layer(5, seed=2)
    slots = jnp.asarray([4, 2, 0, 0, 3], jnp.int32)
    live = jnp.asarray([True, True, False, False, False])
    positions = jnp.asarray([W + 5, 3, 0, 0, 40], jnp.int32)
    out, after = jax.jit(lambda pools, x: laguna._window_decode(
        layer, cfg, spec, pools, x, positions, positions + 1, live,
        slots))(pools, x)
    _others_bit_equal(pools, after, {4, 2})
    _q, k, v = laguna._project(spec, positions, layer, cfg, x)
    for row, slot in ((0, 4), (1, 2)):
        at = int(positions[row]) % W
        for was, now, new in zip(pools, after, (k, v)):
            np.testing.assert_allclose(
                now[slot, at], new[row], rtol=1e-5, atol=1e-6)
            others = np.arange(W) != at
            np.testing.assert_array_equal(now[slot][others], was[slot][others])
    assert out.shape == (5, spec.num_heads * cfg.head_dim)


def test_padding_leaves_the_buffers_bit_equal():
    """The same 40 tokens in a program of 128 slots: 88 padded slots."""
    cfg, params, tokens, blocks, cache = _case(4, n=100, slots=4)
    one = dict(state_slot=jnp.int32(1), state_from=jnp.int32(-1))
    _, cache = _prefill(cfg, params, cache, tokens, 0, 40, 64, blocks, **one)
    _, padded = _prefill(cfg, params, laguna.init_cache(
        cfg, 64, BS, state_slots=4), tokens, 0, 40, 128, blocks, **one)
    for i in WINDOW_LAYERS:
        for a, b in zip(cache[i], padded[i]):
            np.testing.assert_array_equal(a[1], b[1])


@pytest.mark.parametrize("boundary", [64, 128, 192])
def test_a_run_resumed_from_a_snapshot_equals_the_cold_run_bit_for_bit(
        boundary):
    """The first prompt leaves a snapshot ``boundary`` tokens in; a second
    sequence with the same first ``boundary`` tokens starts from it, over the
    first one's pages: its logits and its buffers are, bit for bit, those of
    its own cold prefill."""
    cfg, params, tokens, blocks, cache = _case(3, n=250, slots=6)
    assert boundary % laguna.snapshot_stride(cfg) == 0
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
    # Cold, in the same two chunks: the arithmetic of a chunk is its own.
    theirs = np.arange(40, 56, dtype=np.int32)
    _, cache = _prefill(cfg, params, cache, other, 0, boundary, 256, theirs,
                        **slot(3, -1, 3, 0))
    cold, cache = _prefill(cfg, params, cache, other, boundary,
                           250 - boundary, 256, theirs, **slot(3, 3, 3, 0))
    np.testing.assert_array_equal(resumed, cold)
    _close(resumed, ref.forward(params, _hp(cfg), jnp.asarray(other))[249])
    for i in WINDOW_LAYERS:
        for buf in cache[i]:
            np.testing.assert_array_equal(buf[2], buf[3])


def test_the_compares_default_addressing_equals_explicit_slots():
    """``bench/harness/compare.py`` hands the cache and nothing else: the
    slot is then the first block id of the row's table modulo the slots."""
    cfg, params, tokens, blocks, cache = _case(6, n=150)
    blocks = blocks + 4                      # first block 5: slot 5 % 4 = 1
    assert int(laguna.default_slot(cfg, blocks[0], cache)) == (
        5 % laguna.DEFAULT_STATE_SLOTS) == 1
    _, a = _prefill(cfg, params, cache, tokens, 0, 64, 64, blocks)
    la, a = _prefill(cfg, params, a, tokens, 64, 56, 64, blocks)
    da, a = _decode(cfg, params, a, tokens[120], 120, blocks)
    one = lambda start: dict(state_slot=jnp.int32(1),
                             state_from=jnp.int32(start))
    _, b = _prefill(cfg, params, laguna.init_cache(cfg, 64, BS), tokens,
                    0, 64, 64, blocks, **one(-1))
    lb, b = _prefill(cfg, params, b, tokens, 64, 56, 64, blocks, **one(1))
    db, b = _decode(cfg, params, b, tokens[120], 120, blocks,
                    state_slots=jnp.asarray([1, 0]))
    np.testing.assert_array_equal(la, lb)
    np.testing.assert_array_equal(da[0], db[0])


# -- what each kind sees ---------------------------------------------------------


@pytest.mark.parametrize("back, seen", [(W, False), (W - 1, True), (0, True)])
def test_a_window_layer_sees_its_window_and_nothing_before_it(back, seen):
    """A large key planted ``back`` positions before the query, in a chunk
    that starts mid-buffer: at ``p - W`` the window layer's output at ``p`` is
    bit-equal, at ``p - W + 1`` it moves."""
    cfg, spec, layer, pools, x = _window_layer(64, seed=3)
    p = 50
    run = jax.jit(lambda x: laguna._window_prefill(
        layer, cfg, spec, pools, x, jnp.int32(40), jnp.int32(64),
        (jnp.int32(1), jnp.int32(2), None, None))[0])
    plain = run(x)
    planted = run(x.at[p - back].mul(50.0))
    assert bool(jnp.array_equal(plain[p], planted[p])) != seen


def test_a_full_layer_sees_every_position():
    cfg, params, tokens, blocks, cache = _case(7, n=200)
    spec = cfg.attention_specs["full"]
    assert spec.window is None
    layer = params["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(1), (256, cfg.hidden_size))
    project = lambda layer, cfg, h: laguna._project(
        spec, jnp.arange(256), layer, cfg, h)
    run = jax.jit(lambda x: solar_kda._gqa_prefill(
        layer, cfg, cache[0], x, jnp.int32(0), jnp.zeros(64, jnp.int32),
        jnp.arange(1, 17, dtype=jnp.int32), jnp.int32(200), project,
        laguna._gated)[0])
    plain, planted = run(x), run(x.at[0].mul(50.0))
    assert not jnp.array_equal(plain[199], planted[199])   # 199 positions back


# -- rotary by kind, the gate ----------------------------------------------------


def test_the_full_kinds_last_dimensions_pass_through_and_the_windows_rotate():
    cfg = _cfg()
    layer = laguna.init_params(cfg, jax.random.PRNGKey(0))["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(1), (8, cfg.hidden_size))
    positions = jnp.arange(8) + 100
    full, window = (cfg.attention_specs[k] for k in ("full", "window"))
    q, k, _v = laguna._project(full, positions, layer, cfg, x)
    raw_q = (x @ layer["q_proj"]).reshape(8, full.num_heads, cfg.head_dim)
    raw_k = (x @ layer["k_proj"]).reshape(8, cfg.num_kv_heads, cfg.head_dim)
    half = cfg.head_dim // 2
    _close(q[..., half:], raw_q[..., half:], 1e-6)
    _close(k[..., half:], raw_k[..., half:], 1e-6)
    assert _err(q[..., :half], raw_q[..., :half]) > 0.1
    layer = laguna.init_params(cfg, jax.random.PRNGKey(0))["layers"][1]
    q, _k, _v = laguna._project(window, positions, layer, cfg, x)
    raw_q = (x @ layer["q_proj"]).reshape(8, window.num_heads, cfg.head_dim)
    assert _err(q[..., half:], raw_q[..., half:]) > 0.1
    # Rotate-half pairing: dimension i turns with i + d/2 by the angle
    # position x frequency, and a rotation keeps the pair's length.
    _close(q[..., 0] ** 2 + q[..., half] ** 2,
           raw_q[..., 0] ** 2 + raw_q[..., half] ** 2, 1e-5)


def test_yarns_frequencies_and_factor_are_the_published_ones():
    """At the published sizes, against numbers written out by hand: 64
    rotated dimensions, base 500,000, factor 64 over 4,096 positions,
    ``beta_fast`` 64 and ``beta_slow`` 1 put the ramp between dimensions 5
    and 16 of 32; cos and sin carry 0.1 ln 64 + 1."""
    cfg = PRESETS["laguna-xs.2-ep2"]
    spec = cfg.attention_specs["full"]
    cos, sin = laguna.rope_tables(cfg, spec, jnp.asarray([1]))
    assert cos.shape == (1, 64)
    factor = 0.1 * math.log(64) + 1
    assert abs(factor - 1.4158883083359672) < 1e-12
    by_hand = {
        0: 1.0,                          # above the ramp's low end: kept
        5: 0.128689,                     # 500000^(-10/64), ramp 0
        10: 0.0165601 * (6 / 11 + 5 / 11 / 64),   # ramp 5/11
        16: 0.00141421 / 64,             # 500000^(-1/2) over 64, ramp 1
        31: 500000 ** (-62 / 64) / 64,
    }
    for i, freq in by_hand.items():
        for table, fn in ((cos, math.cos), (sin, math.sin)):
            for at in (i, i + 32):       # duplicated across both halves
                assert abs(float(table[0, at]) - factor * fn(freq)) < 5e-6, i
    ours = sarvam_mla.yarn_inv_freq(64, spec.rope_theta, spec.rope_scaling)
    theirs, amp = ref.inv_freq(dict(
        rope_theta=500000, rope_type="yarn", factor=64,
        original_max_position_embeddings=4096, beta_fast=64, beta_slow=1), 64)
    _close(ours, theirs, 1e-6)
    assert abs(amp - factor) < 1e-12
    # The window kind: plain frequencies over all 128, no factor.
    cos, _sin = laguna.rope_tables(
        cfg, cfg.attention_specs["window"], jnp.asarray([3]))
    assert cos.shape == (1, 128)
    assert abs(float(cos[0, 1]) - math.cos(3 * 10000 ** (-2 / 128))) < 1e-6


def test_the_gate_is_one_sigmoid_a_head():
    cfg = _cfg()
    layer = laguna.init_params(cfg, jax.random.PRNGKey(0))["layers"][1]
    H = cfg.attention_specs["window"].num_heads
    assert layer["g_proj"].shape == (cfg.hidden_size, H)
    x = jax.random.normal(jax.random.PRNGKey(1), (5, cfg.hidden_size))
    out = jax.random.normal(jax.random.PRNGKey(2), (5, H, cfg.head_dim))
    gate = jax.nn.sigmoid(x @ layer["g_proj"])                  # [5, H]
    _close(laguna._gated(layer, cfg, x, out),
           (out * gate[..., None]).reshape(5, -1), 1e-6)
    off = dataclasses.replace(cfg, use_head_gate=False)
    np.testing.assert_array_equal(
        laguna._gated(layer, off, x, out), out.reshape(5, -1))
    assert "g_proj" not in laguna._shapes(off, 1)


# -- the share of the experts ----------------------------------------------------


def test_the_two_shares_add_up_to_the_uncut_layer():
    """The routed parts that shares 0 and 1 compute (this module's FFN with
    the imported ``route`` / ``held_experts``), plus the shared expert counted
    once, are the uncut reference's layer."""
    cfg = _cfg()                          # 4 of a router's 8: two shares
    whole = dataclasses.replace(cfg, num_experts=cfg.router_experts)
    layer = laguna.init_params(whole, jax.random.PRNGKey(3))["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(4), (40, cfg.hidden_size))
    want, _ = ref.routed_ffn(layer, _hp(whole), x)
    live = jnp.ones(x.shape[0], bool)
    shared = laguna._swiglu(x, layer["shared_gate"], layer["shared_up"],
                            layer["shared_down"])
    stacks = ("experts_gate", "experts_up", "experts_down")
    total, pairs = shared, 0
    for first in (0, cfg.num_experts):
        held = dict(layer, router=jnp.roll(layer["router"], -first, axis=1),
                    **{name: layer[name][first:first + cfg.num_experts]
                       for name in stacks})
        y, _who, stats = laguna._ffn(held, cfg, x, live)
        theirs, _ = ref.routed_ffn(
            dict(layer, **{k: held[k] for k in stacks}), _hp(whole), x,
            held=(first, cfg.num_experts), shared=False)
        _close(y - shared, theirs, 1e-4)
        total = total + (y - shared)
        pairs += int(stats[1])
    _close(total, want, 1e-5)
    assert pairs == x.shape[0] * cfg.num_experts_per_tok
    # The dense lead routes nothing.
    lead = laguna.init_params(cfg, jax.random.PRNGKey(3))["layers"][0]
    y, who, stats = laguna._ffn(lead, cfg, x, live)
    assert who is None and stats is None and "router" not in lead
    _close(y, ref._swiglu(x, lead["gate_proj"], lead["up_proj"],
                          lead["down_proj"]), 1e-5)


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
    ("tiny-jamba", ["2cdfebf06dd33db8", "33fb30c09254090f"]),
    ("tiny-sarvam", ["1c658f2274072db4", "e7e232fc0ccfa79d"]),
    ("tiny-xing", ["357b464d512bd9ae", "9621c52aeb04919a"]),
])
def test_the_other_modules_programs_lower_as_before_this_module(preset, want):
    """The new layer kinds, the attention specs and the hooks that
    ``solar_kda.py``'s softmax path gained are decided in Python at trace
    time: every other preset's ``prefill`` and ``decode`` lower to the text
    they had at the commit before (hashes taken there, same JAX), at the
    default precision as the engine jits them."""
    with jax.default_matmul_precision(None):
        assert _lowered(preset) == want


# -- the engine, both pools -------------------------------------------------


def _engine_config(**overrides):
    return config_from_preset("tiny-laguna", **{
        "model.dtype": "float32", "scheduler.prefill_buckets": (64, 128),
        "scheduler.max_num_seqs": 4, "scheduler.mixed_batch": False,
        **overrides})


def _serve(eng, prompts, max_tokens=12, between=None):
    got = {}
    for i, prompt in enumerate(prompts):
        eng.add_request(f"r{i}", prompt_token_ids=prompt,
                        sampling_params=SamplingParams(
                            max_tokens=max_tokens, temperature=0.0,
                            ignore_eos=True))
    steps = 0
    while eng.has_unfinished():
        for out in eng.step():
            got.setdefault(out.seq_id, []).append(out.new_token_id)
        steps += 1
        if between is not None:
            between(steps)
    return got


def test_the_engine_serves_it_end_to_end():
    """Pages for the full layers and slots for the window layers in one
    tree, a resumed admission, the K=8 window with the rows' slots, the
    records' positions by kind, the counters, the reference's tokens."""
    eng = LLMEngine(_engine_config())
    cfg = eng.config.model
    pool = eng.state_pool
    assert (pool.live_slots, pool.snapshot_slots, pool.num_slots) == (
        6, 10, 17)
    for i in range(cfg.num_layers):
        lead = ((eng.block_pool.num_blocks, BS) if i in (0, 4) else (17, W))
        for side in eng.kv_caches[i]:
            assert side.shape == (*lead, cfg.num_kv_heads, cfg.head_dim)
    assert eng._state_bytes() == 17 * laguna.state_bytes_per_slot(cfg)
    assert eng._kv_bytes(1) == BS * laguna.cache_bytes_per_token(cfg)
    assert eng._attn_kinds == [("full", None, 2, False),
                               ("window", W, 3, True)]
    rng = np.random.default_rng(0)
    shared = rng.integers(1, 260, 200).tolist()
    prompts = [shared + rng.integers(1, 260, n).tolist() for n in (30, 100)]
    got = {}
    for i, prompt in enumerate(prompts):   # one after the other: a resume
        got[f"r{i}"] = _serve(eng, [prompt])["r0"]
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
    assert stats["moe_assignments"]["held"] > 0
    windows = eng.obs.windows_payload()["windows"]
    decodes = [w for w in windows if w["rows"]]
    assert decodes and all("window_fn" in w["programs"] for w in decodes)
    assert all(w["state_rows"] == w["rows"] == 1 for w in decodes)
    # A layer of each kind: the context in whole blocks, and the window.
    for w in decodes:
        assert w["kv_tokens_slots"] == -(-W // BS) * BS == 32
        assert w["kv_tokens"] - 32 >= 224 and (w["kv_tokens"] - 32) % BS == 0
    prefills = sorted((w for w in windows if not w["rows"]),
                      key=lambda w: w["dispatched_at"])
    assert [w["state_resumed"] for w in prefills] == [False, False, True]
    assert all(w["kv_tiles_live"] > 0 for w in prefills)
    # 24 decode steps planned a window: a row a layer a step, by kind.
    steps = sum(w["k"] for w in decodes)
    assert stats["attn_positions"]["window"] == 3 * W * steps
    assert stats["attn_positions"]["full"] > 2 * 230 * steps


def test_the_k_step_window_equals_single_steps():
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, 260, n).tolist() for n in (70, 41)]
    window = _serve(LLMEngine(_engine_config()), prompts, max_tokens=20)
    single = _serve(LLMEngine(_engine_config(
        **{"scheduler.multi_step_window": False})), prompts, max_tokens=20)
    assert window == single and all(len(t) == 20 for t in window.values())


def test_a_preempted_sequence_frees_its_slot_and_comes_back_the_same():
    """A block pool too small for both sequences' growth: the scheduler
    preempts the younger, its slot comes back at once, it is admitted again
    from zeros, and the tokens are those of a pool with room."""
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, 260, n).tolist() for n in (60, 90)]
    plain = _serve(LLMEngine(_engine_config()), prompts, max_tokens=24)
    eng = LLMEngine(_engine_config(**{"cache.num_blocks": 13}))
    live = []
    got = _serve(eng, prompts, max_tokens=24,
                 between=lambda _steps: live.append(eng.state_pool.num_live))
    assert eng.scheduler.num_preemptions >= 1
    assert 2 in live and 1 in live[live.index(2):]
    assert got == plain
    assert eng.state_pool.num_live == 0


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
    on, round two resumes the three window layers from round one's snapshot
    and the two full layers from its pages; the tokens are those of caching
    off."""
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


def test_a_model_of_one_kind_counts_its_positions_too():
    """``_attn_kinds`` of a model without specs: its one scalar window (or
    none) over the layers that keep keys; the records read as before."""
    eng = LLMEngine(config_from_preset("tiny-llama"))
    assert eng._attn_kinds == [("full", None, 2, False)]
    assert eng.stats()["attn_positions"] == {"full": 0, "window": 0}
    solar = PRESETS["tiny-solar"]
    assert [solar.layer_kind(i) in PAGED_KINDS for i in range(4)] == [
        True, False, False, False]
