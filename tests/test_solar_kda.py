"""``models/solar_kda.py``: gated delta-rule layers beside a gated softmax layer
without position encoding, over routed experts held by share; against
``bench/reference/solar_kda.py`` (the recurrence token by token), through its
own caches, through the engine with both pools, and the two Pallas kernels in
interpret mode.  CPU, the ``tiny-solar`` preset, float32, seeded weights."""

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
from production_stack_tpu.engine.models import get_model, sarvam_mla, solar_kda
from production_stack_tpu.engine.ops.pallas import kda

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS = 16   # tokens a cache block


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_solar_kda",
        os.path.join(ROOT, "bench", "reference", "solar_kda.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _cfg(**changes):
    return dataclasses.replace(PRESETS["tiny-solar"], dtype="float32",
                               **changes)


def _hp(cfg, **changes):
    """The reference's view of ``cfg``: the configuration file's keys."""
    kinds = solar_kda._kinds(cfg)
    hp = dict(
        hidden_size=cfg.hidden_size, num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        rms_norm_eps=cfg.rms_norm_eps, use_gqa_gate=cfg.use_gqa_gate,
        kda_allow_neg_eigval=cfg.kda_allow_neg_eigval,
        gqa_layers=[i for i, kind in enumerate(kinds) if kind == "gqa"],
        linear_attn_config=dict(
            num_heads=cfg.linear_num_heads, head_dim=cfg.linear_head_dim,
            short_conv_kernel_size=cfg.linear_conv_kernel),
        n_routed_experts=cfg.num_experts,
        num_experts_per_tok=cfg.num_experts_per_tok,
        routed_scaling_factor=cfg.routed_scaling_factor,
        vocab_size=cfg.vocab_size,
        published={"n_routed_experts": cfg.router_experts})
    hp.update(changes)
    return hp


def _params(cfg, seed=0):
    return solar_kda.init_params(cfg, jax.random.PRNGKey(seed))


def _prefill(cfg, params, cache, tokens, start, n, T, blocks, **more):
    """Chunk ``tokens[start:start + n]`` in a ``T``-slot program."""
    slots = np.zeros(T, np.int32)
    slots[:n] = tokens[start:start + n]
    prefix = np.zeros(64, np.int32)
    prefix[:start // BS] = blocks[:start // BS]
    new = np.zeros(T // BS, np.int32)
    held = -(-n // BS)
    new[:held] = blocks[start // BS:start // BS + held]
    return solar_kda.prefill(
        params, cfg, jnp.asarray(slots), jnp.int32(start),
        jnp.asarray(prefix), jnp.asarray(new), jnp.int32(n), cache, **more)


def _decode(cfg, params, cache, token, pos, blocks, **more):
    """One live row at ``pos`` beside one padding row."""
    tables = np.zeros((2, 64), np.int32)
    tables[0, :len(blocks)] = blocks
    return solar_kda.decode(
        params, cfg, jnp.asarray([token, 0]), jnp.asarray([pos, 0]),
        jnp.asarray(tables), jnp.asarray([pos + 1, 0]),
        jnp.asarray([blocks[pos // BS], 0]), jnp.asarray([pos % BS, 0]),
        cache, **more)


def _case(seed=0, n=150, slots=None):
    cfg = _cfg()
    params = _params(cfg, seed)
    tokens = np.random.default_rng(seed).integers(
        1, cfg.vocab_size, n).astype(np.int32)
    blocks = np.arange(1, 1 + -(-n // BS), dtype=np.int32)
    return cfg, params, tokens, blocks, solar_kda.init_cache(
        cfg, 64, BS, state_slots=slots)


def _close(got, want, tol=2e-5):
    got, want = np.asarray(got), np.asarray(want)
    assert np.max(np.abs(got - want)) <= tol * np.max(np.abs(want))


def _err(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return np.max(np.abs(got - want)) / np.max(np.abs(want))


def test_the_registry_serves_the_preset_with_the_module():
    assert get_model(PRESETS["solar-open2-250b-ep8"].name) is solar_kda
    assert get_model(PRESETS["tiny-solar"].name) is solar_kda
    # The shared pieces are imported, not copied.
    assert solar_kda.route is sarvam_mla.route
    assert solar_kda.held_experts is sarvam_mla.held_experts
    assert solar_kda._swiglu is sarvam_mla._swiglu


@pytest.mark.parametrize("seed", [0, 1])
def test_prefill_in_two_chunks_then_decode_matches_the_reference(seed):
    """Chunkwise prefill over a carried state, the one-step decode through
    the caches, against one full forward of the recurrence token by token."""
    cfg, params, tokens, blocks, cache = _case(seed)
    want = np.asarray(ref.forward(params, _hp(cfg), jnp.asarray(tokens)))
    _, cache = _prefill(cfg, params, cache, tokens, 0, 64, 64, blocks)
    logits, cache = _prefill(cfg, params, cache, tokens, 64, 56, 64, blocks)
    _close(logits, want[119])
    for pos in range(120, 150):
        logits, cache = _decode(cfg, params, cache, tokens[pos], pos, blocks)
        _close(logits[0], want[pos])


def test_beta_above_one_occurs():
    """``kda_allow_neg_eigval``: beta = 2 sigmoid reaches past 1, where
    ``I - beta k k^T`` has a negative eigenvalue; the cases above run it."""
    cfg, params, tokens, _blocks, _cache = _case(0)
    x = params["embed_tokens"][tokens]
    layer = params["layers"][1]
    beta = ref.delta_inputs(layer, _hp(cfg), x)[4]
    assert float(beta.max()) > 1.0 > float(beta.min())
    off = ref.delta_inputs(layer, _hp(cfg, kda_allow_neg_eigval=False), x)[4]
    assert float(off.max()) <= 1.0


@pytest.mark.parametrize("boundary", [64, 128, 192])
def test_a_run_resumed_from_a_snapshot_equals_the_uninterrupted_run(boundary):
    """The first prompt leaves a snapshot ``boundary`` tokens in (a multiple
    of the stride); a second sequence with the same first ``boundary`` tokens
    starts from it, over the first one's pages, and equals its own
    uninterrupted prefill bit for bit downstream of the same arithmetic."""
    cfg, params, tokens, blocks, cache = _case(3, n=250, slots=6)
    assert boundary % solar_kda.snapshot_stride(cfg) == 0
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
                              250 - boundary, 256, mine,
                              **slot(2, 4, 2, 0))
    whole, cache = _prefill(cfg, params, cache, other, 0, 250, 256,
                            np.arange(40, 56, dtype=np.int32),
                            **slot(3, -1, 3, 0))
    _close(resumed, whole, 1e-5)
    want = ref.forward(params, _hp(cfg), jnp.asarray(other))[249]
    _close(resumed, want)
    for layer in cache[1:]:
        _close(layer[0][2], layer[0][3], 1e-5)       # the two live states
        _close(layer[1][2], layer[1][3], 1e-5)       # and their conv rows


def test_padding_and_dead_rows_leave_the_state_bit_equal():
    cfg, params, tokens, blocks, cache = _case(4, n=100, slots=4)
    _, cache = _prefill(cfg, params, cache, tokens, 0, 40, 64, blocks,
                        state_slot=jnp.int32(1), state_from=jnp.int32(-1))
    # The same 40 tokens in a program of 128 slots: 88 padded slots.
    _, padded = _prefill(cfg, params, solar_kda.init_cache(
        cfg, 64, BS, state_slots=4), tokens, 0, 40, 128, blocks,
        state_slot=jnp.int32(1), state_from=jnp.int32(-1))
    for a, b in zip(cache[1:], padded[1:]):
        np.testing.assert_array_equal(a[0][1], b[0][1])
        np.testing.assert_array_equal(a[1][1], b[1][1])
    # A decode batch whose row is dead (its write parked on the null block).
    before = [(np.asarray(s), np.asarray(c)) for s, c in cache[1:]]
    tables = np.zeros((2, 64), np.int32)
    tables[0, :len(blocks)] = blocks
    _, after = solar_kda.decode(
        params, cfg, jnp.asarray([5, 0]), jnp.asarray([40, 0]),
        jnp.asarray(tables), jnp.asarray([41, 0]), jnp.asarray([0, 0]),
        jnp.asarray([8, 0]), cache, state_slots=jnp.asarray([1, 0]))
    for (s, c), layer in zip(before, after[1:]):
        np.testing.assert_array_equal(s, layer[0])
        np.testing.assert_array_equal(c, layer[1])


def test_the_compares_default_addressing_equals_explicit_slots():
    """``bench/harness/compare.py`` hands the cache and nothing else: the
    slot is then the first block id of the row's table modulo the slots."""
    cfg, params, tokens, blocks, cache = _case(6, n=150)
    slots = solar_kda.DEFAULT_STATE_SLOTS
    blocks = blocks + 4                      # first block 5: slot 5 % 4 = 1
    assert int(solar_kda.default_slot(cfg, blocks[0], cache)) == 5 % slots == 1
    _, a = _prefill(cfg, params, cache, tokens, 0, 64, 64, blocks)
    la, a = _prefill(cfg, params, a, tokens, 64, 56, 64, blocks)
    da, a = _decode(cfg, params, a, tokens[120], 120, blocks)
    one = lambda start: dict(state_slot=jnp.int32(1),
                             state_from=jnp.int32(start))
    _, b = _prefill(cfg, params, solar_kda.init_cache(cfg, 64, BS), tokens,
                    0, 64, 64, blocks, **one(-1))
    lb, b = _prefill(cfg, params, b, tokens, 64, 56, 64, blocks, **one(1))
    db, b = _decode(cfg, params, b, tokens[120], 120, blocks,
                    state_slots=jnp.asarray([1, 0]))
    np.testing.assert_array_equal(la, lb)
    np.testing.assert_array_equal(da[0], db[0])


def _state_in_bf16(q, k, v, g, beta, s0, snapshot_len=None, chunk=None):
    o, state = ref.delta_rule(q, k, v, g, beta, s0)
    return o, state, None


def _no_gate(layer, cfg, x, o):
    T = x.shape[0]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                          + cfg.rms_norm_eps)
    return (o * layer["o_norm"]).reshape(T, -1).astype(x.dtype)


def _decay_a_head(layer, cfg, x, mixed, live):
    q, k, v, g, beta = _kda_inputs(layer, cfg, x, mixed, live)
    return q, k, v, jnp.broadcast_to(g.mean(-1, keepdims=True), g.shape), beta


_kda_inputs = solar_kda._kda_inputs


@pytest.mark.parametrize("name, attr, fault", [
    ("a state rounded to bf16", "kda_chunk_plain", _state_in_bf16),
    ("the output gate left out", "_kda_out", _no_gate),
    ("a decay a head, not a channel", "_kda_inputs", _decay_a_head),
])
def test_a_planted_fault_fails(monkeypatch, name, attr, fault):
    cfg, params, tokens, blocks, cache = _case(2, n=120)
    want = np.asarray(ref.forward(params, _hp(cfg), jnp.asarray(tokens)))[119]
    good, _ = _prefill(cfg, params, cache, tokens, 0, 120, 128, blocks)
    assert _err(good, want) <= 2e-5
    if attr == "kda_chunk_plain":
        monkeypatch.setattr(ref, "STATE_DTYPE", jnp.bfloat16)
    monkeypatch.setattr(solar_kda, attr, fault)
    bad, _ = _prefill(cfg, params, solar_kda.init_cache(cfg, 64, BS), tokens,
                      0, 120, 128, blocks)
    assert _err(bad, want) > 1e-3, name


def _routed_layer(cfg, seed=0):
    full = dataclasses.replace(cfg, num_experts=cfg.router_experts)
    layer = _params(full, seed)["layers"][1]
    x = jax.random.normal(jax.random.PRNGKey(seed + 7),
                          (40, cfg.hidden_size), jnp.float32)
    return full, layer, x


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """The routed parts that shares 0-7 compute (this module's FFN with the
    imported ``route`` / ``held_experts``), plus the shared expert counted
    once, are the uncut reference's layer."""
    cfg = _cfg(num_experts=1)            # 1 of a router's 8: eight shares
    full, layer, x = _routed_layer(cfg)
    want, _ = ref.routed_ffn(layer, _hp(full), x)
    live = jnp.ones(x.shape[0], bool)
    shared = solar_kda._swiglu(x, layer["shared_gate"], layer["shared_up"],
                               layer["shared_down"])
    total, pairs = shared, 0
    for first in range(cfg.router_experts):
        held = dict(layer, router=jnp.roll(layer["router"], -first, axis=1),
                    router_bias=jnp.roll(layer["router_bias"], -first),
                    **{name: layer[name][first:first + 1] for name in (
                        "experts_gate", "experts_up", "experts_down")})
        y, _who, stats = solar_kda._ffn(held, cfg, x, live)
        theirs, _ = ref.routed_ffn(
            dict(layer, **{k: layer[k][first:first + 1] for k in (
                "experts_gate", "experts_up", "experts_down")}),
            _hp(full), x, held=(first, 1), shared=False)
        _close(y - shared, theirs, 1e-4)
        total = total + (y - shared)
        pairs += int(stats[1])
    _close(total, want, 1e-5)
    assert pairs == x.shape[0] * cfg.num_experts_per_tok


def test_return_choice_and_stats_leave_the_logits_bit_equal():
    cfg, params, tokens, blocks, cache = _case(5)
    plain, _ = _prefill(cfg, params, cache, tokens, 0, 64, 64, blocks)
    logits, _, who, stats = _prefill(
        cfg, params, solar_kda.init_cache(cfg, 64, BS), tokens, 0, 64, 64,
        blocks, return_choice=True, return_stats=True)
    np.testing.assert_array_equal(plain, logits)
    assert who.shape == (cfg.num_layers, 64, cfg.num_experts_per_tok)
    assert solar_kda.stats_names(cfg) == sarvam_mla.ROUTING_STATS
    assert int(stats[0]) == 64 * cfg.num_layers * cfg.num_experts_per_tok


def test_the_served_preset_is_the_share_the_file_states():
    cfg = PRESETS["solar-open2-250b-ep8"]
    assert (cfg.num_experts, cfg.router_experts) == (40, 320)
    assert (cfg.vocab_size, cfg.published_vocab_size) == (24576, 196608)
    assert solar_kda._kinds(cfg) == ["gqa", "kda", "kda", "kda"]
    # One softmax layer's K and V: 8 x 128 x 2 x 2 B a position.
    assert solar_kda.cache_bytes_per_token(cfg) == 4096
    # Three layers of 64 x 128 x 128 float32 and 3 x 24,576 bf16: 13.0 MB.
    assert solar_kda.state_bytes_per_slot(cfg) == 3 * (4194304 + 147456)
    shapes = jax.eval_shape(
        lambda: solar_kda.init_params(cfg, jax.random.PRNGKey(0)))
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(shapes))
    assert abs(held / 1e9 - 6.6) < 0.1              # ISSUE 48's arithmetic
    gqa = sum(x.size for x in jax.tree_util.tree_leaves(shapes["layers"][0]))
    kda_ = sum(x.size for x in jax.tree_util.tree_leaves(shapes["layers"][1]))
    experts = 40 * 3 * 4096 * 1280
    assert abs((gqa - experts) / 1e6 - 126) < 1     # the full gate counted
    assert abs((kda_ - experts) / 1e6 - 155) < 1


# -- the two kernels, interpreted ---------------------------------------------


def _recurrence_inputs(T, H, D, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (T, H, D)) * D ** -0.5
    k = solar_kda._l2(jax.random.normal(ks[1], (T, H, D)))
    v = jax.random.normal(ks[2], (T, H, D))
    g = -jax.random.uniform(ks[3], (T, H, D), minval=0.0, maxval=1.6)
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (T, H)))
    s0 = jax.random.normal(ks[5], (H, D, D)) * 0.1
    return q, k, v, g, beta, s0


@pytest.mark.parametrize("snapshot_len", [None, 0, 48, 256])
def test_the_prefill_kernel_is_the_recurrence(snapshot_len):
    """``kda_prefill_pallas`` (interpreted), two tiles of tokens, against the
    reference's token-by-token scan; the snapshot against a shorter scan."""
    q, k, v, g, beta, s0 = _recurrence_inputs(512, 2, 128)
    o, s1, snap = kda.kda_prefill_pallas(
        q, k, v, g, beta, s0, snapshot_len, interpret=True)
    want_o, want_s = ref.delta_rule(q, k, v, g, beta, s0)
    _close(o, want_o, 1e-5)
    _close(s1, want_s, 1e-5)
    if snapshot_len is None:
        assert snap is None
    else:
        n = snapshot_len
        _close(snap, ref.delta_rule(
            q[:n], k[:n], v[:n], g[:n], beta[:n], s0)[1] if n else s0, 1e-5)
    plain = solar_kda.kda_chunk_plain(q, k, v, g, beta, s0, snapshot_len)
    _close(plain[0], want_o, 1e-5)
    _close(plain[1], want_s, 1e-5)


def test_the_decode_kernel_is_one_step_in_place():
    q, k, v, g, beta, _ = _recurrence_inputs(4, 32, 128, seed=1)
    state = jax.random.normal(jax.random.PRNGKey(3), (6, 32, 128, 128)) * 0.1
    slots = jnp.asarray([4, 2, 0, 0], jnp.int32)
    live = jnp.asarray([True, True, False, False])
    g = jnp.where(live[:, None, None], g, 0.0)
    beta = jnp.where(live[:, None], beta, 0.0)
    o, after = kda.kda_decode_pallas(q, k, v, g, beta, state, slots,
                                     interpret=True)
    want_o, want_rows = solar_kda.kda_step_plain(
        q, k, v, g, beta, state[slots])
    _close(o[:2], want_o[:2], 1e-6)
    _close(after[slots[:2]], want_rows[:2], 1e-6)
    for untouched in (0, 1, 3, 5):       # the null slot: dead rows, bit-equal
        np.testing.assert_array_equal(after[untouched], state[untouched])
    one = ref.delta_rule(q[0][None], k[0][None], v[0][None], g[0][None],
                         beta[0][None], state[4])
    _close(o[0], one[0][0], 1e-5)


# -- a slot is read and written where it lies ---------------------------------


@pytest.fixture
def kernels_serve(monkeypatch):
    """The module's TPU branch on the CPU: both kernels, interpreted."""
    monkeypatch.setattr(solar_kda, "use_pallas_kda", lambda cfg: True)
    for name in ("kda_prefill_pallas", "kda_decode_pallas"):
        monkeypatch.setattr(kda, name, functools.partial(
            getattr(kda, name), interpret=True))


def _layer_case(T, seed=0, slots=6):
    """One ``kda`` layer at the kernels' tile (2 heads of 128), a pool whose
    every slot holds something, a chunk's normed input."""
    cfg = _cfg(linear_num_heads=2, linear_head_dim=128)
    layer = _params(cfg, seed)["layers"][1]
    ks = jax.random.split(jax.random.PRNGKey(seed + 7), 3)
    pools = (jax.random.normal(ks[0], (slots, 2, 128, 128)) * 0.1,
             jax.random.normal(ks[1], solar_kda.rows_pool_shape(
                 slots, 3, solar_kda._conv_width(cfg))))
    return cfg, layer, pools, jax.random.normal(ks[2], (T, cfg.hidden_size))


def _others_bit_equal(before, after, written):
    for was, now in zip(before, after):
        for slot in set(range(was.shape[0])) - set(written):
            np.testing.assert_array_equal(was[slot], now[slot])


# (T, valid, start, slot, snapshot slot, snapshot length)
@pytest.mark.parametrize("T, valid, start, slot, snap_slot, snap_len", [
    (256, 200, -1, 1, None, None),   # from zeros, no snapshot (the compare's)
    (256, 200, -1, 1, 4, 64),        # from zeros, a snapshot mid-chunk
    (256, 256, 1, 1, 1, 0),          # a second chunk: start == slot, and the
                                     # served "no snapshot": its own slot at 0
    (512, 300, 4, 2, 0, 0),          # resumed from a snapshot, the null slot
    (512, 512, 4, 2, 5, 448),        # ... a snapshot at the last boundary
    (512, 500, 4, 2, 5, 256),        # ... at the second tile's first token
], ids=["zeros", "zeros-snapshot", "own-slot", "resumed", "resumed-last",
        "resumed-tile"])
def test_a_prefill_writes_the_slots_it_names_and_nothing_else(
        kernels_serve, T, valid, start, slot, snap_slot, snap_len):
    """``_kda_prefill`` through ``kda_prefill_pallas`` (which takes and hands
    back the state as the pool keeps it) against ``kda_chunk_plain`` and
    XLA's scatter; every slot the chunk does not name keeps its bits."""
    cfg, layer, pools, x = _layer_case(T)
    live = jnp.arange(T) < valid
    named = tuple(None if v is None else jnp.int32(v)
                  for v in (slot, start, snap_slot, snap_len))
    run = jax.jit(lambda pools, x: solar_kda._kda_prefill(
        layer, cfg, pools, x, live, jnp.int32(valid), named))
    out, after = run(pools, x)
    with pytest.MonkeyPatch.context() as plain:
        plain.setattr(solar_kda, "use_pallas_kda", lambda cfg: False)
        want_out, want = jax.jit(lambda pools, x: solar_kda._kda_prefill(
            layer, cfg, pools, x, live, jnp.int32(valid), named))(pools, x)
    _close(out, want_out, 1e-5)
    written = {slot} | ({snap_slot} - {None})
    for slot_ in written:
        _close(after[0][slot_], want[0][slot_], 1e-5)
    np.testing.assert_array_equal(after[1], want[1])   # the rows: one code
    _others_bit_equal(pools, after, written)
    _others_bit_equal(pools, want, written)
    # The rows are the last three of the stream at each place.
    W = solar_kda._conv_width(cfg)
    u = solar_kda._dot(x, layer["qkv_proj"]).astype(x.dtype)
    head = (jnp.zeros((3, W)) if start < 0 else pools[1][start].reshape(3, W))
    full = jnp.concatenate([head, u])
    np.testing.assert_array_equal(
        after[1][slot].reshape(-1), full[valid:valid + 3].reshape(-1))
    if snap_slot not in (None, slot):
        np.testing.assert_array_equal(
            after[1][snap_slot].reshape(-1),
            full[snap_len:snap_len + 3].reshape(-1))


@pytest.mark.parametrize("path", ["kernel", "plain"])
def test_a_decode_step_leaves_dead_rows_and_unnamed_slots_alone(
        path, request):
    """Rows 0 and 1 live on slots 4 and 2; two padding rows share the null
    slot and a dead row names slot 3: of both pools only slots 4 and 2 move,
    and their rows shift by one."""
    if path == "kernel":
        request.getfixturevalue("kernels_serve")
    cfg, layer, pools, x = _layer_case(5, seed=2)
    slots = jnp.asarray([4, 2, 0, 0, 3], jnp.int32)
    live = jnp.asarray([True, True, False, False, False])
    out, after = jax.jit(lambda pools, x: solar_kda._kda_decode(
        layer, cfg, pools, x, live, slots))(pools, x)
    _others_bit_equal(pools, after, {4, 2})
    W = solar_kda._conv_width(cfg)
    u = solar_kda._dot(x, layer["qkv_proj"]).astype(x.dtype)
    for row, slot in ((0, 4), (1, 2)):
        was, now = (a[1][slot].reshape(3, W) for a in (pools, after))
        np.testing.assert_array_equal(now[:2], was[1:])
        np.testing.assert_array_equal(now[2], u[row])
        assert not np.array_equal(after[0][slot], pools[0][slot])
    assert out.shape == (5, 2 * 128)


# -- the engine, both pools -------------------------------------------------


def _engine_config(**overrides):
    return config_from_preset("tiny-solar", **{
        "model.dtype": "float32", "scheduler.prefill_buckets": (64, 128),
        "scheduler.max_num_seqs": 4, "scheduler.mixed_batch": False,
        **overrides})


def _engine(**overrides):
    return LLMEngine(_engine_config(**overrides))


def test_the_engine_serves_it_end_to_end():
    """Allocation of pages and slots in one tree, a resumed admission, the
    K=8 window with the rows' slots, the counters, the reference's tokens."""
    eng = _engine()
    cfg = eng.config.model
    pool = eng.state_pool
    assert (pool.live_slots, pool.snapshot_slots, pool.num_slots) == (
        6, 10, 17)
    assert eng.kv_caches[0][0].shape == (
        eng.block_pool.num_blocks, BS, cfg.num_kv_heads, cfg.head_dim)
    for state, conv in eng.kv_caches[1:]:
        assert state.shape == (17, 4, 16, 16) and state.dtype == jnp.float32
        assert conv.shape == (17, 3 * 3 * 4 * 16 // 64, 64)
    assert eng._state_bytes() == 17 * solar_kda.state_bytes_per_slot(cfg)
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
    assert stats["state_snapshots_taken"] == 2
    assert stats["state_recomputed_tokens"] == 0
    assert stats["state_slots_in_use"] == 2          # snapshots; none live
    windows = eng.obs.windows_payload()["windows"]
    decodes = [w for w in windows if w["rows"]]
    assert decodes and all("window_fn" in w["programs"] for w in decodes)
    assert all(w["state_rows"] == w["rows"] for w in decodes)
    prefills = sorted((w for w in windows if not w["rows"]),
                      key=lambda w: w["dispatched_at"])
    assert [w["state_resumed"] for w in prefills] == [False, False, True]
    assert all("moe_assigned" in w for w in windows)


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
        _engine(**overrides)


def test_two_rounds_through_the_async_engine_with_and_without_caching():
    """Two rounds of two sessions through ``AsyncEngine`` (the step thread,
    the handler's prefix chain): with prefix caching on, round two resumes
    from round one's snapshot; the tokens are those of caching off."""
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
            # The answer in the history is synthetic, as the cell's is.
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
