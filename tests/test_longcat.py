"""models/longcat.py (a layer of two latent attentions and two dense FFNs with
one routed FFN across them, a softmax router some of whose outputs are
identity experts, held by share) against its plain reference,
bench/reference/longcat.py: the tiny preset, seeded weights, float32, on the
CPU.  The reference is imported by path from the benchmark's own file, so the
tests and the chip's compare hold the module to one text.
"""

import dataclasses
import importlib.util
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import PRESETS, config_from_preset
from production_stack_tpu.engine.core.engine import LLMEngine
from production_stack_tpu.engine.core.sequence import SamplingParams
from production_stack_tpu.engine.models import get_model, longcat, sarvam_mla
from test_laguna import _lowered   # the same shapes, the same hashing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS = 16   # tokens a cache block
STACKS = ("experts_gate", "experts_up", "experts_down")


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_longcat",
        os.path.join(ROOT, "bench", "reference", "longcat.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()


@pytest.fixture(autouse=True)
def _highest_precision(request):
    if "lower" in request.node.name:   # pins the served text
        yield
        return
    with jax.default_matmul_precision("highest"):
        yield


def _cfg(**changes):
    return dataclasses.replace(PRESETS["tiny-longcat"], dtype="float32",
                               **changes)


def _hp(cfg, **changes):
    """The reference's view of ``cfg``: the configuration file's keys."""
    hp = dict(
        hidden_size=cfg.hidden_size, num_attention_heads=cfg.num_heads,
        kv_lora_rank=cfg.kv_lora_rank, q_lora_rank=cfg.q_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        mla_scale_q_lora=True, mla_scale_kv_lora=True,
        rope_theta=cfg.rope_theta, rms_norm_eps=cfg.rms_norm_eps,
        n_routed_experts=cfg.num_experts, moe_topk=cfg.num_experts_per_tok,
        zero_expert_num=cfg.zero_expert_num,
        routed_scaling_factor=cfg.routed_scaling_factor,
        vocab_size=cfg.vocab_size,
        published={"n_routed_experts": cfg.router_experts})
    hp.update(changes)
    return hp


_PROGRAMS = {}


def _program(step, cfg, more, fault=None):
    """The module's ``prefill`` or ``decode`` (looked up when it is traced,
    so that a planted fault is in it) jitted once a configuration, a set of
    flags and a fault."""
    key = (step, repr(cfg), tuple(sorted(more.items())), fault)
    if key not in _PROGRAMS:
        _PROGRAMS[key] = jax.jit(lambda params, *args: getattr(
            longcat, step)(params, cfg, *args, **more))
    return _PROGRAMS[key]


def _want(params, cfg, tokens, **changes):
    """The reference's logits for the whole sequence, jitted once."""
    hp = _hp(cfg, **changes)
    key = ("reference", repr(sorted(hp.items(), key=str)))
    if key not in _PROGRAMS:
        _PROGRAMS[key] = jax.jit(lambda p, t: ref.forward(p, hp, t))
    return _PROGRAMS[key](params, jnp.asarray(tokens))


def _prefill(cfg, params, cache, tokens, start, n, T, blocks, fault=None,
             **more):
    """Chunk ``tokens[start:start + n]`` in a ``T``-slot program."""
    slots = np.zeros(T, np.int32)
    slots[:n] = tokens[start:start + n]
    prefix = np.zeros(64, np.int32)
    prefix[:start // BS] = blocks[:start // BS]
    new = np.zeros(T // BS, np.int32)
    held = -(-n // BS)
    new[:held] = blocks[start // BS:start // BS + held]
    return _program("prefill", cfg, more, fault)(
        params, jnp.asarray(slots), jnp.int32(start), jnp.asarray(prefix),
        jnp.asarray(new), jnp.int32(n), cache)


def _decode(cfg, params, cache, token, pos, blocks, fault=None, **more):
    """One live row at ``pos`` beside one padding row."""
    tables = np.zeros((2, 64), np.int32)
    tables[0, :len(blocks)] = blocks
    return _program("decode", cfg, more, fault)(
        params, jnp.asarray([token, 0]), jnp.asarray([pos, 0]),
        jnp.asarray(tables), jnp.asarray([pos + 1, 0]),
        jnp.asarray([blocks[pos // BS], 0]), jnp.asarray([pos % BS, 0]),
        cache)


def _case(seed=0, n=150, **changes):
    cfg = _cfg(**changes)
    params = longcat.init_params(cfg, jax.random.PRNGKey(seed))
    tokens = np.random.default_rng(seed).integers(
        1, cfg.vocab_size, n).astype(np.int32)
    blocks = np.arange(1, 1 + -(-n // BS), dtype=np.int32)
    return cfg, params, tokens, blocks, longcat.init_cache(cfg, 64, BS)


def _err(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def _close(got, want, tol=2e-5):
    assert _err(got, want) <= tol


def _through_the_cache(cfg, params, tokens, blocks, cache, chunks=(64, 36),
                       fault=None):
    """The logits at the last prompt position and at four decode steps, the
    prompt prefilled in ``chunks`` (64-slot programs)."""
    start, rows = 0, {}
    for n in chunks:
        logits, cache = _prefill(cfg, params, cache, tokens, start, n, 64,
                                 blocks, fault)
        start += n
    rows[start - 1] = logits
    for pos in range(start, start + 4):
        logits, cache = _decode(cfg, params, cache, tokens[pos], pos, blocks,
                                fault)
        rows[pos] = logits[0]
    return rows


# -- the module and the preset -----------------------------------------------


def test_the_registry_serves_the_preset_and_the_shared_pieces_are_imported():
    assert get_model(PRESETS["tiny-longcat"].name) is longcat
    assert get_model(PRESETS["longcat-flash-omni-ep32"].name) is longcat
    # One latent attention, one cache layout, one router, one dispatch.
    for name in ("held_experts", "route", "init_cache", "prefill_attention",
                 "decode_attention", "cache_bytes_per_token", "_swiglu",
                 "attention_paths", "prefill_attn_tiles"):
        assert getattr(longcat, name) is getattr(sarvam_mla, name), name
    assert longcat.stats_names(_cfg()) == sarvam_mla.ROUTING_STATS + (
        "moe_zero_assigned",)


def test_the_served_preset_is_the_share_the_file_states():
    cfg = PRESETS["longcat-flash-omni-ep32"]
    assert (cfg.num_experts, cfg.router_experts, cfg.zero_expert_num,
            cfg.router_width, cfg.num_experts_per_tok) == (16, 512, 256, 768,
                                                           12)
    assert (cfg.vocab_size, cfg.published_vocab_size) == (16384, 131072)
    assert (cfg.num_layers, cfg.attn_per_layer, cfg.cache_layers) == (4, 2, 8)
    assert sarvam_mla.cache_width(cfg) == cfg.head_dim == 576
    # 576 values of content in 640 lanes, eight arrays, bf16.
    assert longcat.cache_bytes_per_token(cfg) == 640 * 2 * 8 == 10_240
    assert (cfg.router_scoring, cfg.norm_topk_prob) == ("softmax", False)
    assert cfg.rope_scaling is None and cfg.rope_theta == 1e7
    assert sarvam_mla.softmax_scale(cfg) == 192 ** -0.5
    shapes = jax.eval_shape(
        lambda: longcat.init_params(cfg, jax.random.PRNGKey(0)))
    size = lambda tree: sum(x.size for x in jax.tree_util.tree_leaves(tree))
    layer = shapes["layers"][0]
    expert = size([layer[k] for k in STACKS]) // cfg.num_experts
    assert expert == 3 * 6144 * 2048 == 37_748_736          # ISSUE 61's table
    norms = 2 * (6144 + 1536 + 512) + 2 * 6144 + 768        # scales, the bias
    assert size(layer) - 16 * expert - norms == 638_844_928  # 638.8 M a layer
    assert abs(size(shapes) * 2 / 1e9 - 10.34) < 0.01
    # Every other preset keeps one array a layer and the router it had.
    for name, other in PRESETS.items():
        if "longcat" not in name:
            assert other.cache_layers == other.num_layers, name
            assert (other.router_scoring, other.norm_topk_prob,
                    other.zero_expert_num, other.mla_scale_q_lora,
                    other.mla_scale_kv_lora) == ("sigmoid", True, 0, False,
                                                 False), name


def test_the_cache_is_two_arrays_a_layer():
    cfg = _cfg()
    cache = longcat.init_cache(cfg, 8, BS)
    assert len(cache) == 2 * cfg.num_layers == cfg.cache_layers == 4
    assert all(c.shape == (8, BS, 128) for c in cache)
    assert longcat.cache_bytes_per_token(cfg) == 128 * 4 * 4
    assert "4 cache arrays (2 a layer)" in longcat.layer_form(cfg)
    assert longcat.layer_form(PRESETS["longcat-flash-omni-ep32"]) == (
        "2 latent attentions + 2 dense FFN + 1 routed FFN (shortcut), router "
        "768 = 512 + 256 identity, 16 held; 8 cache arrays (2 a layer)")


# -- against the reference ---------------------------------------------------


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("chunks", [(64, 36), (48, 52), (32, 32, 36)],
                         ids=["64+36", "48+52", "32+32+36"])
def test_prefill_in_chunks_then_decode_matches_the_reference(seed, chunks):
    """Every chunk after the first attends to cached latents in both of a
    layer's arrays (expanded); the decode steps read them through the cache
    (absorbed)."""
    cfg, params, tokens, blocks, cache = _case(seed)
    want = _want(params, cfg, tokens)
    for pos, logits in _through_the_cache(
            cfg, params, tokens, blocks, cache, chunks).items():
        _close(logits, want[pos])


@pytest.mark.parametrize("seed", [3, 4])
def test_a_prompt_in_one_chunk_with_no_cached_prefix_matches_too(seed):
    cfg, params, tokens, blocks, cache = _case(seed, 70)
    want = _want(params, cfg, tokens)
    logits, cache = _prefill(cfg, params, cache, tokens, 0, 60, 64, blocks)
    _close(logits, want[59])
    logits, cache = _decode(cfg, params, cache, tokens[60], 60, blocks)
    _close(logits[0], want[60])


def test_padding_slots_and_rows_change_nothing():
    """The same 40 tokens in a 48- and a 64-slot program."""
    cfg, params, tokens, blocks, _ = _case(5, 64)
    got = [_prefill(cfg, params, longcat.init_cache(cfg, 64, BS), tokens, 0,
                    40, T, blocks)[0] for T in (48, 64)]
    _close(got[0], got[1], 1e-6)


def _full_layer(cfg, seed=0):
    """(the configuration with every real expert held, one layer's weights
    with all of them, normed inputs)."""
    full = dataclasses.replace(cfg, num_experts=cfg.router_experts)
    layer = longcat.init_params(full, jax.random.PRNGKey(seed))["layers"][0]
    x = jax.random.normal(jax.random.PRNGKey(seed + 7),
                          (40, cfg.hidden_size), jnp.float32)
    return full, layer, x


def _share(layer, cfg, first):
    """The layer as the chip that holds real experts ``first .. first + E -
    1`` sees it: its experts' stacks, and the router's real columns turned so
    that its experts come first (the identities stay where they are)."""
    E, real = cfg.num_experts, cfg.router_experts
    held = dict(layer, **{k: layer[k][first:first + E] for k in STACKS})
    turn = lambda w: jnp.concatenate(
        [jnp.roll(w[..., :real], -first, axis=-1), w[..., real:]], -1)
    held["router"] = turn(layer["router"])
    held["router_bias"] = turn(layer["router_bias"])
    return held


def test_the_shares_add_up_to_the_uncut_layer():
    """The parts of ``MoE(u)`` that shares 0-3 compute, with the identity
    term (which every chip computes alike) counted once, are the uncut
    reference's layer."""
    cfg = _cfg(num_experts=2)           # 2 of a router's 8 real: four shares
    full, layer, x = _full_layer(cfg)
    want, _short, picked = ref.moe(layer, _hp(full), x)
    live = jnp.ones(x.shape[0], bool)
    whole, _who, counted = longcat.moe(_share(layer, cfg, 0), cfg, x, live)
    first_part = None
    total, pairs = 0.0, 0
    for first in range(0, cfg.router_experts, cfg.num_experts):
        held = _share(layer, cfg, first)
        who, g = longcat.route(held, cfg, x)
        part, stats = longcat.held_experts(held, cfg, x, who, g, live)
        theirs, _, _ = ref.moe(
            dict(layer, **{k: layer[k][first:first + cfg.num_experts]
                           for k in STACKS}),
            _hp(full), x, held=(first, cfg.num_experts), identity=False)
        _close(part, theirs, 1e-5)
        if first == 0:
            first_part = part
        total = total + part
        pairs += int(stats[1])
    identity = whole - first_part          # what share 0 adds beside its own
    _close(total + identity, want, 1e-5)
    zero = int(counted[-1])
    assert zero == int(picked.sum()) > 0
    assert pairs + zero == x.shape[0] * cfg.num_experts_per_tok


def test_the_router_is_a_softmax_that_is_not_renormalised():
    cfg = _cfg()
    _full, layer, x = _full_layer(cfg, 1)
    who, g = longcat.route(layer, cfg, x)
    p = jax.nn.softmax(x @ layer["router"], -1)
    assert p.shape[1] == cfg.router_width == 12
    want = jax.lax.top_k(p + layer["router_bias"], cfg.num_experts_per_tok)[1]
    assert np.array_equal(np.asarray(who), np.asarray(want))
    _close(g, 6.0 * jnp.take_along_axis(p, who, -1), 1e-6)
    assert float(jnp.max(jnp.abs(g.sum(-1) - 6.0))) > 1.0   # no sum to 6
    # The bias selects at near-ties and never weighs.
    assert float(jnp.std(layer["router_bias"])) < float(jnp.std(p)) / 2


def test_an_identity_pick_returns_its_input_times_its_share():
    """Every column of the router but the identities' pushed far down: all
    picks are identities, no expert runs, and MoE(u) is u times the sum of
    the shares."""
    cfg = _cfg()
    _full, layer, x = _full_layer(cfg, 2)
    real = cfg.router_experts
    only = dict(layer, router_bias=jnp.where(
        jnp.arange(cfg.router_width) >= real, 1.0, 0.0))
    only = _share(only, cfg, 0)
    live = jnp.arange(x.shape[0]) < 33      # seven rows are padding
    out, who, counted = longcat.moe(only, cfg, x, live)
    assert int(who.min()) >= real
    p = jax.nn.softmax(x @ layer["router"], -1)
    share = 6.0 * jnp.take_along_axis(p, who, -1).sum(-1)
    _close(out, share[:, None] * x, 1e-6)
    assert [int(n) for n in counted] == [33 * 3, 0, 0, 0, 33 * 3]


def test_return_choice_and_stats_leave_the_logits_bit_equal():
    cfg, params, tokens, blocks, cache = _case(5)
    plain, cache = _prefill(cfg, params, cache, tokens, 0, 64, 64, blocks)
    again, _, choice, stats = _prefill(
        cfg, params, longcat.init_cache(cfg, 64, BS), tokens, 0, 64, 64,
        blocks, return_choice=True, return_stats=True)
    assert np.array_equal(np.asarray(plain), np.asarray(again))
    k = cfg.num_experts_per_tok
    assert choice.shape == (cfg.num_layers, 64, k)
    assert choice.dtype == jnp.int32
    assert int(choice.max()) >= cfg.router_experts    # ids over all 12
    assert stats.shape == (5,) and stats.dtype == jnp.int32
    # The reference's own count of identity picks over the same positions.
    _x, _short, picked = ref.hidden(
        params, _hp(cfg), jnp.asarray(tokens[:64]))
    named = dict(zip(longcat.stats_names(cfg), (int(n) for n in stats)))
    assert named["moe_zero_assigned"] == int(picked.sum()) > 0
    assert named["moe_assigned"] == 64 * cfg.num_layers * k
    assert named["moe_zero_assigned"] == int(
        (np.asarray(choice) >= cfg.router_experts).sum())
    assert named["moe_assigned_here"] == int(
        (np.asarray(choice) < cfg.num_experts).sum())
    plain, _ = _decode(cfg, params, cache, tokens[64], 64, blocks)
    again, _, choice, stats = _decode(
        cfg, params, cache, tokens[64], 64, blocks, return_choice=True,
        return_stats=True)
    assert np.array_equal(np.asarray(plain), np.asarray(again))
    assert choice.shape == (cfg.num_layers, 2, k)
    # The padding row is routed nowhere and not counted.
    assert int(stats[0]) == cfg.num_layers * k
    assert int(stats[4]) == int(
        (np.asarray(choice)[:, 0] >= cfg.router_experts).sum())


def test_the_reference_follows_a_choice_and_measures_its_shortfall():
    cfg, params, tokens, blocks, cache = _case(6, 64)
    _, _, choice = _prefill(cfg, params, cache, tokens, 0, 64, 64, blocks,
                            return_choice=True)
    hp = _hp(cfg)
    own = ref.forward(params, hp, jnp.asarray(tokens))
    followed, shortfall = ref.forward(params, hp, jnp.asarray(tokens),
                                      choice=choice)
    _close(followed, own, 1e-5)
    assert shortfall.shape == (cfg.num_layers, 64)
    assert float(shortfall.max()) < 1e-3
    worse = np.asarray(choice).copy()
    worse[0, 10, 0] = np.setdiff1d(np.arange(cfg.router_width),
                                   worse[0, 10])[-1]
    _, shortfall = ref.forward(params, hp, jnp.asarray(tokens),
                               choice=jnp.asarray(worse))
    assert float(shortfall[0, 10]) > 0
    worse[0, 10, 0] = cfg.router_width       # past the identities
    _, shortfall = ref.forward(params, hp, jnp.asarray(tokens),
                               choice=jnp.asarray(worse))
    assert np.isinf(float(shortfall[0, 10]))


# -- planted faults ----------------------------------------------------------

_RIGHT = {"moe": longcat.moe, "route": longcat.route,
          "_swiglu": longcat._swiglu, "rms_norm": longcat.rms_norm}


def _identities_add_nothing(monkeypatch):
    def moe(layer, cfg, x, live):
        who, g = _RIGHT["route"](layer, cfg, x)
        routed, stats = longcat.held_experts(layer, cfg, x, who, g, live)
        return routed, who, jnp.concatenate([stats, stats[:1] * 0])
    monkeypatch.setattr(longcat, "moe", moe)


def _shares_renormalised(monkeypatch):
    def route(layer, cfg, x):
        who, g = _RIGHT["route"](layer, cfg, x)
        return who, cfg.routed_scaling_factor * g / g.sum(-1, keepdims=True)
    monkeypatch.setattr(longcat, "route", route)


def _shortcut_lands_before_attn_1(monkeypatch):
    """``b = a + FFN_0(u) + m``, ``y = c + FFN_1(.)``: the routed FFN's
    result taken up by the dense FFN that follows it in the trace."""
    waiting = []

    def moe(layer, cfg, x, live):
        out, who, counted = _RIGHT["moe"](layer, cfg, x, live)
        waiting.append(out)
        return jnp.zeros_like(out), who, counted

    def swiglu(x, gate, up, down):
        out = _RIGHT["_swiglu"](x, gate, up, down)
        return out + waiting.pop() if waiting else out

    monkeypatch.setattr(longcat, "moe", moe)
    monkeypatch.setattr(longcat, "_swiglu", swiglu)


def _ffn_0_reads_a_unnormed(monkeypatch):
    calls = []

    def rms_norm(x, weight, eps):
        calls.append(1)
        # A layer: norm_a0, norm_f0, norm_a1, norm_f1; then the final norm.
        if len(calls) % 4 == 2:
            return x
        return _RIGHT["rms_norm"](x, weight, eps)

    monkeypatch.setattr(longcat, "rms_norm", rms_norm)


def test_the_dense_ffn_after_the_routed_one_comes_first_in_the_trace(
        monkeypatch):
    """What ``_shortcut_lands_before_attn_1`` rests on, so that the fault it
    plants is the one it names."""
    order = []
    monkeypatch.setattr(longcat, "moe", lambda *a: (
        order.append("moe"), _RIGHT["moe"](*a))[1])
    monkeypatch.setattr(longcat, "_swiglu", lambda *a: (
        order.append("ffn"), _RIGHT["_swiglu"](*a))[1])
    cfg, params, tokens, blocks, cache = _case(7, 64)
    _prefill(cfg, params, cache, tokens, 0, 64, 64, blocks, "order")
    assert order == ["moe", "ffn", "ffn"] * cfg.num_layers


@pytest.mark.parametrize("fault", [
    _identities_add_nothing, _shares_renormalised,
    _shortcut_lands_before_attn_1, _ffn_0_reads_a_unnormed,
    {"mla_scale_q_lora": False}, {"mla_scale_kv_lora": False},
], ids=["identities add nothing", "shares renormalised",
        "m added before Attn_1", "FFN_0 fed a un-normed", "no alpha_q",
        "no alpha_kv"])
def test_a_planted_fault_fails(monkeypatch, fault):
    cfg, params, tokens, blocks, cache = _case(7)
    want = _want(params, cfg, tokens)
    right = _through_the_cache(cfg, params, tokens, blocks, cache)
    assert max(_err(v, want[pos]) for pos, v in right.items()) < 2e-5
    if isinstance(fault, dict):
        cfg, name = dataclasses.replace(cfg, **fault), None
    else:
        fault(monkeypatch)
        name = fault.__name__
    wrong = _through_the_cache(
        cfg, params, tokens, blocks, longcat.init_cache(cfg, 64, BS),
        fault=name)
    assert min(_err(v, want[pos]) for pos, v in wrong.items()) > 1e-3


# -- what the shared file's other users lower to -----------------------------


@pytest.mark.parametrize("preset, want", [
    # prefill: taken again at PR 62, whose one change to it off a TPU is that a
    # window layer's buffer goes in as a pool of one page (a gather of [0]).
    ("tiny-laguna", ["46ba9af80051a782", "58f54adccb7f2109"]),
])
def test_lagunas_programs_lower_as_before_this_module(preset, want):
    """The scale flags of ``_project``, the router's scoring and
    renormalisation and the count of cache arrays are decided in Python at
    trace time: ``tiny-laguna``'s ``prefill`` and ``decode`` (it imports the
    router and the dispatch) lower to the text they had at the commit before
    (hashes taken there, same JAX; ``decode``'s still is that one), as
    ``tests/test_laguna.py`` holds the other five presets."""
    with jax.default_matmul_precision(None):
        assert _lowered(preset) == want


def test_longcats_own_programs_hold_both_scales_and_a_softmax():
    """... and the new preset's do trace them: its text differs from the
    same preset's with a flag off."""
    with jax.default_matmul_precision(None):
        own = _lowered("tiny-longcat")
        for flag in ("mla_scale_q_lora", "mla_scale_kv_lora"):
            PRESETS["_probe"] = dataclasses.replace(
                PRESETS["tiny-longcat"], **{flag: False})
            try:
                assert _lowered("_probe") != own, flag
            finally:
                del PRESETS["_probe"]


# -- the engine --------------------------------------------------------------


def _engine_config(**overrides):
    return config_from_preset("tiny-longcat", **{
        "model.dtype": "float32", "scheduler.prefill_buckets": (32, 64),
        "scheduler.max_num_seqs": 4, "scheduler.mixed_batch": False,
        **overrides})


def test_the_engine_serves_it_end_to_end(caplog):
    """Allocation by the module's init_cache (two arrays a layer), a
    prefix-cache hit on the latent blocks of all four, the K-step window,
    the routing counters with the identities' own, the reference's greedy
    tokens."""
    with caplog.at_level(logging.INFO):
        eng = LLMEngine(_engine_config())
    cfg = eng.config.model
    assert ("Layer: 2 latent attentions + 2 dense FFN + 1 routed FFN "
            "(shortcut), router 12 = 8 + 4 identity, 4 held; 4 cache arrays "
            "(2 a layer)") in caplog.text
    assert [c.shape for c in eng.kv_caches] == [
        (eng.block_pool.num_blocks, BS, sarvam_mla.cache_lanes(cfg))
    ] * (2 * cfg.num_layers)
    assert eng._kv_bytes(1) == BS * longcat.cache_bytes_per_token(cfg) == (
        BS * 128 * 4 * 4)
    # One kind of layer, four arrays of it.
    assert eng._attn_kinds == [("full", None, 4, False)]
    rng = np.random.default_rng(0)
    shared = rng.integers(1, 260, 64).tolist()
    prompts = [shared + rng.integers(1, 260, n).tolist() for n in (30, 20)]
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
            # The engine's token is the reference's, or ties with it.
            assert logits.max() - logits[token] <= 1e-4 * np.abs(logits).max()
    stats = eng.stats()
    assert stats["prefix_cache_hit_tokens"] == 64
    windows = eng.obs.windows_payload()["windows"]
    decodes = [w for w in windows if w["rows"]]
    assert decodes and all("window_fn" in w["programs"] for w in decodes)
    k = cfg.num_experts_per_tok
    for w in windows:
        rows = w["tokens_emitted"] if w["rows"] else w["new_tokens"]
        assert w["moe_assigned"] == rows * cfg.num_layers * k
        assert 0 <= w["moe_zero_assigned"] <= (
            w["moe_assigned"] - w["moe_assigned_here"])
    zero = sum(w["moe_zero_assigned"] for w in windows)
    assert stats["moe_zero_assigned"] == zero > 0
    # 4 identities of 12 outputs: about a third of the picks.
    assert 0.15 < zero / sum(w["moe_assigned"] for w in windows) < 0.55
    # A record's kv_tokens is ONE array's positions (whole blocks); the
    # positions the rows attended count every array: a row an array a step.
    for w in decodes:
        assert w["kv_tokens"] % BS == 0 and 80 <= w["kv_tokens"] <= 112
    steps = sum(w["k"] for w in decodes)
    assert steps == 22    # 11 tokens after the first, two requests
    assert stats["attn_positions"]["full"] > 4 * 84 * steps
    assert stats["attn_positions"]["full"] % 4 == 0
    assert stats["attn_positions"]["window"] == 0


def test_the_k_step_window_equals_single_steps():
    def serve(**overrides):
        eng = LLMEngine(_engine_config(**overrides))
        rng = np.random.default_rng(2)
        for i, n in enumerate((70, 41)):
            eng.add_request(
                f"r{i}", prompt_token_ids=rng.integers(1, 260, n).tolist(),
                sampling_params=SamplingParams(
                    max_tokens=20, temperature=0.0, ignore_eos=True))
        got = {}
        while eng.has_unfinished():
            for out in eng.step():
                got.setdefault(out.seq_id, []).append(out.new_token_id)
        return got

    window = serve()
    single = serve(**{"scheduler.multi_step_window": False})
    assert window == single and all(len(t) == 20 for t in window.values())


async def test_the_identities_counter_is_on_metrics():
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.server.api_server import build_engine_app
    from production_stack_tpu.engine.server.async_engine import AsyncEngine

    engine = AsyncEngine(config_from_preset("tiny-longcat", **{
        "cache.num_blocks": 64, "scheduler.max_num_seqs": 2,
        "scheduler.prefill_buckets": (16, 32),
        "scheduler.mixed_batch": False}))
    client = TestClient(TestServer(build_engine_app(engine, "tiny-longcat")))
    await client.start_server()
    try:
        resp = await client.post("/v1/completions", json={
            "model": "tiny-longcat", "prompt": "hello there", "max_tokens": 6,
            "ignore_eos": True, "temperature": 0})
        assert resp.status == 200
        metrics = await (await client.get("/metrics")).text()
        values = {line.split()[0]: float(line.split()[1])
                  for line in metrics.splitlines()
                  if line.startswith("tpu:moe_")}
        pairs = sum(v for name, v in values.items()
                    if name.startswith("tpu:moe_assignments_total"))
        cfg = PRESETS["tiny-longcat"]
        assert pairs > 0 and pairs % (
            cfg.num_layers * cfg.num_experts_per_tok) == 0
        assert 0 < values["tpu:moe_zero_assigned_total"] <= values[
            'tpu:moe_assignments_total{where="away"}']
        windows = (await (await client.get("/debug/windows")).json())[
            "windows"]
        assert sum(w["moe_zero_assigned"] for w in windows) == values[
            "tpu:moe_zero_assigned_total"]
    finally:
        await client.close()
        await engine.close()


def test_a_router_without_identities_counts_none():
    eng = LLMEngine(config_from_preset("tiny-sarvam", **{
        "scheduler.prefill_buckets": (32, 64), "scheduler.max_num_seqs": 4,
        "scheduler.mixed_batch": False}))
    eng.add_request("r", prompt_token_ids=[5, 6, 7],
                    sampling_params=SamplingParams(
                        max_tokens=9, temperature=0.0, ignore_eos=True))
    while eng.has_unfinished():
        eng.step()
    assert eng.stats()["moe_zero_assigned"] == 0
    assert eng.stats()["moe_assignments"]["held"] > 0
    assert all("moe_zero_assigned" not in w
               for w in eng.obs.windows_payload()["windows"])


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


def test_prompt_logprobs_and_a_checkpoint_are_refused_by_name(tmp_path):
    cfg, params, tokens, blocks, cache = _case(8, 64)
    with pytest.raises(ValueError, match="prompt logprobs"):
        longcat.prefill(params, cfg, *[None] * 5, cache,
                        prompt_targets=jnp.zeros(64, jnp.int32))
    with pytest.raises(ValueError, match="no checkpoint loader"):
        LLMEngine(config_from_preset(
            "tiny-longcat", weights_path=str(tmp_path),
            **{"scheduler.mixed_batch": False}))
