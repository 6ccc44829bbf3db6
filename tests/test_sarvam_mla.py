"""models/sarvam_mla.py (latent attention over routed experts held by share)
against its plain reference, bench/reference/sarvam_mla.py: the tiny preset,
seeded weights, float32, on the CPU.  The reference is imported by path from
the benchmark's own file, so the tests and the chip's compare hold the module
to one text.
"""

import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import PRESETS, config_from_preset
from production_stack_tpu.engine.core.engine import LLMEngine
from production_stack_tpu.engine.core.sequence import SamplingParams
from production_stack_tpu.engine.models import get_model, sarvam_mla

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BS = 16   # tokens a cache block


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_sarvam_mla",
        os.path.join(ROOT, "bench", "reference", "sarvam_mla.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()


@pytest.fixture(autouse=True)
def _highest_precision(request):
    if "lower_to_the_text" in request.node.name:   # pins the served text
        yield
        return
    with jax.default_matmul_precision("highest"):
        yield


def _cfg(**changes):
    return dataclasses.replace(PRESETS["tiny-sarvam"], dtype="float32",
                               **changes)


def _hp(cfg, **changes):
    """The reference's view of ``cfg``: the configuration file's keys."""
    hp = dict(
        hidden_size=cfg.hidden_size, num_attention_heads=cfg.num_heads,
        kv_lora_rank=cfg.kv_lora_rank, qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        use_qk_norm=cfg.use_qk_norm, rope_theta=cfg.rope_theta,
        rope_scaling=cfg.rope_scaling, rms_norm_eps=cfg.rms_norm_eps,
        num_experts=cfg.num_experts,
        num_experts_per_tok=cfg.num_experts_per_tok,
        first_k_dense_replace=cfg.first_k_dense_replace,
        routed_scaling_factor=cfg.routed_scaling_factor,
        vocab_size=cfg.vocab_size,
        published={"num_experts": cfg.router_experts})
    hp.update(changes)
    return hp


def _params(cfg, seed=0):
    return sarvam_mla.init_params(cfg, jax.random.PRNGKey(seed))


def _prefill(cfg, params, cache, tokens, start, n, T, blocks, **more):
    """Chunk ``tokens[start:start + n]`` in a ``T``-slot program."""
    slots = np.zeros(T, np.int32)
    slots[:n] = tokens[start:start + n]
    prefix = np.zeros(64, np.int32)
    prefix[:start // BS] = blocks[:start // BS]
    new = np.zeros(T // BS, np.int32)
    held = -(-n // BS)
    new[:held] = blocks[start // BS:start // BS + held]
    return sarvam_mla.prefill(
        params, cfg, jnp.asarray(slots), jnp.int32(start),
        jnp.asarray(prefix), jnp.asarray(new), jnp.int32(n), cache, **more)


def _decode(cfg, params, cache, token, pos, blocks, **more):
    """One live row at ``pos`` beside one padding row."""
    tables = np.zeros((2, 64), np.int32)
    tables[0, :len(blocks)] = blocks
    return sarvam_mla.decode(
        params, cfg, jnp.asarray([token, 0]), jnp.asarray([pos, 0]),
        jnp.asarray(tables), jnp.asarray([pos + 1, 0]),
        jnp.asarray([blocks[pos // BS], 0]), jnp.asarray([pos % BS, 0]),
        cache, **more)


def _case(seed=0, n=150):
    cfg = _cfg()
    params = _params(cfg, seed)
    tokens = np.random.default_rng(seed).integers(
        1, cfg.vocab_size, n).astype(np.int32)
    blocks = np.arange(1, 1 + -(-n // BS), dtype=np.int32)
    return cfg, params, tokens, blocks, sarvam_mla.init_cache(cfg, 64, BS)


def _close(got, want, tol=2e-5):
    got, want = np.asarray(got), np.asarray(want)
    assert np.max(np.abs(got - want)) <= tol * np.max(np.abs(want))


def test_the_registry_serves_the_preset_with_the_module():
    assert get_model(PRESETS["tiny-sarvam"].name) is sarvam_mla
    assert get_model(PRESETS["sarvam-105b-ep4"].name) is sarvam_mla


@pytest.mark.parametrize("seed", [0, 1])
def test_prefill_in_two_chunks_then_decode_matches_the_reference(seed):
    """The second chunk attends to the first's cached latents (expanded);
    the decode steps read them through the cache (absorbed)."""
    cfg, params, tokens, blocks, cache = _case(seed)
    want = ref.forward(params, _hp(cfg), jnp.asarray(tokens))
    logits, cache = _prefill(cfg, params, cache, tokens, 0, 64, 64, blocks)
    _close(logits, want[63])
    logits, cache = _prefill(cfg, params, cache, tokens, 64, 36, 64, blocks)
    _close(logits, want[99])
    for pos in range(100, 104):
        logits, cache = _decode(cfg, params, cache, tokens[pos], pos, blocks)
        _close(logits[0], want[pos])


def test_absorbed_decode_equals_expanded_attention():
    """One position, two paths: as the last slot of a prefill chunk
    (expanded K and V) and as a decode step over the cache (absorbed)."""
    cfg, params, tokens, blocks, cache = _case(3)
    expanded, _ = _prefill(cfg, params, cache, tokens, 0, 81, 96, blocks)
    _, cache = _prefill(cfg, params, sarvam_mla.init_cache(cfg, 64, BS),
                        tokens, 0, 80, 96, blocks)
    absorbed, _ = _decode(cfg, params, cache, tokens[80], 80, blocks)
    _close(absorbed[0], expanded, 1e-5)


def test_head_groups_give_the_ungrouped_attention(monkeypatch):
    cfg, params, tokens, blocks, cache = _case(4)
    whole, _ = _prefill(cfg, params, cache, tokens, 0, 64, 64, blocks)
    whole2, _ = _prefill(cfg, params, _, tokens, 64, 50, 64, blocks)
    monkeypatch.setattr(sarvam_mla, "SCORE_ROWS", 128)   # 2 heads a group
    monkeypatch.setattr(sarvam_mla, "KEY_TILE", 32)      # 2 tiles of prefix
    cache = sarvam_mla.init_cache(cfg, 64, BS)
    grouped, cache = _prefill(cfg, params, cache, tokens, 0, 64, 64, blocks)
    grouped2, _ = _prefill(cfg, params, cache, tokens, 64, 50, 64, blocks)
    _close(grouped, whole, 1e-5)
    _close(grouped2, whole2, 1e-5)


def _routed_layer(cfg, seed=0):
    """(a routed layer's weights with every one of the router's experts, the
    same layer as the module holds it, normed inputs)."""
    full = dataclasses.replace(cfg, num_experts=cfg.router_experts)
    layer = _params(full, seed)["layers"][cfg.first_k_dense_replace]
    x = jax.random.normal(jax.random.PRNGKey(seed + 7),
                          (40, cfg.hidden_size), jnp.float32)
    return full, layer, x


def _share(layer, cfg, first):
    """The layer as the chip that holds experts ``first .. first + E - 1``
    sees it: its experts' stacks, and the router's columns turned so that
    its experts come first (the module holds the first E by definition)."""
    E = cfg.num_experts
    held = dict(layer)
    for name in ("experts_gate", "experts_up", "experts_down"):
        held[name] = layer[name][first:first + E]
    held["router"] = jnp.roll(layer["router"], -first, axis=1)
    held["router_bias"] = jnp.roll(layer["router_bias"], -first)
    return held


def test_the_four_shares_add_up_to_the_uncut_layer():
    """The routed parts that shares 0-3 compute, plus the shared expert
    once, are the uncut reference's layer."""
    cfg = _cfg(num_experts=2)           # 2 of a router's 8: four shares
    full, layer, x = _routed_layer(cfg)
    want, _ = ref.routed_ffn(layer, _hp(full), x)
    live = jnp.ones(x.shape[0], bool)
    total = sarvam_mla._swiglu(x, layer["shared_gate"], layer["shared_up"],
                               layer["shared_down"])
    pairs = 0
    for first in range(0, cfg.router_experts, cfg.num_experts):
        held = _share(layer, cfg, first)
        who, g = sarvam_mla.route(held, cfg, x)
        part, stats = sarvam_mla.held_experts(held, cfg, x, who, g, live)
        # The same share through the reference, handed that share's range.
        theirs, _ = ref.routed_ffn(
            dict(layer, **{k: layer[k][first:first + cfg.num_experts]
                           for k in ("experts_gate", "experts_up",
                                     "experts_down")}),
            _hp(full), x, held=(first, cfg.num_experts), shared=False)
        _close(part, theirs, 1e-5)
        total = total + part
        pairs += int(stats[1])
    _close(total, want, 1e-5)
    assert pairs == x.shape[0] * cfg.num_experts_per_tok


def test_grouped_dispatch_equals_running_every_expert():
    cfg = _cfg()
    _full, layer, x = _routed_layer(cfg, 1)
    layer = _share(layer, cfg, 0)
    who, g = sarvam_mla.route(layer, cfg, x)
    live = jnp.arange(x.shape[0]) < 33      # seven rows are padding
    got, stats = sarvam_mla.held_experts(layer, cfg, x, who, g, live)
    E = cfg.num_experts
    weights = jnp.sum(jax.nn.one_hot(who, cfg.router_experts) * g[..., None],
                      axis=1)[:, :E] * live[:, None]
    gate = jnp.einsum("th,ehi->tei", x, layer["experts_gate"])
    up = jnp.einsum("th,ehi->tei", x, layer["experts_up"])
    down = jnp.einsum("tei,eih->teh", jax.nn.silu(gate) * up,
                      layer["experts_down"])
    _close(got, jnp.einsum("te,teh->th", weights, down), 1e-5)
    here = np.asarray((who < E) & live[:, None])
    rows = np.bincount(np.asarray(who)[here], minlength=E)
    assert [int(n) for n in stats] == [
        33 * cfg.num_experts_per_tok, here.sum(), (rows > 0).sum(),
        rows.max()]


def test_return_choice_leaves_the_logits_bit_equal():
    cfg, params, tokens, blocks, cache = _case(5)
    plain, cache = _prefill(cfg, params, cache, tokens, 0, 64, 64, blocks)
    again, _, choice, stats = _prefill(
        cfg, params, sarvam_mla.init_cache(cfg, 64, BS), tokens, 0, 64, 64,
        blocks, return_choice=True, return_stats=True)
    assert np.array_equal(np.asarray(plain), np.asarray(again))
    routed = cfg.num_layers - cfg.first_k_dense_replace
    assert choice.shape == (routed, 64, cfg.num_experts_per_tok)
    assert choice.dtype == jnp.int32
    assert int(choice.max()) >= cfg.num_experts      # ids over the router's 8
    assert int(stats[0]) == 64 * routed * cfg.num_experts_per_tok
    plain, _ = _decode(cfg, params, cache, tokens[64], 64, blocks)
    again, _, choice = _decode(cfg, params, cache, tokens[64], 64, blocks,
                               return_choice=True)
    assert np.array_equal(np.asarray(plain), np.asarray(again))
    assert choice.shape == (routed, 2, cfg.num_experts_per_tok)


def test_the_reference_follows_a_choice_and_measures_its_shortfall():
    cfg, params, tokens, blocks, cache = _case(6, 64)
    _, _, choice = _prefill(cfg, params, cache, tokens, 0, 64, 64, blocks,
                            return_choice=True)
    hp = _hp(cfg)
    own = ref.forward(params, hp, jnp.asarray(tokens))
    followed, shortfall = ref.forward(params, hp, jnp.asarray(tokens),
                                      choice=choice)
    _close(followed, own, 1e-5)
    assert float(shortfall.max()) < 1e-3
    worse = np.asarray(choice).copy()
    worse[0, 10, 0] = np.setdiff1d(np.arange(cfg.router_experts),
                                   worse[0, 10])[-1]
    _, shortfall = ref.forward(params, hp, jnp.asarray(tokens),
                               choice=jnp.asarray(worse))
    assert float(shortfall[0, 10]) > 0
    worse[0, 10, 0] = worse[0, 10, 1]       # an expert named twice
    _, shortfall = ref.forward(params, hp, jnp.asarray(tokens),
                               choice=jnp.asarray(worse))
    assert np.isinf(float(shortfall[0, 10]))


def _wrong_expert(layer, cfg, x):
    who, g = _WRONG["route"](layer, cfg, x)
    k = cfg.num_experts_per_tok
    s = jax.nn.sigmoid(sarvam_mla._dot(x, layer["router"]))
    nxt = jax.lax.top_k(s + layer["router_bias"], k + 1)[1][:, k]
    return who.at[:, k - 1].set(nxt.astype(jnp.int32)), g


def _no_renorm(layer, cfg, x):
    who, _ = _WRONG["route"](layer, cfg, x)
    s = jax.nn.sigmoid(sarvam_mla._dot(x, layer["router"]))
    return who, cfg.routed_scaling_factor * jnp.take_along_axis(s, who, -1)


def _bias_in_g(layer, cfg, x):
    who, _ = _WRONG["route"](layer, cfg, x)
    s = jax.nn.sigmoid(sarvam_mla._dot(x, layer["router"]))
    chosen = jnp.take_along_axis(s + layer["router_bias"], who, -1)
    return who, cfg.routed_scaling_factor * chosen / chosen.sum(
        -1, keepdims=True)


def _key_not_rotated(x, cos, sin):
    # The rotary key is the one call with a single head; queries have many.
    return x if x.shape[-2] == 1 else _WRONG["apply_rope"](x, cos, sin)


_WRONG = {"route": sarvam_mla.route, "apply_rope": sarvam_mla.apply_rope}


@pytest.mark.parametrize("name, attr, fault", [
    ("a wrong expert", "route", _wrong_expert),
    ("shares left unnormalised", "route", _no_renorm),
    ("the bias used in g", "route", _bias_in_g),
    ("a rotary key not rotated", "apply_rope", _key_not_rotated),
])
def test_a_planted_fault_fails(monkeypatch, name, attr, fault):
    cfg, params, tokens, blocks, cache = _case(7)
    want = ref.forward(params, _hp(cfg), jnp.asarray(tokens))
    monkeypatch.setattr(sarvam_mla, attr, fault)
    _, cache = _prefill(cfg, params, cache, tokens, 0, 64, 64, blocks)
    logits, cache = _prefill(cfg, params, cache, tokens, 64, 36, 64, blocks)
    err = float(np.max(np.abs(np.asarray(logits) - np.asarray(want[99])))
                / np.max(np.abs(np.asarray(want[99]))))
    assert err > 1e-3, name


def test_yarn_frequencies_keep_the_fast_and_stretch_the_slow():
    scaling = PRESETS["sarvam-105b-ep4"].rope_scaling
    got = np.asarray(sarvam_mla.yarn_inv_freq(64, 10000.0, scaling))
    plain = 10000.0 ** -(np.arange(0, 64, 2) / 64)
    want = np.asarray(ref._inv_freq(64, 10000.0, scaling))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    np.testing.assert_allclose(got[0], plain[0], rtol=1e-6)
    np.testing.assert_allclose(got[-1], plain[-1] / 40, rtol=1e-6)
    assert abs(sarvam_mla.softmax_scale(PRESETS["sarvam-105b-ep4"])
               - 192 ** -0.5 * (0.1 * np.log(40) + 1) ** 2) < 1e-9


def test_the_served_preset_is_the_share_the_file_states():
    cfg = PRESETS["sarvam-105b-ep4"]
    assert (cfg.num_experts, cfg.router_experts) == (32, 128)
    assert (cfg.vocab_size, cfg.published_vocab_size) == (65536, 262144)
    assert (cfg.num_layers, cfg.first_k_dense_replace) == (6, 1)
    assert sarvam_mla.cache_width(cfg) == cfg.head_dim == 576
    # 576 values of content in 640 lanes on the device, six layers, bf16.
    assert sarvam_mla.cache_bytes_per_token(cfg) == 640 * 2 * 6
    shapes = jax.eval_shape(
        lambda: sarvam_mla.init_params(cfg, jax.random.PRNGKey(0)))
    held = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert abs(held * 2 / 1e9 - 10.92) < 0.01      # ISSUE 39's table


def _engine(**overrides):
    return LLMEngine(config_from_preset("tiny-sarvam", **{
        "model.dtype": "float32", "scheduler.prefill_buckets": (32, 64),
        "scheduler.max_num_seqs": 4, "scheduler.mixed_batch": False,
        **overrides}))


def test_the_engine_serves_it_end_to_end():
    """Allocation by the module's init_cache, a prefix-cache hit on the
    latent blocks, the K=8 window, the routing counters, and the reference's
    greedy tokens."""
    eng = _engine()
    cfg = eng.config.model
    assert [c.shape for c in eng.kv_caches] == [
        (eng.block_pool.num_blocks, BS, sarvam_mla.cache_lanes(cfg))
    ] * cfg.num_layers
    assert eng._kv_bytes(1) == BS * sarvam_mla.cache_bytes_per_token(cfg)
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
        # Attention is causal: one pass over prompt + answer gives the
        # reference's logits behind every token the engine chose.
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
    assert all(w["k"] <= 8 for w in decodes)
    routed = cfg.num_layers - cfg.first_k_dense_replace
    for w in windows:
        assert w["moe_assigned_here"] <= w["moe_assigned"]
        steps = w["k"] if w["rows"] else 1
        assert w["experts_touched"] <= cfg.num_experts * routed * steps
        if w["rows"]:
            assert w["moe_assigned"] == (
                w["tokens_emitted"] * routed * cfg.num_experts_per_tok)
        else:
            assert w["moe_assigned"] == (
                w["new_tokens"] * routed * cfg.num_experts_per_tok)
    held = sum(w["moe_assigned_here"] for w in windows)
    assert stats["moe_assignments"] == {
        "held": held, "away": sum(w["moe_assigned"] for w in windows) - held}
    assert 0 < held < sum(w["moe_assigned"] for w in windows)
    assert stats["moe_experts_touched"] == sum(
        w["experts_touched"] for w in windows)


def test_a_model_that_routes_nothing_counts_nothing():
    eng = LLMEngine(config_from_preset("tiny-llama"))
    eng.add_request("r", prompt_token_ids=[5, 6, 7],
                    sampling_params=SamplingParams(
                        max_tokens=9, temperature=0.0, ignore_eos=True))
    while eng.has_unfinished():
        eng.step()
    assert eng.stats()["moe_assignments"] == {"held": 0, "away": 0}
    assert all("moe_assigned" not in w
               for w in eng.obs.windows_payload()["windows"])


@pytest.mark.parametrize("what, overrides", [
    ("--quantization", {"model.quantization": "int8"}),
    ("--kv-cache-dtype int8", {"cache.kv_cache_dtype": "int8"}),
    ("LoRA", {"lora.max_loras": 2}),
    ("host KV offload", {"cache.host_offload_gb": 0.5}),
    ("remote KV store", {"cache.remote_kv_url": "kv://127.0.0.1:1"}),
    ("speculative", {"scheduler.speculative_ngram": 3}),
    ("mixed prefill", {"scheduler.mixed_batch": True}),
])
def test_what_the_module_lacks_is_refused_at_boot_by_name(what, overrides):
    with pytest.raises(ValueError, match=what):
        _engine(**overrides)


def test_a_mesh_is_refused_at_boot():
    with pytest.raises(ValueError, match="tp=2|more than one device"):
        _engine(**{"parallel.tensor_parallel": 2})


def test_a_checkpoint_path_is_refused(tmp_path):
    with pytest.raises(ValueError, match="no checkpoint loader"):
        LLMEngine(config_from_preset(
            "tiny-sarvam", weights_path=str(tmp_path),
            **{"scheduler.mixed_batch": False}))


def test_the_decode_walks_its_pages_tile_after_tile(monkeypatch):
    """Seven blocks of context in tiles of four: two tiles, the second half
    live; a padding row beside the live one."""
    cfg, params, tokens, blocks, cache = _case(8)
    want = ref.forward(params, _hp(cfg), jnp.asarray(tokens))
    _, cache = _prefill(cfg, params, cache, tokens, 0, 100, 112, blocks)
    monkeypatch.setattr(sarvam_mla, "PAGE_TILE", 4)
    logits, _ = _decode(cfg, params, cache, tokens[100], 100, blocks)
    _close(logits[0], want[100])


def _lowered_hashes():
    """sha256 (16 hex) of the lowered text of the four step programs a dense
    cell runs, for the tiny llama preset, jitted as ``LLMEngine`` jits
    them."""
    import hashlib
    from functools import partial

    from production_stack_tpu.engine.core import step_programs
    from production_stack_tpu.engine.models import llama
    from production_stack_tpu.engine.sampling import sample_tokens

    cfg = PRESETS["tiny-llama"]
    params = jax.eval_shape(
        lambda: llama.init_params(cfg, jax.random.PRNGKey(0)))
    S, bmax, nb, V = 8, 512, 1024, cfg.vocab_size
    kv = [(jax.ShapeDtypeStruct((nb, BS, cfg.num_kv_heads, cfg.head_dim),
                                jnp.dtype(cfg.dtype)),) * 2
          for _ in range(cfg.num_layers)]
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)

    def named(name, fn):
        fn = partial(fn)
        fn.__name__ = fn.__qualname__ = name
        return fn

    def sha(fn, *args, **kwargs):
        return hashlib.sha256(
            fn.lower(*args, **kwargs).as_text().encode()).hexdigest()[:16]

    decode = partial(llama.decode, cfg=cfg, mesh=None)
    return {
        "prefill_fn": sha(
            jax.jit(named("prefill_fn", step_programs.prefill_program(
                partial(llama.prefill, cfg=cfg, mesh=None, sp_mode="ring"),
                ("cached_len", "valid_len"), BS, bmax)),
                donate_argnames=("kv_caches",),
                static_argnames=("prompt_topk",)),
            params, i32(256 + 256 // BS + bmax + 2), kv_caches=kv),
        "window_fn": sha(
            jax.jit(named("window_fn", step_programs.window_program(
                decode, n_steps=8, block_size=BS, vocab=V)),
                static_argnames=("use_penalties", "use_min_floor"),
                donate_argnames=("kv_caches",)),
            params, tokens=i32(S), positions=i32(S), ctx_lens=i32(S),
            done=jax.ShapeDtypeStruct((S,), jnp.bool_), min_left=i32(S),
            block_tables=i32(S, bmax), max_steps=i32(S), kv_caches=kv,
            temps=f32(S), top_ps=f32(S), top_ks=i32(S), min_ps=f32(S),
            seq_seeds=i32(S), stop_ids=i32(S, 4), key_base=i32(),
            counts=jax.ShapeDtypeStruct((S, 1), jnp.int16),
            seen=jax.ShapeDtypeStruct((S, 1), jnp.bool_),
            presence=f32(S), frequency=f32(S), repetition=f32(S),
            use_penalties=False, use_min_floor=True),
        "win_advance_fn": sha(
            jax.jit(named("win_advance_fn", step_programs.table_scatter)),
            i32(S, bmax), i32(S, 2), i32(S, 2)),
        "sample_fn": sha(
            jax.jit(named("sample_fn", sample_tokens)),
            f32(S, V), f32(S), f32(S), i32(S),
            jax.ShapeDtypeStruct((2,), jnp.uint32), i32(S)),
    }


def test_a_dense_models_step_programs_lower_to_the_text_they_had():
    """The step programs of a model that counts nothing are those of the
    commit before PR 39 (2b161f0), byte for byte: a module that hands back
    routing counts adds a result to ``window_program`` for itself alone.
    Pinned under the pinned JAX: a PR that means to change a dense cell's
    programs re-pins these and says so.  PR 43 meant to: ``window_fn`` and
    ``sample_fn`` hold the sampler, whose work now stands under two
    conditionals (0fcdfab572754c1d and b98d249a33611f1d before it; the
    tokens are the same, tests/test_sampler_paths.py).  PR 49 meant to:
    ``prefill_fn`` takes what the host builds for a chunk as one int32 vector
    and slices it (``step_programs.prefill_program``; 6992c098e4286882 before
    it; the model is handed the same values, tests/test_dispatch_build.py).
    PR 60 meant to: ``window_fn`` loops as many steps as its longest row was
    budgeted, up to the eight its outputs hold, where it scanned eight
    (caf2df83d2edb4da before it; the tokens are the same on the steps that
    run, tests/test_window_plan.py)."""
    assert _lowered_hashes() == {
        "prefill_fn": "7b371a1a2d3fe956", "window_fn": "e286ea5021c96819",
        "win_advance_fn": "325e8149c1081481",
        "sample_fn": "18cb405d3f877b3e"}
