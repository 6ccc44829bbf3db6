"""models/sarvam_mla.py serving the ``xing4_0`` family (several residual
streams mixed by a Sinkhorn-normalised matrix, a low-rank query path, every
routed expert held) against its plain reference, bench/reference/xing_mhc.py:
the tiny preset, seeded weights, float32, on the CPU.  The reference is
imported by path from the benchmark's own file, so the tests and the chip's
compare hold the module to one text.
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
from test_sarvam_mla import BS, ROOT, _decode, _prefill   # the same driving


def _load_reference():
    spec = importlib.util.spec_from_file_location(
        "reference_xing_mhc",
        os.path.join(ROOT, "bench", "reference", "xing_mhc.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


ref = _load_reference()


@pytest.fixture(autouse=True)
def _highest_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _cfg(**changes):
    return dataclasses.replace(PRESETS["tiny-xing"], dtype="float32",
                               **changes)


def _hp(cfg, **changes):
    """The reference's view of ``cfg``: the configuration file's keys."""
    hp = dict(
        hidden_size=cfg.hidden_size, num_attention_heads=cfg.num_heads,
        kv_lora_rank=cfg.kv_lora_rank, q_lora_rank=cfg.q_lora_rank,
        qk_nope_head_dim=cfg.qk_nope_head_dim,
        qk_rope_head_dim=cfg.qk_rope_head_dim, v_head_dim=cfg.v_head_dim,
        rope_theta=cfg.rope_theta, rope_scaling=cfg.rope_scaling,
        rms_norm_eps=cfg.rms_norm_eps, n_routed_experts=cfg.num_experts,
        num_experts_per_tok=cfg.num_experts_per_tok,
        first_k_dense_replace=cfg.first_k_dense_replace,
        routed_scaling_factor=cfg.routed_scaling_factor,
        vocab_size=cfg.vocab_size, hc_mult=cfg.hc_mult,
        hc_sinkhorn_iters=cfg.hc_sinkhorn_iters, hc_eps=cfg.hc_eps,
        mhc_h_res_clamp_min=-cfg.hc_res_clamp,
        mhc_h_res_clamp_max=cfg.hc_res_clamp)
    hp.update(changes)
    return hp


def _case(seed=0, n=150, **changes):
    cfg = _cfg(**changes)
    params = sarvam_mla.init_params(cfg, jax.random.PRNGKey(seed))
    tokens = np.random.default_rng(seed).integers(
        1, cfg.vocab_size, n).astype(np.int32)
    blocks = np.arange(1, 1 + -(-n // BS), dtype=np.int32)
    return cfg, params, tokens, blocks, sarvam_mla.init_cache(cfg, 64, BS)


def _err(got, want) -> float:
    got, want = np.asarray(got), np.asarray(want)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


def test_the_registry_serves_the_presets_with_the_latent_module():
    assert get_model(PRESETS["tiny-xing"].name) is sarvam_mla
    assert get_model(PRESETS["xing4.0-29b-a4b-stage"].name) is sarvam_mla
    cfg = PRESETS["tiny-xing"]
    assert (cfg.num_layers, cfg.first_k_dense_replace) == (3, 1)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters) == (4, 20)
    assert sarvam_mla.residual_path(cfg) == (4, 20, "xla")
    assert sarvam_mla.residual_path(PRESETS["tiny-sarvam"]) is None
    assert sarvam_mla.stats_names(cfg) == (
        sarvam_mla.ROUTING_STATS + sarvam_mla.RESIDUAL_STATS)
    assert sarvam_mla.stats_names(PRESETS["tiny-sarvam"]) == (
        sarvam_mla.ROUTING_STATS)


@pytest.mark.parametrize("seed, cached", [(0, 0), (1, 0), (0, 64), (1, 64)])
def test_prefill_then_decode_matches_the_reference(seed, cached):
    """Every row within 1e-4 of the logits' scale: a prefill with no prefix
    (``cached`` 0: 100 tokens in one program) or over a cached one (64 cached,
    36 new: expanded attention over the cache's latents), then decode steps
    through the paged cache (absorbed)."""
    cfg, params, tokens, blocks, cache = _case(seed)
    want, _ = ref.forward(params, _hp(cfg), jnp.asarray(tokens))
    if cached:
        logits, cache = _prefill(cfg, params, cache, tokens, 0, 64, 64, blocks)
        assert _err(logits, want[63]) <= 1e-4
        logits, cache = _prefill(cfg, params, cache, tokens, 64, 36, 64, blocks)
    else:
        logits, cache = _prefill(cfg, params, cache, tokens, 0, 100, 112,
                                 blocks)
    assert _err(logits, want[99]) <= 1e-4
    for pos in range(100, 104):
        logits, cache = _decode(cfg, params, cache, tokens[pos], pos, blocks)
        assert _err(logits[0], want[pos]) <= 1e-4


def test_return_choice_and_return_stats_leave_the_logits_bit_equal():
    cfg, params, tokens, blocks, cache = _case(5)
    plain, cache = _prefill(cfg, params, cache, tokens, 0, 64, 64, blocks)
    again, _, choice, stats = _prefill(
        cfg, params, sarvam_mla.init_cache(cfg, 64, BS), tokens, 0, 64, 64,
        blocks, return_choice=True, return_stats=True)
    assert np.array_equal(np.asarray(plain), np.asarray(again))
    routed = cfg.num_layers - cfg.first_k_dense_replace
    assert choice.shape == (routed, 64, cfg.num_experts_per_tok)
    counted = dict(zip(sarvam_mla.stats_names(cfg), (int(n) for n in stats)))
    pairs = 64 * routed * cfg.num_experts_per_tok
    # Every expert is held: each pair falls here.
    assert counted["moe_assigned"] == counted["moe_assigned_here"] == pairs
    assert counted["mhc_entries"] == 64 * 2 * cfg.num_layers * cfg.hc_mult**2
    plain, _ = _decode(cfg, params, cache, tokens[64], 64, blocks)
    again, _, choice, stats = _decode(
        cfg, params, cache, tokens[64], 64, blocks, return_choice=True,
        return_stats=True)
    assert np.array_equal(np.asarray(plain), np.asarray(again))
    assert choice.shape == (routed, 2, cfg.num_experts_per_tok)
    # One live row beside a padding row: the padding row is not counted.
    assert int(stats[5]) == 2 * cfg.num_layers * cfg.hc_mult**2


def test_h_res_is_doubly_stochastic_and_the_counter_says_how_nearly():
    """Columns sum to 1 by construction; rows within 1e-3 for most tokens
    and, for the tail whose matrix is nearly a permutation, as far off as
    ``mhc_err_e6`` says: the counter is the reference's own largest row
    error over the first mapping's tokens, and no smaller than any."""
    cfg, params, tokens, blocks, cache = _case(2)
    _, _, stats = _prefill(cfg, params, cache, tokens, 0, 64, 64, blocks,
                           return_stats=True)
    layer = params["layers"][0]
    X = jnp.broadcast_to(params["embed_tokens"][tokens[:64]][:, None],
                         (64, cfg.hc_mult, cfg.hidden_size))
    _pre, _post, ours, counted = sarvam_mla._mhc(
        layer, cfg, "attn", X, jnp.ones(64, bool))
    h_pre, h_post, theirs = ref.mapping(layer, _hp(cfg), "attn", X)
    ours = ours.transpose(2, 0, 1)                               # [T, n, n]
    np.testing.assert_allclose(ours, theirs, atol=1e-6)
    np.testing.assert_allclose(_pre, h_pre, atol=1e-6)
    np.testing.assert_allclose(_post, h_post, atol=1e-6)
    assert float(jnp.max(h_post)) > 1.0          # twice a sigmoid
    cols = np.abs(np.asarray(theirs).sum(1) - 1)
    rows = np.abs(np.asarray(theirs).sum(2) - 1).max(1)
    assert cols.max() <= 1e-5
    assert np.median(rows) <= 1e-3
    assert abs(int(counted[2]) - rows.max() * 1e6) <= 2
    assert int(stats[6]) >= int(counted[2])
    assert int(stats[6]) <= 1e5                   # the worst of 384: <= 0.1
    # Twenty normalisations are not three: the seeded exponents spread.
    _, _, three = ref.mapping(layer, _hp(cfg, hc_sinkhorn_iters=3), "attn", X)
    assert float(jnp.max(jnp.abs(three - theirs))) > 0.05


@pytest.mark.parametrize("T", [16, 300, 2048])
def test_the_normalisation_kernel_is_the_plain_form(T):
    """``ops/pallas/mhc_sinkhorn.py`` in interpret mode against
    :func:`sarvam_mla._sinkhorn`: the same divisions in the same order."""
    from production_stack_tpu.engine.ops.pallas.mhc_sinkhorn import (
        mhc_sinkhorn_pallas,
    )

    E = jnp.exp(jnp.clip(
        3 * jax.random.normal(jax.random.PRNGKey(T), (4, 4, T)), -30, 30))
    got = mhc_sinkhorn_pallas(E, iters=20, eps=1e-6, interpret=True)
    np.testing.assert_allclose(got, sarvam_mla._sinkhorn(E, 20, 1e-6),
                               atol=1e-6)


def test_the_clamp_is_counted_where_it_bites():
    cfg, params, tokens, blocks, cache = _case(3)
    _, _, stats = _prefill(cfg, params, cache, tokens, 0, 64, 64, blocks,
                           return_stats=True)
    assert int(stats[4]) == 0
    for layer in params["layers"]:
        layer["hc_ffn_alpha"] = layer["hc_ffn_alpha"].at[2].set(25.0)
    logits, _, stats = _prefill(
        cfg, params, sarvam_mla.init_cache(cfg, 64, BS), tokens, 0, 64, 64,
        blocks, return_stats=True)
    want, _ = ref.forward(params, _hp(cfg), jnp.asarray(tokens[:64]))
    assert 0 < int(stats[4]) < int(stats[5])
    assert np.isfinite(np.asarray(logits)).all()
    assert _err(logits, want[63]) <= 1e-4


def _three_iterations(M, iters, eps):
    return _WAS["_sinkhorn"](M, 3, eps)


def _post_without_its_two(layer, cfg, sub, X, live):
    h_pre, h_post, h_res, counted = _WAS["_mhc"](layer, cfg, sub, X, live)
    return h_pre, h_post / 2, h_res, counted


def _streams_summed(layer, cfg, sub, X, live):
    h_pre, h_post, h_res, counted = _WAS["_mhc"](layer, cfg, sub, X, live)
    return jnp.ones_like(h_pre), h_post, h_res, counted


def _no_query_norm(x, weight, eps):
    return x if x.shape[-1] == PRESETS["tiny-xing"].q_lora_rank else (
        _WAS["rms_norm"](x, weight, eps))


_WAS = {"_sinkhorn": sarvam_mla._sinkhorn, "_mhc": sarvam_mla._mhc,
        "rms_norm": sarvam_mla.rms_norm}


@pytest.mark.parametrize("name, attr, fault, changes", [
    ("three normalisations for twenty", "_sinkhorn", _three_iterations, {}),
    ("H_post without its 2", "_mhc", _post_without_its_two, {}),
    ("the streams summed in place of H_pre", "_mhc", _streams_summed, {}),
    ("the query's latent not normed", "rms_norm", _no_query_norm, {}),
    # The reference clamps at 30; the program, by this fault, never, and
    # exp overflows where a drawn exponent passes 88.
    ("no clamp", None, None, {"hc_res_clamp": 1e30}),
])
def test_a_planted_fault_fails(monkeypatch, name, attr, fault, changes):
    cfg, params, tokens, blocks, cache = _case(7)
    if name == "no clamp":
        for layer in params["layers"]:
            layer["hc_attn_alpha"] = layer["hc_attn_alpha"].at[2].set(40.0)
    want, _ = ref.forward(params, _hp(cfg), jnp.asarray(tokens))
    if attr:
        monkeypatch.setattr(sarvam_mla, attr, fault)
    cfg = dataclasses.replace(cfg, **changes)
    _, cache = _prefill(cfg, params, cache, tokens, 0, 64, 64, blocks)
    logits, cache = _prefill(cfg, params, cache, tokens, 64, 36, 64, blocks)
    err = _err(logits, want[99])
    assert not err <= 1e-3, name      # nan fails too


def _matrices(tree) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(tree) if x.ndim > 1)


def test_the_served_preset_is_the_stage_the_issue_counts():
    """ISSUE 44's arithmetic, matrix by matrix (norm scales, the router's
    bias and the mappings' scalars are vectors: kilobytes)."""
    cfg = PRESETS["xing4.0-29b-a4b-stage"]
    assert (cfg.num_experts, cfg.router_experts) == (64, 64)
    assert (cfg.vocab_size, cfg.published_vocab_size) == (131072, 0)
    assert (cfg.num_layers, cfg.first_k_dense_replace) == (6, 1)
    assert (cfg.q_lora_rank, cfg.use_qk_norm) == (768, False)
    assert sarvam_mla.cache_width(cfg) == cfg.head_dim == 576
    assert sarvam_mla.cache_bytes_per_token(cfg) == 7680
    shapes = jax.eval_shape(
        lambda: sarvam_mla.init_params(cfg, jax.random.PRNGKey(0)))
    lead, routed = shapes["layers"][0], shapes["layers"][1]
    attention = _matrices({k: routed[k] for k in (
        "q_a_proj", "q_b_proj", "kv_a_proj", "kv_b_proj", "o_proj")})
    mappings = _matrices({k: v for k, v in routed.items()
                          if k.startswith("hc_")})
    assert round(attention / 1e6, 2) == 28.41
    assert round(mappings / 1e6, 2) == 0.69
    assert round(_matrices(routed) / 1e6, 2) == 744.98
    assert round(_matrices(lead) / 1e6, 2) == 128.19
    assert round(_matrices(shapes) / 1e9, 3) == 4.793
    held = sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(shapes))
    assert abs(held / 1e9 - 9.59) < 0.01    # the mappings are float32
    assert {v.dtype for k, v in routed.items() if k.startswith("hc_")} == {
        jnp.dtype("float32")}


def _scopes(jaxpr, found):
    for eqn in jaxpr.eqns:
        found.add("mhc" in str(eqn.source_info.name_stack))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _scopes(sub, found)
    return found


@pytest.mark.parametrize("preset, mapped", [("tiny-sarvam", False),
                                            ("tiny-xing", True)])
def test_one_residual_stream_is_decided_in_python(preset, mapped):
    """``hc_mult`` 0: no operation of the mapping is in the traced program."""
    cfg = PRESETS[preset]
    params = jax.eval_shape(
        lambda: sarvam_mla.init_params(cfg, jax.random.PRNGKey(0)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    kv = [jax.ShapeDtypeStruct((64, BS, sarvam_mla.cache_lanes(cfg)),
                               jnp.dtype(cfg.dtype))] * cfg.num_layers
    jaxpr = jax.make_jaxpr(
        lambda p, t, pos, bt, cl, sb, so, kv: sarvam_mla.decode(
            p, cfg, t, pos, bt, cl, sb, so, kv, return_stats=True))(
        params, i32(4), i32(4), i32(4, 32), i32(4), i32(4), i32(4), kv)
    assert (True in _scopes(jaxpr.jaxpr, set())) is mapped
    assert jaxpr.out_avals[-1].shape == (len(sarvam_mla.stats_names(cfg)),)
    names = {k for layer in params["layers"] for k in layer}
    assert any(k.startswith("hc_") for k in names) is mapped
    assert ("q_a_proj" in names) is mapped
    assert ("q_proj" in names) is not mapped


def _engine(**overrides):
    return LLMEngine(config_from_preset("tiny-xing", **{
        "model.dtype": "float32", "scheduler.prefill_buckets": (32, 64),
        "scheduler.max_num_seqs": 4, "scheduler.mixed_batch": False,
        **overrides}))


def test_the_engine_serves_it_end_to_end(caplog):
    """Scheduler, prefix cache, the K = 8 window: the reference's greedy
    tokens, and the residual path's counters on the flight records and in
    the engine's totals."""
    with caplog.at_level("INFO"):
        eng = _engine()
    assert "Residual: streams=4 sinkhorn=20 (xla)" in caplog.text
    cfg = eng.config.model
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
            eng.params, hp, jnp.asarray(prompt + got[f"r{i}"], jnp.int32))[0])
        for step, token in enumerate(got[f"r{i}"]):
            logits = want[len(prompt) - 1 + step]
            # The engine's token is the reference's, or ties with it.
            assert logits.max() - logits[token] <= 1e-4 * np.abs(logits).max()
    stats = eng.stats()
    assert stats["prefix_cache_hit_tokens"] == 64
    windows = eng.obs.windows_payload()["windows"]
    decodes = [w for w in windows if w["rows"]]
    assert decodes and all("window_fn" in w["programs"] for w in decodes)
    assert max(w["k"] for w in decodes) == 8
    per_token = 2 * cfg.num_layers * cfg.hc_mult**2
    for w in windows:
        tokens = w["tokens_emitted"] if w["rows"] else w["new_tokens"]
        assert w["mhc_entries"] == tokens * per_token
        assert w["mhc_clamped"] == 0
        assert 0 < w["mhc_err_e6"] <= 1e5
        assert w["moe_assigned_here"] == w["moe_assigned"]
    assert stats["mhc_entries"] == sum(w["mhc_entries"] for w in windows)
    assert stats["mhc_clamped"] == 0
    assert stats["mhc_sinkhorn_err"] == max(
        w["mhc_err_e6"] for w in windows) / 1e6
    assert stats["moe_assignments"]["away"] == 0


def test_one_stream_counts_no_mapping():
    eng = LLMEngine(config_from_preset("tiny-sarvam", **{
        "scheduler.mixed_batch": False, "scheduler.max_num_seqs": 4}))
    eng.add_request("r", prompt_token_ids=[5, 6, 7],
                    sampling_params=SamplingParams(
                        max_tokens=9, temperature=0.0, ignore_eos=True))
    while eng.has_unfinished():
        eng.step()
    stats = eng.stats()
    assert (stats["mhc_entries"], stats["mhc_sinkhorn_err"]) == (0, 0.0)
    windows = eng.obs.windows_payload()["windows"]
    assert windows and all(
        "mhc_entries" not in w and "moe_assigned" in w for w in windows)


async def test_the_three_counters_are_on_metrics():
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.server.api_server import build_engine_app
    from production_stack_tpu.engine.server.async_engine import AsyncEngine

    engine = AsyncEngine(config_from_preset("tiny-xing", **{
        "cache.num_blocks": 64, "scheduler.max_num_seqs": 2,
        "scheduler.prefill_buckets": (16, 32),
        "scheduler.mixed_batch": False}))
    client = TestClient(TestServer(build_engine_app(engine, "tiny-xing")))
    await client.start_server()
    try:
        resp = await client.post("/v1/completions", json={
            "model": "tiny-xing", "prompt": "hi", "max_tokens": 4,
            "ignore_eos": True, "temperature": 0})
        assert resp.status == 200
        metrics = await (await client.get("/metrics")).text()
        values = {line.split()[0]: float(line.split()[1])
                  for line in metrics.splitlines()
                  if line.startswith("tpu:mhc_")}
        cfg = PRESETS["tiny-xing"]
        assert values["tpu:mhc_entries_total"] % (
            2 * cfg.num_layers * cfg.hc_mult**2) == 0
        assert values["tpu:mhc_entries_total"] > 0
        assert values["tpu:mhc_clamped_total"] == 0
        assert 0 < values["tpu:mhc_sinkhorn_err"] < 0.1
    finally:
        await client.close()
        await engine.close()


@pytest.mark.parametrize("what, overrides", [
    ("speculative", {"scheduler.speculative_ngram": 3}),
    ("--quantization", {"model.quantization": "int8"}),
    ("--kv-cache-dtype int8", {"cache.kv_cache_dtype": "int8"}),
    ("host KV offload", {"cache.host_offload_gb": 0.5}),
    ("remote KV store", {"cache.remote_kv_url": "kv://127.0.0.1:1"}),
])
def test_what_the_module_lacks_is_refused_at_boot_by_name(what, overrides):
    with pytest.raises(ValueError, match=what):
        _engine(**overrides)


def test_a_mesh_is_refused_at_boot():
    with pytest.raises(ValueError, match="tp=2|more than one device"):
        _engine(**{"parallel.tensor_parallel": 2})
