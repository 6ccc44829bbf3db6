"""PR 61's cases: a served module with two latent cache arrays a layer and a
router some of whose outputs are identity experts (``models/longcat.py``)
through the harness on the CPU, and the reader and the bytes function its cell
brings.  A file of its own: the files that were there are not edited."""

import copy
import json
import os
import sys
import types

import pytest

from conftest import BENCH, benchmark_file, hold_a_cell_to_the_rule
from harness import layers
from test_join import load

DATA = os.path.join(BENCH, "tests", "data")
CELL = "longcat-flash-omni-ep32.sessions-20k"
CONFIG = "longcat-flash-omni-ep32"
LATENT = "latent_decode_attention_pallas"


def _config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def _rehearsal():
    with open(os.path.join(DATA, "rehearsal", "bench", "configs",
                           "rehearsal-longcat.json")) as f:
        return json.load(f)


def test_the_rehearsal_runs_through_the_harness(tmp_path):
    """``tiny-longcat`` through ``run.py`` on the CPU: engine and router as
    children, the sessions mix, the compare (which makes the cache by the
    module's ``init_cache``, four arrays, and follows the engine's choice over
    all 12 outputs) against ``reference/longcat.py``; counts only, ``correct``,
    the routing counters with the identities' share."""
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--benchmark",
         os.path.join(DATA, "rehearsal", "BENCHMARK-longcat.json"),
         "--workload", "rehearsal-longcat.sessions-prefix", "--seed",
         "3900000061", "--seconds", "6", "--trace", "1", "--out-dir",
         str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.splitlines()[-1])
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert metrics["compiles_in_window"] == 0
    assert metrics["prefix_hit_share"] > 50
    # 4 identities of 12 outputs, 4 of 8 real experts held, 3 picks a token.
    assert 15 < metrics["zero_expert_share"] < 55
    assert 15 < metrics["routed_here_share"] < 55
    assert 0 < metrics["experts_touched_share"] <= 100
    assert set(result["compared"]) >= {
        "decode_step_1", "choice_shortfall", "return_choice_logits_differ",
        "served_path_faults"}
    # No timing leaves a CPU rehearsal.
    for name in ("decode_step_bw_share", "latent_decode_bw_share",
                 "decode_step_dev_ms"):
        assert metrics.get(name) is None
    with open(os.path.join(str(tmp_path), "engine.log")) as f:
        log = f.read()
    assert ("Layer: 2 latent attentions + 2 dense FFN + 1 routed FFN "
            "(shortcut), router 12 = 8 + 4 identity, 4 held; 4 cache arrays "
            "(2 a layer)") in log


@pytest.mark.parametrize("fault", ["no_identity", "renormalised", "no_scale"])
def test_the_compare_runs_on_the_tiny_preset_and_refuses_a_wrong_reference(
        fault):
    """``compare.run`` alone on the rehearsal's file, and the reference made
    wrong in one way (``tools/compare_rows_longcat.py``'s switch) refused by
    the file's limits."""
    from harness import compare
    from reference import longcat as ref

    config, root = _rehearsal(), os.path.dirname(BENCH)
    ok, notes, rows = compare.run(config, 1, 3900000061, "cpu", root)
    assert ok, notes
    assert set(rows) >= {"decode_step_0", "decode_step_1", "choice_shortfall"}
    ref.FAULT = fault
    try:
        ok, _notes, rows = compare.run(config, 1, 3900000061, "cpu", root)
    finally:
        ref.FAULT = None
    limit = config["compare"]["logits_rtol"]
    assert not ok and max(
        v for name, (v, _l) in rows.items()
        if "step" in name or "prefill" in name) > limit


def test_the_bytes_are_those_of_the_issues_table():
    from harness.sizes import held
    from reduce import longcat_bytes as lb

    hp = held(_config())
    assert lb.attention_params(hp) == (
        6144 * 1536 + 1536 * 12288 + 6144 * 576 + 512 * 16384
        + 8192 * 6144) == 90_570_752
    assert lb.router_width(hp) == 768
    # 638.8 M a layer outside its experts, 37.75 M an expert (ISSUE 61).
    assert lb.layer_params(hp) == (
        2 * 90_570_752 + 2 * 3 * 6144 * 12288 + 6144 * 768) == 638_844_928
    assert lb.expert_bytes(hp) == 2 * 37_748_736
    assert lb.non_expert_bytes(hp) == 2 * (
        4 * 638_844_928 + 6144 * 16384)
    assert abs(lb.non_expert_bytes(hp) / 1e9 - 5.31) < 0.005
    # The chip's share: 4 layers, 16 experts each, an eighth of the head and
    # of the embedding: 5.17 B parameters.
    held_params = (4 * (638_844_928 + 16 * 37_748_736) + 2 * 6144 * 16384)
    assert abs(held_params / 1e9 - 5.17) < 0.005
    assert lb.cache_arrays(hp) == 8
    assert lb.latent_bytes_per_token(hp) == 9_216       # 576 x 2 B x 8
    # 16 rows at 24,000 positions, 8 steps; 3 of 16 held experts a layer.
    record = {"k": 8, "kv_tokens": 16 * 24000, "experts_touched": 8 * 4 * 3}
    assert lb.latent_read_bytes(hp, 16 * 24000, 8) == 8 * 16 * 24000 * 9216
    assert abs(lb.latent_read_bytes(hp, 16 * 24000, 1) / 1e9 - 3.54) < 0.005
    assert lb.decode_step_bytes(hp, record) == (
        8 * lb.non_expert_bytes(hp) + 96 * 75_497_472
        + 8 * 16 * 24000 * 9216)
    assert 9.5e9 < lb.decode_step_bytes(hp, record) / 8 < 10.5e9  # ~10 GB


def _trace():
    """``data/join_small.*`` with the latent kernel in it: each window of 2
    steps calls it eight times a step (two layers held x two arrays x ...:
    here 16 calls a window = 2 steps of 8 arrays)."""
    trace = load("join_small.trace.json")
    for module in trace["modules"]:
        if module[0] == "window_fn":
            module[3][LATENT] = 16
    trace["ops"] += [[LATENT, 4.0e-04, 32]]
    return trace


def _windows():
    """... and the records a ``longcat`` engine writes: 2 rows at ~480
    positions in ``kv_tokens`` (one array's), the routing counts with the
    identities' own."""
    windows = copy.deepcopy(load("join_small.windows.json"))
    for w in windows["windows"]:
        if w["rows"]:
            w.update(moe_assigned=2 * 2 * 4 * 12, moe_assigned_here=4,
                     experts_touched=2 * 4 * 3, expert_rows_max=1,
                     moe_zero_assigned=60)
        else:
            w.update(moe_assigned=100 * 4 * 12, moe_assigned_here=100,
                     experts_touched=40, expert_rows_max=9,
                     moe_zero_assigned=1620)
    return windows


def _context(trace, config=None, windows=None):
    return layers.Context(
        cell={"name": CELL, "config": CONFIG, "chips": 1},
        config=config or _config(),
        records=[], late_ms=[], got={
            "windows": windows or _windows(), "wall_t0": 0.0,
            "seconds": 4e9, "before": {"prom": {}}, "after": {
                "prom": {}, "device": {"kind": "TPU v5 lite"}}},
        summary={}, dirs=[BENCH], trace=trace)


def _read(ctx, name):
    return layers.read_all(ctx, [name])[name]


def test_the_readers_on_a_sliced_trace():
    from harness.sizes import held
    from reduce import longcat_bytes as lb

    hp, ctx = held(_config()), _context(_trace())
    # The kernel: 16 calls a window = 2 steps of 8 arrays, over 992 and 1,024
    # positions an array, 1,152 B of content a position an array, in 400 us.
    want = 2 * 8 * (992 + 1024) * 1152 / 819e9 / 4.0e-04 * 100.0
    assert _read(ctx, "latent_decode_bw_share") == pytest.approx(want)
    assert 0 < want < 100
    records = [w for w in _windows()["windows"] if w["rows"]]
    seconds = (16000 + 14700) / 1e9
    total = sum(lb.decode_step_bytes(hp, w) for w in records)
    assert _read(ctx, "decode_step_bw_share") == pytest.approx(
        total / 819e9 / seconds * 100.0)
    assert _read(ctx, "decode_step_dev_ms") == pytest.approx(
        (16000 + 14700) / 4 / 1e6)
    # 3 of 16 held experts a layer a step.
    assert _read(ctx, "experts_touched_share") == pytest.approx(
        100.0 * 3 / 16)
    # Identity picks of all picks, prefill and decode records alike.
    every = _windows()["windows"]
    assert _read(ctx, "zero_expert_share") == pytest.approx(
        100.0 * sum(w["moe_zero_assigned"] for w in every)
        / sum(w["moe_assigned"] for w in every))
    assert 30 < _read(ctx, "zero_expert_share") < 36
    assert _read(ctx, "routed_here_share") == pytest.approx(
        100.0 * sum(w["moe_assigned_here"] for w in every)
        / sum(w["moe_assigned"] for w in every))


def test_the_readers_find_nothing_where_nothing_was_counted():
    """On the parent's records (no routing counts, no ``moe_zero_assigned``),
    without a trace, and on another architecture's configuration every new
    reader returns None and raises nothing."""
    from readers import longcat_decode

    device = ("latent_decode_bw_share", "decode_step_bw_share")
    counted = ("experts_touched_share", "zero_expert_share")
    plain = _context(load("join_small.trace.json"),
                     windows=load("join_small.windows.json"))
    for name in device + counted:
        assert _read(plain, name) is None, name
    # An engine that routes and has no identity counter (the parent's).
    older = _windows()
    for w in older["windows"]:
        del w["moe_zero_assigned"]
    assert _read(_context(_trace(), windows=older),
                 "zero_expert_share") is None
    plain.trace = None
    for name in device:
        assert _read(plain, name) is None, name
    with open(os.path.join(BENCH, "configs", "sarvam-105b-ep4.json")) as f:
        other = types.SimpleNamespace(config=json.load(f))
    for what in ("bw_share", "latent_bw_share", "touched_share",
                 "zero_share"):
        assert longcat_decode.read(other, {"what": what}) is None


def test_the_file_states_the_source_whole_and_every_cut():
    from harness.sizes import held

    config = _config()
    assert config["reduced"] == ["num_layers", "n_routed_experts",
                                 "vocab_size"]
    published = config["published"]
    for key, value in published.items():
        if key not in config["reduced"]:
            assert config[key] == value, key         # no width differs
    assert (config["num_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (4, 16, 16384)
    assert (published["num_layers"], published["n_routed_experts"],
            published["vocab_size"], published["zero_expert_num"],
            published["moe_topk"]) == (28, 512, 131072, 256, 12)
    # The floors: four layers, eight experts, an eighth of the vocabulary.
    assert config["num_layers"] >= 4 and config["n_routed_experts"] >= 8
    assert config["vocab_size"] * 8 >= published["vocab_size"]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):       # the builder's machine has it
        with open(catalog) as f:
            entry = next(c for c in map(json.loads, f)
                         if c["name"] == "LongCat-Flash-Omni")
        assert published == entry["config"]
        assert config["source"] == entry["source_url"]
    hp = held(config)
    assert hp["published"]["n_routed_experts"] == 512
    spec = config["compare"]
    assert spec["reference"] == "longcat" and spec["follow_choice"]
    assert (spec["layers"], spec["decode_steps"]) == (2, 2)
    assert spec["prompt_tokens"] == [4400, 300]
    for key in ("hidden_size", "ffn_hidden_size", "expert_ffn_hidden_size",
                "q_lora_rank", "kv_lora_rank", "mla_scale_q_lora",
                "mla_scale_kv_lora", "n_routed_experts", "zero_expert_num",
                "moe_topk", "routed_scaling_factor", "vocab_size"):
        assert key in spec["preset_keys"], key
    for key in ("stands_for", "assumed"):
        assert config[key]
    for word in ("32", "224", "0.25", "text"):
        assert word in config["stands_for"], word
    for key in ("mla_scale", "layer", "router", "router_bias",
                "rotary_pairing", "kv_cache", "serving"):
        assert key in config["assumed"], key
    for word in ("float8", "identit", "seeds"):
        assert word in spec["why_rtol"], word
    assert "seeds" in spec["why_shortfall"]
    assert config["engine_argv"] == [
        "--max-model-len", "32768", "--max-num-seqs", "16",
        "--prefill-buckets", "256,2048", "--window-ring-size", "8192",
        "--no-mixed-batch"]
    assert config["router_argv"] == ["--no-fleet-admission"]


def test_the_entries_that_list_the_cell_hold_the_rule():
    """The rule, not a count (``conftest.hold_a_cell_to_the_rule``); the
    dropped-in reader and bytes function are where the harness looks."""
    cell, names = hold_a_cell_to_the_rule(CELL, own=(
        "decode_step_dev_ms", "decode_step_bw_share",
        "latent_decode_bw_share", "experts_touched_share",
        "zero_expert_share", "routed_here_share", "prefix_hit_share"))
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "sessions-20k", 1)
    assert len(cell["why"]) <= 200
    bench = benchmark_file()
    # The identities' share is this cell's alone; every entry cell 3's
    # closed loop reports lists this cell too.
    new = next(m for m in bench["per_layer"]
               if m["name"] == "zero_expert_share")
    assert new["workloads"] == [CELL] and bench["per_layer"][-1] is new
    third = "sarvam-105b-ep4.sessions-20k"
    for m in bench["per_layer"]:
        if third in m["workloads"]:
            assert CELL in m["workloads"], m["name"]
    for name, what in (("decode_step_bw_share", "bw_share"),
                       ("latent_decode_bw_share", "latent_bw_share"),
                       ("experts_touched_share", "touched_share"),
                       ("zero_expert_share", "zero_share")):
        spec = layers.spec_of(name, [BENCH], CONFIG)
        assert (spec["reader"], spec["args"]["what"]) == (
            "longcat_decode", what)
    # The shared files still serve the configurations they served.
    assert layers.spec_of("latent_decode_bw_share", [BENCH],
                          "sarvam-105b-ep4")["reader"] == "latent_decode_bw"
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["file"] == "bench/configs/longcat-flash-omni-ep32.json"
    assert entry["reduced"] == _config()["reduced"]
    assert bench["configs"][-1] == entry and bench["workloads"][-1] == cell
    for dropped in ("readers/longcat_decode.py", "reduce/longcat_bytes.py",
                    "reference/longcat.py", "tools/compare_rows_longcat.py"):
        assert os.path.exists(os.path.join(BENCH, dropped))
