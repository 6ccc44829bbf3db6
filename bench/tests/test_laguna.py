"""PR 56's cases: a served module with pages for its full layers and rolling
buffers in state slots for its window layers (``models/laguna.py``) through
the harness on the CPU, and the reader and the bytes function its cell
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
CELL = "laguna-xs.2-ep2.sessions-20k"
CONFIG = "laguna-xs.2-ep2"
PAGED, WINDOW = "paged_decode_attention_pallas", "window_decode_attention_pallas"


def _config():
    with open(os.path.join(BENCH, "configs", "laguna-xs.2-ep2.json")) as f:
        return json.load(f)


def _rehearsal():
    with open(os.path.join(DATA, "rehearsal", "bench", "configs",
                           "rehearsal-laguna.json")) as f:
        return json.load(f)


def test_the_window_rehearsal_runs_through_the_harness(tmp_path):
    """``tiny-laguna`` through ``run.py`` on the CPU: engine and router as
    children, the sessions mix, the compare (which hands the cache and no
    slot, and follows the engine's choice of experts) against
    ``reference/laguna.py``; counts only, ``correct``, both pools' counters and
    the window's share of the positions."""
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--benchmark",
         os.path.join(DATA, "rehearsal", "BENCHMARK-laguna.json"),
         "--workload", "rehearsal-laguna.sessions-prefix", "--seed",
         "3900000056", "--seconds", "6", "--trace", "1", "--out-dir",
         str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.splitlines()[-1])
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert metrics["compiles_in_window"] == 0
    # A user new in the window shares the system prompt's keys and no state.
    assert 50 < metrics["state_resume_share"] <= 100
    assert 0 <= metrics["state_recompute_share"] < 25
    assert metrics["prefix_hit_share"] > 50
    # A window of 24 in pages of 8 against contexts of hundreds.
    assert 0 < metrics["window_positions_share"] < 25
    assert 0 < metrics["experts_touched_share"] <= 100
    assert set(result["compared"]) >= {
        "decode_step_1", "choice_shortfall", "served_path_faults"}
    # No timing leaves a CPU rehearsal.
    for name in ("decode_step_bw_share", "paged_decode_bw_share",
                 "window_decode_bw_share", "routed_decode_bw_share",
                 "decode_step_dev_ms"):
        assert metrics.get(name) is None


def test_the_compare_runs_on_the_tiny_preset():
    """``compare.run`` alone on the rehearsal's file: the module's default
    slot addressing against the reference's window-as-a-mask, and the planted
    whole-context fault refused by the file's limits."""
    from harness import compare
    from reference import laguna as ref

    config, root = _rehearsal(), os.path.dirname(BENCH)
    ok, notes, rows = compare.run(config, 1, 3900000056, "cpu", root)
    assert ok, notes
    assert set(rows) >= {"decode_step_0", "decode_step_1", "choice_shortfall"}
    ref.FAULT = "whole_context"
    try:
        ok, _notes, rows = compare.run(config, 1, 3900000056, "cpu", root)
    finally:
        ref.FAULT = None
    limit = config["compare"]["logits_rtol"]
    assert not ok and max(
        v for name, (v, _l) in rows.items() if "step" in name) > limit


def test_the_bytes_are_those_of_the_issues_table():
    from harness.sizes import held
    from reduce import laguna_bytes as lb

    hp = held(_config())
    assert (lb.layers_of(hp, "full_attention"),
            lb.layers_of(hp, "sliding_attention"), lb.sparse_layers(hp)) == (
                2, 6, 7)
    assert lb.expert_bytes(hp) == 3 * 2048 * 512 * 2 == 6_291_456
    # W_q and W_o at the layer's heads, W_k and W_v, a gate a head.
    assert lb.attention_params(hp, 48) == (
        2 * 2048 * 48 * 128 + 2 * 2048 * 1024 + 2048 * 48) == 29_458_432
    assert lb.attention_params(hp, 64) == 37_879_808
    assert lb.routed_fixed_params(hp) == 2048 * 256 + 3 * 2048 * 512
    assert lb.non_expert_bytes(hp) == 2 * (
        2 * 29_458_432 + 6 * 37_879_808 + 3 * 2048 * 8192
        + 7 * 3_670_016 + 2048 * 50176)
    assert abs(lb.non_expert_bytes(hp) / 1e9 - 0.93) < 0.005   # the issue's
    assert lb.kv_bytes_per_position(hp) == 4096
    # 16 rows at 24,000 positions, 8 steps: the record's kv_tokens is a layer
    # of each kind, 16 x (24,000 + 512); its slots' part the window's.
    tokens, slots = 16 * (24000 + 512), 16 * 512
    assert lb.paged_read_bytes(hp, tokens, slots, 8) == (
        8 * 16 * 24000 * 4096 * 2)
    assert lb.window_read_bytes(hp, slots, 8) == 8 * 16 * 512 * 4096 * 6
    assert abs(lb.window_read_bytes(hp, slots, 1) / 1e6 - 201.3) < 0.1
    record = {"k": 8, "kv_tokens": tokens, "kv_tokens_slots": slots,
              "experts_touched": 8 * 7 * 43}
    assert lb.routed_bytes(hp, 8 * 7 * 43, 8) == (
        8 * 7 * 43 * 6_291_456 + 8 * 7 * 3_670_016 * 2)
    assert lb.decode_step_bytes(hp, record) == (
        8 * (lb.non_expert_bytes(hp) - 7 * 3_670_016 * 2)
        + lb.routed_bytes(hp, 8 * 7 * 43, 8)
        + lb.paged_read_bytes(hp, tokens, slots, 8)
        + lb.window_read_bytes(hp, slots, 8))


def _trace():
    """``data/join_small.*`` with both decode reads in it: each window of 2
    steps holds the paged kernel twice a step (the two full layers: the 4
    calls that are there) and the window's call six times a step."""
    trace = load("join_small.trace.json")
    for module in trace["modules"]:
        if module[0] == "window_fn":
            module[3][WINDOW] = 12
    trace["ops"] += [[WINDOW, 2.0e-04, 24]]
    return trace


def _windows():
    """... and the records a ``laguna`` engine writes: 2 rows at ~480
    positions, a layer of each kind in ``kv_tokens``, the window's 2 x 512
    under ``kv_tokens_slots``, the routing counts."""
    windows = copy.deepcopy(load("join_small.windows.json"))
    for w in windows["windows"]:
        if w["rows"]:
            w.update(kv_tokens=w["kv_tokens"] + 1024, kv_tokens_slots=1024,
                     moe_assigned=2 * 2 * 7 * 8, moe_assigned_here=100,
                     experts_touched=2 * 7 * 15, expert_rows_max=2)
    return windows


def _context(trace, prom=None, config=None, windows=None):
    before, after = prom or ({}, {})
    return layers.Context(
        cell={"name": CELL, "config": CONFIG, "chips": 1},
        config=config or _config(),
        records=[], late_ms=[], got={
            "windows": windows or _windows(), "wall_t0": 0.0,
            "seconds": 4e9, "before": {"prom": before}, "after": {
                "prom": after, "device": {"kind": "TPU v5 lite"}}},
        summary={}, dirs=[BENCH], trace=trace)


def _read(ctx, name):
    return layers.read_all(ctx, [name])[name]


def test_the_readers_on_a_sliced_trace():
    from harness.sizes import held
    from reduce import laguna_bytes as lb

    hp, ctx = held(_config()), _context(_trace())
    # The paged kernel: 4 calls a window = 2 steps of 2 full layers, over
    # 992 and 1,024 positions a layer, in 1 us.
    want = (4 * 1024 + 4 * 992) * 4096 / 819e9 / 1.0e-06 * 100.0
    assert _read(ctx, "paged_decode_bw_share") == pytest.approx(want)
    # The window's call: 12 calls a window = 2 steps of 6 layers over 2 rows'
    # 512 rows, in 200 us.
    want = 2 * 12 * 1024 * 4096 / 819e9 / 2.0e-04 * 100.0
    assert _read(ctx, "window_decode_bw_share") == pytest.approx(want)
    assert 0 < want < 100
    records = [w for w in _windows()["windows"] if w["rows"]]
    seconds = (16000 + 14700) / 1e9
    total = sum(lb.decode_step_bytes(hp, w) for w in records)
    assert _read(ctx, "decode_step_bw_share") == pytest.approx(
        total / 819e9 / seconds * 100.0)
    routed = sum(lb.routed_bytes(hp, w["experts_touched"], 2) for w in records)
    assert _read(ctx, "routed_decode_bw_share") == pytest.approx(
        routed / 819e9 / seconds * 100.0)
    assert routed < total
    assert _read(ctx, "decode_step_dev_ms") == pytest.approx(
        (16000 + 14700) / 4 / 1e6)
    # 15 of 128 held experts a layer a step; 1,024 of 992 / 1,024 positions.
    assert _read(ctx, "experts_touched_share") == pytest.approx(
        100.0 * 15 / 128)
    assert _read(ctx, "window_positions_share") == pytest.approx(
        100.0 * 2048 / (992 + 1024))


def test_the_state_pools_counters_give_the_two_shares():
    before = {"tpu:state_resumes_total": 10.0,
              "tpu:state_resume_miss_total": 32.0,
              "tpu:state_recomputed_tokens_total": 32000.0,
              "tpu:prefix_cache_query_tokens_total": 700000.0}
    after = {"tpu:state_resumes_total": 310.0,
             "tpu:state_resume_miss_total": 32.0,
             "tpu:state_recomputed_tokens_total": 41000.0,
             "tpu:prefix_cache_query_tokens_total": 7900000.0}
    ctx = _context(None, prom=(before, after))
    assert _read(ctx, "state_resume_share") == 100.0
    assert _read(ctx, "state_recompute_share") == pytest.approx(0.125)
    after["tpu:state_resume_miss_total"] = 132.0
    assert _read(ctx, "state_resume_share") == 75.0


def test_the_readers_find_nothing_where_nothing_was_counted():
    """On the parent's records (no ``kv_tokens_slots``, no routing counts, no
    call of the window's name), without a trace, and on another
    architecture's configuration every new reader returns None and raises
    nothing."""
    from readers import laguna_decode

    device = ("paged_decode_bw_share", "window_decode_bw_share",
              "decode_step_bw_share", "routed_decode_bw_share")
    counted = ("experts_touched_share", "window_positions_share",
               "state_resume_share")
    plain = _context(load("join_small.trace.json"),
                     windows=load("join_small.windows.json"))
    for name in device + counted:
        assert _read(plain, name) is None, name
    plain.trace = None
    for name in device:
        assert _read(plain, name) is None, name
    with open(os.path.join(BENCH, "configs",
                           "solar-open2-250b-ep8.json")) as f:
        other = types.SimpleNamespace(config=json.load(f))
    for what in ("step_bw_share", "window_bw_share", "resume_share",
                 "window_positions_share"):
        assert laguna_decode.read(other, {"what": what}) is None


def test_the_file_states_the_source_whole_and_every_cut():
    from harness.sizes import held

    config = _config()
    lists = ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer")
    assert set(config["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size", *lists}
    published = config["published"]
    for key, value in published.items():
        if key not in config["reduced"]:
            assert config[key] == value, key         # no width differs
    for key in lists:                                 # two whole periods
        assert config[key] == published[key][:8]
    assert (config["num_hidden_layers"], config["num_experts"],
            config["vocab_size"]) == (8, 128, 50176)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):       # the builder's machine has it
        with open(catalog) as f:
            entry = next(c for c in map(json.loads, f)
                         if c["name"] == "Laguna-XS.2")
        assert published == entry["config"]
        assert config["source"] == entry["source_url"]
    hp = held(config)
    assert hp["published"]["num_experts"] == 256 and hp["head_dim"] == 128
    spec = config["compare"]
    assert spec["follow_choice"] and spec["layers"] >= 5
    assert spec["prompt_tokens"] == [4400, 300] and spec["decode_steps"] >= 2
    # The compared layers hold the dense lead, window layers and a routed
    # full layer; the longer prompt crosses the window and wraps its buffer.
    assert hp["mlp_layer_types"][0] == "dense"
    assert "sparse" in hp["mlp_layer_types"][:spec["layers"]]
    kinds = hp["layer_types"][:spec["layers"]]
    assert kinds.count("full_attention") >= 2 and "sliding_attention" in kinds
    assert spec["prompt_tokens"][0] > 8 * hp["sliding_window"]
    assert spec["prompt_tokens"][1] < hp["sliding_window"]
    for key in ("stands_for", "assumed"):
        assert config[key]
    for silence in ("gating", "router"):
        assert "silence" in config["assumed"][silence], silence
    for word in ("float8", "whole context", "seeds"):
        assert word in spec["why_rtol"], word
        assert word in spec["why_shortfall"] or word == "whole context"
    assert config["engine_argv"] == [
        "--max-model-len", "32768", "--max-num-seqs", "16",
        "--prefill-buckets", "256,2048", "--window-ring-size", "8192",
        "--no-mixed-batch"]


def test_the_entries_that_list_the_cell_hold_the_rule():
    """The rule, not a count (``conftest.hold_a_cell_to_the_rule``); the
    dropped-in reader and bytes function are where the harness looks."""
    cell, names = hold_a_cell_to_the_rule(CELL, own=(
        "decode_step_dev_ms", "decode_step_bw_share", "paged_decode_bw_share",
        "window_decode_bw_share", "routed_decode_bw_share",
        "experts_touched_share", "window_positions_share",
        "state_resume_share", "state_recompute_share",
        "prefix_chain_hashed_share", "build_transfers_per_dispatch",
        "dispatch_behind_share"))
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "sessions-20k", 1)
    assert len(cell["why"]) <= 200
    bench = benchmark_file()
    # The routed layers' part of the step's share is this cell's alone; the
    # whole step is the name every cell gives it.
    part = next(m for m in bench["per_layer"]
                if m["name"] == "routed_decode_bw_share")
    assert part["workloads"] == [CELL]
    for name, what in (("routed_decode_bw_share", "routed_bw_share"),
                       ("decode_step_bw_share", "step_bw_share"),
                       ("paged_decode_bw_share", "paged_bw_share")):
        spec = layers.spec_of(name, [BENCH], CONFIG)
        assert (spec["reader"], spec["args"]["what"]) == (
            "laguna_decode", what)
    entry = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert entry["file"] == "bench/configs/laguna-xs.2-ep2.json"
    assert entry["reduced"] == _config()["reduced"]
    for dropped in ("readers/laguna_decode.py", "reduce/laguna_bytes.py",
                    "reference/laguna.py"):
        assert os.path.exists(os.path.join(BENCH, dropped))
