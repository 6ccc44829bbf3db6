"""PR 63's cases: a served module with pages of 32 key heads for 30 and a
3-layer slot of 96 x 192 delta-rule state in one cache tree
(``models/olmo_hybrid.py``) through the harness on the CPU, and the reader and
the bytes and operations functions its cell brings.  A file of its own: the
files that were there are not edited."""

import json
import os
import sys
import types

import pytest

from conftest import BENCH, benchmark_file, hold_a_cell_to_the_rule
from harness import layers
from test_join import load

DATA = os.path.join(BENCH, "tests", "data")
CONFIG = "olmo-hybrid-7b-stage"
CELL = CONFIG + ".sessions-20k"
DECODE, PREFILL = "kda_decode_pallas", "gdn_prefill_pallas"


def _config():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def test_the_delta_rule_rehearsal_runs_through_the_harness(tmp_path):
    """``tiny-olmo`` through ``run.py`` on the CPU: engine and router as
    children, the sessions mix, the compare (which hands the cache and no
    slot) against ``reference/olmo_hybrid.py``; counts only, ``correct``, the
    state pool's counters and the state's own."""
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--benchmark",
         os.path.join(DATA, "rehearsal", "BENCHMARK-olmo.json"), "--workload",
         "rehearsal-olmo.sessions-prefix", "--seed", "3900000063",
         "--seconds", "6", "--trace", "1", "--out-dir", str(tmp_path)],
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
    assert 0 < metrics["gdn_state_absmax"] < 1000
    assert set(result["compared"]) >= {"decode_step_1", "served_path_faults"}
    # No timing leaves a CPU rehearsal.
    for name in ("decode_step_bw_share", "kda_decode_bw_share",
                 "gdn_prefill_roofline_share", "decode_step_dev_ms"):
        assert metrics.get(name) is None


def test_the_compare_runs_on_the_tiny_preset():
    """``compare.run`` alone on the rehearsal's file: the module's default
    slot addressing against the token-by-token reference, and each planted
    fault refused by the file's limit."""
    from harness import compare
    from reference import olmo_hybrid as ref

    with open(os.path.join(DATA, "rehearsal", "bench", "configs",
                           "rehearsal-olmo.json")) as f:
        config = json.load(f)
    limit = config["compare"]["logits_rtol"]
    root = os.path.dirname(BENCH)
    ok, notes, rows = compare.run(config, 1, 3900000063, "cpu", root)
    assert ok, notes
    assert len(rows) == 4 and all(v <= limit for v, _limit in rows.values())
    for fault in ("beta_not_doubled", "decay_a_channel", "norm_before"):
        ref.FAULT = fault
        try:
            ok, _notes, rows = compare.run(config, 1, 3900000063, "cpu", root)
        finally:
            ref.FAULT = None
        assert not ok and max(v for v, _l in rows.values()) > limit, fault


def test_the_bytes_and_operations_are_those_of_the_issues_table():
    from harness.sizes import held
    from reduce import olmo_bytes as ob

    hp = held(_config())
    assert (ob.full_layers(hp), ob.linear_layers(hp)) == (1, 3)
    assert ob.conv_channels(hp) == 11_520
    assert ob.linear_params(hp) == 88_750_332                # 88.7 M
    assert ob.full_params(hp) == 58_990_080                  # 59.0 M
    assert ob.mlp_params(hp) == 126_812_160                  # 126.8 M
    assert ob.params(hp) == 1_603_227_636
    assert abs(ob.weight_bytes(hp) / 1e9 - 2.436) < 0.001    # period + head
    assert ob.kv_bytes_per_token(hp) == 15_360               # 30 heads, not 32
    assert ob.state_bytes(hp) == 30 * 96 * 192 * 4 == 2_211_840
    assert ob.conv_bytes(hp) == 3 * 11_520 * 2 == 69_120
    assert ob.slot_bytes(hp) == 3 * (2_211_840 + 69_120)
    # 16 rows' slots read and written, 8 steps; 16 x 24,000 positions.
    assert ob.decode_state_bytes(hp, 16, 8) == 8 * 16 * 2 * 6_842_880
    assert ob.decode_read_bytes(hp, 16 * 24000, 8) == 8 * 16 * 24000 * 15_360
    assert ob.recurrence_flops(hp, 256) == 7 * 256 * 30 * 96 * 192
    assert ob.recurrence_bytes(hp, 256) == (
        256 * 30 * (2 * 96 + 2 * 192 + 2) * 4 + 2 * 2_211_840)


def _trace():
    """``data/join_small.*`` with the delta-rule kernels in it: a prefill of
    100 new tokens (3 calls, a layer each) and two windows of 2 steps x 3
    layers over 2 rows."""
    trace = load("join_small.trace.json")
    for module in trace["modules"]:
        if module[0] == "window_fn":
            module[3][DECODE] = 6
        if module[0] == "prefill_fn":
            module[3][PREFILL] = 3
    trace["ops"] += [[DECODE, 2.0e-04, 12], [PREFILL, 1.0e-03, 3]]
    return trace


def _context(trace, prom=None, config=None):
    before, after = prom or ({}, {})
    return layers.Context(
        cell={"name": CELL, "config": CONFIG, "chips": 1},
        config=config or _config(),
        records=[], late_ms=[], got={
            "windows": load("join_small.windows.json"), "wall_t0": 0.0,
            "seconds": 4e9, "before": {"prom": before}, "after": {
                "prom": after, "device": {"kind": "TPU v5 lite"}}},
        summary={}, dirs=[BENCH], trace=trace)


def _read(ctx, name):
    return layers.read_all(ctx, [name])[name]


def test_the_readers_on_a_sliced_trace():
    from harness.sizes import held
    from reduce import olmo_bytes as ob

    hp, ctx = held(_config()), _context(_trace())
    # Two windows x 6 calls x 2 rows x a layer's unpadded state read and
    # written, in 200 us.
    want = 2 * 6 * 2 * 2 * 2_211_840 / 819e9 / 2.0e-04 * 100.0
    assert _read(ctx, "kda_decode_bw_share") == pytest.approx(want)
    assert 0 < want < 100
    # 3 calls over 100 new tokens: the larger of the two bounds.
    flops = ob.recurrence_flops(hp, 100) / 197e12
    moved = ob.recurrence_bytes(hp, 100) / 819e9
    assert _read(ctx, "gdn_prefill_roofline_share") == pytest.approx(
        3 * max(flops, moved) / 1.0e-03 * 100.0)
    # The whole step: 4 steps of weights, the softmax layer's keys and 2
    # rows' slots, over the two windows' device time.
    total = (4 * ob.weight_bytes(hp) + 2 * (992 + 1024) * 15_360
             + 2 * ob.decode_state_bytes(hp, 2, 2))
    assert _read(ctx, "decode_step_bw_share") == pytest.approx(
        total / 819e9 / ((16000 + 14700) / 1e9) * 100.0)
    assert _read(ctx, "decode_step_dev_ms") == pytest.approx(
        (16000 + 14700) / 4 / 1e6)
    # The paged kernel's reader, the file that is there: calls x positions
    # x one layer's K and V at 30 key heads, whatever the depth.
    assert layers.spec_of("paged_decode_bw_share", [BENCH], CONFIG)[
        "reader"] == "paged_decode_bw"
    from reduce.kv_bytes import decode_read_bytes
    assert decode_read_bytes(hp, 1000, 1 / hp["num_hidden_layers"]) == (
        1000 * 15_360)


def test_the_states_counter_is_the_records_largest():
    ctx = _context(None)
    records = ctx.got["windows"]["windows"]
    assert _read(ctx, "gdn_state_absmax") is None
    for i, w in enumerate(records):
        w["gdn_state_absmax_e3"] = 1500 + i
    assert _read(ctx, "gdn_state_absmax") == pytest.approx(
        (1500 + len(records) - 1) / 1e3)


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
    """On the parent's counters and records (no such preset, no kernels) and
    on a trace without the kernels every new reader returns None and raises
    nothing; and on another architecture's configuration."""
    from readers import olmo_decode

    names = ("kda_decode_bw_share", "gdn_prefill_roofline_share",
             "gdn_state_absmax", "state_resume_share",
             "state_recompute_share")
    plain = _context(load("join_small.trace.json"))
    for name in names:
        assert _read(plain, name) is None, name
    plain.trace = None
    for name in names + ("decode_step_bw_share",):
        assert _read(plain, name) is None, name
    with open(os.path.join(BENCH, "configs",
                           "solar-open2-250b-ep8.json")) as f:
        other = types.SimpleNamespace(config=json.load(f))
    for what in ("step_bw_share", "kda_decode_bw_share", "resume_share",
                 "state_absmax", "gdn_prefill_roofline_share"):
        assert olmo_decode.read(other, {"what": what}) is None


def test_the_file_keeps_every_published_key_and_cuts_depth_alone():
    from harness.sizes import held

    config = _config()
    assert config["reduced"] == ["num_hidden_layers", "layer_types"]
    assert all(config[k] == v for k, v in config["published"].items()
               if k not in config["reduced"])
    assert config["layer_types"] == config["published"]["layer_types"][:4]
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):       # the builder's machine has it
        with open(catalog) as f:
            entry = next(c for c in map(json.loads, f)
                         if c["name"] == "Olmo-Hybrid-7B")
        assert config["published"] == entry["config"]
        assert config["source"] == entry["source_url"]
    hp = held(config)
    assert (hp["num_hidden_layers"], hp["vocab_size"], hp["head_dim"],
            hp["hidden_size"], hp["intermediate_size"]) == (
        4, 100352, 128, 3840, 11008)
    assert (hp["num_attention_heads"], hp["num_key_value_heads"]) == (30, 30)
    assert (hp["linear_num_value_heads"], hp["linear_key_head_dim"],
            hp["linear_value_head_dim"], hp["linear_conv_kernel_dim"]) == (
        30, 96, 192, 4)
    spec = config["compare"]
    assert "follow_choice" not in spec and spec["layers"] == 4
    assert spec["prompt_tokens"] == [4400, 300]
    for key in ("stands_for", "assumed"):
        assert config[key]
    for key in ("block_form", "head_dim", "qk_norm", "no_position_encoding",
                "delta_rule", "kv_cache", "state_pool"):
        assert config["assumed"][key], key
    for word in ("float8", "seeds", "beta_not_doubled"):
        assert word in spec["why_rtol"], word
    assert config["engine_argv"] == [
        "--max-model-len", "32768", "--max-num-seqs", "16",
        "--prefill-buckets", "256,2048", "--window-ring-size", "8192",
        "--no-mixed-batch"]


def test_the_entries_that_list_the_cell_hold_the_rule():
    cell, names = hold_a_cell_to_the_rule(CELL, own=(
        "decode_step_dev_ms", "decode_step_bw_share", "kda_decode_bw_share",
        "gdn_prefill_roofline_share", "gdn_state_absmax",
        "paged_decode_bw_share", "state_resume_share",
        "state_recompute_share", "prefix_hit_share", "prefill_dev_ms",
        "prefix_chain_hashed_share", "build_transfers_per_dispatch",
        "dispatch_behind_share"))
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "sessions-20k", 1)
    # The step and its share by this module's bytes, not the dense reader's.
    for name in ("decode_step_bw_share", "state_resume_share",
                 "kda_decode_bw_share"):
        assert layers.spec_of(name, [BENCH], CONFIG)[
            "reader"] == "olmo_decode", name
    # Solar's cell still reads the shared kernel by its own bytes.
    assert layers.spec_of("kda_decode_bw_share", [BENCH],
                          "solar-open2-250b-ep8")["reader"] == "solar_decode"
    entry = next(c for c in benchmark_file()["configs"]
                 if c["name"] == CONFIG)
    assert entry["file"] == "bench/configs/olmo-hybrid-7b-stage.json"
    assert entry["reduced"] == ["num_hidden_layers", "layer_types"]
