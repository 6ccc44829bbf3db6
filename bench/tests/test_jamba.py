"""PR 53's cases: a served module with pages and a 26-layer slot of
state-space state in one cache tree (``models/jamba.py``) through the harness
on the CPU, and the reader and the bytes and operations functions its cell
brings.  A file of its own: the files that were there are not edited."""

import json
import os
import sys
import types

import pytest

from conftest import BENCH, benchmark_file, hold_a_cell_to_the_rule
from harness import layers
from test_join import load

DATA = os.path.join(BENCH, "tests", "data")
CELL = "jamba2-3b.sessions-20k"
DECODE, PREFILL = "ssm_decode_pallas", "ssm_prefill_pallas"


def _config():
    with open(os.path.join(BENCH, "configs", "jamba2-3b.json")) as f:
        return json.load(f)


def test_the_state_space_rehearsal_runs_through_the_harness(tmp_path):
    """``tiny-jamba`` through ``run.py`` on the CPU: engine and router as
    children, the sessions mix, the compare (which hands the cache and no
    slot) against ``reference/jamba.py``; counts only, ``correct``, and the
    state pool's counters."""
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--benchmark",
         os.path.join(DATA, "rehearsal", "BENCHMARK-jamba.json"), "--workload",
         "rehearsal-jamba.sessions-prefix", "--seed", "3900000053",
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
    assert set(result["compared"]) >= {"decode_step_1", "served_path_faults"}
    # No timing leaves a CPU rehearsal.
    for name in ("decode_step_bw_share", "ssm_decode_bw_share",
                 "ssm_prefill_roofline_share", "decode_step_dev_ms"):
        assert metrics.get(name) is None


def test_the_compare_runs_on_the_tiny_preset():
    """``compare.run`` alone on the rehearsal's file: the module's default
    slot addressing against the token-by-token reference, and a planted fault
    refused by the file's limit."""
    from harness import compare
    from reference import jamba as ref

    with open(os.path.join(DATA, "rehearsal", "bench", "configs",
                           "rehearsal-jamba.json")) as f:
        config = json.load(f)
    root = os.path.dirname(BENCH)
    ok, notes, rows = compare.run(config, 1, 3900000053, "cpu", root)
    assert ok, notes
    assert len(rows) == 4 and all(v <= 0.05 for v, _limit in rows.values())
    ref.FAULT = "no_dt_bias"
    try:
        ok, _notes, rows = compare.run(config, 1, 3900000053, "cpu", root)
    finally:
        ref.FAULT = None
    assert not ok and max(v for v, _limit in rows.values()) > 0.05


def test_the_bytes_and_operations_are_those_of_the_issues_table():
    from harness.sizes import held
    from reduce import jamba_bytes as jb

    hp = held(_config())
    assert (jb.attention_layers(hp), jb.mamba_layers(hp)) == (2, 26)
    assert jb.inner(hp) == 5120
    assert jb.mixer_params(hp) == 41_241_792                 # 41.2 M
    assert jb.attention_params(hp) == 13_762_560             # 13.8 M
    assert jb.mlp_params(hp) == 62_914_560                   # 62.9 M
    assert jb.params(hp) == 3_029_337_472                    # 3.03 B
    assert abs(jb.weight_bytes(hp) / 1e9 - 6.06) < 0.005     # 6.06 GB
    assert jb.kv_bytes_per_token(hp) == 1024
    assert jb.state_bytes(hp) == 16 * 5120 * 4 == 327_680
    assert jb.conv_bytes(hp) == 3 * 5120 * 2 == 30_720
    assert jb.slot_bytes(hp) == 9_318_400
    # 16 rows' slots read and written, 8 steps; 16 x 24,000 positions.
    assert jb.decode_state_bytes(hp, 16, 8) == 8 * 16 * 2 * 9_318_400
    assert jb.decode_read_bytes(hp, 16 * 24000, 8) == 8 * 16 * 24000 * 1024
    assert jb.recurrence_flops(hp, 256) == 7 * 256 * 16 * 5120
    assert jb.recurrence_bytes(hp, 256) == (
        256 * (4 * 5120 + 32) * 4 + 2 * 327_680)


def _trace():
    """``data/join_small.*`` with the state-space kernels in it: a prefill of
    100 new tokens (26 calls, a layer each) and two windows of 2 steps x 26
    layers over 2 rows."""
    trace = load("join_small.trace.json")
    for module in trace["modules"]:
        if module[0] == "window_fn":
            module[3][DECODE] = 52
        if module[0] == "prefill_fn":
            module[3][PREFILL] = 26
    trace["ops"] += [[DECODE, 4.0e-04, 104], [PREFILL, 2.0e-03, 26]]
    return trace


def _context(trace, prom=None, config=None):
    before, after = prom or ({}, {})
    return layers.Context(
        cell={"name": CELL, "config": "jamba2-3b", "chips": 1},
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
    from reduce import jamba_bytes as jb

    hp, ctx = held(_config()), _context(_trace())
    # Two windows x 52 calls x 2 rows x a layer's state and convolution
    # rows read and written, in 400 us.
    want = 2 * 52 * 2 * 2 * (327_680 + 30_720) / 819e9 / 4.0e-04 * 100.0
    assert _read(ctx, "ssm_decode_bw_share") == pytest.approx(want)
    assert 0 < want < 100
    # 26 calls over 100 new tokens: the bytes bind, not the operations.
    flops = jb.recurrence_flops(hp, 100) / 197e12
    moved = jb.recurrence_bytes(hp, 100) / 819e9
    assert moved > flops
    assert _read(ctx, "ssm_prefill_roofline_share") == pytest.approx(
        26 * moved / 2.0e-03 * 100.0)
    # The whole step: 4 steps of weights, the two attention layers' keys
    # and 2 rows' slots, over the two windows' device time.
    total = (4 * jb.weight_bytes(hp) + 2 * (992 + 1024) * 1024
             + 2 * jb.decode_state_bytes(hp, 2, 2))
    assert _read(ctx, "decode_step_bw_share") == pytest.approx(
        total / 819e9 / ((16000 + 14700) / 1e9) * 100.0)
    assert _read(ctx, "decode_step_dev_ms") == pytest.approx(
        (16000 + 14700) / 4 / 1e6)
    # The paged kernel's reader, the file that is there: calls x positions
    # x one layer's K and V, whatever the depth.
    assert layers.spec_of("paged_decode_bw_share", [BENCH], "jamba2-3b")[
        "reader"] == "paged_decode_bw"


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
    from readers import jamba_decode

    names = ("ssm_decode_bw_share", "ssm_prefill_roofline_share",
             "state_resume_share", "state_recompute_share")
    plain = _context(load("join_small.trace.json"))
    for name in names:
        assert _read(plain, name) is None, name
    plain.trace = None
    for name in names + ("decode_step_bw_share",):
        assert _read(plain, name) is None, name
    with open(os.path.join(BENCH, "configs",
                           "solar-open2-250b-ep8.json")) as f:
        other = types.SimpleNamespace(config=json.load(f))
    for what in ("step_bw_share", "ssm_decode_bw_share", "resume_share"):
        assert jamba_decode.read(other, {"what": what}) is None


def test_the_file_keeps_every_published_key_and_cuts_nothing():
    from harness.sizes import held

    config = _config()
    assert config["reduced"] == []
    assert all(config[k] == v for k, v in config["published"].items())
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):       # the builder's machine has it
        with open(catalog) as f:
            entry = next(c for c in map(json.loads, f)
                         if c["name"] == "AI21-Jamba2-3B")
        assert config["published"] == entry["config"]
        assert config["source"] == entry["source_url"]
    hp = held(config)
    assert (hp["num_hidden_layers"], hp["vocab_size"], hp["head_dim"]) == (
        28, 65536, 128)
    spec = config["compare"]
    assert "follow_choice" not in spec and spec["layers"] == 14
    assert spec["prompt_tokens"] == [4400, 300]
    assert spec["layers"] % hp["attn_layer_period"] == 0
    for key in ("stands_for", "assumed"):
        assert config[key]
    for word in ("float8", "bfloat16 state", "seeds"):
        assert word in spec["why_rtol"], word
    assert config["engine_argv"] == [
        "--max-model-len", "32768", "--max-num-seqs", "16",
        "--prefill-buckets", "256,2048", "--window-ring-size", "8192",
        "--no-mixed-batch"]


def test_the_entries_that_list_the_cell_hold_the_rule():
    cell, names = hold_a_cell_to_the_rule(CELL, own=(
        "decode_step_dev_ms", "decode_step_bw_share", "ssm_decode_bw_share",
        "ssm_prefill_roofline_share", "paged_decode_bw_share",
        "state_resume_share", "state_recompute_share",
        "paged_coalesced_share", "prefix_chain_hashed_share",
        "build_transfers_per_dispatch", "dispatch_behind_share"))
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "jamba2-3b", "sessions-20k", 1)
    # The step and its share by this module's bytes, not the dense reader's.
    for name in ("decode_step_bw_share", "state_resume_share"):
        assert layers.spec_of(name, [BENCH], "jamba2-3b")[
            "reader"] == "jamba_decode", name
    entry = next(c for c in benchmark_file()["configs"]
                 if c["name"] == "jamba2-3b")
    assert entry["file"] == "bench/configs/jamba2-3b.json"
    assert entry["reduced"] == []
