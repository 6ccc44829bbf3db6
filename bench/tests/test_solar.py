"""PR 48's cases: a served module with pages and recurrent state in one cache
tree (``models/solar_kda.py``) through the harness on the CPU, and the reader
and the bytes and operations functions its cell brings.  A file of its own:
the files that were there are not edited."""

import json
import os
import sys
import types

import pytest

from conftest import BENCH, benchmark_file, hold_a_cell_to_the_rule
from harness import layers
from test_join import load

DATA = os.path.join(BENCH, "tests", "data")
CELL = "solar-open2-250b-ep8.sessions-20k"
DECODE, PREFILL = "kda_decode_pallas", "kda_prefill_pallas"


def _config():
    with open(os.path.join(BENCH, "configs",
                           "solar-open2-250b-ep8.json")) as f:
        return json.load(f)


def test_the_delta_rule_rehearsal_runs_through_the_harness(tmp_path):
    """``tiny-solar`` through ``run.py`` on the CPU: engine and router as
    children, the sessions mix, the compare (which hands the cache and no
    slot) following the engine's choice against ``reference/solar_kda.py``;
    counts only, ``correct``, and the state pool's counters."""
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--benchmark",
         os.path.join(DATA, "rehearsal", "BENCHMARK-solar.json"), "--workload",
         "rehearsal-solar.sessions-prefix", "--seed", "3900000048",
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
    assert 0 < metrics["experts_touched_share"] <= 100
    assert metrics["prefix_hit_share"] > 50
    assert set(result["compared"]) >= {
        "choice_shortfall", "return_choice_logits_differ", "decode_step_1"}
    # No timing leaves a CPU rehearsal.
    for name in ("decode_step_bw_share", "kda_decode_bw_share",
                 "kda_prefill_roofline_share"):
        assert metrics.get(name) is None


def test_the_bytes_and_operations_are_those_of_the_issues_table():
    from harness.sizes import held
    from reduce import solar_bytes as sb

    hp = held(_config())
    assert (sb.gqa_layers(hp), sb.kda_layers(hp)) == (1, 3)
    assert sb.expert_bytes(hp) == 3 * 4096 * 1280 * 2          # 31.46 MB
    assert sb.kv_bytes_per_token(hp) == 4096
    assert sb.state_bytes(hp) == 64 * 128 * 128 * 4            # 4.19 MB
    assert sb.conv_bytes(hp) == 3 * 3 * 8192 * 2               # 147 KB
    # A row's state and convolution rows read and written in three layers.
    assert sb.decode_state_bytes(hp, 16, 8) == 8 * 16 * 3 * 2 * (
        4194304 + 147456)
    assert sb.decode_read_bytes(hp, 16 * 24000, 8) == 8 * 16 * 24000 * 4096
    # One softmax layer with the full gate (109.1 M without the experts'
    # router and shared expert), three delta-rule layers (138.0 M), four
    # routers (1.31 M) and shared experts (15.73 M), 24,576 columns of the
    # head: 1.38 GB, the issue's "1.2 GB of non-expert weights + 0.2 of head".
    softmax = 3 * 4096 * 8192 + 2 * 4096 * 1024
    delta = (4 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64
             + 4 * 24576)
    want = 2 * (softmax + 3 * delta + 4 * (4096 * 320 + 3 * 4096 * 1280)
                + 4096 * 24576)
    assert sb.non_expert_bytes(hp) == want
    assert abs(want / 1e9 - 1.38) < 0.01
    assert sb.recurrence_flops(hp, 256) == 7 * 256 * 64 * 128 * 128
    assert sb.recurrence_bytes(hp, 256) == (
        256 * 64 * (5 * 128 + 1) * 4 + 2 * 4194304)


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
    trace["ops"] += [[DECODE, 4.0e-05, 12], [PREFILL, 2.0e-05, 3]]
    return trace


def _context(trace, payload=None, prom=None):
    payload = payload or load("join_small.windows.json")
    for w in payload["windows"]:
        if w["rows"]:
            w.update(experts_touched=2 * 4 * 11, moe_assigned=2 * 4 * 8 * 2)
    before, after = prom or ({}, {})
    return layers.Context(
        cell={"name": CELL, "config": "solar-open2-250b-ep8", "chips": 1},
        config=_config(), records=[],
        late_ms=[], got={
            "windows": payload, "wall_t0": 0.0, "seconds": 4e9,
            "before": {"prom": before}, "after": {
                "prom": after, "device": {"kind": "TPU v5 lite"}}},
        summary={}, dirs=[BENCH], trace=trace)


def _read(ctx, name):
    return layers.read_all(ctx, [name])[name]


def test_the_readers_on_a_sliced_trace():
    from harness.sizes import held
    from reduce import solar_bytes as sb

    hp, ctx = held(_config()), _context(_trace())
    # Two windows x 6 calls x 2 rows x a state read and written, in 40 us.
    want = 2 * 6 * 2 * 2 * 4194304 / 819e9 / 4.0e-05 * 100.0
    assert _read(ctx, "kda_decode_bw_share") == pytest.approx(want)
    # 3 calls over 100 new tokens: the bytes bind, not the operations.
    flops = sb.recurrence_flops(hp, 100) / 197e12
    moved = sb.recurrence_bytes(hp, 100) / 819e9
    assert moved > flops
    assert _read(ctx, "kda_prefill_roofline_share") == pytest.approx(
        3 * moved / 2.0e-05 * 100.0)
    # The whole step: 4 steps of non-expert weights, the touched experts,
    # the softmax layer's keys and 2 rows' states in three layers.
    total = (4 * sb.non_expert_bytes(hp) + 2 * 88 * sb.expert_bytes(hp)
             + 2 * (992 + 1024) * 4096 + 2 * sb.decode_state_bytes(hp, 2, 2))
    assert _read(ctx, "decode_step_bw_share") == pytest.approx(
        total / 819e9 / ((16000 + 14700) / 1e9) * 100.0)
    assert _read(ctx, "experts_touched_share") == pytest.approx(
        100.0 * 2 * 88 / (40 * 4 * 4))
    assert _read(ctx, "decode_step_dev_ms") == pytest.approx(
        (16000 + 14700) / 4 / 1e6)


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
    """On the parent's counters and records (no state pool) and on a trace
    without the kernels every new reader returns None and raises nothing;
    and on another architecture's configuration."""
    from readers import solar_decode

    names = ("kda_decode_bw_share", "kda_prefill_roofline_share",
             "decode_step_bw_share", "state_resume_share",
             "state_recompute_share")
    plain = layers.Context(
        cell={"name": CELL, "config": "solar-open2-250b-ep8", "chips": 1},
        config=_config(), records=[],
        late_ms=[], got={
            "windows": load("join_small.windows.json"), "wall_t0": 0.0,
            "seconds": 4e9, "before": {"prom": {}}, "after": {
                "prom": {}, "device": {"kind": "TPU v5 lite"}}},
        summary={}, dirs=[BENCH], trace=load("join_small.trace.json"))
    for name in names + ("experts_touched_share",):
        assert _read(plain, name) is None, name
    plain.trace = None
    for name in names:
        assert _read(plain, name) is None, name
    with open(os.path.join(BENCH, "configs", "sarvam-105b-ep4.json")) as f:
        other = types.SimpleNamespace(config=json.load(f))
    assert solar_decode.read(other, {"what": "bw_share"}) is None


def test_the_file_keeps_every_published_width():
    config = _config()
    changed = {k for k, v in config["published"].items()
               if config.get(k) != v}
    assert changed == set(config["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size", "gqa_layers"}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"], config["gqa_layers"]) == (4, 40, 24576, [0])
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):       # the builder's machine has it
        with open(catalog) as f:
            entry = next(c for c in map(json.loads, f)
                         if c["name"] == "Solar-Open2-250B")
        assert config["published"] == entry["config"]
        assert config["source"] == entry["source_url"]
    spec = config["compare"]
    assert spec["follow_choice"] and spec["layers"] == 4
    assert spec["prompt_tokens"] == [4400, 300]
    for key in ("stands_for", "assumed"):
        assert config[key]
    for key in ("why_rtol", "why_shortfall"):
        assert "float8" in spec[key], key
    assert "bfloat16 state" in spec["why_rtol"]
    assert (spec["logits_rtol"], spec["choice_shortfall"]) == (0.065, 0.2)


def test_the_entries_that_list_the_cell_hold_the_rule():
    cell, names = hold_a_cell_to_the_rule(CELL, own=(
        "decode_step_dev_ms", "decode_step_bw_share", "kda_decode_bw_share",
        "kda_prefill_roofline_share", "paged_decode_bw_share",
        "experts_touched_share", "state_resume_share",
        "state_recompute_share"))
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "solar-open2-250b-ep8", "sessions-20k", 1)
    # The whole step against this module's bytes under the name every cell
    # gives it; the routed layers' part is another quantity (cell 7's).
    assert "routed_decode_bw_share" not in names
    for name in ("decode_step_bw_share", "experts_touched_share",
                 "state_resume_share"):
        assert layers.spec_of(name, [BENCH], cell["config"])[
            "reader"] == "solar_decode", name
    entry = next(c for c in benchmark_file()["configs"]
                 if c["name"] == cell["config"])
    assert entry["file"] == "bench/configs/solar-open2-250b-ep8.json"
