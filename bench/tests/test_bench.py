import json
import os
import sys
import types

import pytest

from conftest import BENCH, benchmark_file, hold_a_cell_to_the_rule
from generators import open_loop, sessions
from harness import layers
from harness.client import Record
from reduce import stats, xplane

DATA = os.path.join(BENCH, "tests", "data")


def traffic(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


# -- the arrival schedule and the lengths are a pure function of the seed --


def test_open_loop_schedule_is_a_pure_function_of_the_seed():
    t = traffic("chat-steady")
    a = open_loop.schedule(t, 4.0, 45, seed=3_000_000_001)
    b = open_loop.schedule(t, 4.0, 45, seed=3_000_000_001)
    c = open_loop.schedule(t, 4.0, 45, seed=7)
    assert a == b and a != c
    assert len(a) == 180 and a[0]["at"] == 0.0 and a[-1]["at"] < 45
    # Every seed: the same arrivals and sizes; only the bytes differ.
    strip = lambda plan: [{k: v for k, v in p.items() if k != "text"}
                          for p in plan]
    assert strip(a) == strip(c)
    assert all(32 <= p["prompt_tokens"] <= 2048
               and 16 <= p["output_tokens"] <= 512 for p in a)
    # No two prompts share a 16-byte block at the start.
    assert len({p["text"][:16] for p in a}) == len(a)


def test_sessions_population_is_a_pure_function_of_the_seed():
    t = traffic("sessions-prefix")
    a = sessions.population(t, 11, "u")
    b = sessions.population(t, 11, "u")
    c = sessions.population(t, 12, "u")
    msgs = lambda pop: [s.messages for s in pop["sessions"]]
    assert msgs(a) == msgs(b) and msgs(a) != msgs(c)
    assert a["system"] == c["system"] and len(a["system"]) == 1000
    assert [s.round for s in a["sessions"]] == [i % 8 for i in range(12)]
    # User 3 starts at round 3: system, history + question... 3 answers in.
    roles = [m["role"] for m in a["sessions"][3].messages]
    assert roles == ["system", "user", "assistant", "user", "assistant",
                     "user", "assistant"]
    # A round's prompt is a prefix of the next round's.
    s = a["sessions"][0]
    s.ask()
    before = json.dumps(s.messages)[:-1]
    s.answer()
    s.ask()
    assert json.dumps(s.messages).startswith(before)
    thinks = sessions.think_times(t, a["rngs"][0])
    assert sum(thinks) / len(thinks) == pytest.approx(0.5, rel=0.05)


# -- the warm-up is a fixed list of requests, then the mix as the proof -----


def test_the_warm_up_sends_its_fixed_requests_then_one_stretch_if_clean():
    import asyncio

    from generators import warm

    sent = []

    class FakeClient:
        async def chat(self, phase, messages, max_tokens):
            sent.append((phase, len(messages[0]["content"]), max_tokens))

    class FakeStable:
        def __init__(self, dirty_stretches):
            self.calls, self.dirty = [], dirty_stretches

        async def check(self, after):
            self.calls.append(after)
            return after == "traffic" and self.calls.count("traffic") > self.dirty

    spec = traffic("chat-steady")["warmup"]["bursts"]
    stretches = []

    async def stretch(cycle):
        stretches.append(cycle)

    stable = FakeStable(dirty_stretches=0)
    asyncio.run(warm.until_stable(FakeClient(), spec, stable, stretch))
    # The bursts, each led by the long prompt; the last overfills the batch.
    assert len(sent) == sum(spec["sizes"]) and spec["sizes"][-1] > 16
    assert sent[:2] == [("warmup", 1500, 24), ("warmup", 1500, 24)]
    assert stable.calls == ["programs", "traffic"] and stretches == [0]
    # A stretch that still compiled is replayed, as before.
    stable = FakeStable(dirty_stretches=2)
    asyncio.run(warm.until_stable(FakeClient(), spec, stable, stretch))
    assert stable.calls == ["programs", "traffic", "traffic", "traffic"]


def test_loop_lag_keeps_the_latest_wakeups_of_a_window():
    from harness.hostmon import LoopLag

    lag = LoopLag()
    lag.lags = [(99.0, 2.0), (100.5, 0.003), (101.0, 0.9), (101.05, 0.3),
                (120.0, 0.002), (146.0, 5.0)]
    worst = lag.worst(100.0, 145.0)
    assert worst == [[0.5, 3.0], [1.0, 900.0], [1.05, 300.0], [20.0, 2.0]]
    assert lag.worst(200.0, 245.0) == []


def test_a_childs_cpu_seconds_are_read_from_proc(tmp_path):
    import time

    from harness.children import Child

    child = Child("spin", [sys.executable, "-c", (
        "import time\nt = time.process_time()\n"
        "while time.process_time() - t < 0.3: pass\ntime.sleep(30)")],
        str(tmp_path)).start()
    try:
        deadline = time.monotonic() + 20
        used = child.cpu_seconds()
        while used["process"] < 0.3 and time.monotonic() < deadline:
            time.sleep(0.1)
            used = child.cpu_seconds()
        assert 0.3 <= used["busiest_thread"] <= used["process"] < 5
    finally:
        child.stop(grace_s=5)


# -- percentile, tpot and failure arithmetic on a hand-made record set -----


def rec(due, first=None, last=None, asked=11, got=11, status=200, done=True,
        ended=None, error=None, reason="length"):
    return Record(phase="measure", due=due, asked=asked, sent=due + 0.001,
                  first=first, last=last,
                  ended=ended if ended is not None else last,
                  status=status, error=error, finish_reason=reason,
                  prompt_tokens=100, completion_tokens=got, done=done)


def test_percentile_and_supported_percentile():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(list(range(101)), 95) == 95
    assert stats.percentile([10, 20], 95) == pytest.approx(19.5)
    assert stats.highest_supported_percentile(200) == 95.0
    assert stats.highest_supported_percentile(100) == 90.0
    assert stats.highest_supported_percentile(8) == 0.0


def test_summary_counts_a_429_a_short_answer_and_an_undrained_request():
    t0, seconds, drain = 100.0, 10.0, 2.0
    records = [
        rec(101.0, first=101.2, last=102.2),              # ok: tpot 100 ms
        rec(102.0, first=102.4, last=104.4),              # ok: tpot 200 ms
        rec(103.0, status=429, done=False, ended=103.01),  # shed
        rec(104.0, first=104.1, last=104.5, got=7),       # short answer
        rec(109.5, first=110.5, last=113.0, ended=113.0),  # past the drain
        rec(109.0, first=109.2, last=None, done=False, ended=None),  # open
        rec(99.0, first=99.5, last=100.5),    # pre-roll: due before t0
        rec(110.5, first=110.6, last=110.9),  # due after the window
    ]
    records[5].ended = 200.0
    s = stats.summarize(records, t0, seconds, drain)
    assert s["attempted"] == 6 and s["failed"] == 4
    assert s["failure_reasons"] == {
        "status 429": 1, "short answer": 1, "not drained": 2}
    assert s["metrics"]["ttft_p50_ms"] == pytest.approx(300.0)
    assert s["metrics"]["ttft_mean_ms"] == pytest.approx(300.0)
    assert s["metrics"]["tpot_p95_ms"] == pytest.approx(195.0)
    # Finished inside [100, 110): records 0, 1, the short one and the
    # pre-roll one -> 11 + 11 + 7 + 11 tokens over 10 s.
    assert s["metrics"]["out_tok_s"] == pytest.approx(4.0)
    assert s["samples"] == {"ttft": 2, "tpot": 2, "finished_inside": 4}


def test_the_tail_mean_and_the_new_users_median_on_hand_made_records(tmp_path):
    # The slowest tenth of 25 values is two and a half of them.
    assert stats.tail_mean([float(v) for v in range(1, 26)], 0.1) == (
        pytest.approx((25 + 24 + 0.5 * 23) / 2.5))
    assert stats.tail_mean([7.0], 0.1) == pytest.approx(7.0)
    assert stats.tail_mean([1.0, 3.0], 1.0) == pytest.approx(2.0)
    # Twenty rounds of 100-119 ms; three of them start a user born in the
    # window (round 0, not the seat's first user) and read 400, 500, 900.
    records = []
    for i in range(20):
        r = rec(100.0 + 0.4 * i, first=100.1 + 0.401 * i, last=100.3 + 0.4 * i)
        r.meta = {"user": f"u{i % 4}.0", "round": i % 8}
        records.append(r)
    for i, ttft in ((3, 0.4), (9, 0.5), (15, 0.9)):
        records[i].meta = {"user": f"u{i % 4}.1", "round": 0}
        records[i].first = records[i].due + ttft
        records[i].last = records[i].first + 0.2
    m = stats.summarize(records, 100.0, 10.0, 2.0)["metrics"]
    assert m["ttft_new_user_p50_ms"] == pytest.approx(500.0)
    assert m["ttft_slow10_mean_ms"] == pytest.approx((900 + 500) / 2)
    assert m["ttft_p90_ms"] == pytest.approx(
        stats.percentile([(r.first - r.due) * 1e3 for r in records], 90))
    # A seat's first user at round 0 found its history in the seeded cache.
    assert not stats.starts_a_user(records[0])
    # The tool tables a statistic from kept records by the same function.
    sys.path.insert(0, os.path.join(BENCH, "tools"))
    try:
        import spread
    finally:
        sys.path.pop(0)
    import dataclasses
    for name in ("c2-A-1", "c2-A-2", "c2-B-1", "c2-B-2"):
        (tmp_path / name).mkdir()
        (tmp_path / name / "run.json").write_text(json.dumps(
            {"window": {"t0": 100.0, "seconds": 10.0, "drain_s": 2.0}}))
        (tmp_path / name / "records.jsonl").write_text("".join(
            json.dumps(dataclasses.asdict(r)) + "\n" for r in records))
    sets = spread.from_records(str(tmp_path), "c2", ["ttft_slow10_mean_ms"])
    assert sets == {s: [{"ttft_slow10_mean_ms": pytest.approx(700.0)}] * 2
                    for s in "AB"}


def test_client_ttft_leaves_out_what_the_trace_stalled():
    from readers import client_ttft

    records = [rec(100.0 + i, first=100.1 + i, last=100.5 + i)
               for i in range(8)]
    records += [rec(108.0, first=130.0, last=131.0),   # due under the trace
                rec(109.0, first=130.0, last=131.0)]
    got = {"t0": 100.0, "seconds": 10.0, "drain_s": 75.0, "wall_t0": 5000.0}
    ctx = types.SimpleNamespace(records=records, got=got)
    assert client_ttft.read(ctx, {"percentile": 95}) > 20000.0
    got["trace_wall"] = [5007.5, 5009.0]
    assert client_ttft.read(ctx, {"percentile": 95}) == pytest.approx(100.0)
    assert client_ttft.read(
        types.SimpleNamespace(records=[], got=got), {"percentile": 95}) is None


# -- the trace reduction on a small recorded trace -------------------------


def test_union_and_reduce_on_hand_made_planes():
    assert xplane.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9.5)]) == [
        (0, 3), (5, 7), (9, 9.5)]
    planes = [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ("x", 0.0, 1e9)]}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ("jit_window_fn(1)", 1e9, 3e8), ("jit_prefill_fn(2)", 2e9, 1e8),
                ("jit_window_fn(1)", 3e9, 3e8)]},
            {"name": "XLA Ops", "events": [
                ("fusion.1", 1e9, 2e8), ("paged.2", 1.1e9, 2e8),
                ("fusion.1", 2e9, 1e8), ("fusion.1", 3e9, 3e8)]}]},
    ]
    out = xplane.reduce(planes, "tpu")
    assert out["busy_s"] == pytest.approx(0.7)
    assert out["window_s"] == pytest.approx(2.3)
    assert out["programs"][0] == ["window_fn", pytest.approx(0.6), 2]
    assert out["ops"][0][:2] == ["fusion", pytest.approx(0.6)]
    assert out["gaps"][0][0] == pytest.approx(0.9)
    assert [m[3] for m in out["modules"]] == [
        {"fusion": 1, "paged": 1}, {"fusion": 1}, {"fusion": 1}]
    assert xplane.reduce(planes, "cpu")["busy_s"] is None


def test_reduce_gives_the_known_busy_share_of_the_recorded_trace():
    path = os.path.join(DATA, "trace_small.json")
    sys.path.insert(0, os.path.join(BENCH, "tools"))
    try:
        from slice_trace import expand
    finally:
        sys.path.pop(0)
    with open(path) as f:
        planes = expand(json.load(f))
    with open(os.path.join(DATA, "trace_small.expected.json")) as f:
        want = json.load(f)
    out = xplane.reduce(planes, "tpu")
    # An independent count: paint every operation onto a 0.1 us grid.
    ops = [ln for ln in planes[0]["lines"] if ln["name"] == "XLA Ops"][0]
    lo = min(s for _n, s, _d in ops["events"])
    hi = max(s + d for _n, s, d in ops["events"])
    grid = bytearray(int((hi - lo) / 1e2) + 2)
    for _n, s, d in ops["events"]:
        a, b = int((s - lo) / 1e2), int((s + d - lo) / 1e2)
        grid[a:b + 1] = b"\x01" * (b + 1 - a)
    assert out["busy_s"] == pytest.approx(sum(grid) / 1e7, rel=0.05)
    assert out["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert out["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert out["programs"][0][0] == want["top_program"]
    assert 0 < out["busy_s"] < out["window_s"]
    assert len(out["modules"]) >= 3 and out["gaps"]


# -- new files, no edit -----------------------------------------------------


def test_a_dropped_in_config_traffic_generator_and_metric_are_found(tmp_path):
    """A later PR's cell: a benchmark entry plus new files, none edited."""
    import run

    with open(os.path.join(BENCH, "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "new-model", "source": "https://example.org/new",
        "file": "bench/configs/new-model.json", "reduced": [], "why": "new"})
    bench["workloads"].append({
        "name": "new-model.bursts", "config": "new-model",
        "traffic": "bursts", "chips": 1, "why": "new"})
    bench["per_layer"].append({
        "name": "answer_42", "unit": "x", "better": "higher",
        "source": "program_counter", "layer": "Scheduler",
        "moves": "out_tok_s", "workloads": ["new-model.bursts"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    root = tmp_path / "bench"
    for d in ("configs", "traffic", "cells", "layer_metrics", "generators",
              "readers"):
        (root / d).mkdir(parents=True)
    (root / "configs" / "new-model.json").write_text(
        json.dumps({"model": "tiny-llama", "engine_argv": []}))
    (root / "traffic" / "bursts.json").write_text(
        json.dumps({"generator": "bursty", "burst": 3}))
    (root / "cells" / "new-model.bursts.json").write_text(
        json.dumps({"rate_rps": 9.0}))
    (root / "generators" / "bursty.py").write_text(
        "def plan(traffic, cell):\n"
        "    return [cell['rate_rps']] * traffic['burst']\n")
    (root / "layer_metrics" / "answer_42.json").write_text(
        json.dumps({"reader": "fortytwo", "args": {"times": 2}}))
    (root / "readers" / "fortytwo.py").write_text(
        "def read(ctx, args):\n    return 21.0 * args['times']\n")

    import importlib

    _bench, cell, config, tr, cell_params, dirs = run.resolve(
        str(tmp_path / "BENCHMARK.json"), "new-model.bursts")
    try:
        assert config["model"] == "tiny-llama" and cell_params["rate_rps"] == 9.0
        gen = importlib.import_module("generators." + tr["generator"])
        assert gen.plan(tr, cell_params) == [9.0, 9.0, 9.0]
        # The new metric by its new reader, an old one by a reader that is
        # there; a reader that finds nothing gives None.
        got = layers.read_all(
            types.SimpleNamespace(late_ms=[], delta=lambda family: None,
                                  dirs=dirs, cell=cell),
            ["answer_42", "gen_late_p95_ms", "prefix_hit_share"])
        assert got == {"answer_42": 42.0, "gen_late_p95_ms": None,
                       "prefix_hit_share": None}
        # The cells that are there resolve as before.
        assert run.resolve(str(tmp_path / "BENCHMARK.json"),
                           "m7b-int8.chat-steady")[2]["model"] == "mistral-7b"
    finally:
        sys.path.remove(str(root))
        for name in ("generators.bursty", "readers.fortytwo"):
            sys.modules.pop(name, None)


# -- a configuration of another architecture: routed experts ----------------

# A reference that is wrong in one way: the body of ``forward`` in a module
# of its own beside ``reference/routed.py``.
FAULT = """
from unittest import mock

import jax
import jax.numpy as jnp

from reference import routed

TOP = "moe_num_active_primary_experts"


def unnormalised(logits, top, choice=None):
    probs = jax.nn.softmax(logits, -1)
    best, who = jax.lax.top_k(probs, top)
    rows = jnp.arange(logits.shape[0])[:, None]
    return (jnp.zeros_like(probs).at[rows, who].set(best),
            jnp.zeros(logits.shape[0]))


def forward(params, hp, tokens):
    BODY
"""
FAULTS = {
    "routed_top1":
        "return routed.forward(params, dict(hp, **{TOP: 1}), tokens)",
    "routed_five_of_six":
        "return routed.forward(params, dict(hp, **{TOP: hp[TOP] - 1}), tokens)",
    "routed_unnormalised":
        "with mock.patch.object(routed, '_route', unnormalised):\n"
        "        return routed.forward(params, hp, tokens)",
    "routed_window_off_by_a_block":
        "return routed.forward(params, dict(hp, sliding_window_size="
        "hp['sliding_window_size'] + 16), tokens)",
}
ROUTED_SIZES = {
    "hidden_size": 64, "num_attention_heads": 4, "head_dim": 16,
    "num_key_value_heads": 2, "num_hidden_layers": 8,
    "moe_num_primary_experts": 16, "moe_num_active_primary_experts": 6,
    "moe_ffn_hidden_size": 32, "sliding_window_size": 128,
    "vocab_size": 384, "rms_norm_eps": 1e-05, "rope_theta": 10000.0}
# Float32 against float32 on the CPU: a sound run reads at most 3.3e-7 and the
# mildest fault (five of six) at least 1.4e-2, twelve seeds each.
ROUTED_RTOL = 1e-3
# What the dropped-in cell reports per layer: the dense readers' step (the
# paged kernel is on its path) and what every model's engine counts.
ROUTED_REPORTS = ("decode_step_dev_ms", "device_idle_share",
                  "compiles_in_window", "seqs_per_window",
                  "gen_late_p95_ms.chat-steady")


@pytest.fixture(scope="module")
def routed_cell(tmp_path_factory):
    """A later PR's configuration whose published keys are not llama's (an
    expert count and an expert width of its own, no ``intermediate_size``)
    and which holds 2 of its 8 published layers: new files, none edited.
    The program's side of that PR is a preset: in float32, because in the
    served dtype a top k flips on rounding and a flipped position reads
    another model's error (PERF.md, PR 34; ``tools/flip_rate.py``)."""
    import run

    tmp_path = tmp_path_factory.mktemp("routed")
    patch = pytest.MonkeyPatch()
    patch.syspath_prepend(run.ROOT)
    from production_stack_tpu.engine.config import PRESETS, ModelConfig

    with open(os.path.join(BENCH, "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "routed-tiny", "source": "https://example.org/routed",
        "file": "bench/configs/routed-tiny.json",
        "reduced": ["num_hidden_layers"], "why": "routed experts, a window"})
    bench["workloads"].append({
        "name": "routed-tiny.chat-steady", "config": "routed-tiny",
        "traffic": "chat-steady", "chips": 1, "why": "new"})
    # A later PR's cell joins the lists of the entries it reports: an
    # append to each, no entry edited otherwise and none without a list.
    for m in bench["per_layer"]:
        if m["name"] in ROUTED_REPORTS:
            m["workloads"].append("routed-tiny.chat-steady")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    root = tmp_path / "bench"
    for d in ("configs", "reference"):
        (root / d).mkdir(parents=True)
    file = dict(
        ROUTED_SIZES, published=ROUTED_SIZES, num_hidden_layers=2,
        reduced=["num_hidden_layers"], model="routed-tiny", engine_argv=[],
        compare={
            "reference": "routed", "layers": 2, "decode_steps": 2,
            "logits_rtol": ROUTED_RTOL, "prompt_tokens": [300, 100],
            "preset_keys": {
                "hidden_size": "hidden_size", "head_dim": "head_dim",
                "num_attention_heads": "num_heads",
                "num_key_value_heads": "num_kv_heads",
                "moe_num_primary_experts": "num_experts",
                "moe_num_active_primary_experts": "num_experts_per_tok",
                "moe_ffn_hidden_size": "intermediate_size",
                "sliding_window_size": "sliding_window",
                "vocab_size": "vocab_size"}})
    (root / "configs" / "routed-tiny.json").write_text(json.dumps(file))
    for name, body in FAULTS.items():
        (root / "reference" / (name + ".py")).write_text(
            FAULT.replace("BODY", body))
    patch.setitem(PRESETS, "routed-tiny", ModelConfig(
        name="mixtral-routed-tiny", num_layers=8, intermediate_size=32,
        num_experts=16, num_experts_per_tok=6, sliding_window=128,
        dtype="float32"))
    bench, cell, config, _tr, _p, dirs = run.resolve(
        str(tmp_path / "BENCHMARK.json"), "routed-tiny.chat-steady")
    try:
        yield types.SimpleNamespace(
            bench=bench, cell=cell, config=config, dirs=dirs, root=run.ROOT)
    finally:
        sys.path.remove(str(root))
        for name in FAULTS:
            sys.modules.pop("reference." + name, None)
        patch.undo()


def test_a_dropped_in_config_of_another_architecture_cut_in_depth(routed_cell):
    """The file alone drives the sizes and the readers count the held
    layers; the compare itself is the tests below."""
    import run
    from harness.sizes import held

    bench, cell, config, dirs = (routed_cell.bench, routed_cell.cell,
                                 routed_cell.config, routed_cell.dirs)
    assert held(config)["num_hidden_layers"] == 2
    assert config["published"]["num_hidden_layers"] == 8
    # A preset that disagrees with the file is refused before any run.
    wider = dict(config, moe_ffn_hidden_size=64, published=dict(
        ROUTED_SIZES, moe_ffn_hidden_size=64))
    from harness import compare

    ok, notes, _rows = compare.run(wider, 1, 1, "cpu", env_root=run.ROOT)
    assert not ok and "moe_ffn_hidden_size=64" in notes[0]
    # A size changed at the top level with no word in ``reduced`` is
    # refused, not followed; so is a reduced key with no held value.
    with pytest.raises(SystemExit, match="moe_ffn_hidden_size"):
        held(dict(config, moe_ffn_hidden_size=64))
    with pytest.raises(SystemExit, match="vocab_size"):
        held(dict({k: v for k, v in config.items() if k != "vocab_size"},
                  reduced=["num_hidden_layers", "vocab_size"]))
    # The readers, every per-layer metric the new cell reports: 16 kernel
    # calls over the 2 held layers are 8 steps of 10 ms; the dense-MLP
    # bandwidth share is not this cell's and is never read.
    names = run.metric_names(bench, cell["name"], traced=True)
    assert sorted(names) == sorted(ROUTED_REPORTS)
    assert "decode_step_bw_share" not in names
    assert "decode_step_bw_share" in run.metric_names(
        bench, "m7b-int8.chat-steady", traced=True)
    kernel = "paged_decode_attention_pallas"
    trace = {"modules": [
        ["window_fn", 0, 80e6, {kernel: 16, "fusion": 40}],
        ["prefill_fn", 90e6, 30e6, {"flash_prefill_attention": 2}]],
        "busy_s": 0.11, "window_s": 0.125, "span_ns": [0, 125e6],
        "ops": [], "gaps": []}
    edge = {"prom": {}, "compile_events": 21,
            "device": {"kind": "TPU v5 lite"}}
    ctx = layers.Context(
        cell=cell, config=config, records=[], late_ms=[1.0, 2.0],
        got={"windows": {"windows": []}, "t0": 100.0, "wall_t0": 1e9,
             "seconds": 45, "drain_s": 15, "before": edge, "after": edge},
        summary={}, dirs=dirs, trace=trace)
    values = layers.read_all(ctx, names)
    assert set(values) == set(names)
    assert values["decode_step_dev_ms"] == pytest.approx(10.0)
    assert values["device_idle_share"] == pytest.approx(12.0)
    assert values["compiles_in_window"] == 0.0


@pytest.mark.parametrize("reference", ["routed", *FAULTS])
def test_a_routed_configuration_holds_every_row_and_a_fault_does_not(
        routed_cell, reference):
    """The compare by the file alone, every row under the tolerance as a
    dense file's; a reference that routes to fewer experts, leaves out the
    renormalisation or sees a block further back is another model, and reads
    so."""
    from harness import compare

    config = routed_cell.config
    ok, notes, rows = compare.run(
        dict(config, compare=dict(config["compare"], reference=reference)),
        1, 3_300_000_001, "cpu", env_root=routed_cell.root)
    assert len(notes) == 4 and list(rows) == [
        "prefill_of_300_tokens_256_cached", "prefill_of_100_tokens_no_prefix",
        "decode_step_0", "decode_step_1"], notes
    assert notes[0].startswith("prefill of 300 tokens, 256 cached")
    assert notes[1].startswith("prefill of 100 tokens, no prefix")
    assert all(limit == ROUTED_RTOL for _err, limit in rows.values())
    worst = max(err for err, _limit in rows.values())
    if reference == "routed":
        assert ok and 0 < worst <= ROUTED_RTOL, notes
    else:
        assert not ok and worst > ROUTED_RTOL, notes


def test_two_prompts_of_one_length_keep_a_row_each(routed_cell):
    from harness import compare

    config = routed_cell.config
    ok, _notes, rows = compare.run(
        dict(config, compare=dict(config["compare"], prompt_tokens=[100, 100],
                                  decode_steps=1)),
        1, 3_300_000_002, "cpu", env_root=routed_cell.root)
    assert ok and list(rows) == [
        "prefill_of_100_tokens_no_prefix",
        "prefill_of_100_tokens_no_prefix_the_second_prompt", "decode_step_0"]


# -- what a served module may offer: its choice, its cache ------------------

ROUTED_ROWS = ["prefill_of_300_tokens_256_cached",
               "prefill_of_100_tokens_no_prefix", "decode_step_0",
               "decode_step_1"]
SHORTFALL = 0.1   # the tests' limit; the stub swaps near-ties under 0.05


@pytest.fixture
def stub_config(routed_cell, monkeypatch):
    """``configure(module, **compare keys)``: the routed configuration's file
    with ``module`` (``routed_stub.module(...)``, or any other) as what the
    program serves its preset with."""
    from production_stack_tpu.engine.config import PRESETS, ModelConfig
    from production_stack_tpu.engine.models.registry import MODEL_REGISTRY

    def configure(module, **more):
        monkeypatch.setitem(MODEL_REGISTRY, "routedstub", module)
        monkeypatch.setitem(PRESETS, "routed-stub", ModelConfig(
            name="routedstub-tiny", num_layers=8, intermediate_size=32,
            num_experts=16, num_experts_per_tok=6, sliding_window=128,
            dtype="float32"))
        config = routed_cell.config
        return dict(config, model="routed-stub",
                    compare=dict(config["compare"], **more))

    return configure


def followed(stub_config, routed_cell, seed=3_800_000_001, **switches):
    import routed_stub
    from harness import compare

    config = stub_config(routed_stub.module(**switches), follow_choice=True,
                         choice_shortfall=SHORTFALL, why_shortfall="a test")
    return compare.run(config, 1, seed, "cpu", env_root=routed_cell.root)


def test_a_followed_choice_that_differs_in_near_ties_is_correct(
        stub_config, routed_cell):
    """(a) The module hands back the experts it chose; where the k-th and the
    (k+1)-th are all but tied it chose the other one, as a sound computation
    in another precision would.  The reference follows: every row holds the
    float32 tolerance, the shortfall is small and not zero, and the share of
    positions the reference alone would have routed otherwise is said."""
    ok, notes, rows = followed(stub_config, routed_cell,
                               fault="swap_near_ties")
    assert ok, notes
    assert list(rows) == ROUTED_ROWS + [
        "choice_shortfall", "return_choice_logits_differ"]
    assert all(rows[r][0] <= ROUTED_RTOL for r in ROUTED_ROWS)
    assert 0 < rows["choice_shortfall"][0] < 0.05
    assert rows["choice_shortfall"][1] == SHORTFALL
    assert rows["return_choice_logits_differ"] == [0, 0]
    said = [n for n in notes if "would have chosen otherwise" in n]
    assert len(said) == 1 and " 0 of " not in said[0], notes
    # The sound module: the same rows, and nothing flipped in float32.
    ok, notes, sound = followed(stub_config, routed_cell)
    assert ok and sound["choice_shortfall"][0] == 0, notes
    assert any("otherwise at 0 of 404 positions" in n for n in notes), notes


@pytest.mark.parametrize("fault, entry", [
    ("k_plus_8", "choice_shortfall"), ("no_renorm", "a row"),
    ("misreport", "a row")])
def test_a_wrong_choice_a_wrong_share_and_a_choice_not_used_are_not_correct(
        stub_config, routed_cell, fault, entry):
    """(b) Each by the entry that is there for it: an expert far down the
    ranking is followed faithfully and fails the shortfall; shares that are
    not renormalised, or a reported choice that the program did not use, fail
    a row."""
    ok, notes, rows = followed(stub_config, routed_cell, fault=fault)
    assert not ok, notes
    worst = max(rows[r][0] for r in ROUTED_ROWS)
    if entry == "choice_shortfall":
        assert rows["choice_shortfall"][0] > 3 * SHORTFALL
        assert worst <= ROUTED_RTOL, notes
    else:
        assert worst > 10 * ROUTED_RTOL, notes


def test_a_module_with_a_cache_of_its_own_is_handed_it_back(
        stub_config, routed_cell):
    """(c) One array a layer where the engine keeps a K and a V: the compare
    asks the module, and the rows are the plain module's."""
    import routed_stub
    from harness import compare

    plain = compare.run(routed_cell.config, 1, 3_800_000_002, "cpu",
                        env_root=routed_cell.root)
    stub = routed_stub.module(one_array=True)
    made = []
    stub.init_cache = (lambda inner: lambda *a: made.append(a[1:3])
                       or inner(*a))(stub.init_cache)
    ok, notes, rows = compare.run(stub_config(stub), 1, 3_800_000_002, "cpu",
                                  env_root=routed_cell.root)
    assert ok and plain[0] and rows == plain[2], notes
    assert made == [(96, 16)]


def test_the_dense_rehearsal_reads_what_it_always_read():
    """(d) ``mistral`` on the CPU rehearsal: the four rows of PRs 33 and 34,
    to the digit."""
    import run
    from harness import compare

    path = os.path.join(DATA, "rehearsal", "BENCHMARK.json")
    config = run.resolve(path, "rehearsal.sessions-prefix")[2]
    try:
        ok, notes, rows = compare.run(config, 1, 1, "cpu", env_root=run.ROOT)
    finally:
        sys.path.remove(os.path.join(DATA, "rehearsal", "bench"))
    assert ok and [f"{err:.3e}" for err, _limit in rows.values()] == [
        "3.511e-03", "3.176e-03", "3.286e-03", "3.339e-03"], notes
    assert "choice_shortfall" not in rows


def test_a_followed_choice_with_no_limit_or_no_choice_is_refused(
        stub_config, routed_cell):
    """(e) From the file alone, before anything runs; and a module that
    cannot return its choice, before anything is built."""
    from harness import compare
    from production_stack_tpu.engine.models import llama

    spec = dict(routed_cell.config["compare"], follow_choice=True)
    with pytest.raises(SystemExit, match="choice_shortfall"):
        compare.check_file(dict(routed_cell.config, compare=spec))
    compare.check_file({"model": "tiny-llama"})   # no compare block: fine
    ok, notes, rows = compare.run(
        stub_config(llama, follow_choice=True, choice_shortfall=SHORTFALL),
        1, 1, "cpu", env_root=routed_cell.root)
    assert not ok and rows == {} and "takes no return_choice" in notes[0]


def test_held_keeps_the_published_value_of_a_reduced_key(routed_cell):
    """(f)"""
    from harness.sizes import held

    hp = held(routed_cell.config)
    assert hp["num_hidden_layers"] == 2
    assert hp["published"]["num_hidden_layers"] == 8
    assert hp["published"] == routed_cell.config["published"]
    assert "published" not in hp["published"]


def test_a_share_of_the_experts_and_a_one_array_cache_are_additions_only(
        stub_config, routed_cell):
    """A later PR's configuration that holds 8 of its 16 experts (the router
    keeps its width) and whose module keeps one cache array a layer: a file
    that lists the expert count in ``reduced``, the module, and the reference
    that is there; no file of ``bench/`` is edited."""
    import routed_stub
    from harness import compare

    config = stub_config(
        routed_stub.module(held=8, one_array=True, fault="swap_near_ties"),
        follow_choice=True, choice_shortfall=SHORTFALL,
        preset_keys={k: v for k, v in
                     routed_cell.config["compare"]["preset_keys"].items()
                     if k != "moe_num_primary_experts"})
    config = dict(config, moe_num_primary_experts=8,
                  reduced=["num_hidden_layers", "moe_num_primary_experts"])
    detail = {}
    ok, notes, rows = compare.run(config, 1, 3_800_000_003, "cpu",
                                  env_root=routed_cell.root, detail=detail)
    assert ok and 0 < rows["choice_shortfall"][0] < 0.05, notes
    # Ids over the router's whole width came back, for every position.
    choice = detail["choice"]
    assert [c.shape for c in choice] == [(2, 302, 6), (2, 102, 6)]
    assert min(c.min() for c in choice) >= 0
    assert max(c.max() for c in choice) > 8


@pytest.mark.parametrize(
    "cell_name", [w["name"] for w in benchmark_file()["workloads"]])
def test_every_name_in_the_benchmark_file_has_its_files(cell_name):
    """A cell's generator and reference are there, and every per-layer
    entry that lists it finds a spec file and a reader for the cell's
    configuration and moves what the cell reports."""
    import run

    path = os.path.join(BENCH, "..", "BENCHMARK.json")
    _b, _c, config, tr, _p, _dirs = run.resolve(path, cell_name)
    assert os.path.exists(os.path.join(
        BENCH, "generators", tr["generator"] + ".py"))
    assert os.path.exists(os.path.join(
        BENCH, "reference", config["compare"]["reference"] + ".py"))
    hold_a_cell_to_the_rule(cell_name)


def test_no_spec_file_is_left_without_an_entry():
    """Every file under ``layer_metrics/`` is read by some entry: a
    quantity's file by its entry (or its split copies), a configuration's
    own file by an entry that lists one of that configuration's cells."""
    bench = benchmark_file()
    bases = {m["name"].split(".")[0]: set() for m in bench["per_layer"]}
    for m in bench["per_layer"]:
        bases[m["name"].split(".")[0]] |= set(m["workloads"])
    configs = {w["name"]: w["config"] for w in bench["workloads"]}
    top = os.path.join(BENCH, "layer_metrics")
    for where, _dirs, files in os.walk(top):
        for file in files:
            quantity = file[:-len(".json")].split(".")[0]
            assert quantity in bases, file
            config = os.path.relpath(where, top)
            if config != ".":
                assert config in {configs[c] for c in bases[quantity]}, (
                    config, file)


def test_the_benchmark_file_keeps_inside_the_contract_limits():
    import re

    path = os.path.join(BENCH, "..", "BENCHMARK.json")
    with open(path) as f:
        raw = f.read()
    bench = json.loads(raw)
    assert len(raw) <= 64 * 1024
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    line = lambda s: 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s
    assert 1 <= bench["run_seconds"] <= 51
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert name.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["file"].startswith(bench["paths"][0] + "/")
        assert any(w["config"] == c["name"] for w in bench["workloads"])
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert name.match(w["name"]) and name.match(w["traffic"])
        assert line(w["why"]) and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
    ends = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in ends
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert name.match(m["name"]) and unit.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in ends and line(m["layer"])
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(names) == len(set(names))
    # Room for the next cell's entries, and a list on every entry, so that a
    # PR that adds a cell never meets an entry that covers it unasked.
    cells = {w["name"] for w in bench["workloads"]}
    assert 1 <= len(bench["per_layer"]) <= 128
    for m in bench["per_layer"]:
        assert m.get("workloads") and set(m["workloads"]) <= cells, m["name"]
        assert len(m["workloads"]) == len(set(m["workloads"])), m["name"]
