"""The record <-> program join and the readers on it, on a small hand-made
pair (``data/join_small.trace.json`` = a reduced trace, ``.windows.json`` =
the engine's ``/debug/windows`` payload; unix ns = trace ns + OFF).  Every
expected value below is worked out by hand from those two files."""

import copy
import json
import os
import shutil

import pytest

from conftest import BENCH
from harness import layers
from reduce import join, kv_bytes

DATA = os.path.join(BENCH, "tests", "data")
OFF = 1_790_000_000_000_000_000

HP = {"hidden_size": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
      "num_hidden_layers": 2}

# What this PR dropped into bench/ beside what was there.
NEW_FILES = [
    "reduce/join.py", "reduce/kv_bytes.py", "readers/idle_by_phase.py",
    "readers/program_ms.py", "readers/prefill_pad.py",
    "readers/paged_decode_bw.py",
] + ["layer_metrics/%s.json" % m for m in (
    "idle_schedule_share", "idle_build_share", "idle_collect_share",
    "idle_wait_share", "idle_unattributed_share", "prefill_dev_ms",
    "prefill_pad_share", "paged_decode_bw_share")]
IDLE = ("idle_schedule_share", "idle_build_share", "idle_collect_share",
        "idle_wait_share", "idle_unattributed_share")


def load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def context(payload=None, trace=None, chips=1, engine_argv=(), dirs=None):
    payload = payload if payload is not None else load("join_small.windows.json")
    return layers.Context(
        cell={"name": "c", "config": "c", "chips": chips},
        config={"published": HP, "engine_argv": list(engine_argv)},
        records=[], late_ms=[],
        got={"windows": payload, "wall_t0": OFF / 1e9 - 1.0, "seconds": 45,
             "after": {"device": {"kind": "TPU v5 lite"}}},
        summary={}, dirs=dirs or [BENCH],
        trace=trace if trace is not None else load("join_small.trace.json"))


def read(name, ctx):
    return layers.read_all(ctx, [name])[name]


def test_every_named_program_finds_its_record_and_the_offset_bracket():
    got = join.joined(context())
    # Five of the six programs carry a name the records know; the eager
    # broadcast does not.  Records are served newest first: index 2 is the
    # prefill (window_id 0), index 0 the second window.
    assert got["pairs"] == [(0, 2), (1, 2), (3, 1), (4, 0), (5, 0)]
    assert got["other"] == 1
    # Lower edge: the first window_fn, launched at OFF+8960, started at
    # 9000 -> OFF-40.  Upper edge: both windows were read back 40 ns after
    # they ended (25000 -> OFF+25040, 40000 -> OFF+40040) -> OFF+40.
    assert got["offset_ns"] == [OFF - 40, OFF + 40]
    assert join.offset_ns(got) == OFF


def test_idle_between_programs_by_the_phase_of_the_step_thread():
    # Idle between programs: 200 + 100 + 3300 + 100 + 100 = 3800 of 39000 ns.
    # At the bracket's middle (OFF) a span's number in the file is its
    # place on the trace's clock:
    #   (5000,5200): the sampler's launch to 5150 (150), its collect (50)
    #   (5500,5600): collect to 5560 (60), sample (40)
    #   (5700,9000): sample to 5900 (200), emit to 6400 (500), wait to 7900
    #     (1500), nothing to 8000 (100), schedule to 8500 (500), build to
    #     8900 (400), launch to 9000 (100)
    #   (25000,25100): the first window's collect to 25040 (40), sample (60)
    #   (25200,25300): its sample (100)
    want = {"idle_schedule_share": 500, "idle_build_share": 150 + 400 + 100,
            "idle_collect_share": 50 + 60 + 40 + 200 + 500 + 40 + 60 + 100,
            "idle_wait_share": 1500, "idle_unattributed_share": 100}
    ctx = context()
    total = 0.0
    for name, ns in want.items():
        value = read(name, ctx)
        assert value == pytest.approx(100.0 * ns / 39000), name
        total += value
    assert total == pytest.approx(100.0 * 3800 / 39000)


def test_prefill_padding_and_device_time():
    ctx = context()
    assert read("prefill_pad_share", ctx) == pytest.approx(
        100.0 * (1 - 100 / 256))
    assert layers.is_count("prefill_pad_share.chat-steady", ctx.dirs, "c")
    assert read("prefill_dev_ms", ctx) == pytest.approx(0.004)
    assert read("prefill_dev_ms.chat-steady", ctx) == pytest.approx(0.004)


def test_decode_kernel_bytes_against_the_published_bandwidth():
    # 2 (K, V) x 2 layers x 2 KV heads x 4 x 2 bytes = 64 bytes a position.
    assert kv_bytes.kv_bytes_per_token(HP) == 64
    assert kv_bytes.kv_bytes_per_token(dict(HP, num_key_value_heads=8), 4) == 64
    # Two windows of 4 kernel calls / 2 layers = 2 steps each, over 992 and
    # 1024 positions in whole blocks.
    assert kv_bytes.decode_read_bytes(HP, 992, 2) == 2 * 992 * 64
    want = 2 * (992 + 1024) * 64 / 819e9 / 1.0e-06 * 100.0
    assert read("paged_decode_bw_share", context()) == pytest.approx(want)


@pytest.mark.parametrize("chips, engine_argv, shards", [
    (4, ["--max-num-seqs", "16"], 1),      # four replicas, a whole cache each
    (4, ["--tensor-parallel", "4"], 4),    # one engine over four chips
    (1, ["--tensor-parallel", "2"], 2),
])
def test_kv_heads_are_split_by_the_engine_not_by_the_cell(
        chips, engine_argv, shards):
    assert kv_bytes.kv_shards({"engine_argv": engine_argv}) == shards
    want = 2 * (992 + 1024) * 64 / shards / 819e9 / 1.0e-06 * 100.0
    ctx = context(chips=chips, engine_argv=engine_argv)
    assert read("paged_decode_bw_share", ctx) == pytest.approx(want)


def test_a_shifted_record_empties_the_bracket_and_the_readers_say_nothing():
    payload = load("join_small.windows.json")
    late = payload["windows"][0]          # the second window, 1 ms late
    late["program_ns"] = [ns + 1_000_000 for ns in late["program_ns"]]
    late["launch_ns"] += 1_000_000
    ctx = context(payload)
    assert join.joined(ctx) is None
    for name in IDLE + ("paged_decode_bw_share",):
        assert read(name, ctx) is None, name


def test_launches_outside_the_profilers_session_are_no_match():
    payload = load("join_small.windows.json")
    payload["profile"] = {
        "start_unix_ns": [OFF + 10**10, OFF + 10**10 + 500],
        "stop_unix_ns": [OFF + 2 * 10**10, OFF + 2 * 10**10 + 500]}
    assert join.match(load("join_small.trace.json")["modules"], payload) is None
    del payload["profile"]   # no stamps: the bracket alone decides
    assert join.match(
        load("join_small.trace.json")["modules"], payload) is not None


def test_an_engine_from_before_the_spans_gives_nothing_and_raises_nothing():
    old = load("join_small.windows.json")
    del old["phases"], old["profile"]
    for rec in old["windows"]:
        for key in ("programs", "program_ns", "phases", "launch_ns",
                    "collected_ns", "kv_tokens", "new_tokens",
                    "bucket_tokens", "cached_tokens"):
            rec.pop(key, None)
    unnamed = load("join_small.trace.json")
    for m in unnamed["modules"]:
        if m[0].endswith("_fn"):
            m[0] = "_unknown"
    ctx = context(copy.deepcopy(old), unnamed)
    for name in IDLE + ("prefill_dev_ms", "prefill_pad_share",
                        "paged_decode_bw_share"):
        assert read(name, ctx) is None, name


def test_a_dropped_in_copy_of_the_new_files_is_found_by_name(tmp_path):
    """What this PR adds is files beside the benchmark's own: laid into an
    empty directory that is looked in first, as ``run.resolve`` does for a
    later PR's ``paths[0]``, every new per-layer name of BENCHMARK.json
    finds its file there and reads the hand-computed pair."""
    for rel in NEW_FILES:
        dest = tmp_path / rel
        dest.parent.mkdir(parents=True, exist_ok=True)
        shutil.copy(os.path.join(BENCH, rel), dest)
    with open(os.path.join(BENCH, "..", "BENCHMARK.json")) as f:
        per_layer = json.load(f)["per_layer"]
    files = {os.path.basename(p)[:-len(".json")] for p in NEW_FILES
             if p.startswith("layer_metrics/")}
    new = [m["name"] for m in per_layer if m["name"].split(".")[0] in files]
    # Every quantity of the files is an entry, split or not, and no count.
    assert {name.split(".")[0] for name in new} == files
    dirs = [str(tmp_path), BENCH]
    for name in new:
        assert layers.spec_file(name, dirs, "c").startswith(
            str(tmp_path)), name
    values = layers.read_all(context(dirs=dirs), new)
    assert all(v is not None for v in values.values()), values
    assert sum(values[n] for n in IDLE) == pytest.approx(100.0 * 3800 / 39000)
