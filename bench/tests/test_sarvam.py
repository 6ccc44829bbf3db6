"""PR 39's cases: a served module with a latent cache and routed experts held
by share (``models/sarvam_mla.py``) through the harness on the CPU, and the
readers and bytes functions its cell brings.  A file of its own: the files
that were there are not edited."""

import json
import os
import sys
import types

from conftest import BENCH

DATA = os.path.join(BENCH, "tests", "data")




def test_the_latent_routed_rehearsal_runs_through_the_harness(tmp_path):
    """``models/sarvam_mla.py`` at a toy size through ``run.py`` on the CPU:
    engine and router as children, the sessions mix, the compare following
    the engine's choice; counts only, ``correct``, and the routing counters
    read from the flight records."""
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--benchmark",
         os.path.join(DATA, "rehearsal", "BENCHMARK-sarvam.json"), "--workload",
         "rehearsal-sarvam.sessions-prefix", "--seed", "3900000021",
         "--seconds", "6", "--trace", "1", "--out-dir", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.splitlines()[-1])
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert metrics["compiles_in_window"] == 0
    assert 0 < metrics["routed_here_share"] < 100
    assert 0 < metrics["experts_touched_share"] <= 100
    assert metrics["prefix_hit_share"] > 50
    assert set(result["compared"]) >= {
        "choice_shortfall", "return_choice_logits_differ", "decode_step_1"}
    # No timing leaves a CPU rehearsal.
    assert "host_gap_share" not in metrics


def test_the_routed_readers_find_nothing_where_nothing_was_counted():
    """On the parent's records (no routing counts) and on a trace without
    the program every new reader returns None and raises nothing."""
    from readers import routed_decode

    with open(os.path.join(BENCH, "configs", "sarvam-105b-ep4.json")) as f:
        config = json.load(f)
    windows = [{"dispatched_at": 10.0, "rows": 3, "k": 8, "kv_tokens": 4096,
                "programs": ["window_fn"]}]
    ctx = types.SimpleNamespace(
        config=config, trace=None, got={"windows": {"windows": windows}},
        window_records=lambda: windows)
    for what in ("step_ms", "bw_share", "touched_share", "here_share"):
        assert routed_decode.read(
            ctx, {"what": what, "program": "window_fn"}) is None
    windows[0].update(moe_assigned=3 * 8 * 5 * 8, moe_assigned_here=240,
                      experts_touched=8 * 5 * 12, expert_rows_max=2)
    assert routed_decode.read(ctx, {"what": "here_share"}) == 25.0
    assert routed_decode.read(ctx, {"what": "touched_share"}) == 37.5


def test_the_routed_bytes_are_those_of_the_issues_table():
    from harness.sizes import held
    from reduce import latent_bytes, routed_bytes

    with open(os.path.join(BENCH, "configs", "sarvam-105b-ep4.json")) as f:
        hp = held(json.load(f))
    assert routed_bytes.routed_layers(hp) == 5
    assert routed_bytes.expert_bytes(hp) == 3 * 4096 * 2048 * 2   # 50.33 MB
    # Six layers' attention, the dense FFN, five routers and shared experts,
    # the head's 65,536 columns: 2.33 GB of bf16.
    assert routed_bytes.non_expert_bytes(hp) == 2 * (
        6 * 94_633_984 + 201_326_592 + 5 * (524_288 + 25_165_824)
        + 4096 * 65536)
    assert latent_bytes.latent_bytes_per_token(hp) == 6912
    assert latent_bytes.decode_read_bytes(hp, 1000, 8) == 8 * 1000 * 6912


def test_the_entries_that_list_the_cell_hold_the_rule():
    from conftest import hold_a_cell_to_the_rule
    from harness import layers

    cell, _names = hold_a_cell_to_the_rule(
        "sarvam-105b-ep4.sessions-20k", own=(
            "decode_step_dev_ms", "decode_step_bw_share",
            "latent_decode_bw_share", "experts_touched_share",
            "routed_here_share"))
    spec = layers.spec_of("decode_step_bw_share", [BENCH], cell["config"])
    assert (spec["reader"], spec["args"]["what"]) == (
        "routed_decode", "bw_share")
