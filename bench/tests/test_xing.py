"""PR 44's cases: the latent module serving a model with several residual
streams, a low-rank query path and every expert held (``tiny-xing``) through
the harness on the CPU, and the reader and bytes function its cell brings.  A
file of its own: the files that were there are not edited."""

import json
import os
import sys
import types

from conftest import BENCH

DATA = os.path.join(BENCH, "tests", "data")


def _config():
    with open(os.path.join(BENCH, "configs",
                           "xing4.0-29b-a4b-stage.json")) as f:
        return json.load(f)


def test_the_several_streams_rehearsal_runs_through_the_harness(tmp_path):
    """``tiny-xing`` through ``run.py`` on the CPU: engine and router as
    children, the sessions mix, the compare following the engine's choice
    against ``reference/xing_mhc.py``; counts only, ``correct``, and the
    residual path's counters read from the flight records and ``/metrics``."""
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--benchmark",
         os.path.join(DATA, "rehearsal", "BENCHMARK-xing.json"), "--workload",
         "rehearsal-xing.sessions-prefix", "--seed", "3900000044",
         "--seconds", "6", "--trace", "1", "--out-dir", str(tmp_path)],
        env=env, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    result = json.loads(out.stdout.splitlines()[-1])
    assert result["correct"] and result["failed"] == 0
    assert result["attempted"] > 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert metrics["compiles_in_window"] == 0
    assert 0 < metrics["experts_touched_share"] <= 100
    assert metrics["mhc_clamped_share"] == 0
    assert 0 < metrics["mhc_sinkhorn_err"] <= 1e5
    assert metrics["prefix_hit_share"] > 50
    assert set(result["compared"]) >= {
        "choice_shortfall", "return_choice_logits_differ", "decode_step_1"}
    # No timing leaves a CPU rehearsal.
    assert metrics.get("decode_step_bw_share") is None


def test_the_readers_find_nothing_where_nothing_was_counted():
    """On the parent's records (no such counts) and on a trace without the
    program the new reader returns None and raises nothing."""
    from readers import prom_ratio, xing_decode

    windows = [{"dispatched_at": 10.0, "rows": 3, "k": 8, "kv_tokens": 4096,
                "programs": ["window_fn"]}]
    ctx = types.SimpleNamespace(
        config=_config(), trace=None, got={"windows": {"windows": windows}},
        window_records=lambda: windows, delta=lambda family: None)
    for what in ("bw_share", "touched_share", "sinkhorn_err",
                 "sinkhorn_bw_share"):
        assert xing_decode.read(ctx, {
            "what": what, "program": "window_fn",
            "marker": "mhc_sinkhorn_pallas"}) is None
    with open(os.path.join(BENCH, "layer_metrics",
                           "mhc_clamped_share.json")) as f:
        assert prom_ratio.read(ctx, json.load(f)["args"]) is None
    windows[0].update(moe_assigned=3 * 8 * 5 * 4, moe_assigned_here=480,
                      experts_touched=8 * 5 * 12, mhc_err_e6=31000)
    windows.append({"dispatched_at": 11.0, "rows": 0, "k": 1,
                    "bucket_tokens": 256, "mhc_err_e6": 47000})
    assert xing_decode.read(ctx, {"what": "touched_share"}) == 18.75
    assert xing_decode.read(ctx, {"what": "sinkhorn_err"}) == 47000


def test_the_bytes_are_those_of_the_issues_table():
    from harness.sizes import held
    from reduce import latent_bytes, xing_bytes

    hp = held(_config())
    assert xing_bytes.routed_layers(hp) == 5
    assert xing_bytes.expert_bytes(hp) == 3 * 3584 * 1024 * 2    # 22.02 MB
    assert xing_bytes.mapping_bytes(hp) == 2 * 14336 * 24 * 4
    assert xing_bytes.sinkhorn_bytes(hp, 16) == 2 * 16 * 16 * 4
    # Six layers' attention (28.41 M each) and mappings, the dense lead's
    # SwiGLU, five routers and shared experts, the head's 131,072 columns:
    # 1.60 GB, the issue's "non-expert weights".
    want = 2 * (6 * 28_409_856 + 99_090_432 + 5 * (229_376 + 11_010_048)
                + 3584 * 131072) + 6 * 2_752_512
    assert xing_bytes.non_expert_bytes(hp) == want
    assert abs(want / 1e9 - 1.60) < 0.01
    assert latent_bytes.latent_bytes_per_token(hp) == 6912


def test_the_file_keeps_every_published_width():
    config = _config()
    changed = {k for k, v in config["published"].items()
               if config.get(k) != v}
    assert changed == set(config["reduced"]) == {
        "num_hidden_layers", "first_k_dense_replace"}
    assert (config["num_hidden_layers"], config["first_k_dense_replace"]) == (
        6, 1)
    assert (config["n_routed_experts"], config["num_experts_per_tok"],
            config["vocab_size"]) == (64, 4, 131072)
    spec = config["compare"]
    assert spec["follow_choice"] and spec["choice_shortfall"] == 0.1
    for key in ("stands_for", "assumed"):
        assert config[key]
    for key in ("why_rtol", "why_shortfall"):
        assert "float8" in spec[key] and "three" in spec[key], key


def test_the_entries_that_list_the_cell_hold_the_rule():
    from conftest import hold_a_cell_to_the_rule
    from harness import layers

    cell, _names = hold_a_cell_to_the_rule(
        "xing4.0-29b-a4b-stage.sessions-20k", own=(
            "decode_step_dev_ms", "decode_step_bw_share",
            "latent_decode_bw_share", "experts_touched_share",
            "mhc_bw_share", "mhc_clamped_share", "mhc_sinkhorn_err"))
    for name in ("decode_step_bw_share", "experts_touched_share"):
        assert layers.spec_of(name, [BENCH], cell["config"])[
            "reader"] == "xing_decode", name
