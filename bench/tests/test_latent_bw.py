"""``latent_decode_bw_share`` on the hand-made pair of ``test_join.py``
(``data/join_small.*``), its two decode windows holding the latent kernel in
the paged one's place: twelve calls each, two steps of the configuration's
six layers."""

import json
import os

import pytest

from conftest import BENCH
from harness import layers
from harness.sizes import held
from reduce import latent_bytes
from test_join import load

MARKER = "latent_decode_attention_pallas"
KERNEL_S = 5.0e-05


def _config():
    with open(os.path.join(BENCH, "configs", "sarvam-105b-ep4.json")) as f:
        return json.load(f)


def _trace(marker=MARKER):
    trace = load("join_small.trace.json")
    for module in trace["modules"]:
        if module[3].pop("paged_decode_attention_pallas", None):
            module[3][marker] = 12
    trace["ops"] = [[marker, KERNEL_S, 24] if row[0].startswith("paged")
                    else row for row in trace["ops"]]
    return trace


def _context(trace, payload=None):
    return layers.Context(
        cell={"name": "sarvam-105b-ep4.sessions-20k",
              "config": "sarvam-105b-ep4", "chips": 1},
        config=_config(), records=[], late_ms=[],
        got={"windows": payload or load("join_small.windows.json"),
             "wall_t0": 0.0, "seconds": 45,
             "after": {"device": {"kind": "TPU v5 lite"}}},
        summary={}, dirs=[BENCH], trace=trace)


def _read(ctx):
    return layers.read_all(ctx, ["latent_decode_bw_share"])[
        "latent_decode_bw_share"]


def test_latent_kernel_bytes_against_the_published_bandwidth():
    # 576 values x 2 bytes x 6 layers a position; two windows of 12 calls /
    # 6 layers = 2 steps each, over 992 and 1024 positions in whole blocks.
    want = 2 * (992 + 1024) * 6912 / 819e9 / KERNEL_S * 100.0
    assert latent_bytes.latent_bytes_per_token(held(_config())) == 6912
    assert _read(_context(_trace())) == pytest.approx(want)
    assert 0 < want < 100


def test_a_program_without_the_kernel_reads_nothing_and_raises_nothing():
    """The parent's trace: the XLA walk has no operation of that name."""
    assert _read(_context(load("join_small.trace.json"))) is None
    assert _read(_context(_trace("fusion_of_the_walk"))) is None
    assert _read(_context(None)) is None


def test_a_shifted_record_says_nothing():
    payload = load("join_small.windows.json")
    late = payload["windows"][0]
    late["program_ns"] = [ns + 1_000_000 for ns in late["program_ns"]]
    late["launch_ns"] += 1_000_000
    assert _read(_context(_trace(), payload)) is None


def test_the_entry_lists_the_cells_that_run_the_kernel():
    """One entry, and on its list every cell whose configuration keeps a
    latent cache (a ``kv_lora_rank`` in its file) and no other."""
    with open(os.path.join(BENCH, "..", "BENCHMARK.json")) as f:
        bench = json.load(f)
    entry = [m for m in bench["per_layer"]
             if m["name"].split(".")[0] == "latent_decode_bw_share"]
    latent = []
    for cell in bench["workloads"]:
        file = next(c["file"] for c in bench["configs"]
                    if c["name"] == cell["config"])
        with open(os.path.join(BENCH, "..", file)) as f:
            if "kv_lora_rank" in json.load(f):
                latent.append(cell["name"])
    assert len(latent) >= 2
    assert entry == [{
        "name": "latent_decode_bw_share", "unit": "%", "better": "higher",
        "source": "device_trace", "layer": "Kernels", "moves": "tpot_p95_ms",
        "workloads": latent}]
