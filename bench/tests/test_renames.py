"""PR 58 merged the suffixed copies of ``per_layer`` (128 entries) into one
entry a quantity.  ``data/per_layer_renames.json`` holds, for every (entry,
cell) pair of the list as it was, the reader, args and count it resolved to
on that tree and the entry that reads it since: the proof that the merge
changed no reading."""

import json
import os

import pytest

from conftest import BENCH, benchmark_file
from harness import layers

with open(os.path.join(BENCH, "tests", "data", "per_layer_renames.json")) as f:
    PAIRS = json.load(f)["pairs"]
BENCHMARK = benchmark_file()


def test_the_table_holds_every_entry_that_was_there():
    assert len({p["old"] for p in PAIRS}) == 128
    retired = {p["old"] for p in PAIRS if p["new"] is None}
    assert retired == {"host_gap_share"}


@pytest.mark.parametrize(
    "pair", PAIRS, ids=[f"{p['old']}@{p['cell']}" for p in PAIRS])
def test_a_merged_entry_reads_what_its_old_name_read(pair):
    bench = BENCHMARK
    entries = {m["name"]: m for m in bench["per_layer"]}
    if pair["new"] is None:
        assert pair["old"] not in entries
        return
    entry = entries[pair["new"]]
    assert pair["cell"] in entry["workloads"]
    config = next(w["config"] for w in bench["workloads"]
                  if w["name"] == pair["cell"])
    spec = layers.spec_of(pair["new"], [BENCH], config)
    assert (spec["reader"], spec.get("args", {}), bool(spec.get("count"))) == (
        pair["reader"], pair["args"], pair["count"])
    assert layers.is_count(pair["new"], [BENCH], config) == pair["count"]


def test_no_old_name_is_left_that_reads_something_else():
    """An old name that is still an entry means what it meant, but for
    ``routed_decode_bw_share``: the whole step's share went to
    ``decode_step_bw_share`` and the name keeps the routed layers' part."""
    names = {m["name"] for m in BENCHMARK["per_layer"]}
    for pair in PAIRS:
        if pair["old"] in names and pair["old"] != pair["new"]:
            assert pair["old"] == "routed_decode_bw_share", pair
