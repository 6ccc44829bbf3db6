"""``BENCHMARK.json`` against the files it names, off the chip (PR 59): a PR
to the program may append per-layer entries, and an entry whose file or
reader is missing was until now seen only by ``bench/tests`` (not tier-1) or
on the chip.  No JAX, no server: the file, ``bench/harness/layers.py``'s own
lookup, and an import of each reader."""

import importlib
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")


def _load():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


BENCHMARK = _load()
CELLS = {w["name"]: w for w in BENCHMARK["workloads"]}
PAIRS = [(m["name"], cell) for m in BENCHMARK["per_layer"]
         for cell in m.get("workloads", CELLS)]


@pytest.fixture(scope="module")
def layers():
    """``bench/`` is no package: its directory goes on the path as
    ``bench/run.py`` puts it there (and comes off again)."""
    sys.path.insert(0, BENCH)
    try:
        yield importlib.import_module("harness.layers")
    finally:
        sys.path.remove(BENCH)


def test_the_file_loads_and_names_what_the_harness_needs():
    assert BENCHMARK["command"] == ["python3", "bench/run.py"]
    assert BENCHMARK["paths"] == ["bench"]
    configs = {c["name"]: c for c in BENCHMARK["configs"]}
    for cell in CELLS.values():
        assert cell["config"] in configs, cell
        assert cell["chips"] in (1, 4)
        assert os.path.exists(os.path.join(
            BENCH, "traffic", cell["traffic"] + ".json")), cell
    for config in configs.values():
        assert os.path.exists(os.path.join(ROOT, config["file"])), config
    assert len(json.dumps(BENCHMARK)) < 64 * 1024


def test_per_layer_names_are_unique_and_at_most_128():
    names = [m["name"] for m in BENCHMARK["per_layer"]]
    assert len(names) == len(set(names))
    assert 1 <= len(names) <= 128
    assert not set(names) & {m["name"] for m in BENCHMARK["end_to_end"]}


def test_every_entry_lists_known_cells_and_moves_what_they_report():
    for m in BENCHMARK["per_layer"]:
        cells = m.get("workloads", list(CELLS))
        assert cells and set(cells) <= set(CELLS), m["name"]
        assert len(cells) == len(set(cells)), m["name"]
        reported = [e for e in BENCHMARK["end_to_end"]
                    if e["name"] == m["moves"]]
        assert reported, m["name"]
        assert set(cells) <= set(reported[0].get("workloads", CELLS)), (
            m["name"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_layers_of_one_name_are_spelled_alike():
    layers_named = {m["layer"] for m in BENCHMARK["per_layer"]}
    assert len({name.lower() for name in layers_named}) == len(layers_named)


@pytest.mark.parametrize("name,cell", PAIRS)
def test_the_entry_has_its_file_and_its_reader_in_the_cell(layers, name, cell):
    config = CELLS[cell]["config"]
    spec = layers.spec_of(name, [BENCH], config, missing_ok=True)
    assert spec is not None, f"no layer_metrics file for {name} in {cell}"
    assert os.path.exists(os.path.join(
        BENCH, "readers", spec["reader"] + ".py")), (name, spec["reader"])
    assert isinstance(spec.get("args", {}), dict)


def test_every_reader_an_entry_names_is_importable(layers):
    readers = {layers.spec_of(name, [BENCH], CELLS[cell]["config"])["reader"]
               for name, cell in PAIRS}
    sys.path.insert(0, BENCH)
    try:
        for reader in sorted(readers):
            module = importlib.import_module("readers." + reader)
            assert callable(getattr(module, "read", None)), reader
    finally:
        sys.path.remove(BENCH)
