"""The benchmark's own tests: ``python -m pytest bench/tests -q`` (not part
of the repo's tier-1 run).  ``bench/`` is no package, so its directory goes
on the path as ``run.py`` puts it there."""

import importlib
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


def benchmark_file():
    with open(os.path.join(BENCH, "..", "BENCHMARK.json")) as f:
        return json.load(f)


def entries_of(bench, cell):
    """The per-layer entries that list ``cell``."""
    return [m for m in bench["per_layer"] if cell in m["workloads"]]


def hold_a_cell_to_the_rule(cell_name, own=()):
    """What each configuration's test asks of ``BENCHMARK.json``, as a rule
    and with no count: every entry that lists the cell resolves to a spec
    file and an importable reader *for that configuration*; what it
    ``moves`` the cell reports; the cell is on the list of every quantity
    the configuration brings a file for (``layer_metrics/<config>/``) and of
    each of ``own``, the quantities its own reader produces.  Returns the
    cell and the names that list it."""
    from harness import layers

    bench = benchmark_file()
    cell = next(w for w in bench["workloads"] if w["name"] == cell_name)
    config = cell["config"]
    reported = {m["name"] for m in bench["end_to_end"]
                if cell_name in m.get("workloads", [cell_name])}
    mine = entries_of(bench, cell_name)
    assert mine, cell_name
    for m in mine:
        spec = layers.spec_of(m["name"], [BENCH], config, missing_ok=True)
        assert spec is not None, m["name"]
        assert hasattr(importlib.import_module("readers." + spec["reader"]),
                       "read"), m["name"]
        assert m["moves"] in reported, m["name"]
    names = {m["name"].split(".")[0] for m in mine}
    brought = os.path.join(BENCH, "layer_metrics", config)
    if os.path.isdir(brought):
        for file in os.listdir(brought):
            assert file[:-len(".json")] in names, (config, file)
    assert set(own) <= names, set(own) - names
    return cell, [m["name"] for m in mine]
