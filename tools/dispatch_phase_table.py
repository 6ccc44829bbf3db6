"""`build` and `launch` ms a dispatch, by kind, from one run of a benchmark cell.

    python tools/dispatch_phase_table.py [--root _parent] [--label parent] -- \
        --workload solar-open2-250b-ep8.sessions-20k --seed 1 --seconds 45 --trace 0

Runs ``<root>/bench/run.py`` with the arguments after ``--`` in this process
(the benchmark as it stands, untouched: its last stdout line is still the
contract's) and keeps what it already fetches and throws away, the engine's
flight records (``GET /debug/windows``: ``WindowRecord.phases``, spans of the
step thread's work on each dispatch, ``time.time_ns()``).  From the records
dispatched inside the measured window it tables, for each kind of dispatch

- ``prefill``         a dedicated prefill (kind ``prefill``),
- ``window_rebuilt``  a decode window built from host state (not provisional),
- ``window_chained``  a decode window chained off an in-flight carry,

the count, a second, and the mean / median / 95th percentile of its ``build``
and ``launch`` spans in ms, for prefills the records' ``kv_tiles_live`` /
``kv_tiles_grid`` added up and the share skipped, how many dispatches a second were unchained
(prefills + rebuilt windows), and for those two kinds **behind / device
empty**: how many were launched while another program was in flight (the
record's ``behind``, PR 51) and how many met an empty device, with the
engine's own counters over the window beside them (``/metrics``:
``tpu:step_dispatch_behind_total{kind}`` and
``tpu:step_dispatch_behind_declined_total{reason}``; nothing from a checkout
without them).  The table is one JSON object on stderr and in
``chiprun_out/phase_table/phase_table.<label>.json``.  Host times: a chip
run's are the TPU host's, a CPU run's say nothing about a deployment.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import statistics
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


UNCHAINED = ("prefill", "window_rebuilt")
BEHIND_FAMILIES = ("tpu:step_dispatch_behind_total",
                   "tpu:step_dispatch_behind_declined_total")


def behind_counters(text: str) -> dict:
    """{family: {label value: count}} of the two families, from /metrics."""
    out = {}
    for line in text.splitlines():
        head, _, value = line.rpartition(" ")
        name, _, labels = head.partition("{")
        if name in BEHIND_FAMILIES and labels:
            out.setdefault(name, {})[labels.split('"')[1]] = float(value)
    return out


def kind_of(w: dict) -> str:
    if w["kind"] == "prefill":
        return "prefill"
    if w["kind"] in ("decode", "spec") and w.get("k", 1) > 1:
        return "window_chained" if w["provisional"] else "window_rebuilt"
    return w["kind"]


def span_ms(w: dict, name: str) -> float:
    return sum(t1 - t0 for n, t0, t1 in w["phases"] if n == name) / 1e6


def table(windows: list, lo: float, hi: float) -> dict:
    inside = [w for w in windows if lo <= w["dispatched_at"] < hi]
    if not inside:
        return {"dispatches": 0}
    # The ring may hold less than the window: rates are over what it holds.
    seconds = hi - max(lo, min(w["dispatched_at"] for w in inside))
    out = {"dispatches": len(inside), "ring_seconds": seconds, "kinds": {}}
    for kind in sorted({kind_of(w) for w in inside}):
        rows = [w for w in inside if kind_of(w) == kind]
        entry = {"n": len(rows), "per_s": len(rows) / seconds}
        if kind in UNCHAINED:
            entry["behind"] = sum(bool(w.get("behind")) for w in rows)
            entry["device_empty"] = len(rows) - entry["behind"]
        if kind == "prefill":
            # Tiles of the prefill attention kernel's grid, a layer, by the
            # kernel's own rule (PR 26; the latent module's since PR 57).
            live = sum(w.get("kv_tiles_live", 0) for w in rows)
            grid = sum(w.get("kv_tiles_grid", 0) for w in rows)
            entry["kv_tiles"] = {"live": live, "grid": grid,
                                 "skipped_share": 1 - live / max(grid, 1)}
        for phase in ("build", "launch"):
            ms = sorted(span_ms(w, phase) for w in rows)
            entry[phase + "_ms"] = {
                "mean": statistics.fmean(ms),
                "p50": ms[len(ms) // 2],
                "p95": ms[min(len(ms) - 1, int(0.95 * len(ms)))],
            }
        out["kinds"][kind] = entry
    unchained = sum(out["kinds"].get(k, {"n": 0})["n"] for k in UNCHAINED)
    out["unchained_per_s"] = unchained / seconds
    return out


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--root", default=HERE,
                   help="the checkout whose bench/run.py and engine run")
    p.add_argument("--label", default="change")
    args, rest = p.parse_known_args()
    rest = [a for a in rest if a != "--"]
    root = os.path.abspath(args.root)

    spec = importlib.util.spec_from_file_location(
        "bench_run", os.path.join(root, "bench", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    kept = {}
    drive = run.drive

    async def keeping(*a, **kw):
        got = await drive(*a, **kw)
        kept.update(windows=got.get("windows"), wall_t0=got.get("wall_t0"),
                    seconds=got.get("seconds"))
        return got

    run.drive = keeping
    scrapes = []
    parse_prom = run.scrape.parse_prom

    def keeping_labels(text):
        scrapes.append(behind_counters(text))
        return parse_prom(text)

    run.scrape.parse_prom = keeping_labels
    sys.argv = [os.path.join(root, "bench", "run.py"), *rest]
    try:
        run.main()
    finally:
        if kept.get("windows"):
            result = table(kept["windows"]["windows"], kept["wall_t0"],
                           kept["wall_t0"] + kept["seconds"])
            result.update(label=args.label, root=root, argv=rest)
            # The engine's counters over the window: the last scrape less
            # the first (before and after the measured window).
            if scrapes and scrapes[-1]:
                result["counters"] = {
                    family: {label: n - scrapes[0].get(family, {}).get(label, 0)
                             for label, n in labels.items()}
                    for family, labels in scrapes[-1].items()}
            out_dir = os.path.join(HERE, "chiprun_out", "phase_table")
            os.makedirs(out_dir, exist_ok=True)
            with open(os.path.join(
                    out_dir, f"phase_table.{args.label}.json"), "w") as f:
                json.dump(result, f, indent=1)
            print("phase table " + json.dumps(result), file=sys.stderr)


if __name__ == "__main__":
    main()
