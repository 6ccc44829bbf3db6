"""Spread of each end-to-end metric over two sets of runs, as the builder's
instructions define it: the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median, the wider
of the two sets; five times the widest is the bound to set.  ``check`` is
what the driver holds against half of a bound: the mean of the two sets'
spreads, each set without its run farthest from the median where that
narrows it.

    python bench/tools/spread.py <dir> <prefix>     # <dir>/<prefix>-A-*.out, -B-
    python bench/tools/spread.py <dir> <prefix> --records ttft_p90_ms,ttft_p95_ms

The first reads result lines.  The second reads the kept request records of
each run, ``<dir>/<prefix>-<set>-<seed>/records.jsonl`` with the ``run.json``
beside it (its ``window``: t0, seconds, drain), and computes the named
statistics with ``reduce/stats.py: summarize``, the function a run's own
result line comes from: a candidate is tabled by the code that would judge
it.
"""

import glob
import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness.client import Record  # noqa: E402
from reduce import stats  # noqa: E402


def last_line(path):
    with open(path) as f:
        lines = [ln for ln in f.read().splitlines() if ln.startswith("{")]
    return json.loads(lines[-1])


def spread(values):
    q1, med, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / med


def trimmed(values):
    med = statistics.median(values)
    far = max(values, key=lambda v: abs(v - med))
    rest = list(values)
    rest.remove(far)
    return min(spread(values), spread(rest)) if len(rest) > 1 else spread(values)


def from_result_lines(directory, prefix):
    """{set: [{metric: value} a run]} from the runs' last lines."""
    sets = {}
    for name in "AB":
        runs = [last_line(p) for p in sorted(
            glob.glob(f"{directory}/{prefix}-{name}-*.out"))]
        runs = [r for r in runs if "metrics" in r]
        print(name, "runs", len(runs), "failed", [r["failed"] for r in runs],
              "correct", all(r["correct"] for r in runs))
        sets[name] = [{m: v["value"] for m, v in r["metrics"].items()}
                      for r in runs]
    return sets


def from_records(directory, prefix, names):
    """The same from each run's kept records, by ``stats.summarize``."""
    sets = {}
    for name in "AB":
        sets[name] = []
        for run in sorted(glob.glob(f"{directory}/{prefix}-{name}-*/")):
            with open(run + "run.json") as f:
                window = json.load(f)["window"]
            with open(run + "records.jsonl") as f:
                records = [Record(**json.loads(ln)) for ln in f]
            got = stats.summarize(records, window["t0"], window["seconds"],
                                  window["drain_s"])
            sets[name].append({m: got["metrics"][m] for m in names
                               if m in got["metrics"]})
        print(name, "runs", len(sets[name]))
    return sets


def table(sets):
    for m in sorted(sets["A"][0]):
        row = {}
        for name, runs in sets.items():
            values = [r[m] for r in runs if m in r]
            if len(values) < 2:
                continue
            row[name] = {"median": round(statistics.median(values), 3),
                         "spread": round(spread(values), 4),
                         "trimmed": round(trimmed(values), 4),
                         "min": round(min(values), 3),
                         "max": round(max(values), 3)}
        widest = max(v["spread"] for v in row.values())
        drift = None
        if "A" in row and "B" in row:
            drift = round(row["B"]["median"] / row["A"]["median"] - 1, 4)
        check = round(statistics.mean(v["trimmed"] for v in row.values()), 4)
        print(m, json.dumps(row), "widest", widest, "x5", round(5 * widest, 4),
              "check", check, "B/A-1", drift)


def main() -> None:
    directory, prefix = sys.argv[1], sys.argv[2]
    if len(sys.argv) > 4 and sys.argv[3] == "--records":
        table(from_records(directory, prefix, sys.argv[4].split(",")))
    else:
        table(from_result_lines(directory, prefix))


if __name__ == "__main__":
    main()
