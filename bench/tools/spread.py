"""Spread of each end-to-end metric over two sets of runs, as the builder's
instructions define it: the distance between the first and third quartile
(``statistics.quantiles(values, n=4)``) as a share of the median, the wider
of the two sets; five times the widest is the bound to set.  ``check`` is
what the driver holds against half of a bound: the mean of the two sets'
spreads, each set without its run farthest from the median where that
narrows it.

    python bench/tools/spread.py <dir> <prefix>     # <dir>/<prefix>-A-*.out, -B-
"""

import glob
import json
import statistics
import sys


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


def main() -> None:
    directory, prefix = sys.argv[1], sys.argv[2]
    sets = {}
    for name in "AB":
        runs = [last_line(p) for p in sorted(
            glob.glob(f"{directory}/{prefix}-{name}-*.out"))]
        sets[name] = [r for r in runs if "metrics" in r]
        print(name, "runs", len(sets[name]), "failed",
              [r["failed"] for r in sets[name]], "correct",
              all(r["correct"] for r in sets[name]))
    metrics = sorted(sets["A"][0]["metrics"])
    for m in metrics:
        row = {}
        for name, runs in sets.items():
            values = [r["metrics"][m]["value"] for r in runs if m in r["metrics"]]
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


if __name__ == "__main__":
    main()
