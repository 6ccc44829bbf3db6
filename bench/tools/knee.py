"""Read a sweep's table and say where the knee is (PERF.md has the rule):

    python bench/tools/knee.py <sweep.json> [<cell file to write the rate to>]
"""

import json
import math
import sys


def knee(rows):
    best = None
    for row in sorted(rows, key=lambda r: r["rate_rps"]):
        ok = (row["completed_share"] >= 0.98
              and row["in_flight_end"] <= 1.5 * row["in_flight_mid"] + 4)
        if not ok:
            break
        best = row["rate_rps"]
    return best


def main() -> None:
    with open(sys.argv[1]) as f:
        rows = json.load(f)
    k = knee(rows)
    rate = None if k is None else math.floor(k * 0.8 * 10) / 10
    print(json.dumps({"knee_rps": k, "rate_rps": rate}))
    if rate is not None and len(sys.argv) > 2:
        with open(sys.argv[2], "w") as f:
            json.dump({"rate_rps": rate, "knee_rps": k, "sweep": rows}, f,
                      indent=1)


if __name__ == "__main__":
    main()
