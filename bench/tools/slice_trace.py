"""Cut a small piece out of a recorded trace, for ``tests/data``:

    python bench/tools/slice_trace.py <trace_dir> <out.json> [milliseconds]

Keeps, of the first device plane, the ``XLA Modules`` and ``XLA Ops`` events
that start inside the N milliseconds in which most programs start (so that
the piece holds program boundaries and idle gaps, not one long program).
Names go into a table and times are counted from the piece's start, to keep
the file small; ``expand`` undoes both.
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from reduce import xplane  # noqa: E402


def expand(packed):
    """The planes :func:`reduce.xplane.reduce` takes, from a packed piece."""
    names = packed["names"]
    return [{"name": p["name"], "lines": [
        {"name": ln["name"],
         "events": [(names[i], s, d) for i, s, d in ln["events"]]}
        for ln in p["lines"]]} for p in packed["planes"]]


def main() -> None:
    trace_dir, out = sys.argv[1], sys.argv[2]
    ms = float(sys.argv[3]) if len(sys.argv) > 3 else 12.0
    planes = xplane.load(xplane.find(trace_dir))
    plane = next(p for p in planes
                 if xplane.DEVICE_PLANE["tpu"].match(p["name"]))
    lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
    starts = sorted(e[1] for e in lines[xplane.PROGRAMS_LINE])
    span = ms * 1e6
    lo = max(starts, key=lambda s: sum(1 for t in starts if s <= t < s + span))
    names, index = [], {}
    packed_lines = []
    for name in (xplane.PROGRAMS_LINE, xplane.OPS_LINE):
        events = []
        for n, s, d in lines[name]:
            if lo <= s < lo + span:
                if n not in index:
                    index[n] = len(names)
                    names.append(n)
                events.append([index[n], int(s - lo), int(d)])
        packed_lines.append({"name": name, "events": events})
    with open(out, "w") as f:
        json.dump({"names": names, "planes": [
            {"name": plane["name"], "lines": packed_lines}]}, f,
            separators=(",", ":"))
    print(f"{out}: {sum(len(ln['events']) for ln in packed_lines)} events")


if __name__ == "__main__":
    main()
