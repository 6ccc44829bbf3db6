"""Per-layer metrics: each is a data file ``layer_metrics/<name>.json`` that
names a reader module in ``readers/`` and its arguments.  A configuration
whose reader for a quantity is its own brings
``layer_metrics/<config>/<name>.json``, which is looked for first; an entry
split by what its cells report (``<base>.<suffix>``) reads ``<base>.json``
where it has no file of its own.  A reader takes the run's :class:`Context`
and returns a number, or None where it found nothing to read; the harness
then leaves the metric out of the line.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
from typing import Dict, List, Optional, Tuple


@dataclasses.dataclass
class Context:
    cell: Dict
    config: Dict
    records: List
    late_ms: List[float]
    got: Dict          # drive()'s result: before/after snapshots, windows, ...
    summary: Dict
    dirs: List[str]    # where data files are looked for (run.py resolve)
    trace: Optional[Dict] = None

    def delta(self, family: str) -> Optional[float]:
        """Growth of one Prometheus family over the window."""
        a, b = self.got["before"]["prom"], self.got["after"]["prom"]
        if family not in a or family not in b:
            return None
        return b[family] - a[family]

    def window_records(self) -> List[Dict]:
        """Flight records dispatched inside the window (host wall clock)."""
        lo = self.got["wall_t0"]
        hi = lo + self.got["seconds"]
        return [w for w in self.got["windows"]["windows"]
                if lo <= w["dispatched_at"] < hi]

    def peaks(self) -> Dict:
        with open(find(self.dirs, "", "peaks")) as f:
            table = json.load(f)["devices"]
        kind = self.got["after"]["device"]["kind"]
        if kind not in table:
            raise SystemExit(f"bench: no published peaks for device kind "
                             f"{kind!r}; known: {sorted(table)}")
        return table[kind]


def find(dirs: List[str], kind: str, name: str,
         missing_ok: bool = False) -> Optional[str]:
    """``<dir>/<kind>/<name>.json`` in the first of ``dirs`` that has it."""
    for d in dirs:
        path = os.path.join(d, kind, name + ".json")
        if os.path.exists(path):
            return path
    if missing_ok:
        return None
    raise SystemExit(f"bench: no {kind}/{name}.json under {dirs}")


def spec_file(name: str, dirs: List[str], config: str,
              missing_ok: bool = False) -> Optional[str]:
    """The configuration's own file for the metric
    (``layer_metrics/<config>/<name>.json``: one quantity, another module's
    program and bytes) before the metric's file; and each by the metric's
    name before its base name, the file that the entries of one quantity
    split by cells share (``queue_wait_mean_ms.chat-steady``: the cells
    report different end-to-end metrics, the copies read alike)."""
    base = name.split(".")[0]
    own = os.path.join("layer_metrics", config)
    for kind, stem in ((own, name), (own, base), ("layer_metrics", name)):
        path = find(dirs, kind, stem, missing_ok=True)
        if path:
            return path
    return find(dirs, "layer_metrics", base, missing_ok)


def spec_of(name: str, dirs: List[str], config: str,
            missing_ok: bool = False) -> Optional[Dict]:
    path = spec_file(name, dirs, config, missing_ok)
    if path is None:
        return None
    with open(path) as f:
        return json.load(f)


def is_count(name: str, dirs: List[str], config: str) -> bool:
    """A count may be printed by a CPU rehearsal; a time may not."""
    spec = spec_of(name, dirs, config, missing_ok=True)
    return spec is not None and bool(spec.get("count"))


def read_all(ctx: Context, names: List[str]) -> Dict:
    out = {}
    for name in names:
        spec = spec_of(name, ctx.dirs, ctx.cell["config"])
        reader = importlib.import_module("readers." + spec["reader"])
        out[name] = reader.read(ctx, spec.get("args", {}))
    return out


def served_path_ok(ctx: Context, platform: str,
                   exit_codes: Dict) -> Tuple[bool, List[str]]:
    """Part (i) of ``correct``: every finished request has exactly the
    tokens asked for (a failed one is counted, not forgiven here), usage
    adds up, the engine held the cell's chips, and both servers drained to
    exit code 0."""
    notes = []
    dev = ctx.got["after"]["device"]
    if dev["platform"] != platform or dev["count"] != ctx.cell["chips"]:
        notes.append(f"engine device report {dev}")
    for name, code in exit_codes.items():
        if code != 0:
            notes.append(f"{name} exited {code} on SIGTERM")
    wrong = [
        r for r in ctx.records
        if r.done and r.status == 200 and not r.error and (
            r.completion_tokens != r.asked or r.finish_reason != "length"
            or not r.prompt_tokens)
    ]
    if wrong:
        notes.append(f"{len(wrong)} finished requests with the wrong token "
                     f"count or finish_reason")
    if ctx.summary["attempted"] == 0:
        notes.append("no request was due inside the window")
    return not notes, notes


def breakdown(ctx: Context) -> Dict:
    """The contract's ``breakdown``: device operations by time, and the
    longest idle gaps named by what the engine's flight records say the
    host was doing (host wall clock joined at the profiler's start; good to
    a few milliseconds, so only a hint)."""
    trace = ctx.trace
    ops = [[name, seconds] for name, seconds, _n in trace["ops"][:10]]
    gaps = []
    span0 = trace.get("span_ns", [0, 0])[0]
    wall0 = ctx.got["trace_wall"][0]
    records = sorted(ctx.got["windows"]["windows"],
                     key=lambda w: w["dispatched_at"])
    for seconds, start_ns, _end_ns in trace["gaps"][:10]:
        # The flight record dispatched next after the gap began.
        at = wall0 + (start_ns - span0) / 1e9
        nxt = next((w for w in records if w["dispatched_at"] >= at), None)
        label = "host, before an unrecorded dispatch"
        if nxt is not None:
            label = (f"host, before {nxt['kind']} k={nxt['k']} "
                     f"rows={nxt['rows']}")
        gaps.append([label, seconds])
    return {"device_ops": ops, "idle_gaps": gaps}
