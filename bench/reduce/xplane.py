"""Profiler trace (.xplane.pb) -> device busy/idle, time per program and per
operation, idle gaps.

Two halves: :func:`load` reads the file with ``jax.profiler.ProfileData``
into plain lists, and everything after it is arithmetic on those lists, so
that it can be checked on a small recorded trace without a chip
(``tests/data/trace_small.json``).

What the v5e's trace looks like (read by hand, PR 23): one plane per chip
named ``/device:TPU:<n>``; its line ``XLA Modules`` holds one event per
executed program (``jit_<fn>(<fingerprint>)``; the engine's step programs
show as ``_unknown``) and its line ``XLA Ops`` one event per HLO operation,
named by its whole instruction text, the Pallas kernels among them as
``%paged_decode_attention_pallas.N`` and ``%flash_prefill_attention.N``.  Host threads are
lines of ``/host:CPU``.  Times are nanoseconds on one clock.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Optional, Tuple

Event = Tuple[str, float, float]  # name, start_ns, duration_ns

DEVICE_PLANE = {"tpu": re.compile(r"^/device:TPU:\d+$")}
OPS_LINE = "XLA Ops"
# Operations that only wrap others on the same line (a scan is a ``while``):
# counted for busy time, left out of the per-operation totals.
WRAPPERS = ("while", "conditional", "call")
PROGRAMS_LINE = "XLA Modules"


def load(path: str) -> List[Dict]:
    """[{"name": plane, "lines": [{"name": line, "events": [Event]}]}]"""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    return [
        {"name": plane.name, "lines": [
            {"name": line.name, "events": [
                (short_name(e.name), float(e.start_ns), float(e.duration_ns))
                for e in line.events]}
            for line in plane.lines]}
        for plane in data.planes
    ]


def short_name(event_name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO instruction,
    ``%fusion.12 = bf16[...] fusion(...)``: keep what stands before the
    ``=``."""
    return event_name.split(" = ", 1)[0][:120]


def op_family(name: str) -> str:
    """``%fusion.360.remat`` and ``%fusion.12`` -> ``fusion``."""
    return re.sub(r"[.\d]+(\.remat\d*)?$", "", name.lstrip("%")) or name


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Sorted disjoint cover of (start, end) intervals."""
    out: List[Tuple[float, float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def _line(plane: Dict, name: str) -> Optional[Dict]:
    for line in plane["lines"]:
        if line["name"] == name:
            return line
    return None


def program_name(event_name: str) -> str:
    """``jit_window_fn(123456789)`` -> ``window_fn``."""
    name = event_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def totals(events: List[Event], key=lambda n: n) -> List[List]:
    """[[name, seconds, count]] by total time, longest first."""
    acc: Dict[str, List[float]] = {}
    for name, _start, dur in events:
        slot = acc.setdefault(key(name), [0.0, 0])
        slot[0] += dur
        slot[1] += 1
    return sorted(([k, v[0] / 1e9, v[1]] for k, v in acc.items()),
                  key=lambda row: -row[1])


def modules(programs: List[Event], ops: List[Event]) -> List[List]:
    """[[program, start_ns, duration_ns, {operation family: calls}]] for each
    executed program, with the operations that started inside it.  The
    step programs have no names of their own in today's trace
    (``_unknown``), so a reader tells a decode step from a prefill by the
    kernels it holds."""
    out = [[program_name(n), s, d, {}] for n, s, d in sorted(
        programs, key=lambda e: e[1])]
    i = 0
    for name, start, _dur in sorted(ops, key=lambda e: e[1]):
        while i < len(out) and out[i][1] + out[i][2] <= start:
            i += 1
        if i == len(out):
            break
        if out[i][1] <= start:
            fam = op_family(name)
            out[i][3][fam] = out[i][3].get(fam, 0) + 1
    return out


def reduce(planes: List[Dict], platform: str) -> Dict:
    """Busy seconds (union of the operations' intervals, averaged over the
    chips), the traced span, time per program and operation on the first
    chip, and the longest gaps in which that chip ran nothing."""
    pattern = DEVICE_PLANE.get(platform)
    devices = [p for p in planes if pattern and pattern.match(p["name"])]
    out: Dict = {
        "planes": [
            {"name": p["name"],
             "lines": {ln["name"]: len(ln["events"]) for ln in p["lines"]}}
            for p in planes
        ],
        "busy_s": None, "window_s": None, "programs": [], "modules": [],
        "ops": [], "gaps": [],
    }
    if not devices:
        return out
    busy = []
    spans = []
    for plane in devices:
        line = _line(plane, OPS_LINE) or _line(plane, PROGRAMS_LINE)
        cover = union([(s, s + d) for _n, s, d in line["events"]])
        busy.append(sum(e - s for s, e in cover) / 1e9)
        if cover:
            spans.append((cover[0][0], cover[-1][1]))
    # The traced span: from the first to the last device operation on any
    # chip.  (The host planes start earlier, while the profiler starts up.)
    start, end = min(s for s, _ in spans), max(e for _, e in spans)
    out["busy_s"] = sum(busy) / len(busy)
    out["window_s"] = (end - start) / 1e9
    out["span_ns"] = [start, end]
    first = devices[0]
    programs = _line(first, PROGRAMS_LINE)
    ops = _line(first, OPS_LINE)
    if programs:
        out["programs"] = totals(programs["events"], program_name)
        out["modules"] = modules(programs["events"],
                                 ops["events"] if ops else [])
    if ops:
        out["ops"] = [row for row in totals(ops["events"], op_family)
                      if row[0] not in WRAPPERS][:40]
        cover = union([(s, s + d) for _n, s, d in ops["events"]])
        gaps = [(b[0] - a[1], a[1], b[0]) for a, b in zip(cover, cover[1:])]
        out["gaps"] = [[g / 1e9, s, e]
                       for g, s, e in sorted(gaps, reverse=True)[:10]]
    return out


def find(trace_dir: str) -> str:
    paths = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce_dir(trace_dir: str, platform: str) -> Dict:
    return reduce(load(find(trace_dir)), platform)
