"""Flight records (``/debug/windows``) joined to the reduced trace: which
record launched each traced program, the offset between the host's clock
and the trace's, and what the engine's step thread was doing while the
device ran nothing.  Pure arithmetic on the two payloads, checked on a
small recorded pair (``tests/data/join_small.*.json``).

The device runs an engine's programs in the order the step thread launched
them.  Every record lists its programs with the unix ns of each launch
(``programs``, ``program_ns``; the first is its ``launch_ns``), so all
records together give one launch sequence, and the traced programs of
those names are a contiguous run of it.  The run is found by name, among
the launches made while the profiler's session stood (the payload's
``profile``), and proven by time: with the right alignment there is an
offset (unix ns = trace ns + offset) under which no program starts on the
device before it was launched nor ends after the read-back that waited for
it (``collected_ns``) returned.  The offsets that satisfy every matched
program form a bracket; its width is the join's residual, and the readers
take its middle, so that they are off by at most half of it.  A wrong
alignment is off by a whole dispatch and leaves the bracket empty: then,
and where more than one alignment holds, there is no join and every reader
on it returns None.

An engine from before the records carried programs and phases gives
nothing to join: every function here then returns None.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

INF = float("inf")
# A program in the trace was launched inside the profiler's session, give
# or take what was already queued when the session began.
SESSION_SLACK_NS = 2_000_000_000


def launches(records: List[Dict]) -> List[Tuple[int, str, int]]:
    """[(launch unix ns, program, index of its record)] in launch order."""
    out = []
    for i, rec in enumerate(records):
        names, stamps = rec.get("programs"), rec.get("program_ns")
        if not names or not stamps or len(names) != len(stamps):
            continue
        out.extend((ns, name, i) for name, ns in zip(names, stamps))
    out.sort(key=lambda row: row[0])
    return out


def done_by(rec: Dict, launched_ns: int) -> float:
    """Unix ns by which a program of ``rec`` launched at ``launched_ns`` had
    ended: the end of the read-back that followed it (``collected_ns``);
    for one launched later, inside a ``sample`` or ``collect`` phase (a
    first token sampled at a window's collect is read back there), that
    phase's end; unknown (inf) for a dispatch that read nothing back."""
    collected = rec.get("collected_ns")
    if collected is not None and launched_ns <= collected:
        return collected
    for name, start, end in rec.get("phases") or []:
        if name in ("collect", "sample") and start <= launched_ns <= end:
            return end
    return INF


def session(payload: Dict) -> Tuple[float, float]:
    """Unix ns between which a traced program was launched, from the
    payload's ``profile`` (the engine's own stamps around start_trace and
    stop_trace); unbounded where it has none."""
    profile = payload.get("profile") or {}
    start = profile.get("start_unix_ns")
    stop = profile.get("stop_unix_ns")
    return (start[0] - SESSION_SLACK_NS if start else -INF,
            stop[1] if stop else INF)


def match(modules: List[List], payload: Dict) -> Optional[Dict]:
    """Which record launched each traced program.

    ``modules``: the reduced trace's, [program, start_ns, duration_ns,
    {family: calls}] in start order.  Returns None where there is nothing
    to join or not exactly one alignment holds; else ``{"pairs": [(index
    into modules, index into the payload's records)], "offset_ns": [lo,
    hi], "other": programs in the trace that no record names (eager
    operations)}``."""
    records = payload.get("windows") or []
    seq = launches(records)
    if not seq:
        return None
    known = {name for _ns, name, _i in seq}
    mine = [j for j, m in enumerate(modules) if m[0] in known]
    if not mine:
        return None
    names = [modules[j][0] for j in mine]
    first, last = session(payload)
    found = []
    for i0 in range(len(seq) - len(mine) + 1):
        if seq[i0][0] < first or seq[i0 + len(mine) - 1][0] > last:
            continue
        if any(seq[i0 + k][1] != names[k] for k in range(len(names))):
            continue
        lo, hi = -INF, INF
        for k, j in enumerate(mine):
            ns, _name, r = seq[i0 + k]
            # Whole ns: a float holds today's unix ns only to 256 ns.
            start = round(modules[j][1])
            end = round(modules[j][1] + modules[j][2])
            lo = max(lo, ns - start)
            hi = min(hi, done_by(records[r], ns) - end)
            if lo > hi:
                break
        else:
            if hi < INF:  # a read-back among them has to close the bracket
                found.append((i0, lo, hi))
    if len(found) != 1:
        return None
    i0, lo, hi = found[0]
    return {
        "pairs": [(j, seq[i0 + k][2]) for k, j in enumerate(mine)],
        "offset_ns": [lo, hi],
        "other": len(modules) - len(mine),
    }


def joined(ctx) -> Optional[Dict]:
    """:func:`match` for one run, from ``ctx.trace`` and the engine's
    ``/debug/windows`` payload, kept on ``ctx.got`` for the next reader."""
    if ctx.trace is None or not ctx.trace.get("modules"):
        return None
    if "join" not in ctx.got:
        ctx.got["join"] = match(
            ctx.trace["modules"], ctx.got.get("windows") or {})
    return ctx.got["join"]


def offset_ns(got: Dict) -> int:
    """The middle of the bracket, a whole number."""
    lo, hi = got["offset_ns"]
    return (lo + hi) // 2


def idle_intervals(modules: List[List]) -> List[Tuple[float, float]]:
    """The stretches between programs in which the device ran none, on the
    trace's clock."""
    out = []
    end = None
    for _name, start, dur, _inside in sorted(modules, key=lambda m: m[1]):
        if end is not None and start > end:
            out.append((end, start))
        end = start + dur if end is None else max(end, start + dur)
    return out


def thread_phases(payload: Dict) -> List[Tuple[str, int, int]]:
    """Every phase span of the step thread the payload holds, the records'
    and the loose ones, as (phase, start unix ns, end unix ns)."""
    spans = [tuple(p) for p in payload.get("phases") or []]
    for rec in payload.get("windows") or []:
        spans.extend(tuple(p) for p in rec.get("phases") or [])
    spans.sort(key=lambda p: p[1])
    return spans


def idle_by_phase(modules: List[List], payload: Dict,
                  offset: int) -> Dict[Optional[str], float]:
    """Seconds of inter-program idle by the phase the step thread was in
    (``None``: in no span).  The values sum to the idle between programs.
    ``offset`` is a whole number: the spans come down to the trace's
    clock, where a float is exact."""
    out: Dict[Optional[str], float] = {}
    spans = [(name, start - offset, end - offset)
             for name, start, end in thread_phases(payload)]
    i = 0
    for lo, hi in idle_intervals(modules):
        covered = 0.0
        while i < len(spans) and spans[i][2] <= lo:
            i += 1
        k = i
        while k < len(spans) and spans[k][1] < hi:
            name, start, end = spans[k]
            part = min(end, hi) - max(start, lo)
            if part > 0:
                out[name] = out.get(name, 0.0) + part / 1e9
                covered += part
            k += 1
        out[None] = out.get(None, 0.0) + max(0.0, hi - lo - covered) / 1e9
    return out
