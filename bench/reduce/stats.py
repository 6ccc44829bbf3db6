"""Request records -> end-to-end metrics.  Pure arithmetic, no clock."""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0-100), linear interpolation between order
    statistics (numpy's default)."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def highest_supported_percentile(n: int, beyond: int = 10) -> float:
    """The highest percentile with at least ``beyond`` samples above it."""
    return 0.0 if n <= beyond else 100.0 * (n - beyond) / n


def failure(rec, window_end: float, drain_s: float) -> Optional[str]:
    """Why a request counts as failed, or None."""
    if rec.status != 200:
        return f"status {rec.status}"
    if rec.error:
        return "sse error"
    if not rec.done or rec.last is None or rec.ended > window_end + drain_s:
        return "not drained"
    if rec.completion_tokens != rec.asked:
        return "short answer"
    if rec.finish_reason != "length":
        return f"finish_reason {rec.finish_reason}"
    return None


def tpot_s(rec) -> Optional[float]:
    """Seconds per output token after the first, from the first SSE event to
    the one that carried finish_reason (not the gap between events: with
    random weights most tokens have no text and so no event)."""
    if rec.completion_tokens is None or rec.completion_tokens < 2:
        return None
    return (rec.last - rec.first) / (rec.completion_tokens - 1)


def tail_mean(values: Sequence[float], share: float) -> float:
    """The mean of the slowest ``share`` of ``values``; the value at the
    edge counts by the fraction of it that lies inside, so that one request
    more or fewer moves the result smoothly."""
    xs = sorted(values, reverse=True)
    if not xs:
        raise ValueError("tail mean of no values")
    want = len(xs) * share
    whole = min(len(xs), math.floor(want))
    total = sum(xs[:whole])
    if whole < len(xs):
        total += xs[whole] * (want - whole)
    return total / want


def starts_a_user(rec) -> bool:
    """A session's first request by a user born after the cache was seeded
    (``generators/sessions.py``: ``<seat>.<born>`` at round 0): the whole
    history is new behind the shared system prompt."""
    user = str(rec.meta.get("user", ""))
    return rec.meta.get("round") == 0 and not user.endswith(".0")


def summarize(records: List, t0: float, seconds: float,
              drain_s: float) -> Dict:
    """End-to-end numbers of one window [t0, t0 + seconds).

    attempted: requests due inside the window.  failed: see :func:`failure`.
    Tails are over the attempted requests that succeeded; ``out_tok_s`` is
    over every request that finished inside the window, whenever it was
    due, so that work started in the pre-roll balances work cut off at the
    end."""
    t1 = t0 + seconds
    attempted = [r for r in records
                 if r.phase == "measure" and t0 <= r.due < t1]
    reasons: Dict[str, int] = {}
    ok = []
    for r in attempted:
        why = failure(r, t1, drain_s)
        if why is None:
            ok.append(r)
        else:
            reasons[why] = reasons.get(why, 0) + 1
    finished_inside = [
        r for r in records
        if r.phase == "measure" and r.last is not None and t0 <= r.last < t1
        and r.completion_tokens
    ]
    ttft = [(r.first - r.due) * 1e3 for r in ok]
    tpot = [t * 1e3 for t in (tpot_s(r) for r in ok) if t is not None]
    out = {
        "attempted": len(attempted),
        "failed": len(attempted) - len(ok),
        "failure_reasons": reasons,
        "samples": {"ttft": len(ttft), "tpot": len(tpot),
                    "finished_inside": len(finished_inside)},
        "highest_supported_percentile": {
            "ttft": highest_supported_percentile(len(ttft)),
            "tpot": highest_supported_percentile(len(tpot)),
        },
        "late_ms": [(r.sent - r.due) * 1e3 for r in attempted
                    if r.sent is not None],
        "metrics": {},
    }
    m = out["metrics"]
    if ttft:
        m["ttft_mean_ms"] = sum(ttft) / len(ttft)
        m["ttft_p50_ms"] = percentile(ttft, 50)
        m["ttft_p90_ms"] = percentile(ttft, 90)
        m["ttft_p95_ms"] = percentile(ttft, 95)
        m["ttft_slow10_mean_ms"] = tail_mean(ttft, 0.1)
    new_users = [(r.first - r.due) * 1e3 for r in ok if starts_a_user(r)]
    if new_users:
        m["ttft_new_user_p50_ms"] = percentile(new_users, 50)
    if tpot:
        m["tpot_p95_ms"] = percentile(tpot, 95)
    m["out_tok_s"] = sum(r.completion_tokens for r in finished_inside) / seconds
    return out
