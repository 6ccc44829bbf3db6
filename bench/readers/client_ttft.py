"""A percentile of the time to first token at the client (due time to first
SSE event): for what users feel but what runs too unsteadily in a cell to
carry a bound there, so it stands among the per-layer metrics.  Read in the
traced run, where /stop_profile stalls every stream for tens of seconds:
only requests due before the trace began count, so the stall is not in the
number."""

from reduce.stats import failure, percentile


def read(ctx, args):
    t0 = ctx.got["t0"]
    t1 = t0 + ctx.got["seconds"]
    cut = t1
    if ctx.got.get("trace_wall"):
        cut = min(t1, t0 + ctx.got["trace_wall"][0] - ctx.got["wall_t0"])
    ttft = [(r.first - r.due) * 1e3 for r in ctx.records
            if r.phase == "measure" and t0 <= r.due < cut
            and r.first is not None
            and failure(r, t1, ctx.got["drain_s"]) is None]
    if not ttft:
        return None
    return percentile(ttft, args["percentile"])
