"""Mean time to first token at the client (from the send, through the
router) minus the engine's own mean (``tpu:ttft_seconds`` over the window).
Both on host clocks; the two populations differ by the requests in flight at
the window's edges."""


def read(ctx, args):
    t0 = ctx.got["t0"]
    t1 = t0 + ctx.got["seconds"]
    mine = [r.first - r.sent for r in ctx.records
            if r.phase == "measure" and r.first is not None
            and t0 <= r.first < t1]
    total = ctx.delta("tpu:ttft_seconds_sum")
    count = ctx.delta("tpu:ttft_seconds_count")
    if not mine or not count:
        return None
    return (sum(mine) / len(mine) - total / count) * 1e3
