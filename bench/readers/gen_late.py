"""How late the generator sent: send time minus due time, a percentile over
the window's requests.  A starved generator is not a fast server."""

from reduce.stats import percentile


def read(ctx, args):
    if not ctx.late_ms:
        return None
    return percentile(ctx.late_ms, args.get("percentile", 95))
