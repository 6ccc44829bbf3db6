"""Growth of one Prometheus family over growth of another, times a scale:
a mean of a histogram (``_sum`` over ``_count``) or a hit share."""


def read(ctx, args):
    num, den = ctx.delta(args["num"]), ctx.delta(args["den"])
    if num is None or not den:
        return None
    return num / den * args.get("scale", 1.0)
