"""Share of the traced span in which no operation ran on the device (mean
over the chips), percent."""


def read(ctx, args):
    if ctx.trace is None or not ctx.trace.get("window_s"):
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
