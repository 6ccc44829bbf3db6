"""Of the traced span, the percent in which the device ran the programs named
``program`` (the name the engine jits and tracks them under: ``prefill_fn``,
``window_fn``): their device seconds over the span.  None without a trace or
where it holds no program of that name."""


def read(ctx, args):
    if ctx.trace is None or not ctx.trace.get("window_s"):
        return None
    seconds = [s for name, s, _n in ctx.trace["programs"]
               if name == args["program"]]
    if not seconds:
        return None
    return 100.0 * sum(seconds) / ctx.trace["window_s"]
