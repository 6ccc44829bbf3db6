"""Mean device milliseconds of the traced programs named ``program`` (the
name the engine jits and tracks it under: ``prefill_fn``, ``window_fn``).
None where the trace holds none of that name — an engine from before its
step programs had names shows them as ``_unknown``."""


def read(ctx, args):
    if ctx.trace is None:
        return None
    durs = [dur for name, _start, dur, _inside in ctx.trace["modules"]
            if name == args["program"]]
    if not durs:
        return None
    return sum(durs) / len(durs) / 1e6
