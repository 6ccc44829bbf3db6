"""Device milliseconds per model step of the programs that hold a given
kernel, from the trace.  The step programs have no names of their own in
today's trace, so a program counts as a decode step where the operations
inside it include ``marker`` (the paged decode kernel) and none of
``without`` (the prefill kernel).  A K-step window calls the kernel K times a
layer, so steps = kernel calls / layers, whatever K is: the layers the chip
holds (``harness/sizes.py``), not the published depth.  A mean over the
batch sizes the traced span happened to hold."""

from harness.sizes import held


def step_ms(ctx, args):
    if ctx.trace is None:
        return None
    layers = held(ctx.config)["num_hidden_layers"]
    seconds = calls = 0.0
    for _program, _start, dur, inside in ctx.trace["modules"]:
        if inside.get(args["marker"]) and not any(
                inside.get(k) for k in args.get("without", [])):
            seconds += dur / 1e9
            calls += inside[args["marker"]]
    if not calls:
        return None
    return seconds / (calls / layers) * 1e3


def read(ctx, args):
    return step_ms(ctx, args)
