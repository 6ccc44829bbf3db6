"""Programs the engine compiled (or loaded from the persistent cache for the
first time) inside the window: compile events of ``/debug/compiles``
(the sum of its executables' counts) after minus before.  Should be 0."""


def read(ctx, args):
    return float(ctx.got["after"]["compile_events"]
                 - ctx.got["before"]["compile_events"])
