"""Of the traced span, the percent in which no program ran on the device
(the gaps between the trace's programs) AND the engine's step thread was in
one of ``phases`` (``null``: in no span at all).  The step thread's spans
ride the flight records on the host's clock; ``reduce/join.py`` puts them
on the trace's, at the middle of the offset's bracket.  One reader, five
metrics: schedule; build + launch; collect + sample + emit; wait; none.
They sum to the idle between programs, which is ``device_idle_share`` less
the idle inside programs."""

from reduce import join


def read(ctx, args):
    got = join.joined(ctx)
    if got is None or not ctx.trace.get("window_s"):
        return None
    if "idle_by_phase" not in ctx.got:
        ctx.got["idle_by_phase"] = join.idle_by_phase(
            ctx.trace["modules"], ctx.got["windows"], join.offset_ns(got))
    idle = ctx.got["idle_by_phase"]
    phases = args["phases"]
    seconds = (idle.get(None, 0.0) if phases is None
               else sum(idle.get(p, 0.0) for p in phases))
    return 100.0 * seconds / ctx.trace["window_s"]
