"""Who waited for whom on the device, request by request, as the engine's
flight recorder booked it (``/debug/windows``; the program's
``EngineObs._on_record_close``).  The record on which a request of two tokens
or more finished carries ``finished``: a row ``[seq_id, tokens, span_s,
own_s, prefill_s, prefills, rest_s]`` -- from the close of the record that
gave its first token to that record's own close, divided into the records
the request rode, the ``prefill`` records of other prompts that closed
meanwhile (and how many), and the remainder.  The record that carried a
request's first prefill chunk has ``behind_s``: how long, after its dispatch,
the record before it was still being collected.

``part``: ``span`` | ``own`` | ``prefill``, in ms a token after the first
(the part / (tokens - 1), request by request, before the statistic);
``behind``, a record's ``behind_s`` in ms.  ``stat``: ``mean`` | ``p50`` |
``p95``.

Only requests whose span began inside the window and closed before the
profiler started count (as ``readers/client_ttft.py`` cuts: /stop_profile
stalls every stream), and only first prefill records that closed in the same
stretch.  None where nothing qualifies: a program from before it kept the
account has neither field."""

from reduce.stats import percentile

COLUMN = {"span": 2, "own": 3, "prefill": 4}


def cut(ctx):
    lo = ctx.got["wall_t0"]
    hi = lo + ctx.got["seconds"]
    if ctx.got.get("trace_wall"):
        hi = min(hi, ctx.got["trace_wall"][0])
    return lo, hi


def values(ctx, part):
    lo, hi = cut(ctx)
    out = []
    for w in ctx.window_records():
        closed = w.get("collected_at")
        if closed is None or closed >= hi:
            continue
        if part == "behind":
            if w.get("behind_s") is not None:
                out.append(w["behind_s"] * 1e3)
            continue
        for row in w.get("finished", ()):
            tokens = row[1]
            if closed - row[2] < lo or tokens < 2:
                continue
            out.append(row[COLUMN[part]] * 1e3 / (tokens - 1))
    return out


def read(ctx, args):
    got = values(ctx, args["part"])
    if not got:
        return None
    stat = args["stat"]
    if stat == "mean":
        return sum(got) / len(got)
    return percentile(got, {"p50": 50, "p95": 95}[stat])
