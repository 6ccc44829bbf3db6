"""The engine's flight records (``/debug/windows``) dispatched inside the
window.  ``what``: ``seqs_per_window`` is the mean number of live sequences
per decode-carrying dispatch."""


def read(ctx, args):
    records = ctx.window_records()
    if not records:
        return None
    if args["what"] == "seqs_per_window":
        rows = [w["rows"] for w in records if w["rows"] > 0]
        return sum(rows) / len(rows) if rows else None
    raise ValueError(f"flight_windows: unknown what={args['what']!r}")
