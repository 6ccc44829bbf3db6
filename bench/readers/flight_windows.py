"""The engine's flight records (``/debug/windows``) dispatched inside the
window.  ``what``: ``seqs_per_window`` is the mean number of live sequences
per decode-carrying dispatch; ``host_gap_share`` is the host-gap seconds the
records carry over the window's seconds, in percent (host clock)."""


def read(ctx, args):
    records = ctx.window_records()
    if not records:
        return None
    if args["what"] == "seqs_per_window":
        rows = [w["rows"] for w in records if w["rows"] > 0]
        return sum(rows) / len(rows) if rows else None
    if args["what"] == "host_gap_share":
        return 100.0 * sum(w["host_gap_s"] for w in records) / ctx.got["seconds"]
    raise ValueError(f"flight_windows: unknown what={args['what']!r}")
