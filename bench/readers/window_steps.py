"""How long the engine planned its decode windows, from the flight records
(``/debug/windows``) dispatched inside the window: ``k``, the steps a window
was planned to run, and ``cut``, what set it (``cap``: the configured window;
``finish``: the first row's last token; ``host``: the fewest steps that cover
the step thread's own pass).  Windows alone: a record that carries a ``cut``;
an engine from before the field has none, and each ``what`` then reads None.

``what``: ``mean`` is the mean ``k``; ``finish_share`` the percentage of the
windows whose ``cut`` is ``finish``."""


def read(ctx, args):
    windows = [w for w in ctx.window_records()
               if w["kind"] == "decode" and w.get("cut") is not None]
    if not windows:
        return None
    if args["what"] == "mean":
        return sum(w["k"] for w in windows) / len(windows)
    if args["what"] == "finish_share":
        return 100.0 * sum(w["cut"] == "finish" for w in windows) / len(windows)
    raise ValueError(f"window_steps: unknown what={args['what']!r}")
