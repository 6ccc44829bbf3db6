"""Share of the prefilled token slots that held no prompt token: 1 - new
tokens over bucket tokens, summed over the window's flight records that
carried a prefill or a chunk, percent.  The scheduler pads every prompt to
the smallest bucket that holds it.  A count, from the records alone."""


def read(ctx, args):
    records = [w for w in ctx.window_records() if w.get("bucket_tokens")]
    slots = sum(w["bucket_tokens"] for w in records)
    if not slots:
        return None
    return 100.0 * (1.0 - sum(w["new_tokens"] for w in records) / slots)
