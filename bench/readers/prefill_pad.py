"""Share of the prefilled token slots that held no prompt token: 1 - new
tokens over bucket tokens, summed over the window's flight records that
carried a prefill or a chunk, percent.  The scheduler covers a prompt with
the cheapest run of the prefill programs it has (``cover_prefill``, PR 32):
full chunks and one padded chunk, each a record of its own, so the padding
left is that of the last chunk of every run.  A count, from the records
alone."""


def read(ctx, args):
    records = [w for w in ctx.window_records() if w.get("bucket_tokens")]
    slots = sum(w["bucket_tokens"] for w in records)
    if not slots:
        return None
    return 100.0 * (1.0 - sum(w["new_tokens"] for w in records) / slots)
