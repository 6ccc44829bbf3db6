"""The paged latent (MLA) decode kernel against the chip's memory
bandwidth: the bytes of latent cache it must read for the traced programs
that hold it (``reduce/latent_bytes.py``, from the context lengths their
flight records carry) over the published bytes/s, over the kernel's seconds
in the trace.  The 576 values a position that the algorithm needs, not the
640 lanes the device stores, counted for the very programs in the trace: a
lower bound, so the share cannot pass 100 % however the device pads unless
the join is wrong.  None where the trace holds no operation of that name (a
program from before the kernel, or a model without a latent cache)."""

from harness.sizes import held
from reduce import join
from reduce.latent_bytes import decode_read_bytes


def read(ctx, args):
    got = join.joined(ctx)
    if got is None:
        return None
    marker = args["marker"]
    seconds = sum(s for name, s, _n in ctx.trace["ops"] if name == marker)
    if not seconds:
        return None
    hp = held(ctx.config)
    records = ctx.got["windows"]["windows"]
    matched = dict(got["pairs"])
    total = 0.0
    for j, (_name, _start, _dur, inside) in enumerate(ctx.trace["modules"]):
        calls = inside.get(marker)
        if not calls:
            continue
        if j not in matched:
            return None   # a program with the kernel that no record owns
        total += decode_read_bytes(
            hp, records[matched[j]]["kv_tokens"],
            calls / hp["num_hidden_layers"], args.get("kv_dtype_bytes", 2))
    least_s = total / (ctx.peaks()["hbm_gbs"] * 1e9)
    return 100.0 * least_s / seconds
