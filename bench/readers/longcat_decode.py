"""A ``longcat`` configuration's decode step: two latent attentions, two dense
FFNs and one routed FFN a layer, some of whose picks are identity experts.
From the flight records (``k``, ``kv_tokens``, ``experts_touched``,
``moe_assigned``, ``moe_zero_assigned``: counted on the device by the served
module) and, for the device metrics, the traced programs joined to their
records (``reduce/join.py``); the bytes are ``reduce/longcat_bytes.py``'s.
``what``:

``bw_share``: the bytes the traced ``program``s must move (a step: the
non-expert weights held; + the records' ``experts_touched`` x one expert; + the
records' ``kv_tokens`` x the latent's bytes a position over all eight arrays)
over the published bytes/s, over their device seconds, percent: the whole
decode step's share of the HBM roofline.
``latent_bw_share``: calls of the kernel named ``marker`` in the traced
programs x what one call must read (one array's latent pages for the record's
``kv_tokens``), over the published bytes/s, over the kernel's seconds in the
trace, percent.
``touched_share``: held experts with at least one row over (held experts x
layers x decode steps) of the window's decode records, percent.
``zero_share``: picks that named an identity expert over all picks
(``moe_zero_assigned`` / ``moe_assigned``) of the window's records, prefill
and decode, percent.

None where the configuration is not a ``longcat`` one (its source has no
``zero_expert_num``), or the records or the trace hold nothing to read (an
engine from before the counter, a program without the kernel)."""

from harness.sizes import held
from readers.routed_decode import _traced
from reduce import join
from reduce import longcat_bytes as lb


def read(ctx, args):
    what = args["what"]
    if "zero_expert_num" not in ctx.config.get("published", {}):
        return None
    hp = held(ctx.config)
    if what == "zero_share":
        records = [w for w in ctx.window_records()
                   if w.get("moe_assigned") and "moe_zero_assigned" in w]
        if not records:
            return None
        return 100.0 * sum(w["moe_zero_assigned"] for w in records) / sum(
            w["moe_assigned"] for w in records)
    if what == "touched_share":
        records = [w for w in ctx.window_records()
                   if w.get("moe_assigned") and w["rows"] and w.get("k")]
        if not records:
            return None
        slots = hp["n_routed_experts"] * hp["num_layers"] * sum(
            w["k"] for w in records)
        return 100.0 * sum(w["experts_touched"] for w in records) / slots
    peak_bytes = lambda: ctx.peaks()["hbm_gbs"] * 1e9   # the device's: late
    if what == "bw_share":
        traced = _traced(ctx, args["program"])
        if not traced or any("experts_touched" not in w for _ns, w in traced):
            return None
        total = sum(lb.decode_step_bytes(hp, w) for _ns, w in traced)
        seconds = sum(ns for ns, _w in traced) / 1e9
        return 100.0 * total / peak_bytes() / seconds
    if what == "latent_bw_share":
        got, marker = join.joined(ctx), args["marker"]
        if got is None:
            return None
        seconds = sum(s for name, s, _n in ctx.trace["ops"] if name == marker)
        if not seconds:
            return None
        records, matched = ctx.got["windows"]["windows"], dict(got["pairs"])
        total = 0.0
        for j, (_name, _start, _dur, inside) in enumerate(
                ctx.trace["modules"]):
            calls = inside.get(marker)
            if not calls:
                continue
            if j not in matched:
                return None   # a program with the kernel that no record owns
            total += lb.latent_read_bytes(
                hp, records[matched[j]]["kv_tokens"],
                calls / lb.cache_arrays(hp))
        return 100.0 * total / peak_bytes() / seconds
    raise ValueError(f"longcat_decode: unknown what={what!r}")
