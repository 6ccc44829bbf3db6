"""``readers/routed_decode.py`` for a ``xing4_0`` configuration, whose file
names its sizes otherwise (``n_routed_experts``, ``q_lora_rank``) and whose
decode step also reads two mappings a layer (``reduce/xing_bytes.py``); and
what its residual path counted.  From the flight records (``experts_touched``,
``mhc_err_e6``: counted on the device by the served module) and, for the
device metric, the traced programs named ``program`` joined to their records.
``what``:

``bw_share``: the bytes those programs must read (the non-expert weights, the
records' ``experts_touched`` x one expert, the records' ``kv_tokens`` x the
latent's bytes a position) over the published bytes/s, over their device
seconds, percent.
``touched_share``: experts with at least one row over (experts x routed
layers x decode steps) of the window's decode records, percent.
``sinkhorn_err``: the largest ``mhc_err_e6`` of the window's records: the
worst |row sum - 1| of a mixing matrix after its last normalisation, in
millionths.

``sinkhorn_bw_share``: the bytes the normalisation kernel named ``marker``
must move for the traced programs that hold it (a matrix a live token a call,
``reduce/xing_bytes.py: sinkhorn_bytes``; the tokens from the joined records:
a window's ``rows``, a prefill's ``new_tokens``) over the published bytes/s,
over the kernel's seconds in the trace, percent.  A fraction of a percent by
design: the kernel moves hundreds of bytes a token and does twenty dependent
iterations on them; it exists to be one launch where XLA made 87.

None where the records carry no such count or the trace holds no such
program: an engine from before the counters, or a model with one stream.
"""

from harness.sizes import held
from readers.routed_decode import _traced
from reduce import join
from reduce.latent_bytes import decode_read_bytes
from reduce.xing_bytes import (
    expert_bytes, non_expert_bytes, routed_layers, sinkhorn_bytes,
)


def read(ctx, args):
    what = args["what"]
    hp = held(ctx.config)
    if what == "sinkhorn_err":
        errs = [w["mhc_err_e6"] for w in ctx.window_records()
                if "mhc_err_e6" in w]
        return max(errs) if errs else None
    if what == "touched_share":
        records = [w for w in ctx.window_records()
                   if w.get("moe_assigned") and w["rows"] and w.get("k")]
        if not records:
            return None
        slots = hp["n_routed_experts"] * routed_layers(hp) * sum(
            w["k"] for w in records)
        return 100.0 * sum(w["experts_touched"] for w in records) / slots
    if what == "bw_share":
        traced = _traced(ctx, args["program"])
        steps = sum(w["k"] for _ns, w in traced)
        if not steps or any("experts_touched" not in w for _ns, w in traced):
            return None
        total = steps * non_expert_bytes(hp) + sum(
            w["experts_touched"] * expert_bytes(hp)
            + decode_read_bytes(hp, w["kv_tokens"], w["k"])
            for _ns, w in traced)
        seconds = sum(ns for ns, _w in traced) / 1e9
        return 100.0 * total / (ctx.peaks()["hbm_gbs"] * 1e9) / seconds
    if what == "sinkhorn_bw_share":
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
            rec = records[matched[j]]
            total += calls * sinkhorn_bytes(
                hp, rec["rows"] or rec.get("new_tokens", 0))
        return 100.0 * total / (ctx.peaks()["hbm_gbs"] * 1e9) / seconds
    raise ValueError(f"xing_decode: unknown what={what!r}")
