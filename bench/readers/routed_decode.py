"""A routed model's decode step, from the flight records' routing counts
(``moe_assigned``, ``moe_assigned_here``, ``experts_touched``: counted on the
device by the served module, ``obs/flight_recorder.py``) and, for the two
device metrics, the traced programs named ``program`` joined to their records
(``reduce/join.py``).  ``what``:

``step_ms``: device milliseconds of those programs over the decode steps
their records planned (``k``).
``bw_share``: the bytes those programs must read (``reduce/routed_bytes.py``,
``reduce/latent_bytes.py``) over the published bytes/s, over their device
seconds, percent.
``touched_share`` / ``here_share``: counts over the window's records,
percent.

None where the records carry no routing counts or the trace holds no such
program: an engine from before the counters, or a model that routes nothing.
"""

from harness.sizes import held
from reduce import join
from reduce.latent_bytes import decode_read_bytes
from reduce.routed_bytes import expert_bytes, non_expert_bytes, routed_layers


def _traced(ctx, program):
    """[(device ns, record)] of the traced ``program``s that a record owns."""
    got = join.joined(ctx)
    if got is None:
        return []
    records = ctx.got["windows"]["windows"]
    modules = ctx.trace["modules"]
    return [(modules[j][2], records[r]) for j, r in got["pairs"]
            if modules[j][0] == program and records[r].get("k")]


def read(ctx, args):
    what = args["what"]
    hp = held(ctx.config)
    if what in ("touched_share", "here_share"):
        records = [w for w in ctx.window_records() if w.get("moe_assigned")]
        if what == "touched_share":
            records = [w for w in records if w["rows"] and w.get("k")]
        if not records:
            return None
        if what == "here_share":
            return 100.0 * sum(w["moe_assigned_here"] for w in records) / sum(
                w["moe_assigned"] for w in records)
        slots = hp["num_experts"] * routed_layers(hp) * sum(
            w["k"] for w in records)
        return 100.0 * sum(w["experts_touched"] for w in records) / slots
    traced = _traced(ctx, args["program"])
    steps = sum(w["k"] for _ns, w in traced)
    if not steps:
        return None
    seconds = sum(ns for ns, _w in traced) / 1e9
    if what == "step_ms":
        return seconds / steps * 1e3
    if what == "bw_share":
        if any("experts_touched" not in w for _ns, w in traced):
            return None
        total = steps * non_expert_bytes(hp) + sum(
            w["experts_touched"] * expert_bytes(hp)
            + decode_read_bytes(hp, w["kv_tokens"], w["k"])
            for _ns, w in traced)
        return 100.0 * total / (ctx.peaks()["hbm_gbs"] * 1e9) / seconds
    raise ValueError(f"routed_decode: unknown what={what!r}")
