"""A ``solar_open2`` configuration's steps: softmax pages and recurrent state
in one decode step, the two delta-rule kernels by their names, and what the
state pool counted.  From the flight records (``experts_touched``, ``rows``,
``kv_tokens``, ``new_tokens``), the engine's counters (``tpu:state_*``) and,
for the device metrics, the traced programs joined to their records
(``reduce/join.py``).  ``what``:

``bw_share``: the bytes the traced ``program``s must move (a step: the
non-expert weights held, ``reduce/solar_bytes.py``; + the records'
``experts_touched`` x one expert; + their ``kv_tokens`` x the softmax layers'
bytes a position; + their ``rows`` x the delta-rule layers x the state and
convolution rows read and written) over the published bytes/s, over their
device seconds, percent: the whole decode step's share of the HBM roofline.
``touched_share``: held experts with at least one row over (held experts x
layers x decode steps) of the window's decode records, percent.
``kda_decode_bw_share``: calls of the kernel named ``marker`` in the traced
programs x their records' ``rows`` x one layer's state read and written, over
the published bytes/s, over the kernel's seconds in the trace, percent.
``kda_prefill_roofline_share``: for the traced programs that hold the kernel
named ``marker``, calls x the larger of (the recurrence's operations over the
published bf16 FLOP/s) and (its bytes over the published bytes/s) for the
record's ``new_tokens``, over the kernel's seconds, percent; counted from the
recurrence (``reduce/solar_bytes.py``), so that any chunk size is held to one
yardstick.
``resume_share``: admissions that started from a snapshot of the state over
admissions with a cached prefix (those, and those cut back to nothing for
want of a snapshot), over the window, percent.

None where the records, the counters or the trace hold nothing to read: an
engine from before the state pool, or a model that keeps no such state.
"""

from harness.sizes import held
from readers.routed_decode import _traced
from reduce import join
from reduce import solar_bytes as sb


def _by_marker(ctx, marker):
    """(kernel seconds in the trace, [(calls, record)] of the traced programs
    that hold the kernel), or None where there is nothing sound to read."""
    got = join.joined(ctx)
    if got is None:
        return None
    seconds = sum(s for name, s, _n in ctx.trace["ops"] if name == marker)
    if not seconds:
        return None
    records, matched = ctx.got["windows"]["windows"], dict(got["pairs"])
    held_by = []
    for j, (_name, _start, _dur, inside) in enumerate(ctx.trace["modules"]):
        calls = inside.get(marker)
        if not calls:
            continue
        if j not in matched:
            return None   # a program with the kernel that no record owns
        held_by.append((calls, records[matched[j]]))
    return seconds, held_by


def read(ctx, args):
    what = args["what"]
    if "linear_attn_config" not in ctx.config.get("published", {}):
        return None
    hp = held(ctx.config)
    if what == "resume_share":
        resumed = ctx.delta("tpu:state_resumes_total")
        missed = ctx.delta("tpu:state_resume_miss_total")
        if resumed is None or missed is None or not resumed + missed:
            return None
        return 100.0 * resumed / (resumed + missed)
    if what == "touched_share":
        records = [w for w in ctx.window_records()
                   if w.get("moe_assigned") and w["rows"] and w.get("k")]
        if not records:
            return None
        slots = hp["n_routed_experts"] * hp["num_hidden_layers"] * sum(
            w["k"] for w in records)
        return 100.0 * sum(w["experts_touched"] for w in records) / slots
    peak_bytes = lambda: ctx.peaks()["hbm_gbs"] * 1e9   # the device's: late
    if what == "bw_share":
        traced = _traced(ctx, args["program"])
        steps = sum(w["k"] for _ns, w in traced)
        if not steps or any("experts_touched" not in w for _ns, w in traced):
            return None
        total = steps * sb.non_expert_bytes(hp) + sum(
            w["experts_touched"] * sb.expert_bytes(hp)
            + sb.decode_read_bytes(hp, w["kv_tokens"], w["k"])
            + sb.decode_state_bytes(hp, w["rows"], w["k"])
            for _ns, w in traced)
        seconds = sum(ns for ns, _w in traced) / 1e9
        return 100.0 * total / peak_bytes() / seconds
    if what == "kda_decode_bw_share":
        got = _by_marker(ctx, args["marker"])
        if got is None:
            return None
        seconds, held_by = got
        total = sum(calls * rec["rows"] * 2 * sb.state_bytes(hp)
                    for calls, rec in held_by)
        return 100.0 * total / peak_bytes() / seconds
    if what == "kda_prefill_roofline_share":
        got = _by_marker(ctx, args["marker"])
        if got is None:
            return None
        seconds, held_by = got
        peak_flops, peak_bw = ctx.peaks()["bf16_tflops"] * 1e12, peak_bytes()
        least = sum(
            calls * max(
                sb.recurrence_flops(hp, rec.get("new_tokens", 0)) / peak_flops,
                sb.recurrence_bytes(hp, rec.get("new_tokens", 0)) / peak_bw)
            for calls, rec in held_by)
        return 100.0 * least / seconds
    raise ValueError(f"solar_decode: unknown what={what!r}")
