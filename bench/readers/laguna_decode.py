"""A ``laguna`` configuration's decode step: pages of the full layers, rolling
buffers of the window layers and routed experts in one step, and each of the
three against the HBM roofline.  From the flight records (``rows``, ``k``,
``kv_tokens``, ``kv_tokens_slots``, ``experts_touched``), the engine's
counters (``tpu:state_*``) and, for the device metrics, the traced programs
joined to their records (``reduce/join.py``); the bytes are
``reduce/laguna_bytes.py``'s.  ``what``:

``step_bw_share``: the bytes the traced ``program``s must move (a step: the
non-expert weights held; + the records' ``experts_touched`` x one expert; +
the full layers' pages for ``kv_tokens - kv_tokens_slots`` positions a layer;
+ the window layers' buffers for ``kv_tokens_slots`` a layer) over the
published bytes/s, over their device seconds, percent: the whole decode step's
share of the HBM roofline.
``routed_bw_share``: of the same programs and seconds, the sparse layers' part
of those bytes alone (touched experts, routers, shared experts): the part of
``step_bw_share`` that the routed FFN accounts for.
``paged_bw_share`` / ``window_bw_share``: calls of the kernel named ``marker``
in the traced programs x what one call must read (a full layer's pages; a
window layer's buffers) by their records, over the published bytes/s, over
the kernel's seconds in the trace, percent.
``touched_share``: held experts with at least one row over (held experts x
sparse layers x decode steps) of the window's decode records, percent.
``window_positions_share``: positions a window layer attended over what it
would attend holding the whole context (``kv_tokens_slots`` over ``kv_tokens -
kv_tokens_slots`` of the window's decode records: the arithmetic of
``tpu:attn_positions_total{kind}``, whose label sets the harness's scrape
adds up), percent; 100 says the window is not engaged.
``resume_share``: admissions that started their window layers from a snapshot
over admissions with a cached prefix, over the window, percent.

None where the configuration is not a ``laguna`` one, or the records, the
counters or the trace hold nothing to read (a program without the window's
own kernel name or ``kv_tokens_slots``)."""

from harness.sizes import held
from readers.routed_decode import _traced
from readers.solar_decode import _by_marker
from reduce import laguna_bytes as lb


def _decodes(ctx):
    return [w for w in ctx.window_records() if w["rows"] and w.get("k")]


def read(ctx, args):
    what = args["what"]
    if ctx.config.get("published", {}).get("model_type") != "laguna":
        return None
    hp = held(ctx.config)
    if what == "resume_share":
        resumed = ctx.delta("tpu:state_resumes_total")
        missed = ctx.delta("tpu:state_resume_miss_total")
        if resumed is None or missed is None or not resumed + missed:
            return None
        return 100.0 * resumed / (resumed + missed)
    if what == "touched_share":
        records = [w for w in _decodes(ctx) if w.get("moe_assigned")]
        if not records:
            return None
        slots = hp["num_experts"] * lb.sparse_layers(hp) * sum(
            w["k"] for w in records)
        return 100.0 * sum(w["experts_touched"] for w in records) / slots
    if what == "window_positions_share":
        records = [w for w in _decodes(ctx) if w.get("kv_tokens_slots")]
        whole = sum(w["kv_tokens"] - w["kv_tokens_slots"] for w in records)
        if not whole:
            return None
        return 100.0 * sum(w["kv_tokens_slots"] for w in records) / whole
    peak_bytes = lambda: ctx.peaks()["hbm_gbs"] * 1e9   # the device's: late
    if what in ("step_bw_share", "routed_bw_share"):
        traced = _traced(ctx, args["program"])
        if not traced or any(
                "experts_touched" not in w or "kv_tokens_slots" not in w
                for _ns, w in traced):
            return None
        total = sum(
            lb.decode_step_bytes(hp, w) if what == "step_bw_share" else
            lb.routed_bytes(hp, w["experts_touched"], w["k"])
            for _ns, w in traced)
        seconds = sum(ns for ns, _w in traced) / 1e9
        return 100.0 * total / peak_bytes() / seconds
    got = _by_marker(ctx, args["marker"])
    if got is None:
        return None
    seconds, held_by = got
    if any("kv_tokens_slots" not in rec for _calls, rec in held_by):
        return None
    if what == "paged_bw_share":
        layers = lb.layers_of(hp, "full_attention")
        total = sum(lb.paged_read_bytes(
            hp, rec["kv_tokens"], rec["kv_tokens_slots"], calls / layers)
            for calls, rec in held_by)
    elif what == "window_bw_share":
        layers = lb.layers_of(hp, "sliding_attention")
        total = sum(lb.window_read_bytes(
            hp, rec["kv_tokens_slots"], calls / layers)
            for calls, rec in held_by)
    else:
        raise ValueError(f"laguna_decode: unknown what={what!r}")
    return 100.0 * total / peak_bytes() / seconds
