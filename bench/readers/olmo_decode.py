"""An ``olmo_hybrid`` configuration's steps: the whole decode step against the
HBM roofline, the two delta-rule kernels by their names, and the state's
counter.  From the flight records (``rows``, ``k``, ``kv_tokens``,
``new_tokens``, ``gdn_state_absmax_e3``) and the traced programs joined to
their records (``reduce/join.py``); the bytes and the operations are
``reduce/olmo_bytes.py``'s.  ``what``:

``step_bw_share``: the bytes the traced ``program``s must move (a step: the
layers' weights and the head once; + the records' ``rows`` x a slot of state
and convolution rows read and written; + their ``kv_tokens`` x the softmax
layer's bytes a position) over the published bytes/s, over their device
seconds, percent: the whole decode step's share of the HBM roofline.
``kda_decode_bw_share``: calls of the kernel named ``marker`` in the traced
programs x their records' ``rows`` x one layer's state read and written, over
the published bytes/s, over the kernel's seconds in the trace, percent.
``gdn_prefill_roofline_share``: for the traced programs that hold the kernel
named ``marker``, calls x the larger of (the recurrence's operations over the
published bf16 FLOP/s) and (its bytes over the published bytes/s) for the
record's ``new_tokens``, over the kernel's seconds, percent.
``resume_share``: admissions that started their delta-rule layers from a
snapshot of the state over admissions with a cached prefix, over the window,
percent (the engine's ``tpu:state_*`` counters).
``state_absmax``: the largest ``|S|`` any dispatch of the window left in a
slot (the records' ``gdn_state_absmax_e3`` / 1000): a state that grows.

None where the configuration is not an ``olmo_hybrid`` one, or the records or
the trace hold nothing to read (a program from before the module).
"""

from harness.sizes import held
from readers.routed_decode import _traced
from readers.solar_decode import _by_marker
from reduce import olmo_bytes as ob


def read(ctx, args):
    what = args["what"]
    if ctx.config.get("published", {}).get("model_type") != "olmo_hybrid":
        return None
    if what == "resume_share":
        resumed = ctx.delta("tpu:state_resumes_total")
        missed = ctx.delta("tpu:state_resume_miss_total")
        if resumed is None or missed is None or not resumed + missed:
            return None
        return 100.0 * resumed / (resumed + missed)
    if what == "state_absmax":
        seen = [w["gdn_state_absmax_e3"] for w in ctx.window_records()
                if "gdn_state_absmax_e3" in w]
        return max(seen) / 1e3 if seen else None
    hp = held(ctx.config)
    peak_bytes = lambda: ctx.peaks()["hbm_gbs"] * 1e9   # the device's: late
    if what == "step_bw_share":
        traced = _traced(ctx, args["program"])
        steps = sum(w["k"] for _ns, w in traced)
        if not steps:
            return None
        total = steps * ob.weight_bytes(hp) + sum(
            ob.decode_read_bytes(hp, w["kv_tokens"], w["k"])
            + ob.decode_state_bytes(hp, w["rows"], w["k"])
            for _ns, w in traced)
        seconds = sum(ns for ns, _w in traced) / 1e9
        return 100.0 * total / peak_bytes() / seconds
    got = _by_marker(ctx, args["marker"])
    if got is None:
        return None
    seconds, held_by = got
    if what == "kda_decode_bw_share":
        total = sum(calls * rec["rows"] * 2 * ob.state_bytes(hp)
                    for calls, rec in held_by)
        return 100.0 * total / peak_bytes() / seconds
    if what == "gdn_prefill_roofline_share":
        peak_flops = ctx.peaks()["bf16_tflops"] * 1e12
        least = sum(
            calls * max(
                ob.recurrence_flops(hp, rec.get("new_tokens", 0)) / peak_flops,
                ob.recurrence_bytes(hp, rec.get("new_tokens", 0))
                / peak_bytes())
            for calls, rec in held_by)
        return 100.0 * least / seconds
    raise ValueError(f"olmo_decode: unknown what={what!r}")
