"""The decode step against the chip's memory bandwidth: the bytes of weights
a step must stream on each chip (from shapes, ``reduce/shapes.py``) over the
published bytes/s, over the step's device time.  KV reads are left out, so
this is a lower bound on the share of the roofline and cannot pass 100 %
unless the step time leaves out part of the work.  The bytes are those of a
dense gated MLP, so the metric lists its cells in ``BENCHMARK.json``; a
configuration of another kind brings a share with a bytes function of its
own beside ``reduce/shapes.py``."""

from harness.sizes import held
from readers.trace_program import step_ms
from reduce.shapes import streamed_weight_bytes


def read(ctx, args):
    ms = step_ms(ctx, args)
    if not ms:
        return None
    spec = ctx.config["compare"]
    weight_bytes = streamed_weight_bytes(
        held(ctx.config), spec.get("quantization"), ctx.cell["chips"])
    least_ms = weight_bytes / (ctx.peaks()["hbm_gbs"] * 1e9) * 1e3
    return 100.0 * least_ms / ms
