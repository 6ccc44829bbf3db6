"""The builder's tool: a routed model at given widths through the compare's
own path, the reference following the engine's choice.  No cell, no entry.

    chiprun -- python3 bench/tools/flip_rate.py --layers 4 --seeds 16
    chiprun -- python3 bench/tools/flip_rate.py --seeds 4 --fault k_plus_8
    JAX_PLATFORMS=cpu python3 bench/tools/flip_rate.py --tiny --seeds 20

It writes down a configuration's file (default: hidden 2,560, 64 experts of
768, top 6, vocabulary 151,936, 4 layers, bfloat16: the widths PR 34 measured;
weights 4.75 GB) with ``compare.follow_choice``, registers a preset for it and
``tests/routed_stub.py`` as the module that serves it (``models/llama.py``'s
routed block with its choice handed back; ``--fault`` makes it a wrong one),
and calls ``harness/compare.py: run`` a seed, as ``run.py`` does for a cell:
prefill in chunks of 256 behind a cached prefix, then decode steps of two
sequences through the paged cache, against ``reference/routed.py`` in float32.
The tool holds no reference and no program of its own; it counts.

Printed, a seed and pooled: ``correct`` and the entries it was decided from
(the worst row against ``--rtol``, the largest shortfall against
``--shortfall``, the first prefill's logits with and without
``return_choice``); the share of positions where the reference alone would
have chosen otherwise, over every position of both sequences and by block;
the error ``max|a-b| / max|b|`` of every compared position; the seconds a
compare took and the device's peak memory.

Without ``--tiny`` it wants the chip and exits on any other backend.  On the
CPU the program's routed block needs
``XLA_FLAGS=--xla_disable_hlo_passes=transpose-folding`` in bfloat16: the
runtime has no bf16 x bf16 -> f32 kernel for the one expert matmul XLA folds a
transpose into ("Unsupported element type for DotThunk").
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(BENCH, "tests"), ROOT]

WIDTHS = dict(   # the routed block of ISSUE 34, by the mixtral switches
    hidden_size=2560, num_heads=28, num_kv_heads=4, head_dim=128,
    num_experts=64, num_experts_per_tok=6, intermediate_size=768,
    vocab_size=151936, sliding_window=4096, rope_theta=1.5e6)
TINY = dict(     # the drop-in test's preset (tests/test_bench.py)
    hidden_size=64, num_heads=4, num_kv_heads=2, head_dim=16,
    num_experts=16, num_experts_per_tok=6, intermediate_size=32,
    vocab_size=384, sliding_window=128, rope_theta=10000.0)
KEYS = {   # key of the file: field of the preset's ModelConfig
    "hidden_size": "hidden_size", "head_dim": "head_dim",
    "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
    "moe_num_primary_experts": "num_experts",
    "moe_num_active_primary_experts": "num_experts_per_tok",
    "moe_ffn_hidden_size": "intermediate_size",
    "sliding_window_size": "sliding_window", "vocab_size": "vocab_size",
    "rope_theta": "rope_theta", "rms_norm_eps": "rms_norm_eps"}


def stats(values) -> dict:
    values = sorted(values)
    if not values:
        return {"n": 0}
    return {"n": len(values), "min": values[0],
            "median": values[len(values) // 2], "max": values[-1]}


def one_seed(config, platform: str, seed: int) -> dict:
    import jax

    from harness import compare

    detail = {}
    t = time.monotonic()
    ok, notes, rows = compare.run(config, 1, seed, platform, ROOT, detail)
    seconds = time.monotonic() - t
    if "shortfall" not in detail:
        sys.exit(f"the compare ended before it ran: {notes}")
    flipped = detail["shortfall"] > 0   # [blocks, every position]
    return {
        "seed": seed, "correct": ok, "compared": rows,
        "all_positions": flipped.shape[1],
        "all_flipped": int(flipped.any(0).sum()),
        "all_flipped_by_block": [int(n) for n in flipped.sum(1)],
        "position_errors": [
            compare.error(a, b)
            for (_name, _where, got), want in zip(detail["got"], detail["want"])
            for a, b in zip(got, want)],
        "seconds": seconds,
        "peak_bytes": (jax.devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use"),
    }


def report(rows) -> dict:
    n_all = sum(r["all_positions"] for r in rows)
    entry = lambda name: [r["compared"][name][0] for r in rows]
    return {
        "seeds": [r["seed"] for r in rows],
        "correct": [r["correct"] for r in rows],
        "flipped_share": sum(r["all_flipped"] for r in rows) / n_all,
        "flipped_share_by_block": [
            sum(r["all_flipped_by_block"][b] for r in rows) / n_all
            for b in range(len(rows[0]["all_flipped_by_block"]))],
        "position_error": stats(
            [e for r in rows for e in r["position_errors"]]),
        "worst_row_by_seed": [
            max(v[0] for k, v in r["compared"].items()
                if k not in ("choice_shortfall", "return_choice_logits_differ"))
            for r in rows],
        "choice_shortfall_by_seed": entry("choice_shortfall"),
        "return_choice_logits_differ": entry("return_choice_logits_differ"),
        "seconds": [r["seconds"] for r in rows],
        "peak_bytes": max(r["peak_bytes"] or 0 for r in rows),
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_800_000_001)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--prompt-tokens", default=None,
                    help="two lengths, default 4400,300 (--tiny: 300,100)")
    ap.add_argument("--decode-steps", type=int, default=64)
    ap.add_argument("--rtol", type=float, default=0.03)
    ap.add_argument("--shortfall", type=float, default=0.1)
    ap.add_argument("--fault", default=None, choices=(
        "swap_near_ties", "k_plus_8", "no_renorm", "misreport"))
    ap.add_argument("--tiny", action="store_true",
                    help="the drop-in test's widths: a CPU rehearsal")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    from production_stack_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    import routed_stub
    from production_stack_tpu.engine.config import PRESETS, ModelConfig
    from production_stack_tpu.engine.models.registry import MODEL_REGISTRY

    widths = TINY if args.tiny else WIDTHS
    cfg = ModelConfig(name="routedstub-flip-rate", num_layers=args.layers,
                      dtype="bfloat16", **widths)
    PRESETS["flip-rate"] = cfg
    MODEL_REGISTRY["routedstub"] = routed_stub.module(fault=args.fault)
    sizes = {ours: getattr(cfg, theirs) for ours, theirs in KEYS.items()}
    sizes["num_hidden_layers"] = args.layers
    config = dict(
        sizes, name="flip-rate", published=sizes, reduced=[],
        model="flip-rate", compare={
            "reference": "routed", "layers": args.layers,
            "decode_steps": args.decode_steps, "logits_rtol": args.rtol,
            "prompt_tokens": [int(n) for n in (args.prompt_tokens or (
                "300,100" if args.tiny else "4400,300")).split(",")],
            "follow_choice": True, "choice_shortfall": args.shortfall,
            "preset_keys": KEYS})
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.tiny:
        sys.exit(f"JAX sees {device}: the real widths are for the chip "
                 f"(--tiny rehearses on the CPU)")
    run = {"device": device.device_kind, "platform": device.platform,
           "widths": widths, "dtype": cfg.dtype, "fault": args.fault,
           "compare": config["compare"]}
    print(json.dumps(run), flush=True)
    rows = []
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        rows.append(one_seed(config, device.platform, seed))
        print(json.dumps(report(rows[-1:])), flush=True)
    pooled = report(rows)
    out = args.out or os.path.join(
        ROOT, "chiprun_out",
        f"flip_rate-{args.layers}-{args.fault or 'sound'}.json")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "w") as f:
        json.dump({"run": run, "pooled": pooled, "seeds": rows}, f)
    print(json.dumps({"pooled": pooled}, indent=1), flush=True)


if __name__ == "__main__":
    main()
