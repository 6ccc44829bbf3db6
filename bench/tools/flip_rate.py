"""The builder's tool: how often does bf16 rounding flip a routed model's
top k, and how does a position read where it did?  No cell, no entry.

    chiprun -- python3 bench/tools/flip_rate.py            # the chip, 3 seeds
    JAX_PLATFORMS=cpu python3 bench/tools/flip_rate.py --tiny --seeds 20

A ``ModelConfig`` made here from the program's mixtral switches (default: the
widths of the configuration the room was made for, 4 layers, bfloat16; weights
4.75 GB) is driven exactly as ``harness/compare.py`` drives a preset
(``compare.programs``, ``compare.drive``: prefill in chunks of 256 behind a
cached prefix, then decode steps of two sequences through the paged cache)
against ``reference/routed.py`` in float32.  A second pass of the same
programs with ``_moe_mlp`` wrapped hands back each block's chosen experts (the
wrapper repeats the router's three operations, which XLA merges with the
program's own; the logits of both passes are compared bit for bit), the
reference gives its own, and a position has **flipped** where the two sets
differ in any block.

Printed, a seed and pooled: the flipped share p over every position of both
sequences and over the compared positions alone, by block; the error
``max|a-b| / max|b|`` of the compared positions that flipped and of those that
did not, and whether the two populations overlap; the device's peak memory
after the engine's passes and after the reference's; and for N = 50 and 130
positions the chance that a sound model has more than s N positions flipped
(the binomial tail at the pooled p and at the upper end of its 95 % interval),
which is what a rule over the share of positions would be argued from; and
how clear the reference's own choice was where the engine's differed (the
margin between the last expert in and the first one out), and how the compared
positions read against the reference when its blocks follow the engine's
choice (``tapped_reference``, ``forced``): the comparison that no near-tie can
turn.

Without ``--tiny`` it wants the chip and exits on any other backend.  On the
CPU the program's routed block needs
``XLA_FLAGS=--xla_disable_hlo_passes=transpose-folding`` in bfloat16: the
runtime has no bf16 x bf16 -> f32 kernel for the one expert matmul XLA folds a
transpose into ("Unsupported element type for DotThunk").
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from unittest import mock

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]

WIDTHS = dict(   # the routed block of ISSUE 34, by the mixtral switches
    hidden_size=2560, num_heads=28, num_kv_heads=4, head_dim=128,
    num_experts=64, num_experts_per_tok=6, intermediate_size=768,
    vocab_size=151936, sliding_window=4096, rope_theta=1.5e6)
TINY = dict(     # the drop-in test's preset (tests/test_bench.py)
    hidden_size=64, num_heads=4, num_kv_heads=2, head_dim=16,
    num_experts=16, num_experts_per_tok=6, intermediate_size=32,
    vocab_size=384, sliding_window=128, rope_theta=10000.0)


MARGINS = (0.02, 0.05, 0.1)   # standard deviations of a position's router logits


def tapped_programs(model, cfg, seen):
    """``compare.programs`` whose every call also appends the experts each
    block chose, [blocks, rows, k], to ``seen``."""
    import jax
    import jax.numpy as jnp

    chosen, inner = [], model._moe_mlp

    def spy(layer, x, cfg_):
        logits = jnp.dot(x, layer["gate"], preferred_element_type=jnp.float32)
        chosen.append(jax.lax.top_k(
            jax.nn.softmax(logits, axis=-1), cfg_.num_experts_per_tok)[1])
        return inner(layer, x, cfg_)

    def with_choice(step):
        def fn(*args):
            chosen.clear()
            model._moe_mlp = spy
            try:
                out, kv = step(*args)
            finally:
                model._moe_mlp = inner
            return out, kv, jnp.stack(chosen)
        return fn

    def keep(jitted):
        def call(*args):
            out, kv, who = jitted(*args)
            seen.append(who)
            return out, kv
        return call

    prefill = jax.jit(with_choice(
        lambda p, t, c, pre, new, v, kv: model.prefill(
            p, cfg, t, c, pre, new, v, kv)), donate_argnums=(6,))
    decode = jax.jit(with_choice(
        lambda p, t, pos, bt, cl, sb, so, kv: model.decode(
            p, cfg, t, pos, bt, cl, sb, so, kv)), donate_argnums=(7,))
    return keep(prefill), keep(decode)


def tapped_reference(hp):
    """``routed.hidden``, jitted, with ``_route`` wrapped: it also returns
    the experts each block chose [blocks, T, top] and how clear the choice
    was [blocks, T], the router logit of the last expert in less that of the
    first one out, in standard deviations of the position's logits.
    ``forced`` [blocks, T, top] is another computation's choice, which the
    blocks then follow, its shares renormalised the same way; the margin is
    then how far its weakest expert lies below the reference's last one in:
    0 where they agree."""
    import jax
    import jax.numpy as jnp

    from reference import routed

    inner = routed._route

    def fn(params, tokens, forced=None):
        chosen, margin = [], []

        def route(logits, top):
            probs = jax.nn.softmax(logits, -1)
            ranked = jnp.sort(logits, -1)
            if forced is None:
                shares, who = inner(logits, top), jax.lax.top_k(probs, top)[1]
                below = ranked[:, -top - 1]
            else:
                who = forced[len(chosen)]
                best = jnp.take_along_axis(probs, who, -1)
                rows = jnp.arange(logits.shape[0])[:, None]
                shares = jnp.zeros_like(probs).at[rows, who].set(
                    best / best.sum(-1, keepdims=True))
                below = jnp.take_along_axis(logits, who, -1).min(-1)
            chosen.append(who)
            margin.append((ranked[:, -top] - below) / jnp.std(logits, -1))
            return shares

        with mock.patch.object(routed, "_route", route):
            x = routed.hidden(params, hp, tokens)
        return x, jnp.stack(chosen), jnp.stack(margin)

    return jax.jit(fn)


def binomial_tail(n: int, p: float, more_than: int) -> float:
    """P[Bin(n, p) > more_than]."""
    return sum(math.comb(n, j) * p**j * (1 - p)**(n - j)
               for j in range(more_than + 1, n + 1))


def wilson_upper(k: int, n: int, z: float = 1.96) -> float:
    centre = (k + z * z / 2) / (n + z * z)
    return centre + z / (n + z * z) * math.sqrt(
        k * (n - k) / n + z * z / 4)


def stats(values) -> dict:
    values = sorted(values)
    if not values:
        return {"n": 0}
    return {"n": len(values), "min": values[0],
            "median": values[len(values) // 2], "max": values[-1]}


def one_seed(args, cfg, hp, seed: int) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from harness import compare
    from production_stack_tpu.engine.models import get_model
    from reference import routed

    model = get_model(cfg.name)
    device = jax.devices()[0]
    peak = lambda: (device.memory_stats() or {}).get("peak_bytes_in_use")
    t = time.monotonic()
    params = model.init_params(cfg, jax.random.PRNGKey(seed % (2**31 - 1)))
    lens, steps = args.prompt_tokens, args.decode_steps
    # The scored pass, the programs as the compare builds them; then the
    # same with each block's choice handed back.
    seqs, got = compare.drive(*compare.programs(model, cfg), params, cfg,
                              lens, steps, seed)
    seen = []
    _seqs, again = compare.drive(*tapped_programs(model, cfg, seen), params,
                                 cfg, lens, steps, seed)
    same = all(np.array_equal(a[2], b[2]) for a, b in zip(got, again))
    engine_s, engine_peak = time.monotonic() - t, peak()

    # The engine's choice by (sequence, index): the prefill chunks in order,
    # then one call a decode step with a row a sequence.
    calls = iter(seen)
    engine = [np.zeros((cfg.num_layers, len(s), cfg.num_experts_per_tok),
                       np.int32) for s in seqs]
    for seq, n in enumerate(lens):
        for start in range(0, n, compare.CHUNK):
            rows = min(compare.CHUNK, n - start)
            engine[seq][:, start:start + rows] = np.asarray(
                next(calls))[:, :rows]
    for step in range(steps):
        who = np.asarray(next(calls))
        for seq, n in enumerate(lens):
            engine[seq][:, n + step] = who[:, seq]

    t = time.monotonic()
    hidden = tapped_reference(hp)
    head = jax.jit(lambda p, x: routed.head(p, hp, x))
    flipped, margins, want, led, shortfall = [], [], [], [], []
    for seq, tokens in enumerate(seqs):
        lo = lens[seq] - 1
        x, who, margin = hidden(params, jnp.asarray(tokens))
        want.append((lo, np.asarray(head(params, x[lo:]))))
        flipped.append(np.any(
            np.sort(np.asarray(who), -1) != np.sort(engine[seq], -1), -1))
        margins.append(np.asarray(margin))
        # The reference again, its blocks following the engine's choice.
        x, _who, short = hidden(params, jnp.asarray(tokens),
                                jnp.asarray(engine[seq]))
        led.append((lo, np.asarray(head(params, x[lo:]))))
        shortfall.append(np.asarray(short))
    reference_s, both_peak = time.monotonic() - t, peak()

    def error(a, ref, seq, index):
        return compare.error(a, ref[seq][1][index - ref[seq][0]])

    # (error, flipped, sequence, index, error against the led reference)
    positions = [
        (error(a, want, seq, index), bool(flipped[seq][:, index].any()),
         seq, index, error(a, led, seq, index))
        for _name, where, logits in got
        for (seq, index), a in zip(where, logits)]
    every = np.concatenate(flipped, axis=1)   # [blocks, all positions]
    margin = np.concatenate(margins, axis=1)
    # How clear the reference's own choice was: where a position flipped,
    # in the first block that did (later blocks read another stream); where
    # none did, the least clear of its blocks.
    first = np.argmax(every, 0)
    at_flip = margin[first, np.arange(every.shape[1])][every.any(0)]
    least = margin.min(0)
    return {
        "seed": seed, "tapped_pass_logits_equal": same,
        "led_shortfall_max": float(np.concatenate(shortfall, axis=1).max()),
        "margin_at_first_flip": [float(m) for m in at_flip],
        "least_margin_share_under": {
            str(m): float((least < m).mean()) for m in MARGINS},
        "all_positions": every.shape[1],
        "all_flipped": int(every.any(0).sum()),
        "all_flipped_by_block": [int(n) for n in every.sum(1)],
        "positions": positions,
        "engine_s": engine_s, "reference_s": reference_s,
        "peak_bytes_after_engine": engine_peak,
        "peak_bytes_after_reference": both_peak,
    }


def report(args, rows) -> dict:
    positions = [p for r in rows for p in r["positions"]]
    flip = [e for e, f, *_ in positions if f]
    calm = [e for e, f, *_ in positions if not f]
    n_all = sum(r["all_positions"] for r in rows)
    k_all = sum(r["all_flipped"] for r in rows)
    p, p_hi = k_all / n_all, wilson_upper(k_all, n_all)
    at_flip = [m for r in rows for m in r["margin_at_first_flip"]]
    return {
        "seeds": [r["seed"] for r in rows],
        "tapped_pass_logits_equal": all(
            r["tapped_pass_logits_equal"] for r in rows),
        "p_all_positions": p, "p_upper_95": p_hi,
        "flipped_of_all": [k_all, n_all],
        "flipped_share_by_block": [
            sum(r["all_flipped_by_block"][b] for r in rows) / n_all
            for b in range(len(rows[0]["all_flipped_by_block"]))],
        "p_compared_positions": len(flip) / len(positions),
        "flipped_of_compared": [len(flip), len(positions)],
        "error_not_flipped": stats(calm), "error_flipped": stats(flip),
        "populations_overlap": bool(flip and calm and min(flip) <= max(calm)),
        # The reference's own margin between the last expert in and the first
        # out (standard deviations of the position's router logits): of the
        # flipped positions in their first flipped block, and the share of
        # all positions whose least clear block is under a margin.
        "margin_at_first_flip": dict(stats(at_flip), **{
            f"share_over_{m}": sum(x > m for x in at_flip) / max(1, len(at_flip))
            for m in MARGINS}),
        "least_margin_share_under": {
            str(m): sum(r["least_margin_share_under"][str(m)]
                        * r["all_positions"] for r in rows) / n_all
            for m in MARGINS},
        # Against the reference led by the engine's own choice: every
        # compared position, and how far below the reference's last expert
        # in the engine's weakest lay at the worst (standard deviations of
        # the position's router logits, every position and block).
        "error_led_by_engine_choice": stats([p[4] for p in positions]),
        "led_shortfall_max": max(r["led_shortfall_max"] for r in rows),
        "over_rtol_not_flipped": sum(e > args.rtol for e in calm),
        "within_rtol_flipped": sum(e <= args.rtol for e in flip),
        "over_rtol_share_by_seed": [
            sum(e > args.rtol for e, *_ in r["positions"])
            / len(r["positions"]) for r in rows],
        "peak_bytes_after_engine": max(
            r["peak_bytes_after_engine"] or 0 for r in rows),
        "peak_bytes_after_reference": max(
            r["peak_bytes_after_reference"] or 0 for r in rows),
        "engine_s": [r["engine_s"] for r in rows],
        "reference_s": [r["reference_s"] for r in rows],
        # P[a sound model has more than s N of N positions flipped]
        "binomial_tail": {
            f"N={n},s={s}": {"at_p": binomial_tail(n, p, int(s * n)),
                             "at_p_upper_95": binomial_tail(
                                 n, p_hi, int(s * n))}
            for n in (50, 130) for s in (0.15, 0.2, 0.25)},
    }


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_400_000_001)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--prompt-tokens", default=None,
                    help="two lengths, default 4400,300 (--tiny: 300,100)")
    ap.add_argument("--decode-steps", type=int, default=64)
    ap.add_argument("--rtol", type=float, default=0.03)
    ap.add_argument("--tiny", action="store_true",
                    help="the drop-in test's widths: a CPU rehearsal")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "flip_rate.json"))
    args = ap.parse_args()
    args.prompt_tokens = [int(n) for n in (args.prompt_tokens or (
        "300,100" if args.tiny else "4400,300")).split(",")]

    from production_stack_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    from production_stack_tpu.engine.config import ModelConfig

    widths = TINY if args.tiny else WIDTHS
    cfg = ModelConfig(name="mixtral-flip-rate", num_layers=args.layers,
                      dtype="bfloat16", **widths)
    hp = {"num_attention_heads": cfg.num_heads,
          "num_key_value_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
          "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.rms_norm_eps,
          "sliding_window_size": cfg.sliding_window,
          "moe_num_active_primary_experts": cfg.num_experts_per_tok,
          "vocab_size": cfg.vocab_size}
    device = jax.devices()[0]
    if device.platform != "tpu" and not args.tiny:
        sys.exit(f"JAX sees {device}: the real widths are for the chip "
                 f"(--tiny rehearses on the CPU)")
    run = {"device": device.device_kind, "platform": device.platform,
           "widths": widths, "layers": args.layers, "dtype": cfg.dtype,
           "prompt_tokens": args.prompt_tokens,
           "decode_steps": args.decode_steps}
    print(json.dumps(run), flush=True)
    rows = []
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        rows.append(one_seed(args, cfg, hp, seed))
        print(json.dumps(dict(report(args, rows[-1:]), binomial_tail=None)),
              flush=True)
    pooled = report(args, rows)
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"run": run, "pooled": pooled, "seeds": rows}, f)
    print(json.dumps({"pooled": pooled}, indent=1), flush=True)


if __name__ == "__main__":
    main()
