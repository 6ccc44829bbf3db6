"""The builder's tool: a configuration's compare alone, seed after seed,
with no servers and no cell.

    chiprun -- python3 bench/tools/compare_rows.py --config sarvam-105b-ep4 --seeds 6
    chiprun -- python3 bench/tools/compare_rows.py --config sarvam-105b-ep4 --seeds 2 --reference-dtype bfloat16
    JAX_PLATFORMS=cpu python3 bench/tools/compare_rows.py --benchmark \
        bench/tests/data/rehearsal/BENCHMARK-sarvam.json --config rehearsal-sarvam --seeds 2

It calls ``harness/compare.py: run`` with the configuration's own file, as
``run.py`` does after the servers have exited: the served module's ``prefill``
and ``decode`` at the held widths against ``reference/<compare.reference>``.
Printed a seed: ``correct``, every compared entry beside its limit, for a
configuration that follows the engine's choice the share of positions the
reference alone would have routed otherwise, the seconds and the device's
peak memory; then the largest of each entry over the seeds.

``--reference-dtype`` computes the *reference* in a lower precision (its
weights, embeddings, router scores and softmax; the module's float32 upcast
``_f32`` is replaced): ``bfloat16`` throughout, or ``float8_e4m3fn`` values
with bfloat16 arithmetic, one precision below what the configuration states:
the reading a limit has to refuse.  ``--layers`` overrides
``compare.layers``.  The tool holds no reference and no program of its own.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, ROOT]


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--config", required=True)
    p.add_argument("--benchmark", default=os.path.join(ROOT, "BENCHMARK.json"))
    p.add_argument("--seeds", type=int, default=4)
    p.add_argument("--first-seed", type=int, default=3900000001)
    p.add_argument("--layers", type=int, default=None)
    p.add_argument("--reference-dtype", default=None,
                   choices=(None, "bfloat16", "float8_e4m3fn"))
    args = p.parse_args()

    with open(args.benchmark) as f:
        bench = json.load(f)
    root = os.path.dirname(os.path.abspath(args.benchmark))
    entry = next(c for c in bench["configs"] if c["name"] == args.config)
    path = next(q for q in (os.path.join(root, entry["file"]),
                            os.path.join(ROOT, entry["file"]))
                if os.path.exists(q))
    with open(path) as f:
        config = json.load(f)
    sys.path.append(os.path.join(root, bench["paths"][0]))
    if args.layers:
        config["compare"]["layers"] = args.layers
    platform = "cpu" if os.environ.get("JAX_PLATFORMS") == "cpu" else "tpu"

    from harness import compare

    import jax
    import jax.numpy as jnp

    if args.reference_dtype:
        ref = importlib.import_module(
            "reference." + config["compare"]["reference"])
        low = jnp.dtype(args.reference_dtype)
        # float8: values rounded to it, arithmetic in bfloat16 (the chip
        # multiplies no float8).
        ref._f32 = (lambda w: w.astype(low)) if low == jnp.bfloat16 else (
            lambda w: w.astype(low).astype(jnp.bfloat16))
    worst = {}
    for seed in range(args.first_seed, args.first_seed + args.seeds):
        t, detail = time.monotonic(), {}
        ok, notes, rows = compare.run(
            config, config.get("chips", 1), seed, platform, ROOT, detail)
        flipped = None
        if "shortfall" in detail:
            flipped = float((detail["shortfall"] > 0).any(0).mean())
        stats = jax.devices()[0].memory_stats() or {}
        print(json.dumps({
            "seed": seed, "correct": ok, "rows": rows,
            "flipped_share": flipped, "seconds": time.monotonic() - t,
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
            "notes": None if rows else notes}), flush=True)
        for name, (value, limit) in rows.items():
            worst[name] = [max(value, worst.get(name, [value])[0]), limit]
    print(json.dumps({"seeds": args.seeds, "worst": worst,
                      "reference_dtype": args.reference_dtype,
                      "device": str(jax.devices()[0])}), flush=True)


if __name__ == "__main__":
    main()
