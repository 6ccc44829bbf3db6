"""Time the residual path's mapping (models/sarvam_mla.py: _sub_layer, the
manifold-constrained hyper-connection of ``hc_mult`` streams) alone on the
chip, at the shapes the xing4.0-29b-a4b-stage cell runs it at: 16 rows (a
decode step, 8 steps a program as the K-step window holds them) and 256 and
2,048 slots (the two prefill programs).

    chiprun -- env PYTHONPATH=. python tools/mhc_microbench.py [--root _parent]

A program is the preset's 12 mappings (6 layers x 2 sub-layers) chained over
streams [T, 4, 3584] in bfloat16 with each sub-layer's function the identity:
the float32 norm of the flattened streams, the 24-column product at the
highest precision, sigmoid and exp, the 20 unrolled normalisations, the
mix-in ``H_pre X`` and the mix-out ``H_res X + H_post y``.  Printed a shape:
ms a program, us a mapping, the bytes a mapping must move at the least (the
streams read once and written once, its float32 ``W``) as a share of 819
GB/s, and the compiled program's operations by kind; the programs' text goes
to ``chiprun_out/mhc_microbench/``.  Between real sub-layers XLA cannot fuse
one mapping's mix-out with the next one's norm as it may here, so the served
cost is this or a little more; the cell's traced runs give the served figure
(the scope ``mhc`` in the profile).  ``PSTPU_DISABLE_PALLAS=1`` times the plain
XLA form of the normalisation in place of the kernel.  A CPU run refuses: a
time comes from the chip alone.

My chip run, PR 44 (one call, kernel then XLA form):

    slots (steps)   kernel                       XLA form (87 fusions a mapping)
    16 (8)          0.087 ms a step,  7.3 us     0.102 ms a step,  8.5 us a mapping
    256 (1)         0.61 ms a program, 51 us     0.66 ms a program, 55 us
    2,048 (1)       8.6 ms a program, 717 us     11.9 ms a program, 989 us

(38 %, 38 % and 20 % of 819 GB/s for the streams read and written once with
the kernel).  The kernel is there for compile time: the XLA form costs 2 s of
compilation a mapping (PERF.md section 6, PR 44).
"""

import argparse
import collections
import json
import os
import re
import sys
import time


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--root", default=".",
                   help="the checkout the module is imported from")
    p.add_argument("--repeat", type=int, default=30)
    p.add_argument("--preset", default="xing4.0-29b-a4b-stage")
    args = p.parse_args()
    sys.path.insert(0, os.path.abspath(args.root))

    import jax
    import jax.numpy as jnp

    from production_stack_tpu.engine.config import PRESETS
    from production_stack_tpu.engine.models import sarvam_mla as m

    if jax.default_backend() != "tpu":
        raise SystemExit("mhc_microbench: needs the chip")
    cfg = PRESETS[args.preset]
    n, d = cfg.hc_mult, cfg.hidden_size
    subs = [(i, sub) for i in range(cfg.num_layers) for sub in ("attn", "ffn")]
    keys = jax.random.split(jax.random.PRNGKey(0), len(subs))
    cols = 2 * n + n * n
    layers = [{} for _ in range(cfg.num_layers)]
    for (i, sub), key in zip(subs, keys):
        layers[i].update({
            f"hc_{sub}_w": jax.random.normal(key, (n * d, cols)) * (n * d)**-.5,
            f"hc_{sub}_alpha": jnp.array([1.0, 1.0, m.HC_RES_SPREAD]),
            f"hc_{sub}_bias": jax.random.normal(key, (cols,)) * 0.5})
    out_dir = os.path.join("chiprun_out", "mhc_microbench")
    os.makedirs(out_dir, exist_ok=True)

    def mappings(X, live):
        for i, sub in subs:
            X, _counted = m._sub_layer(layers[i], cfg, sub, X, live,
                                       lambda h: h)
        return X

    for T, steps in ((16, 8), (256, 1), (2048, 1)):
        def program(X, live):
            return jax.lax.scan(
                lambda x, _: (mappings(x, live), None), X, None,
                length=steps)[0]

        X = (jax.random.normal(jax.random.PRNGKey(1), (T, n, d))
             ).astype(jnp.bfloat16)
        live = jnp.ones((T,), bool)
        compiled = jax.jit(program).lower(X, live).compile()
        text = compiled.as_text()
        with open(os.path.join(out_dir, f"T{T}.hlo.txt"), "w") as f:
            f.write(text)
        ops = collections.Counter(
            match.group(1) for match in re.finditer(
                r"^\s+\S+ = \S+ (\w[\w-]*)\(", text, re.M))
        compiled(X, live).block_until_ready()
        t = time.perf_counter()
        for _ in range(args.repeat):
            got = compiled(X, live)
        got.block_until_ready()
        seconds = (time.perf_counter() - t) / args.repeat
        calls = steps * len(subs)
        least = 2 * T * n * d * 2 + n * d * cols * 4
        print(json.dumps({
            "slots": T, "steps_a_program": steps, "mappings": calls,
            "ms_a_program": seconds * 1e3,
            "ms_a_step": seconds * 1e3 / steps,
            "us_a_mapping": seconds * 1e6 / calls,
            "least_bytes_a_mapping": least,
            "share_of_819_GBs": least * calls / 819e9 / seconds,
            "ops": dict(ops.most_common(8)),
            "finite": bool(jnp.isfinite(got.astype(jnp.float32)).all()),
            "device": str(jax.devices()[0])}), flush=True)


if __name__ == "__main__":
    main()
