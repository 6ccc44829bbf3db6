"""Time a state-pool model's stateful layers alone on the chip: what a dispatch
costs on the pools, beside what it has to move there.

    chiprun -- python tools/state_pool_microbench.py [--root _parent]

One process times one checkout (``--root``: where ``production_stack_tpu`` is
imported from, this repo by default), so parent and change go into one call.
For ``solar-open2-250b-ep8`` (3 ``kda`` layers: ``models/solar_kda.py:
_kda_prefill`` / ``_kda_decode``) and ``jamba2-3b`` (26 ``mamba`` layers:
``models/jamba.py: _mamba_prefill`` / ``_mamba_decode``), at the published
widths and the cells' 59 slots, each layer with weights and a ``(state, conv)``
pair of its own as the root's ``init_cache`` makes them: a prefill chunk of
256 and of 2,048 slots (resumed from a snapshot's slot, a snapshot left in
another) and a decode step of 16 live rows, the mixers alone (no ``o_proj``,
no FFN), the pools donated.  A line a case:

- ``us_per_layer``: device-bound wall time of the program over its layers;
- ``slot_bytes``: what the dispatch has to read and write of a layer's pools
  (the rows' slots and the snapshot's);
- ``pool_copies`` / ``pool_copy_bytes``: the ``copy`` (synchronous) and
  ``copy-start`` (asynchronous) operations of the program THAT RAN whose
  result is as large as one of its pools, and the bytes they write (as many
  are read), a layer;
- ``out_sum`` / ``state_sum``: checksums of the first call's output and of the
  written slot, for one root against another.

``--rehearse`` runs the tiny presets here on the CPU and prints no time; any
other CPU run refuses.
"""

from __future__ import annotations

import argparse
import collections
import importlib
import json
import math
import os
import re
import sys
import time

SLOTS, ROWS = 59, 16
# preset: (module, layer kind, the layer's two steps, tiny preset)
MODELS = {
    "solar-open2-250b-ep8": (
        "solar_kda", "kda", "_kda_prefill", "_kda_decode", "tiny-solar"),
    "jamba2-3b": (
        "jamba", "mamba", "_mamba_prefill", "_mamba_decode", "tiny-jamba"),
}
# What a mixer does not read: the FFN, the block's norms and W_o.
NOT_THE_MIXERS = re.compile(
    r"experts_|shared_|router|gate_proj|up_proj|down_proj|o_proj|layernorm")
_COPY = re.compile(r"= \(?(\w+)\[([\d,]*)\]\S* (copy|copy-start)\(")
_HLO_DTYPE = {"bfloat16": "bf16", "float32": "f32"}


def mixer_params(jax, jnp, model, cfg, layer_idx, key):
    """One layer's mixer weights, seeded: dense 0.02, taps 0.5, scales 1, the
    decays and step biases where the module's own initialiser puts them."""
    dtype = jnp.dtype(cfg.dtype)
    layer = {}
    for name, shape in sorted(model._shapes(cfg, layer_idx).items()):
        if NOT_THE_MIXERS.search(name):
            continue
        key, k = jax.random.split(key)
        if name.endswith("norm") or name == "D":
            layer[name] = jnp.ones(
                shape, jnp.float32 if name == "D" else dtype)
        elif name == "A_log":
            layer[name] = jnp.log(
                jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
        elif name == "dt_bias":
            layer[name] = jnp.full(shape, -4.6, jnp.float32)
        else:
            scale = 0.5 if name == "conv" else 0.02
            layer[name] = (jax.random.normal(k, shape, jnp.float32)
                           * scale).astype(dtype)
    return layer


def pool_copies(text, pools):
    """Of the compiled module ``text``, the ``copy`` (synchronous) and
    ``copy-start`` (asynchronous) operations whose result is as large as one
    of ``pools`` and of its dtype (a bitcast of a pool is still the pool):
    [(operation, index of the first such pool)], a line each."""
    which = {}
    for i, p in enumerate(pools):
        which.setdefault((_HLO_DTYPE[p.dtype.name], math.prod(p.shape)), i)
    found = []
    for line in text.splitlines():
        m = _COPY.search(line)
        if m:
            size = math.prod(map(int, filter(None, m.group(2).split(","))))
            if (m.group(1), size) in which:
                found.append((m.group(3), which[m.group(1), size]))
    return found


def main() -> None:
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--root", default=here)
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, os.path.abspath(args.root))

    import jax
    import jax.numpy as jnp
    from production_stack_tpu.engine.config import PRESETS

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.rehearse:
        sys.exit(f"no TPU here ({dev.platform}): a CPU run times nothing")

    for preset, (module, kind, prefill, decode, tiny) in MODELS.items():
        model = importlib.import_module(
            f"production_stack_tpu.engine.models.{module}")
        cfg = PRESETS[tiny if args.rehearse else preset]
        stateful = [i for i in range(cfg.num_layers)
                    if cfg.layer_kind(i) == kind]
        keys = jax.random.split(jax.random.PRNGKey(0), len(stateful) + 2)
        layers = [mixer_params(jax, jnp, model, cfg, i, k)
                  for i, k in zip(stateful, keys)]
        dtype = jnp.dtype(cfg.dtype)

        def fresh_caches():
            """The stateful layers' pairs as the root shapes them, every slot
            holding something."""
            tree = model.init_cache(cfg, 2, 16, state_slots=SLOTS)
            return [tuple(
                (jax.random.normal(keys[-1], a.shape, jnp.float32)
                 * 0.1).astype(a.dtype) for a in tree[i]) for i in stateful]

        slot_bytes = sum(
            math.prod(a.shape[1:]) * a.dtype.itemsize
            for a in jax.eval_shape(fresh_caches)[0])
        cases = [(f"prefill-{T}", T) for T in (
            (64,) if args.rehearse else (256, 2048))] + [("decode-16", 0)]
        for name, T in cases:
            n = T or ROWS
            x = (jax.random.normal(keys[-2], (n, cfg.hidden_size))
                 ).astype(dtype)
            # The slots are the program's arguments, as the engine's are: a
            # constant slot is a static slice and lowers to another program.
            if T:
                valid = T - 7
                named = jnp.asarray(       # slot, from, snapshot slot, at
                    [3, 20, 21, (valid - 1) // 64 * 64, valid], jnp.int32)
                step = lambda layer, cache, x, named: getattr(model, prefill)(
                    layer, cfg, cache, x, jnp.arange(T) < named[4], named[4],
                    tuple(named[:4]))
                moved = 3 * slot_bytes      # one slot read, two written
            else:
                named = jnp.arange(1, ROWS + 1, dtype=jnp.int32)
                step = lambda layer, cache, x, named: getattr(model, decode)(
                    layer, cfg, cache, x, named > 0, named)
                moved = 2 * ROWS * slot_bytes

            def program(layers, caches, x, named):
                outs, new = [], []
                for layer, cache in zip(layers, caches):
                    out, cache, *_ = step(layer, cache, x, named)
                    outs.append(jnp.sum(out.astype(jnp.float32)))
                    new.append(cache)
                return jnp.stack(outs), new

            fn = jax.jit(program, donate_argnums=(1,))
            caches = fresh_caches()
            pools = caches[0]
            compiled = fn.lower(layers, caches, x, named).compile()
            copies = pool_copies(compiled.as_text(), pools)
            outs, caches = compiled(layers, caches, x, named)
            line = {
                "root": args.root, "device": dev.device_kind,
                "preset": cfg.name, "case": name, "layers": len(layers),
                "pools": [list(p.shape) for p in pools],
                "slot_bytes": moved,
                "pool_copies": dict(collections.Counter(
                    op for op, _ in copies)),
                "pool_copy_bytes": sum(
                    pools[i].nbytes for _, i in copies) // len(layers),
                "out_sum": float(outs[0]),
                "state_sum": float(jnp.sum(jnp.abs(
                    caches[0][0][3 if T else 1]))),
            }
            if not args.rehearse:
                jax.block_until_ready(caches)
                t0 = time.perf_counter()
                for _ in range(args.iters):
                    outs, caches = compiled(layers, caches, x, named)
                jax.block_until_ready((outs, caches))
                line["us_per_layer"] = round(
                    (time.perf_counter() - t0) / args.iters / len(layers)
                    * 1e6, 2)
            print(json.dumps(line), flush=True)
            del caches, compiled


if __name__ == "__main__":
    main()
