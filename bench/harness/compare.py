"""Part (ii) of ``correct``: the engine's model code against the plain
reference, logits to logits, in this process after the servers have exited.

At the configuration's published widths and ``compare.layers`` layers,
seeded random weights in the configuration's own weight format and mesh:
prefill of one prompt in two chunks (the second attends to a cached
prefix), prefill of a second prompt, then decode steps of both through the
paged cache -- the engine's ``llama.prefill`` / ``llama.decode`` with the
kernels it serves with -- against ``reference/<module>.forward`` over the
whole sequence.  Tokens are not compared: with random weights the largest
logit turns on rounding.  The driving code is copied from ``chip_smoke.py``
(PR 21), which compared kernels with the XLA path, not with a reference.
"""

from __future__ import annotations

import dataclasses
import importlib
import os
import sys
from typing import Dict, List, Tuple


def run(config: Dict, chips: int, seed: int, platform: str,
        env_root: str) -> Tuple[bool, List[str]]:
    sys.path.insert(0, env_root)
    if platform == "cpu":
        for k, v in config.get("rehearsal_env", {}).items():
            os.environ.setdefault(k, v)
    from production_stack_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding

    from production_stack_tpu.engine.config import PRESETS, ParallelConfig
    from production_stack_tpu.engine.models import llama
    from production_stack_tpu.engine.parallel import shardings as sh
    from production_stack_tpu.engine.parallel.mesh import build_mesh

    spec = config["compare"]
    notes = []
    if jax.default_backend() != platform or len(jax.devices()) < chips:
        return False, [f"the parent's JAX sees {jax.devices()}"]
    cfg = dataclasses.replace(
        PRESETS[config["model"]], num_layers=spec["layers"],
        quantization=spec.get("quantization"))
    reference = importlib.import_module("reference." + spec["reference"])
    hp = dict(config["published"], head_dim=cfg.head_dim)
    for ours, theirs in (("hidden_size", cfg.hidden_size),
                         ("num_attention_heads", cfg.num_heads),
                         ("num_key_value_heads", cfg.num_kv_heads),
                         ("intermediate_size", cfg.intermediate_size),
                         ("vocab_size", cfg.vocab_size),
                         ("sliding_window", cfg.sliding_window)):
        if hp.get(ours) != theirs:
            return False, [f"preset {config['model']} has {ours}={theirs}, "
                           f"the published config {hp.get(ours)}"]

    mesh = None
    shardings = kv_sharding = None
    tp = spec.get("tensor_parallel", 1)
    if tp > 1:
        mesh = build_mesh(ParallelConfig(tensor_parallel=tp))
        shardings = sh.param_shardings(cfg, mesh)
        kv_sharding = NamedSharding(mesh, sh.kv_cache_spec())
    # 2**31 - 1 keeps any driver seed inside what PRNGKey takes.
    params = llama.init_params(
        cfg, jax.random.PRNGKey(seed % (2**31 - 1)), shardings)
    if mesh is None:
        params = llama.quantize_params(params, cfg)

    bs, num_blocks, bmax, T = 16, 96, 64, 256
    kv_shape = (num_blocks, bs, cfg.num_kv_heads, cfg.head_dim)
    zeros = jax.jit(lambda: jnp.zeros(kv_shape, cfg.dtype),
                    out_shardings=kv_sharding)
    kv = [(zeros(), zeros()) for _ in range(cfg.num_layers)]
    rng = np.random.default_rng(seed)
    prompt_a = rng.integers(1, cfg.vocab_size, 300).astype(np.int32)
    prompt_b = rng.integers(1, cfg.vocab_size, 100).astype(np.int32)
    steps = spec.get("decode_steps", 2)
    decode_tokens = rng.integers(1, cfg.vocab_size, (steps, 2)).astype(np.int32)
    blocks_a = np.arange(1, 33, dtype=np.int32)  # block 0 is the null block
    blocks_b = np.arange(40, 72, dtype=np.int32)

    prefill = jax.jit(
        lambda p, t, c, pre, new, v, kv: llama.prefill(
            p, cfg, t, c, pre, new, v, kv, mesh=mesh),
        donate_argnums=(6,))
    decode = jax.jit(
        lambda p, t, pos, bt, cl, sb, so, kv: llama.decode(
            p, cfg, t, pos, bt, cl, sb, so, kv, mesh=mesh),
        donate_argnums=(7,))

    def run_prefill(prompt, start, blocks, kv):
        chunk = prompt[start:start + T]
        tokens = np.zeros((T,), np.int32)
        tokens[:len(chunk)] = chunk
        prefix = np.zeros((bmax,), np.int32)
        prefix[:start // bs] = blocks[:start // bs]
        new = np.zeros((T // bs,), np.int32)
        n_new = -(-len(chunk) // bs)
        new[:n_new] = blocks[start // bs:start // bs + n_new]
        return prefill(params, jnp.asarray(tokens), jnp.int32(start),
                       jnp.asarray(prefix), jnp.asarray(new),
                       jnp.int32(len(chunk)), kv)

    got = []
    _, kv = run_prefill(prompt_a, 0, blocks_a, kv)    # chunk 1: no prefix
    out, kv = run_prefill(prompt_a, T, blocks_a, kv)  # chunk 2: 256 cached
    got.append(("prefill, 256 cached", np.asarray(out, np.float32)))
    out, kv = run_prefill(prompt_b, 0, blocks_b, kv)
    got.append(("prefill, no prefix", np.asarray(out, np.float32)))
    tables = np.zeros((2, bmax), np.int32)
    tables[0, :32], tables[1, :32] = blocks_a, blocks_b
    ctx = np.array([len(prompt_a), len(prompt_b)], np.int32)
    for step in range(steps):
        ctx = ctx + 1
        pos = ctx - 1
        out, kv = decode(
            params, jnp.asarray(decode_tokens[step]), jnp.asarray(pos),
            jnp.asarray(tables), jnp.asarray(ctx),
            jnp.asarray(tables[np.arange(2), pos // bs]),
            jnp.asarray(pos % bs), kv)
        got.append((f"decode step {step}", np.asarray(out, np.float32)))

    # The reference: each whole sequence in one pass.  Attention is causal,
    # so the logits at a position do not depend on what follows it.
    fwd = jax.jit(lambda p, t: reference.forward(p, hp, t))
    full_a = np.concatenate([prompt_a, decode_tokens[:, 0]])
    full_b = np.concatenate([prompt_b, decode_tokens[:, 1]])
    ref_a = np.asarray(fwd(params, jnp.asarray(full_a)))
    ref_b = np.asarray(fwd(params, jnp.asarray(full_b)))
    want = [ref_a[len(prompt_a) - 1], ref_b[len(prompt_b) - 1]]
    for step in range(steps):
        want.append(np.stack([ref_a[len(prompt_a) + step],
                              ref_b[len(prompt_b) + step]]))

    ok = True
    for (name, a), b in zip(got, want):
        err = float(np.max(np.abs(a - b)) / np.max(np.abs(b)))
        fine = bool(np.isfinite(a).all()) and err <= spec["logits_rtol"]
        ok &= fine
        notes.append(f"{name}: max|a-b|/max|b| = {err:.3e} "
                     f"{'<=' if fine else '>'} {spec['logits_rtol']}")
    return ok, notes
