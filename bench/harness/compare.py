"""Part (ii) of ``correct``: the engine's model code against the plain
reference, logits to logits, in this process after the servers have exited.

Driven by the configuration's file.  At the widths the chip holds
(``sizes.held``) and ``compare.layers`` layers, seeded random weights in the
configuration's own weight format and mesh: prefill of the first prompt in
chunks of 256 (every chunk after the first attends to a cached prefix),
prefill of the second, then decode steps of both through the paged cache --
``prefill`` / ``decode`` of the module the engine serves the preset with
(``models.get_model``), with the kernels it serves with -- against
``reference/<compare.reference>.forward`` over the whole sequence.  The
file's ``compare`` block also gives ``prompt_tokens`` (default [300, 100];
a configuration with a window asks for a prompt longer than it) and
``preset_keys``, {key of the file: field of the preset's ``ModelConfig``},
which have to agree before anything runs (default: the six llama keys).
Tokens are not compared: with random weights the largest logit turns on
rounding.  The driving code is copied from ``chip_smoke.py`` (PR 21), which
compared kernels with the XLA path, not with a reference.

Every row (the end of each prefill, each decode step of both sequences) has
to hold ``logits_rtol``.  ``programs`` and ``drive`` are also what
``tools/flip_rate.py`` drives a routed model with; a row keeps the
(sequence, index) of each of its positions for it.  A routed configuration's
top k meets near-ties that the engine's bf16 arithmetic flips, and no rule
over rows or positions separates those from a fault at real widths
(PERF.md, PR 34): the compare that will is not here yet (PERF.md, section 7).
"""

from __future__ import annotations

import dataclasses
import importlib
import os
import sys
from typing import Dict, List, Tuple

from harness.sizes import held

PRESET_KEYS = {
    "hidden_size": "hidden_size", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "intermediate_size", "vocab_size": "vocab_size",
    "sliding_window": "sliding_window",
}
BLOCK, CHUNK = 16, 256   # tokens a KV block, slots a prefill chunk


def programs(model, cfg, mesh=None):
    """``prefill`` and ``decode`` of the served module, jitted as the
    compare drives them."""
    import jax

    prefill = jax.jit(
        lambda p, t, c, pre, new, v, kv: model.prefill(
            p, cfg, t, c, pre, new, v, kv, mesh=mesh),
        donate_argnums=(6,))
    decode = jax.jit(
        lambda p, t, pos, bt, cl, sb, so, kv: model.decode(
            p, cfg, t, pos, bt, cl, sb, so, kv, mesh=mesh),
        donate_argnums=(7,))
    return prefill, decode


def drive(prefill, decode, params, cfg, lens, steps: int, seed: int,
          kv_sharding=None):
    """The engine's side.  Returns the two whole sequences (prompt, then the
    tokens fed to the decode steps) and a list of rows ``(label, where,
    logits)``: ``where`` is one (sequence, index) a row of ``logits``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    len_a, len_b = lens
    # Blocks a sequence: a power of two that holds the longer one and its
    # decode steps, 32 at the least; block 0 is the null block.
    per = 32
    while per * BLOCK < max(len_a, len_b) + steps:
        per *= 2
    bs, num_blocks, bmax, T = BLOCK, 3 * per, 2 * per, CHUNK
    kv_shape = (num_blocks, bs, cfg.num_kv_heads, cfg.head_dim)
    zeros = jax.jit(lambda: jnp.zeros(kv_shape, cfg.dtype),
                    out_shardings=kv_sharding)
    kv = [(zeros(), zeros()) for _ in range(cfg.num_layers)]
    rng = np.random.default_rng(seed)
    prompt_a = rng.integers(1, cfg.vocab_size, len_a).astype(np.int32)
    prompt_b = rng.integers(1, cfg.vocab_size, len_b).astype(np.int32)
    decode_tokens = rng.integers(1, cfg.vocab_size, (steps, 2)).astype(np.int32)
    blocks_a = np.arange(1, 1 + per, dtype=np.int32)
    blocks_b = np.arange(per + 8, 2 * per + 8, dtype=np.int32)

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
    for seq, (prompt, blocks) in enumerate(
            ((prompt_a, blocks_a), (prompt_b, blocks_b))):
        for start in range(0, len(prompt), T):
            out, kv = run_prefill(prompt, start, blocks, kv)
        label = f"prefill of {len(prompt)} tokens, " + (
            f"{start} cached" if start else "no prefix")
        if any(label == other for other, _w, _l in got):
            label += ", the second prompt"   # two prompts of one length
        got.append((label, [(seq, len(prompt) - 1)],
                    np.asarray(out, np.float32)[None]))
    tables = np.zeros((2, bmax), np.int32)
    tables[0, :per], tables[1, :per] = blocks_a, blocks_b
    ctx = np.array([len_a, len_b], np.int32)
    for step in range(steps):
        ctx = ctx + 1
        pos = ctx - 1
        out, kv = decode(
            params, jnp.asarray(decode_tokens[step]), jnp.asarray(pos),
            jnp.asarray(tables), jnp.asarray(ctx),
            jnp.asarray(tables[np.arange(2), pos // bs]),
            jnp.asarray(pos % bs), kv)
        got.append((f"decode step {step}", [(0, int(pos[0])), (1, int(pos[1]))],
                    np.asarray(out, np.float32)))
    return (np.concatenate([prompt_a, decode_tokens[:, 0]]),
            np.concatenate([prompt_b, decode_tokens[:, 1]])), got


def reference_rows(forward, seqs, got):
    """The reference's logits for the rows of ``got``: each whole sequence
    in one pass, ``forward(tokens) -> [len(tokens), vocab]``.  Attention is
    causal, so the logits at a position do not depend on what follows it."""
    import numpy as np

    ref = [np.asarray(forward(tokens)) for tokens in seqs]
    return [np.stack([ref[s][i] for s, i in where])
            for _name, where, _logits in got]


def error(a, b) -> float:
    """max|a-b| / max|b|: a row's number (the tool's: a position's)."""
    import numpy as np

    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def score(got, want, rtol: float):
    """(ok, notes, {row: [number, limit]}) from the engine's rows and the
    reference's."""
    import numpy as np

    ok, notes, rows = True, [], {}
    for (name, _where, a), b in zip(got, want):
        err = error(a, b)
        fine = bool(np.isfinite(a).all()) and err <= rtol
        ok &= fine
        notes.append(f"{name}: max|a-b|/max|b| = {err:.3e} "
                     f"{'<=' if fine else '>'} {rtol}")
        rows[name.replace(",", "").replace(" ", "_")] = [err, rtol]
    return ok, notes, rows


def run(config: Dict, chips: int, seed: int, platform: str,
        env_root: str) -> Tuple[bool, List[str], Dict[str, List[float]]]:
    """(ok, one note a compared row, {row: [number, limit]})."""
    sys.path.insert(0, env_root)
    if platform == "cpu":
        for k, v in config.get("rehearsal_env", {}).items():
            os.environ.setdefault(k, v)
    from production_stack_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding

    from production_stack_tpu.engine.config import PRESETS, ParallelConfig
    from production_stack_tpu.engine.models import get_model
    from production_stack_tpu.engine.parallel import shardings as sh
    from production_stack_tpu.engine.parallel.mesh import build_mesh

    spec = config["compare"]
    if jax.default_backend() != platform or len(jax.devices()) < chips:
        return False, [f"the parent's JAX sees {jax.devices()}"], {}
    cfg = dataclasses.replace(
        PRESETS[config["model"]], num_layers=spec["layers"],
        quantization=spec.get("quantization"))
    model = get_model(cfg.name)
    reference = importlib.import_module("reference." + spec["reference"])
    hp = held(config)
    hp.setdefault("head_dim", cfg.head_dim)
    for ours, theirs in spec.get("preset_keys", PRESET_KEYS).items():
        if hp.get(ours) != getattr(cfg, theirs):
            return False, [f"preset {config['model']} has {theirs}="
                           f"{getattr(cfg, theirs)}, the configuration's "
                           f"file {ours}={hp.get(ours)}"], {}

    mesh = None
    shardings = kv_sharding = None
    tp = spec.get("tensor_parallel", 1)
    if tp > 1:
        mesh = build_mesh(ParallelConfig(tensor_parallel=tp))
        shardings = sh.param_shardings(cfg, mesh)
        kv_sharding = NamedSharding(mesh, sh.kv_cache_spec())
    # 2**31 - 1 keeps any driver seed inside what PRNGKey takes.
    params = model.init_params(
        cfg, jax.random.PRNGKey(seed % (2**31 - 1)), shardings)
    if mesh is None:
        params = model.quantize_params(params, cfg)

    seqs, got = drive(
        *programs(model, cfg, mesh), params, cfg,
        spec.get("prompt_tokens", [300, 100]), spec.get("decode_steps", 2),
        seed, kv_sharding)
    fwd = jax.jit(lambda p, t: reference.forward(p, hp, t))
    want = reference_rows(lambda tokens: fwd(params, jnp.asarray(tokens)),
                          seqs, got)
    return score(got, want, spec["logits_rtol"])
