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
to hold ``logits_rtol``; a row keeps the (sequence, index) of each of its
positions.  Two things a served module may offer and ``models/llama.py``
need not:

``init_cache(cfg, num_blocks, block_size, sharding)``: the cache is then the
module's, handed to its ``prefill`` / ``decode`` as it came; without it, a K
and a V array a layer, as the engine allocates them.

``return_choice=True`` on ``prefill`` / ``decode``, asked for where the file
says ``compare.follow_choice``: the call returns ``(logits, cache, choice)``,
``choice`` int32 [routed layers, rows, k], the experts each row went to, ids
over the router's published width.  A routed configuration's top k meets
near-ties that the engine's bf16 arithmetic flips, and a flipped position
reads another model's error (PERF.md, PR 34), so the reference follows the
engine's choice at every position of both sequences
(``forward(params, hp, tokens, choice, rows) -> (logits, shortfall)``), every
row holds ``logits_rtol`` as a dense file's does, and the choice itself is
held by ``compare.choice_shortfall``: how far, at the worst, its weakest expert
lay below the reference's own k-th, in the reference's measure.  The logits
of the first prefill with and without ``return_choice`` have to be bit-equal.
``tools/flip_rate.py`` drives ``run`` and reads its ``detail``.
"""

from __future__ import annotations

import dataclasses
import importlib
import inspect
import os
import sys
from typing import Dict, List, Optional, Tuple

from harness.sizes import held

# The llama keys, for a file that names none: a file of another architecture,
# or one whose module keeps a cache of its own shape, gives ``preset_keys``.
PRESET_KEYS = {
    "hidden_size": "hidden_size", "num_attention_heads": "num_heads",
    "num_key_value_heads": "num_kv_heads",
    "intermediate_size": "intermediate_size", "vocab_size": "vocab_size",
    "sliding_window": "sliding_window",
}
BLOCK, CHUNK = 16, 256   # tokens a KV block, slots a prefill chunk


def check_file(config: Dict) -> None:
    """What can be refused from the file alone, before anything runs."""
    spec = config.get("compare") or {}
    if spec.get("follow_choice") and "choice_shortfall" not in spec:
        raise SystemExit(
            f"bench: configuration {config.get('name')!r} says "
            f"compare.follow_choice and gives no compare.choice_shortfall: "
            f"a choice that is followed has to be held by a limit")


def programs(model, cfg, mesh=None, follow: bool = False):
    """``prefill`` and ``decode`` of the served module, jitted as the
    compare drives them; with ``follow`` each also returns its choice."""
    import jax

    more = {"return_choice": True} if follow else {}
    prefill = jax.jit(
        lambda p, t, c, pre, new, v, kv: model.prefill(
            p, cfg, t, c, pre, new, v, kv, mesh=mesh, **more),
        donate_argnums=(6,))
    decode = jax.jit(
        lambda p, t, pos, bt, cl, sb, so, kv: model.decode(
            p, cfg, t, pos, bt, cl, sb, so, kv, mesh=mesh, **more),
        donate_argnums=(7,))
    return prefill, decode


def cache_of(model, cfg, sharding=None):
    """``make(num_blocks, block_size)``: the module's own cache where it
    makes one, else a K and a V array a layer."""
    import jax
    import jax.numpy as jnp

    if hasattr(model, "init_cache"):
        return lambda blocks, bs: model.init_cache(cfg, blocks, bs, sharding)

    def pair_a_layer(blocks, bs):
        zeros = jax.jit(
            lambda: jnp.zeros((blocks, bs, cfg.num_kv_heads, cfg.head_dim),
                              cfg.dtype), out_shardings=sharding)
        return [(zeros(), zeros()) for _ in range(cfg.num_layers)]

    return pair_a_layer


def drive(prefill, decode, params, vocab: int, lens, steps: int, seed: int,
          make_cache, plain_prefill=None):
    """The engine's side.  Returns the two whole sequences (prompt, then the
    tokens fed to the decode steps), a list of rows ``(label, where,
    logits)``, ``where`` one (sequence, index) a row of ``logits``, and what
    following found: None, or with ``plain_prefill`` (the programs then
    return their choice) ``{"choice": [a sequence: int32 [layers, its
    positions, k]], "plain_differs": elements of the first prefill's logits
    that differ from the plain program's}``."""
    import jax.numpy as jnp
    import numpy as np

    len_a, len_b = lens
    # Blocks a sequence: a power of two that holds the longer one and its
    # decode steps, 32 at the least; block 0 is the null block.
    per = 32
    while per * BLOCK < max(len_a, len_b) + steps:
        per *= 2
    bs, num_blocks, bmax, T = BLOCK, 3 * per, 2 * per, CHUNK
    kv = make_cache(num_blocks, bs)
    choice = [None, None]   # a sequence: [layers, its positions, k]
    plain_differs = None

    def note(who, seq, positions, rows) -> None:
        who = np.asarray(who)
        if choice[seq] is None:
            choice[seq] = np.full(
                (who.shape[0], lens[seq] + steps, who.shape[2]), -1, np.int32)
        choice[seq][:, positions] = who[:, rows]

    rng = np.random.default_rng(seed)
    prompt_a = rng.integers(1, vocab, len_a).astype(np.int32)
    prompt_b = rng.integers(1, vocab, len_b).astype(np.int32)
    decode_tokens = rng.integers(1, vocab, (steps, 2)).astype(np.int32)
    blocks_a = np.arange(1, 1 + per, dtype=np.int32)
    blocks_b = np.arange(per + 8, 2 * per + 8, dtype=np.int32)

    def prefill_args(prompt, start, blocks, kv):
        chunk = prompt[start:start + T]
        tokens = np.zeros((T,), np.int32)
        tokens[:len(chunk)] = chunk
        prefix = np.zeros((bmax,), np.int32)
        prefix[:start // bs] = blocks[:start // bs]
        new = np.zeros((T // bs,), np.int32)
        n_new = -(-len(chunk) // bs)
        new[:n_new] = blocks[start // bs:start // bs + n_new]
        return (params, jnp.asarray(tokens), jnp.int32(start),
                jnp.asarray(prefix), jnp.asarray(new), jnp.int32(len(chunk)),
                kv), len(chunk)

    got = []
    for seq, (prompt, blocks) in enumerate(
            ((prompt_a, blocks_a), (prompt_b, blocks_b))):
        for start in range(0, len(prompt), T):
            args, n = prefill_args(prompt, start, blocks, kv)
            if plain_prefill is not None and plain_differs is None:
                plain = np.asarray(plain_prefill(
                    *args[:-1], make_cache(num_blocks, bs))[0])
            out, kv, *who = prefill(*args)
            if who:
                note(who[0], seq, slice(start, start + n), slice(0, n))
                if plain_differs is None:
                    plain_differs = int((plain != np.asarray(out)).sum())
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
        out, kv, *who = decode(
            params, jnp.asarray(decode_tokens[step]), jnp.asarray(pos),
            jnp.asarray(tables), jnp.asarray(ctx),
            jnp.asarray(tables[np.arange(2), pos // bs]),
            jnp.asarray(pos % bs), kv)
        for seq in range(2 if who else 0):
            note(who[0], seq, int(pos[seq]), seq)
        got.append((f"decode step {step}", [(0, int(pos[0])), (1, int(pos[1]))],
                    np.asarray(out, np.float32)))
    return (np.concatenate([prompt_a, decode_tokens[:, 0]]),
            np.concatenate([prompt_b, decode_tokens[:, 1]])), got, (
        {"choice": choice, "plain_differs": plain_differs}
        if plain_prefill is not None else None)


def reference_rows(forward, seqs, got, choice=None):
    """The reference's logits for the rows of ``got``: each whole sequence
    in one pass, ``forward(tokens) -> [len(tokens), vocab]``.  Attention is
    causal, so the logits at a position do not depend on what follows it.
    With ``choice`` (a sequence's [layers, positions, k]) the pass follows it,
    ``forward(tokens, choice, rows) -> ([len(rows), vocab], shortfall
    [layers, positions])`` for the compared positions ``rows`` alone, and the
    shortfalls come back too, a sequence after another along the positions."""
    import numpy as np

    if choice is None:
        ref = [np.asarray(forward(tokens)) for tokens in seqs]
        return [np.stack([ref[s][i] for s, i in where])
                for _name, where, _logits in got]
    ref, shortfall = [], []
    for seq, tokens in enumerate(seqs):
        rows = sorted({i for _n, where, _l in got for s, i in where
                       if s == seq})
        logits, short = forward(tokens, choice[seq], np.asarray(rows, np.int32))
        ref.append(dict(zip(rows, np.asarray(logits))))
        shortfall.append(np.asarray(short))
    return [np.stack([ref[s][i] for s, i in where])
            for _name, where, _logits in got], np.concatenate(shortfall, 1)


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


def score_choice(followed, shortfall, limit: float):
    """(ok, notes, {entry: [number, limit]}) for a choice that was followed:
    the largest shortfall over every routed layer and every position of both
    sequences against the file's limit, and the first prefill's logits with
    and without ``return_choice``, which have to be bit-equal.  The share of
    positions where the reference alone would have chosen otherwise (a
    shortfall over 0 in some layer) is said and judged by nothing."""
    import numpy as np

    worst, differs = float(np.max(shortfall)), followed["plain_differs"]
    fine = worst <= limit   # false for nan too
    flipped = (shortfall > 0).any(0)
    notes = [
        f"choice: the weakest expert handed in lies at most {worst:.3e} "
        f"below the reference's own k-th {'<=' if fine else '>'} {limit}",
        f"choice: the reference alone would have chosen otherwise at "
        f"{int(flipped.sum())} of {flipped.size} positions "
        f"({100 * flipped.mean():.2f} %; by layer "
        f"{[int(n) for n in (shortfall > 0).sum(1)]}); judged by nothing",
        f"choice: {differs} logits of the first prefill differ from the "
        f"program without return_choice; limit 0"]
    return (fine and differs == 0, notes,
            {"choice_shortfall": [worst, limit],
             "return_choice_logits_differ": [differs, 0]})


def run(config: Dict, chips: int, seed: int, platform: str, env_root: str,
        detail: Optional[Dict] = None,
        ) -> Tuple[bool, List[str], Dict[str, List[float]]]:
    """(ok, one note a compared row, {row: [number, limit]}).  ``detail``,
    where a dict is handed in, keeps what the numbers were made from (the
    builder's tool reads it)."""
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

    check_file(config)
    spec = config["compare"]
    if jax.default_backend() != platform or len(jax.devices()) < chips:
        return False, [f"the parent's JAX sees {jax.devices()}"], {}
    cfg = dataclasses.replace(
        PRESETS[config["model"]], num_layers=spec["layers"],
        quantization=spec.get("quantization"))
    model = get_model(cfg.name)
    reference = importlib.import_module("reference." + spec["reference"])
    hp = held(config)
    if not hasattr(model, "init_cache"):
        hp.setdefault("head_dim", cfg.head_dim)
    for ours, theirs in spec.get("preset_keys", PRESET_KEYS).items():
        if hp.get(ours) != getattr(cfg, theirs):
            return False, [f"preset {config['model']} has {theirs}="
                           f"{getattr(cfg, theirs)}, the configuration's "
                           f"file {ours}={hp.get(ours)}"], {}
    follow = bool(spec.get("follow_choice"))
    for step in (model.prefill, model.decode) if follow else ():
        if "return_choice" not in inspect.signature(step).parameters:
            return False, [f"the file says compare.follow_choice and "
                           f"{model.__name__}.{step.__name__} takes no "
                           f"return_choice"], {}

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

    seqs, got, followed = drive(
        *programs(model, cfg, mesh, follow), params, cfg.vocab_size,
        spec.get("prompt_tokens", [300, 100]), spec.get("decode_steps", 2),
        seed, cache_of(model, cfg, kv_sharding),
        programs(model, cfg, mesh)[0] if follow else None)
    if not follow:
        fwd = jax.jit(lambda p, t: reference.forward(p, hp, t))
        want = reference_rows(lambda tokens: fwd(params, jnp.asarray(tokens)),
                              seqs, got)
    else:
        fwd = jax.jit(lambda p, t, c, r: reference.forward(
            p, hp, t, choice=c, rows=r))
        want, shortfall = reference_rows(
            lambda tokens, choice, rows: fwd(
                params, jnp.asarray(tokens), jnp.asarray(choice),
                jnp.asarray(rows)),
            seqs, got, followed["choice"])
    ok, notes, rows = score(got, want, spec["logits_rtol"])
    if follow:
        held_ok, more, entries = score_choice(
            followed, shortfall, spec["choice_shortfall"])
        ok, notes, rows = ok and held_ok, notes + more, {**rows, **entries}
        if detail is not None:
            detail.update(followed, shortfall=shortfall)
    if detail is not None:
        detail.update(seqs=seqs, got=got, want=want)
    return ok, notes, rows
