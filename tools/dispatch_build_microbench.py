"""Time what an unchained dispatch's build costs the host, at the cells' shapes.

    python tools/dispatch_build_microbench.py [--root _parent] [--model tiny-solar]

One process times one checkout (``--root``: where ``production_stack_tpu``
is imported from, this repo by default) on the device JAX finds: the CPU
here, the chip under ``chiprun`` (host times both ways: the step thread's,
with the device empty behind it).  A tiny model, the cells' batch shapes:

- ``engine`` rows: ``LLMEngine._window_build`` of 16 running rows at 24,000
  tokens of context (block 16: 1,501 blocks a row) and of 4 rows at 400,
  and ``_prefill_kwargs`` of a 256-token chunk behind a 20k cached prefix:
  ``host_ms`` until the call returns, ``ready_ms`` until what it returned is
  on the device, the median of ``--repeats``; ``transfers``: calls of
  ``jax.device_put`` in one build plus the 0-d device arrays it made
  eagerly; ``reads_all_token_ids``: whether the build built a row's whole
  token list;
- ``form`` rows (no engine; what decides between the two transfer forms):
  the same 18 per-row vectors + block tables + stop ids handed to a trivial
  jitted consumer, sent as ``puts`` (an array a ``device_put`` behind a
  ``jnp.asarray``: the parent's ``_put``), as ``tree`` (one ``device_put``
  of the tree of arrays with a tree of shardings) and as ``packed`` (one
  ``device_put`` of the packed int32 array, the tables and the stop ids,
  then a jitted unpack); and a prefill's three vectors + six scalars as
  ``puts`` (eager ``jnp.int32`` scalars), ``tree`` (0-d arrays in the tree),
  ``staged`` (one ``device_put`` of the vectors, NumPy scalars to the
  jitted call) and ``packed`` (one int32 vector, sliced by the consumer).
  ``ms``: build + the consumer's launch, host time.

One JSON object a row.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

BLOCK = 16
SHAPES = ((16, 24000), (4, 400))   # (rows, context): cells 3-5, cell 1
MAX_LEN = 32768


def _median_ms(fn, repeats: int):
    """(median ms until fn returns, median ms until its result is ready)."""
    import jax

    host, ready = [], []
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        t1 = time.perf_counter()
        jax.block_until_ready(out)
        t2 = time.perf_counter()
        host.append((t1 - t0) * 1e3)
        ready.append((t2 - t0) * 1e3)
    return statistics.median(host), statistics.median(ready)


class _Count:
    """Calls of ``jax.device_put`` and reads of ``Sequence.all_token_ids``."""

    def __init__(self, sequence_cls):
        import jax

        self.puts = 0
        self.walks = 0
        self._jax, self._put = jax, jax.device_put
        self._cls, self._prop = sequence_cls, sequence_cls.all_token_ids

    def __enter__(self):
        def device_put(*a, **kw):
            self.puts += 1
            return self._put(*a, **kw)

        def all_token_ids(seq):
            self.walks += 1
            return self._prop.fget(seq)

        self._jax.device_put = device_put
        self._cls.all_token_ids = property(all_token_ids)
        return self

    def __exit__(self, *exc):
        self._jax.device_put = self._put
        self._cls.all_token_ids = self._prop


def _eager_scalars(tree) -> int:
    import jax

    return sum(isinstance(x, jax.Array) and x.ndim == 0
               for x in jax.tree_util.tree_leaves(tree))


def engine_rows(model: str, repeats: int) -> None:
    import numpy as np

    from production_stack_tpu.engine.config import config_from_preset
    from production_stack_tpu.engine.core.engine import LLMEngine
    from production_stack_tpu.engine.core.scheduler import PrefillPlan
    from production_stack_tpu.engine.core.sequence import (
        SamplingParams, Sequence, SequenceStatus,
    )

    rows_max = max(r for r, _ in SHAPES)
    cfg = config_from_preset(model, **{
        "model.max_model_len": MAX_LEN,
        "cache.block_size": BLOCK,
        "cache.num_blocks": rows_max * (MAX_LEN // BLOCK) + 64,
        "scheduler.max_model_len": MAX_LEN,
        "scheduler.max_num_seqs": rows_max,
        "scheduler.prefill_buckets": (256, 2048),
        "scheduler.mixed_batch": False,
    })
    engine = LLMEngine(cfg)
    rng = np.random.default_rng(49)
    base = {"model": model, "device": engine.device_report()["kind"]}

    def sequences(rows: int, context: int):
        seqs = []
        for i in range(rows):
            seq = Sequence(
                seq_id=f"r{i}",
                prompt_token_ids=rng.integers(
                    0, 380, size=context - 7).tolist(),
                sampling_params=SamplingParams(
                    max_tokens=100, temperature=0.7 if i % 2 else 0.0,
                    seed=i if i % 3 == 0 else None),
            )
            seq.output_token_ids = rng.integers(0, 380, size=7).tolist()
            seq.status = SequenceStatus.RUNNING
            seq.block_table = engine.block_pool.allocate(
                (context + 8 + BLOCK) // BLOCK)
            seq.state_slot = i + 1
            seqs.append(seq)
        return seqs

    for rows, context in SHAPES:
        seqs = sequences(rows, context)
        steps = [8] * rows
        engine._window_build(seqs, steps)  # compiles what it compiles
        with _Count(Sequence) as c:
            state = engine._window_build(seqs, steps)
        host, ready = _median_ms(
            lambda: {k: v for k, v in engine._window_build(
                seqs, steps).items() if k != "state_kwargs"}, repeats)
        print(json.dumps(dict(
            base, what="window_build", rows=rows, context=context,
            host_ms=host, ready_ms=ready,
            transfers=c.puts + _eager_scalars(state),
            reads_all_token_ids=c.walks > 0)), flush=True)

        seq = seqs[0]
        cached = (context - 256) // BLOCK * BLOCK
        plan = PrefillPlan(
            seq=seq, bucket_len=256,
            new_block_ids=seq.block_table[cached // BLOCK:][:256 // BLOCK],
            prefix_block_ids=seq.block_table[:cached // BLOCK],
            num_new_tokens=min(256, len(seq.prompt_token_ids) - cached),
            cached_len=cached, state_slot=1, state_from=2, snapshot_slot=3,
            snapshot_len=128,
        )
        with _Count(Sequence) as c:
            kwargs, _ = engine._prefill_kwargs(plan)
        host, ready = _median_ms(
            lambda: engine._prefill_kwargs(plan)[0], repeats)
        print(json.dumps(dict(
            base, what="prefill_kwargs", context=context, host_ms=host,
            ready_ms=ready, transfers=c.puts + _eager_scalars(kwargs),
            reads_all_token_ids=c.walks > 0)), flush=True)
        for seq in seqs:
            engine.block_pool.free(seq.block_table)
    engine.close()


def form_rows(repeats: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("dp", "sp"))
    batch = NamedSharding(mesh, P("dp"))
    col = NamedSharding(mesh, P(None, "dp"))
    row = NamedSharding(mesh, P("dp", None))
    seq = NamedSharding(mesh, P("sp"))
    kind = jax.devices()[0].device_kind
    S, bmax, n_vec = 16, MAX_LEN // BLOCK, 18
    rng = np.random.default_rng(49)
    vecs = {f"v{i}": rng.integers(0, 1000, size=S).astype(np.int32)
            for i in range(n_vec)}
    tables = rng.integers(0, 30000, size=(S, bmax)).astype(np.int32)
    stop_ids = np.full((S, 1), 2, np.int32)

    consume = jax.jit(lambda tree: sum(
        jnp.sum(x) for x in jax.tree_util.tree_leaves(tree)))
    unpack = jax.jit(lambda packed: {
        f"v{i}": packed[i] for i in range(n_vec)})

    def puts():
        tree = {k: jax.device_put(jnp.asarray(v), batch)
                for k, v in vecs.items()}
        tree["tables"] = jax.device_put(jnp.asarray(tables), row)
        tree["stop_ids"] = jax.device_put(jnp.asarray(stop_ids), row)
        return consume(tree)

    tree_host = dict(vecs, tables=tables, stop_ids=stop_ids)
    tree_to = dict({k: batch for k in vecs}, tables=row, stop_ids=row)

    def tree():
        return consume(jax.device_put(tree_host, tree_to))

    def packed():
        dev = jax.device_put(
            {"packed": np.stack(list(vecs.values())), "tables": tables,
             "stop_ids": stop_ids},
            {"packed": col, "tables": row, "stop_ids": row})
        state = unpack(dev.pop("packed"))
        state.update(dev)
        return consume(state)

    for name, fn in (("puts", puts), ("tree", tree), ("packed", packed)):
        fn()
        host, ready = _median_ms(fn, repeats)
        print(json.dumps({
            "what": "form.window", "form": name, "device": kind, "rows": S,
            "ms": host, "ready_ms": ready}), flush=True)

    T = 256
    pf = {"tokens": rng.integers(0, 380, size=T).astype(np.int32),
          "new_block_ids": np.arange(T // BLOCK, dtype=np.int32),
          "prefix_block_ids": np.arange(bmax, dtype=np.int32)}
    scalars = {f"s{i}": 100 + i for i in range(6)}
    consume_pf = jax.jit(lambda **kw: sum(jnp.sum(x) for x in kw.values()))

    def pf_puts():
        kw = {k: jax.device_put(jnp.asarray(v), seq) for k, v in pf.items()}
        kw.update({k: jnp.int32(v) for k, v in scalars.items()})
        return consume_pf(**kw)

    rep = NamedSharding(mesh, P())

    def pf_tree():
        host = dict(pf, **{k: np.int32(v) for k, v in scalars.items()})
        to = dict({k: seq for k in pf}, **{k: rep for k in scalars})
        return consume_pf(**jax.device_put(host, to))

    def pf_staged():
        kw = jax.device_put(pf, {k: seq for k in pf})
        return consume_pf(
            **kw, **{k: np.int32(v) for k, v in scalars.items()})

    consume_packed = jax.jit(lambda packed: jnp.sum(packed[:T]) + jnp.sum(
        packed[T:-len(scalars)]) + jnp.sum(packed[-len(scalars):]))

    def pf_packed():
        return consume_packed(jax.device_put(np.concatenate(
            [*pf.values(), np.array(list(scalars.values()), np.int32)]), rep))

    for name, fn in (("puts", pf_puts), ("tree", pf_tree),
                     ("staged", pf_staged), ("packed", pf_packed)):
        fn()
        host, ready = _median_ms(fn, repeats)
        print(json.dumps({
            "what": "form.prefill", "form": name, "device": kind,
            "ms": host, "ready_ms": ready}), flush=True)


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    p.add_argument("--model", default="tiny-llama")
    p.add_argument("--repeats", type=int, default=200)
    p.add_argument("--no-forms", action="store_true")
    args = p.parse_args()
    sys.path.insert(0, os.path.abspath(args.root))
    if not args.no_forms:
        form_rows(args.repeats)
    engine_rows(args.model, args.repeats)


if __name__ == "__main__":
    main()
