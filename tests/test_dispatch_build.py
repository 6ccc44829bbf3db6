"""An unchained dispatch's build (PR 49): a dedicated prefill or a decode
window rebuilt from host state sends what it built in ONE staged transfer
(``LLMEngine._stage``), the window's per-row scalars packed and unpacked on
the device (``win_unpack_fn``), with no O(context) Python on the way.

The builder this replaced (an array a ``_put``, eager ``jnp.int32`` scalars,
``Sequence.all_token_ids`` for a row's last token) is kept HERE, as
``legacy_*``: what the programs receive must equal what it built, array by
array with dtype and sharding, so that token streams cannot move.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from production_stack_tpu.engine.config import (
    CacheConfig,
    EngineConfig,
    LoraServingConfig,
    ModelConfig,
    ParallelConfig,
    SchedulerConfig,
    config_from_preset,
)
from production_stack_tpu.engine.core import step_programs
from production_stack_tpu.engine.core.engine import LLMEngine
from production_stack_tpu.engine.core.sequence import SamplingParams, Sequence
from production_stack_tpu.engine.parallel import shardings as shardings_lib
from production_stack_tpu.engine.parallel.mesh import AXES
from production_stack_tpu.obs.metric_registry import REGISTRY

requires_8_devices = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 (virtual) devices"
)


# -- the parent's builder, as a test helper ---------------------------------


def legacy_decode_batch_arrays(engine, seqs, S):
    bs = engine.block_pool.block_size
    tokens = np.zeros((S,), np.int32)
    positions = np.zeros((S,), np.int32)
    block_tables = np.zeros((S, engine._bmax), np.int32)
    ctx_lens = np.zeros((S,), np.int32)
    for i, seq in enumerate(seqs):
        pos = seq.num_tokens - 1
        tokens[i] = (seq.prompt_token_ids + seq.output_token_ids)[-1]
        positions[i] = pos
        table = seq.block_table[: engine._bmax]
        block_tables[i, : len(table)] = table
        ctx_lens[i] = seq.num_tokens
    return tokens, positions, block_tables, ctx_lens


def legacy_sampling_arrays(seqs, S):
    pad = S - len(seqs)
    sps = [s.sampling_params for s in seqs]
    temps = np.array([sp.temperature for sp in sps] + [0.0] * pad, np.float32)
    top_ps = np.array([sp.top_p for sp in sps] + [1.0] * pad, np.float32)
    top_ks = np.array([sp.top_k for sp in sps] + [0] * pad, np.int32)
    min_ps = np.array([sp.min_p for sp in sps] + [0.0] * pad, np.float32)
    seeds = np.array(
        [sp.seed if sp.seed is not None else i for i, sp in enumerate(sps)]
        + [0] * pad, np.int32)
    return temps, top_ps, top_ks, min_ps, seeds


def legacy_window_build(engine, seqs, steps) -> dict:
    """``_window_build`` as the parent had it (without the model drafter's
    pool, which allocates): every array its own ``_put``."""
    assert engine.draft_block_pool is None
    put = engine._put
    S = engine._decode_bucket(len(seqs))
    tokens, positions, tables, ctx_lens = legacy_decode_batch_arrays(
        engine, seqs, S)
    pad = S - len(seqs)
    sps = [s.sampling_params for s in seqs]
    max_steps = np.zeros((S,), np.int32)
    max_steps[: len(seqs)] = steps
    done = np.ones((S,), bool)
    done[: len(seqs)] = False
    min_left = np.array(
        [max(0, s.sampling_params.min_tokens - len(s.output_token_ids))
         for s in seqs] + [0] * pad, np.int32)
    presence = np.array(
        [sp.presence_penalty for sp in sps] + [0.0] * pad, np.float32)
    frequency = np.array(
        [sp.frequency_penalty for sp in sps] + [0.0] * pad, np.float32)
    repetition = np.array(
        [sp.repetition_penalty for sp in sps] + [1.0] * pad, np.float32)
    stop_lists = [engine._stop_set_ids(s) for s in seqs]
    B = engine._pow2_bucket(max([len(ids) for ids in stop_lists] + [1]), 1)
    stop_ids = np.full((S, B), -1, np.int32)
    for i, ids in enumerate(stop_lists):
        stop_ids[i, : len(ids)] = ids
    use_penalties = bool(
        np.any(presence) or np.any(frequency) or np.any(repetition != 1.0))
    batch_spec = shardings_lib.decode_batch_spec()
    row_spec = P(AXES.DP, None)
    temps, top_ps, top_ks, min_ps, seeds = legacy_sampling_arrays(seqs, S)
    state = {
        "tokens": put(tokens, batch_spec),
        "positions": put(positions, batch_spec),
        "ctx_lens": put(ctx_lens, batch_spec),
        "done": put(done, batch_spec),
        "min_left": put(min_left, batch_spec),
        "tables": put(tables, row_spec),
        "max_steps": put(max_steps, batch_spec),
        "temps": put(temps, batch_spec),
        "top_ps": put(top_ps, batch_spec),
        "top_ks": put(top_ks, batch_spec),
        "min_ps": put(min_ps, batch_spec),
        "seeds": put(seeds, batch_spec),
        "stop_ids": put(stop_ids, row_spec),
        "presence": put(presence, batch_spec),
        "frequency": put(frequency, batch_spec),
        "repetition": put(repetition, batch_spec),
        "use_penalties": use_penalties,
        "use_min_floor": bool(np.any(min_left > 0)),
    }
    if use_penalties:
        L = engine._pow2_bucket(
            max([len(s.output_token_ids) for s in seqs] + [1]), 64)
        out_tokens = np.full((S, L), -1, np.int32)
        for i, s in enumerate(seqs):
            ids = s.output_token_ids[-L:]
            out_tokens[i, : len(ids)] = ids
        Lc = engine._pow2_bucket(max(s.num_tokens for s in seqs), 64)
        ctx_tokens = np.full((S, Lc), -1, np.int32)
        for i, s in enumerate(seqs):
            ids = (s.prompt_token_ids + s.output_token_ids)[-Lc:]
            ctx_tokens[i, : len(ids)] = ids
        state["counts"], state["seen"] = engine._win_occurrence_fn(
            put(out_tokens, row_spec), put(ctx_tokens, row_spec))
    else:
        state["counts"] = put(np.zeros((S, 1), np.int16), row_spec)
        state["seen"] = put(np.zeros((S, 1), bool), row_spec)
    if engine._spec_window_fn is not None:
        H = engine._SPEC_HIST_WINDOW
        hist = np.full((S, H), -1, np.int32)
        for i, s in enumerate(seqs):
            ids = (s.prompt_token_ids + s.output_token_ids)[-H:]
            hist[i, H - len(ids):] = ids
        state["hist"] = put(hist, row_spec)
    if engine.lora_registry is not None:
        adapter = np.zeros((S,), np.int32)
        for i, seq in enumerate(seqs):
            adapter[i] = seq.adapter_idx
        state["adapter"] = put(adapter, batch_spec)
    state["state_kwargs"] = {}
    if engine.state_pool is not None:
        slots = np.zeros((S,), np.int32)
        slots[: len(seqs)] = [s.state_slot for s in seqs]
        state["state_kwargs"] = {"state_slots": put(slots, batch_spec)}
    return state


def legacy_prefill_kwargs(engine, plan) -> dict:
    """``_prefill_kwargs`` as the parent had it, without the static keyword
    arguments (``lora``, ``prompt_topk``), which did not change: three
    ``_put`` and an eager ``jnp.int32`` a scalar."""
    seq = plan.seq
    bs = engine.block_pool.block_size
    T = plan.bucket_len
    new_tokens = seq.prompt_token_ids[
        plan.cached_len: plan.cached_len + plan.num_new_tokens]
    tokens = np.zeros((T,), np.int32)
    tokens[: len(new_tokens)] = new_tokens
    new_block_ids = np.zeros((T // bs,), np.int32)
    new_block_ids[: len(plan.new_block_ids)] = plan.new_block_ids
    prefix_ids = np.zeros((max(engine._bmax, 1),), np.int32)
    prefix_ids[: len(plan.prefix_block_ids)] = plan.prefix_block_ids
    kwargs = dict(
        tokens=engine._put(tokens, P(AXES.SP)),
        cached_len=jnp.int32(plan.cached_len),
        prefix_block_ids=engine._put(prefix_ids, P(AXES.SP)),
        new_block_ids=engine._put(new_block_ids, P(AXES.SP)),
        valid_len=jnp.int32(plan.num_new_tokens),
    )
    if engine.lora_registry is not None:
        kwargs["adapter_idx"] = jnp.int32(seq.adapter_idx)
    sp = seq.sampling_params
    if sp.echo and sp.logprobs:
        targets = np.zeros((T,), np.int32)
        m = min(plan.num_new_tokens,
                len(seq.prompt_token_ids) - plan.cached_len - 1)
        if m > 0:
            targets[:m] = seq.prompt_token_ids[
                plan.cached_len + 1: plan.cached_len + 1 + m]
        kwargs["prompt_targets"] = engine._put(targets, P(AXES.SP))
        kwargs["prompt_topk"] = 20
    if engine.state_pool is not None:
        kwargs.update(
            state_slot=jnp.int32(plan.state_slot),
            state_from=jnp.int32(plan.state_from),
            snapshot_slot=jnp.int32(plan.snapshot_slot),
            snapshot_len=jnp.int32(plan.snapshot_len),
        )
    return kwargs


def capture_model_prefill(engine):
    """What the model's prefill receives inside ``prefill_fn``, as the
    concrete values ``step_programs.prefill_program`` slices out of the
    packed vector: a stand-in for the model that records its keyword
    arguments, driven with the engine's own layout."""
    got = {}

    def model_prefill(params, **kwargs):
        got.update(kwargs)

    unpack = step_programs.prefill_program(
        model_prefill, engine._prefill_scalars,
        engine.block_pool.block_size, max(engine._bmax, 1))

    def run(kwargs):
        got.clear()
        unpack(None, kv_caches=None, **{
            k: v for k, v in kwargs.items() if k != "lora"})
        got.pop("kv_caches")
        return dict(got)

    return run


# -- engines and a run with an admission and a finish mid-chain -------------


def plain_config(**kw) -> EngineConfig:
    # As the benchmark's cells serve: dedicated prefills, no mixed step.
    sched = dict(max_num_seqs=4, prefill_buckets=(16, 32, 64),
                 max_model_len=256, mixed_batch=False)
    sched.update(kw.pop("sched", {}))
    return EngineConfig(
        model=ModelConfig(dtype="float32"),
        cache=CacheConfig(block_size=4, num_blocks=256),
        scheduler=SchedulerConfig(**sched), **kw)


CONFIGS = {
    "plain": lambda: plain_config(),
    "plain-dp2-tp2": lambda: plain_config(
        parallel=ParallelConfig(data_parallel=2, tensor_parallel=2)),
    "state-pool": lambda: config_from_preset("tiny-solar", **{
        "model.dtype": "float32", "scheduler.prefill_buckets": (64, 128),
        "scheduler.max_num_seqs": 4, "scheduler.mixed_batch": False}),
    "lora": lambda: plain_config(
        lora=LoraServingConfig(max_loras=2, max_rank=4)),
    "spec-hist": lambda: plain_config(sched=dict(speculative_ngram=3)),
}

PROMPTS = [
    "the quick brown fox jumps over the lazy dog " * 2,
    "one transfer a dispatch, and no walk over the context",
    "short",
]


def sampling(i: int, **kw) -> SamplingParams:
    """Row 0 greedy, row 1 seeded with both filters, row 2 unseeded
    sampling with a stop id and a floor; lengths that finish one row while
    the others' chain is in flight."""
    return SamplingParams(**{**(
        dict(max_tokens=40, temperature=0.0, ignore_eos=True),
        dict(max_tokens=11, temperature=0.8, top_p=0.9, top_k=20, seed=7,
             ignore_eos=True),
        dict(max_tokens=25, temperature=0.6, min_p=0.05, min_tokens=3,
             stop_token_ids=[5, 9]),
    )[i], **kw})


def run(engine, admit_at=3, adapters=(None, None, None), **sp_kw):
    """Two requests from the start, a third admitted ``admit_at`` steps in
    (mid-chain); the second finishes while the first still runs."""
    streams = {}

    def pump():
        for out in engine.step():
            streams.setdefault(out.seq_id, []).append(out.new_token_id)

    def add(i):
        engine.add_request(
            f"r{i}", prompt=PROMPTS[i], adapter=adapters[i],
            sampling_params=sampling(i, **sp_kw))

    add(0)
    add(1)
    for _ in range(admit_at):
        pump()
    add(2)
    for _ in range(600):
        if not engine.has_unfinished():
            break
        pump()
    assert not engine.has_unfinished()
    return streams


class Builds:
    """Every ``_window_build`` and ``_prefill_kwargs`` of an engine, each
    beside what the parent's builder makes of the same arguments and with
    the counters' growth over the call."""

    def __init__(self, engine):
        self.engine = engine
        self.windows, self.prefills, self.chained = [], [], 0
        window_build = engine._window_build
        prefill_kwargs = engine._prefill_kwargs
        window_chain = engine._window_chain

        def counted(fn, *args):
            before = (engine.build_transfers, engine.unchained_dispatches)
            got = fn(*args)
            return got, (engine.build_transfers - before[0],
                         engine.unchained_dispatches - before[1])

        def on_window(seqs, steps, first=None):
            if first is not None:
                # A window launched behind the prefill that admits a row
                # (PR 51): what the parent built once it had read the token.
                first[0].output_token_ids.append(int(first[1][0]))
            want = legacy_window_build(engine, seqs, steps)
            if first is not None:
                first[0].output_token_ids.pop()
            got, grew = counted(window_build, seqs, steps, first)
            self.windows.append((dict(got), want, grew))
            return got

        def on_prefill(plan):
            want = legacy_prefill_kwargs(engine, plan)
            (got, plp), grew = counted(prefill_kwargs, plan)
            self.prefills.append((dict(got), want, grew))
            return got, plp

        def on_chain(*args):
            self.chained += 1
            return window_chain(*args)

        engine._window_build = on_window
        engine._prefill_kwargs = on_prefill
        engine._window_chain = on_chain


def assert_same_array(name, got, want):
    assert isinstance(got, jax.Array), name
    assert got.dtype == want.dtype, name
    assert got.shape == want.shape, name
    assert got.sharding.is_equivalent_to(want.sharding, want.ndim), (
        name, got.sharding, want.sharding)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want), name)


# -- (a) the device inputs are the parent's, array by array -----------------


@requires_8_devices
@pytest.mark.parametrize("case", sorted(CONFIGS) + ["penalties"])
def test_build_inputs_equal_the_put_path(case):
    sp_kw, adapters = {}, (None, None, None)
    engine = LLMEngine(CONFIGS.get(case, CONFIGS["plain"])())
    if case == "penalties":
        sp_kw = dict(presence_penalty=0.5, repetition_penalty=1.2)
    if case == "lora":
        from test_lora import random_factors

        engine.load_lora(
            "a1", random_factors(engine.config.model, 4, seed=1), rank=4)
        adapters = (None, "a1", "a1")
    builds = Builds(engine)
    run(engine, adapters=adapters, **sp_kw)
    assert len(builds.windows) >= 2 and len(builds.prefills) >= 3
    for got, want, _ in builds.windows:
        got, want = dict(got), dict(want)
        assert got.pop("sample_sorts") in (True, False)
        got_kw, want_kw = got.pop("state_kwargs"), want.pop("state_kwargs")
        assert sorted(got) == sorted(want)
        assert sorted(got_kw) == sorted(want_kw) == (
            ["state_slots"] if case == "state-pool" else [])
        for name, value in dict(want, **want_kw).items():
            mine = got_kw[name] if name in got_kw else got[name]
            if isinstance(value, bool):
                assert mine is value, name
            else:
                assert_same_array(name, mine, value)
    if case == "spec-hist":
        assert all("hist" in got for got, _, _ in builds.windows)
    if case == "lora":
        assert any(np.asarray(got["adapter"]).any()
                   for got, _, _ in builds.windows)
    unpacked = capture_model_prefill(engine)
    for got, want, _ in builds.prefills:
        assert_prefill_inputs_equal(engine, unpacked, got, want)
    engine.close()


def assert_prefill_inputs_equal(engine, unpacked, got, want):
    """One array crosses, replicated (what the mixed window's chunks have
    always been); the model's prefill is handed, name by name, the values
    and dtype the parent's arrays held."""
    packed = got["chunk"]
    assert sorted(set(got) - {"lora", "prompt_topk"}) == ["chunk"]
    assert packed.dtype == jnp.int32 and packed.ndim == 1
    assert packed.sharding.is_equivalent_to(engine._sharding(P()), 1)
    seen = unpacked(got)
    assert sorted(seen) == sorted(want)
    for name, value in want.items():
        if name == "prompt_topk":
            assert seen[name] == value
            continue
        assert seen[name].dtype == value.dtype == jnp.int32, name
        assert seen[name].shape == value.shape, name
        np.testing.assert_array_equal(
            np.asarray(seen[name]), np.asarray(value), name)


def test_prompt_targets_ride_the_same_transfer():
    engine = LLMEngine(plain_config())
    builds = Builds(engine)
    engine.add_request("e", prompt=PROMPTS[1], sampling_params=SamplingParams(
        max_tokens=2, echo=True, logprobs=True, top_logprobs=2))
    while engine.has_unfinished():
        engine.step()
    (got, want, grew), = builds.prefills
    assert grew == (1, 1)
    assert got["prompt_topk"] == 20 and "prompt_targets" in want
    assert_prefill_inputs_equal(
        engine, capture_model_prefill(engine), got, want)
    engine.close()


def test_a_vector_no_bucket_packs_into_is_refused():
    unpack = step_programs.prefill_program(
        lambda params, **kw: kw, ("cached_len", "valid_len"), 4, 8)
    got = unpack(None, np.arange(16 + 4 + 8 + 2, dtype=np.int32), None)
    assert got["tokens"].tolist() == list(range(16))
    assert got["new_block_ids"].tolist() == [16, 17, 18, 19]
    assert got["prefix_block_ids"].tolist() == list(range(20, 28))
    assert (got["cached_len"], got["valid_len"]) == (28, 29)
    with pytest.raises(ValueError, match="no prefill bucket"):
        unpack(None, np.zeros((16 + 4 + 8 + 3,), np.int32), None)


# -- (b) the counters --------------------------------------------------------


@pytest.mark.parametrize("penalties", [False, True])
def test_counters_grow_by_one_dispatch_and_at_most_two_transfers(penalties):
    engine = LLMEngine(plain_config())
    builds = Builds(engine)
    run(engine, **(dict(frequency_penalty=0.3) if penalties else {}))
    assert builds.chained > 0, "the run never chained a window"
    assert builds.windows and builds.prefills
    for _, _, grew in builds.prefills:
        assert grew == (1, 1)
    for _, _, grew in builds.windows:
        assert grew == ((2, 1) if penalties else (1, 1))
    s = engine.stats()
    n = len(builds.windows) + len(builds.prefills)
    # Chained windows count as neither.
    assert s["step_unchained_dispatches"] == n
    assert s["step_build_transfers"] == n + penalties * len(builds.windows)
    for family in ("tpu:step_build_transfers_total",
                   "tpu:step_unchained_dispatch_total"):
        assert REGISTRY[family]["kind"] == "counter"
    engine.close()


# -- (c) no walk over the context --------------------------------------------


@pytest.mark.parametrize("case", ["plain", "spec-hist", "state-pool"])
def test_a_rebuild_never_builds_the_whole_token_list(case, monkeypatch):
    engine = LLMEngine(CONFIGS[case]())
    building = []

    def all_token_ids(seq):
        assert not building, "all_token_ids read inside a dispatch's build"
        return seq.prompt_token_ids + seq.output_token_ids

    monkeypatch.setattr(Sequence, "all_token_ids", property(all_token_ids))
    for name in ("_window_build", "_prefill_kwargs"):
        def guarded(*args, _fn=getattr(engine, name)):
            building.append(1)
            try:
                return _fn(*args)
            finally:
                building.pop()
        setattr(engine, name, guarded)
    builds = Builds(engine)
    # The legacy builder reads the whole list by concatenation, not through
    # the property: only the engine's own reads trip the guard.
    streams = run(engine)
    assert builds.windows and builds.prefills and len(streams) == 3
    engine.close()


def test_block_table_array_follows_the_list():
    seq = Sequence(seq_id="s", prompt_token_ids=[1, 2, 3],
                   sampling_params=SamplingParams())
    assert seq.block_table_array().tolist() == []
    seq.block_table.extend(range(10, 15))
    first = seq.block_table_array()
    assert first.dtype == np.int32 and first.tolist() == [10, 11, 12, 13, 14]
    seq.block_table.extend(range(100, 300))        # grown in place, past 64
    assert seq.block_table_array().tolist() == seq.block_table
    kept = seq._table
    seq.block_table.append(7)                      # O(new blocks): same array
    assert seq.block_table_array().tolist() == seq.block_table
    assert seq._table is kept
    seq.block_table = [4, 5] + [6]                 # a new list: a prefill chunk
    assert seq.block_table_array().tolist() == [4, 5, 6]
    seq.block_table = []                           # a preemption
    assert seq.block_table_array().tolist() == []
    seq.block_table.extend([9, 8])
    assert seq.block_table_array().tolist() == [9, 8]
    del seq.block_table[1:]                        # shrunk in place
    seq.block_table.append(3)
    assert seq.block_table_array().tolist() == [9, 3]


@pytest.mark.parametrize("n_out", [0, 2, 9])
def test_tail_and_last_token_without_the_list(n_out):
    seq = Sequence(seq_id="s", prompt_token_ids=list(range(100, 106)),
                   sampling_params=SamplingParams())
    seq.output_token_ids = list(range(n_out))
    whole = seq.prompt_token_ids + seq.output_token_ids
    assert seq.last_token_id == whole[-1]
    for n in (1, 2, 6, 8, 15, 64):
        assert seq.tail_token_ids(n) == whole[-n:]


def test_win_unpack_names_its_rows():
    rows = step_programs.WIN_ROWS + ("adapter", "state_slots")
    packed = np.arange(len(rows) * 4, dtype=np.int32).reshape(len(rows), 4)
    f32 = packed.view(np.float32)
    f32[rows.index("temps")] = [0.0, 0.7, 1.5, -0.0]
    packed[rows.index("done")] = [0, 1, 0, 1]
    state = jax.jit(step_programs.win_unpack(rows))(
        packed, np.zeros((1,), np.int32), np.full((1,), -1, np.int32))
    assert sorted(state) == sorted(rows + ("counts", "seen"))
    for i, name in enumerate(rows):
        if name in step_programs.WIN_FLOAT_ROWS:
            assert state[name].dtype == jnp.float32
            np.testing.assert_array_equal(
                np.asarray(state[name]).view(np.int32), packed[i])
        elif name == "done":
            assert state[name].dtype == jnp.bool_
            assert state[name].tolist() == [False, True, False, True]
        else:
            assert state[name].dtype == jnp.int32
            np.testing.assert_array_equal(np.asarray(state[name]), packed[i])
    assert state["counts"].shape == (4, 1)
    assert state["counts"].dtype == jnp.int16
    assert state["seen"].shape == (4, 1) and state["seen"].dtype == jnp.bool_
    assert not np.asarray(state["counts"]).any()
    assert rows[step_programs.WIN_SAMPLING_ROWS] == (
        "temps", "top_ps", "top_ks", "min_ps", "seeds",
        "presence", "frequency", "repetition")


# -- (d) the streams, on one device and on eight -----------------------------


@requires_8_devices
@pytest.mark.parametrize("admit_at", [2, 5])
def test_streams_identical_on_one_device_and_on_eight(admit_at):
    """Greedy (r0) and seeded (r1) streams, with r2 admitted and r1
    finishing while r0's chain of windows is in flight."""
    one = LLMEngine(plain_config())
    builds = Builds(one)
    want = run(one, admit_at=admit_at)
    assert builds.chained > 0 and len(builds.windows) >= 2
    assert len(want["r0"]) == 40 and len(want["r1"]) == 11
    eight = LLMEngine(plain_config(parallel=ParallelConfig(
        data_parallel=2, tensor_parallel=2, sequence_parallel=2)))
    got = run(eight, admit_at=admit_at)
    # r2 samples unseeded: its stand-in seed is its row, the same on both.
    assert got == want
    one.close()
    eight.close()
