"""A decode window as long as the host needs and no longer (PR 60).

Two halves of one mechanism.  The program: ``window_fn`` runs
``max(max_steps)`` iterations, up to the ``n_steps`` its outputs are sized
for, and gives token for token what the scan it replaced gave on those rows,
on three modules' tiny configurations.  The plan: every planner of a
pure-decode window takes its length from ``Scheduler._plan_window`` -- the
cap, the first row's last token, the fewest steps that cover the step
thread's pass -- and the engine writes that ``k`` where it wrote the cap:
on the record (with ``cut``), into the key ordinals, into the histogram.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.config import config_from_preset
from production_stack_tpu.engine.core import step_programs as sp
from production_stack_tpu.engine.core.engine import LLMEngine
from production_stack_tpu.engine.core.scheduler import (
    WINDOW_CUTS,
    Scheduler,
    WindowPace,
)
from production_stack_tpu.engine.core.sequence import SamplingParams, Sequence
from production_stack_tpu.engine.kv.block_pool import BlockPool
from production_stack_tpu.engine.sampling import sample_tokens

K = 8
MODULES = {"dense": "tiny-llama", "latent": "tiny-sarvam",
           "state-pool": "tiny-solar"}
SAMPLING = {
    "greedy": dict(temperature=0.0),
    "seeded": dict(temperature=0.8, top_p=0.9, top_k=20),
}


def config(preset="tiny-llama", **overrides):
    return config_from_preset(preset, **{
        "model.dtype": "float32", "scheduler.prefill_buckets": (64, 128),
        "scheduler.max_num_seqs": 4, "scheduler.mixed_batch": False,
        "scheduler.max_model_len": 512, "cache.num_blocks": 256,
        **overrides})


def prompt(seed: int, n: int):
    return [int(t) for t in np.random.default_rng(seed).integers(3, 200, n)]


def drain(engine, requests, limit=600):
    for rid, ids, params in requests:
        engine.add_request(rid, prompt_token_ids=ids, sampling_params=params)
    streams = {}
    for _ in range(limit):
        if not engine.has_unfinished():
            break
        for out in engine.step():
            streams.setdefault(out.seq_id, []).append(out.new_token_id)
    assert not engine.has_unfinished() and not engine.has_pending()
    return streams


# -- (a) the program: a trip count that is a value ----------------------------


def scan_window_program(model_decode, *, block_size, n_steps, vocab):
    """``window_program`` as it stood before PR 60: a ``jax.lax.scan`` over
    ``n_steps`` whatever the rows' budgets, from the same shared pieces."""
    bs = block_size

    def window(params, tokens, positions, ctx_lens, done, min_left,
               block_tables, max_steps, kv_caches, temps, top_ps, top_ks,
               min_ps, seq_seeds, stop_ids, key_base, counts, seen, presence,
               frequency, repetition, use_penalties, use_min_floor,
               state_slots=None):
        stop_valid = stop_ids >= 0
        banned = (sp.stop_mask(stop_ids, stop_valid, vocab)
                  if use_min_floor else None)
        extra = {} if state_slots is None else {"state_slots": state_slots}

        def body(carry, t):
            (tokens, positions, ctx_lens, done, min_left,
             counts, seen, kv_caches) = carry
            active = jnp.logical_and(~done, t < max_steps)
            blk = jnp.take_along_axis(
                block_tables, (positions // bs)[:, None], axis=1)[:, 0]
            logits, kv_caches, *counted = model_decode(
                params, tokens=tokens, positions=positions,
                block_tables=block_tables, ctx_lens=ctx_lens,
                slot_block_ids=jnp.where(active, blk, 0),
                slot_offsets=positions % bs, kv_caches=kv_caches, **extra)
            logits = sp.shape_logits(
                logits, counts, seen, min_left, banned, presence, frequency,
                repetition, use_penalties=use_penalties,
                use_min_floor=use_min_floor)
            sampled = sample_tokens(
                logits, temps, top_ps, top_ks,
                jax.random.PRNGKey(key_base + t), seq_seeds, min_p=min_ps)
            emitted, stop_hit, _, counts, seen = sp.commit_token(
                sampled, active, counts, seen, stop_ids, stop_valid,
                use_penalties=use_penalties)
            return sp.advance_rows(
                sampled, active, stop_hit, tokens, positions, ctx_lens, done,
                min_left) + (counts, seen, kv_caches), (emitted, *counted)

        carry, (emitted, *counted) = jax.lax.scan(
            body, (tokens, positions, ctx_lens, done, min_left, counts, seen,
                   kv_caches), jnp.arange(n_steps))
        *row, kv_caches = carry
        return (emitted, dict(zip(sp.CARRY_KEYS, row)), kv_caches, *counted)

    return window


@functools.lru_cache(maxsize=None)
def decoding(module: str, sampling: str):
    """An engine of ``module`` with three prompts prefilled and decoding,
    (the window state its rebuild stages, both programs over its model).
    The programs donate nothing: every case starts from the same cache."""
    engine = LLMEngine(config(MODULES[module]))
    for i, n in enumerate((40, 23, 57)):
        engine.add_request(
            f"{sampling}{i}", prompt_token_ids=prompt(i + 1, n),
            sampling_params=SamplingParams(
                max_tokens=400, ignore_eos=True,
                seed=11 + i if sampling == "seeded" else None,
                **SAMPLING[sampling]))
    seqs = []
    for _ in range(50):
        seqs = list(engine.scheduler.running)
        if len(seqs) == 3 and all(s.num_generated >= 1 for s in seqs):
            break
        engine.step()
    assert len(seqs) == 3
    # (A window may still be in flight: both programs read the cache it
    # leaves, from the rows the host knows.)
    for s in seqs:  # blocks for a whole window, as a plan would have grown
        need = -(-(s.num_tokens + K) // 16) - len(s.block_table)
        if need > 0:
            engine.scheduler._grow(s, need)
    state = engine._window_build(seqs, [K] * 3)
    cfg = engine.config.model
    model = functools.partial(
        engine.model.decode, cfg=cfg, mesh=engine.mesh,
        **({"return_stats": True} if engine._routing_names else {}))
    dims = dict(block_size=16, n_steps=K, vocab=cfg.vocab_size)
    static = ("use_penalties", "use_min_floor")
    new = jax.jit(sp.window_program(
        model, n_counts=len(engine._routing_names), **dims),
        static_argnames=static)
    old = jax.jit(scan_window_program(model, **dims), static_argnames=static)
    return engine, state, new, old


def run(engine, state, program, max_steps):
    S = state["max_steps"].shape[0]
    budget = np.zeros((S,), np.int32)   # a padding row runs no step
    budget[: len(max_steps)] = max_steps
    return program(
        engine.params, tokens=state["tokens"], positions=state["positions"],
        ctx_lens=state["ctx_lens"], done=state["done"],
        min_left=state["min_left"], block_tables=state["tables"],
        max_steps=jnp.asarray(budget), kv_caches=engine.kv_caches,
        temps=state["temps"], top_ps=state["top_ps"], top_ks=state["top_ks"],
        min_ps=state["min_ps"], seq_seeds=state["seeds"],
        stop_ids=state["stop_ids"], key_base=np.int32(1234),
        counts=state["counts"], seen=state["seen"],
        presence=state["presence"], frequency=state["frequency"],
        repetition=state["repetition"],
        use_penalties=state["use_penalties"],
        use_min_floor=state["use_min_floor"], **state["state_kwargs"])


# what the plan stages / what the scan was staged for the same rows / steps run
BUDGETS = {
    "cap": ([8, 8, 8], [8, 8, 8], 8),
    "first-finish": ([3, 3, 3], [3, 8, 8], 3),     # planned to row 0's last
    "one-step": ([1, 1, 1], [8, 8, 8], 1),
    "uneven": ([2, 5, 0], [2, 5, 0], 5),           # a dead row; the longest
}


@pytest.mark.parametrize("budgets", list(BUDGETS))
@pytest.mark.parametrize("sampling", list(SAMPLING))
@pytest.mark.parametrize("module", list(MODULES))
def test_the_window_emits_what_the_scan_emitted(module, sampling, budgets):
    engine, state, new, old = decoding(module, sampling)
    planned, scanned, steps = BUDGETS[budgets]
    got, carry, _cache, *counted = run(engine, state, new, planned)
    want, _, _, *scan_counted = run(engine, state, old, scanned)
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape == (K, 4)
    # Token for token on the steps that ran ...
    assert (got[:steps] == want[:steps]).all()
    live = np.array(planned) > 0
    assert (got[0, :3][live] >= 0).all() and (got[:, 3] == -1).all()
    # ... and past them what a frozen row emits: nothing.
    assert (got[steps:] == -1).all()
    if planned == scanned:
        # Same budgets: the same window, carry and all.
        assert (got == want).all()
        _, scan_carry, _, *_ = run(engine, state, old, scanned)
        for key in sp.CARRY_KEYS:
            assert (np.asarray(carry[key])
                    == np.asarray(scan_carry[key])).all(), key
    advanced = np.asarray(carry["positions"]) - np.asarray(state["positions"])
    assert advanced[:3].tolist() == planned
    assert len(counted) == len(scan_counted) == bool(engine._routing_names)
    for mine, theirs in zip(counted, scan_counted):
        mine, theirs = np.asarray(mine), np.asarray(theirs)
        assert mine.shape == theirs.shape
        if planned == scanned:
            assert (mine[:steps] == theirs[:steps]).all()
        assert not mine[steps:].any()


def test_a_model_that_counts_says_how_many():
    def counting(params, **kw):
        return params[kw["tokens"]], kw["kv_caches"], jnp.zeros((3,), jnp.int32)

    program = sp.window_program(counting, block_size=4, n_steps=2, vocab=8)
    z = jnp.zeros((2,), jnp.int32)
    with pytest.raises(ValueError, match="n_counts"):
        jax.eval_shape(
            functools.partial(program, use_penalties=False,
                              use_min_floor=False),
            jnp.zeros((8, 8)), tokens=z, positions=z, ctx_lens=z,
            done=z.astype(bool), min_left=z,
            block_tables=jnp.zeros((2, 2), jnp.int32), max_steps=z + 2,
            kv_caches=jnp.zeros((4, 4)), temps=z * 0.0, top_ps=z + 1.0,
            top_ks=z, min_ps=z * 0.0, seq_seeds=z,
            stop_ids=jnp.full((2, 1), -1), key_base=jnp.int32(0),
            counts=jnp.zeros((2, 1), jnp.int16),
            seen=jnp.zeros((2, 1), bool), presence=z * 0.0,
            frequency=z * 0.0, repetition=z + 1.0)


# -- (b) the plan --------------------------------------------------------------


def scheduler(**overrides):
    return Scheduler(config(**overrides).scheduler, BlockPool(256, 16))


def running(sched, budgets, generated=1):
    """Rows that have ``generated`` tokens and ``budgets`` left."""
    for i, left in enumerate(budgets):
        seq = Sequence(f"r{i}", prompt(i, 20),
                       SamplingParams(max_tokens=left + generated))
        seq.output_token_ids = [7] * generated
        seq.block_table = sched.block_pool.allocate(2)
        sched.running.append(seq)
    return sched.running


def paced(sched, step_s, pass_s, monkeypatch):
    monkeypatch.setattr(WindowPace, "MIN_SAMPLES", 4)
    for _ in range(4):
        sched.pace.note(step_s, pass_s)


@pytest.mark.parametrize("budgets,want", [
    ([40, 30, 9], (8, "cap")),
    ([40, 8, 30], (8, "cap")),        # ends with a row's last token, uncut
    ([40, 5, 30], (5, "finish")),
    ([1, 40], (1, "finish")),         # a window of one step is a window
])
def test_a_window_ends_with_the_first_rows_last_token(budgets, want):
    sched = scheduler()
    running(sched, budgets)
    assert sched._window_for_pass() == want
    plan = sched.schedule()
    assert (plan.decode_window, plan.window_cut) == want
    assert plan.decode.steps == [min(want[0], b) for b in budgets]
    assert plan.window_cut in WINDOW_CUTS


def test_a_waiting_prompt_and_an_open_slot_step_a_token_at_a_time():
    sched = scheduler(**{"scheduler.max_num_seqs": 4})
    running(sched, [40, 5])
    sched.waiting.append(Sequence("w", prompt(9, 20), SamplingParams()))
    assert sched._window_for_pass() == (1, None)


@pytest.mark.parametrize("gate", [{}, {"scheduler.mixed_batch": True}])
def test_a_full_batch_runs_to_the_first_finish_past_a_waiting_prompt(gate):
    sched = scheduler(**{"scheduler.max_num_seqs": 2, **gate})
    running(sched, [40, 5])
    sched.add_seq(Sequence("w", prompt(9, 20), SamplingParams(max_tokens=4)))
    assert sched._window_for_pass() == (5, "finish")
    plan = sched.schedule()
    assert plan.prefill_chunk is None and plan.chunk_schedule is None
    assert (plan.decode_window, plan.window_cut) == (5, "finish")
    assert plan.window_fallback is None


def test_the_chained_planner_reckons_past_the_steps_in_flight():
    sched = scheduler()
    rows = running(sched, [40, 11, 8])
    # Window N holds 8 steps of each: row 2 ends in it, row 1 has 3 left.
    plan = sched.schedule_provisional_window(rows, [8, 8, 8])
    assert (plan.decode_window, plan.window_cut) == (3, "finish")
    assert plan.decode.steps == [3, 3, 0] and plan.provisional
    # Nothing left for any row: no window of nothing.
    assert sched.schedule_provisional_window(rows, [40, 11, 8]) is None


def test_the_window_behind_a_prefill_takes_the_same_rule():
    sched = scheduler()
    rows = running(sched, [40, 6])
    first = Sequence("new", prompt(5, 20), SamplingParams(max_tokens=4))
    first.block_table = sched.block_pool.allocate(2)
    rows.append(first)
    plan, why = sched.schedule_window_behind(first)
    # ``first``'s first token is on the device: 3 of its 4 are left to run.
    assert why is None and plan.decode.steps == [3, 3, 3]
    assert (plan.decode_window, plan.window_cut) == (3, "finish")


def test_the_speculative_window_keeps_the_cap():
    sched = scheduler(**{"scheduler.speculative_ngram": 2})
    running(sched, [40, 3])
    assert sched._window_for_pass() == (8, "cap")


def test_host_cover_needs_samples_and_has_a_floor(monkeypatch):
    sched = scheduler()
    running(sched, [40, 30])
    assert sched.pace.cover() is None
    assert sched._window_for_pass() == (8, "cap")
    monkeypatch.setattr(WindowPace, "MIN_SAMPLES", 4)
    for n in range(3):
        sched.pace.note(0.010, 0.0001)
        assert sched.pace.cover() is None, n
    sched.pace.note(0.010, 0.0001)
    # ceil(COVER x 0.1 ms / 10 ms) is 1: never under two steps.
    assert sched.pace.cover() == WindowPace.FLOOR == 2
    assert sched._window_for_pass() == (2, "host")


@pytest.mark.parametrize("pass_ms,want", [
    (2.0, 2), (5.1, 3), (10.0, 4), (19.9, 8), (50.0, 20)])
def test_host_cover_is_the_margin_times_the_pass_over_the_step(
        pass_ms, want, monkeypatch):
    monkeypatch.setattr(WindowPace, "COVER", 4.0)
    sched = scheduler()
    paced(sched, 0.010, pass_ms / 1e3, monkeypatch)
    assert sched.pace.cover() == want
    running(sched, [40, 30])
    assert sched._window_for_pass() == (
        (want, "host") if want < 8 else (8, "cap"))


def test_the_first_finish_wins_where_it_comes_sooner(monkeypatch):
    sched = scheduler()
    paced(sched, 0.010, 0.0075, monkeypatch)
    cover = sched.pace.cover()
    assert 2 < cover < 8
    running(sched, [40, cover])
    assert sched._window_for_pass() == (cover, "finish")
    sched.running[1].output_token_ids.append(7)
    assert sched._window_for_pass() == (cover - 1, "finish")
    sched.running.pop()
    assert sched._window_for_pass() == (cover, "host")


def test_the_means_follow_the_last_windows():
    pace = WindowPace()
    for _ in range(200):
        pace.note(0.010, 0.002)
    for _ in range(4 * WindowPace.HORIZON):
        pace.note(0.005, 0.004)
    assert pace.step_s == pytest.approx(0.005, rel=0.05)
    assert pace.pass_s == pytest.approx(0.004, rel=0.05)


async def test_under_lockstep_no_clock_reaches_a_plan(monkeypatch):
    """A leader's engine plans by cap and by budget: its followers replay
    its events on clocks of their own."""
    from production_stack_tpu.engine.server.async_engine import AsyncEngine

    monkeypatch.setattr(WindowPace, "MIN_SAMPLES", 1)

    class Channel:
        heartbeat_seconds = 10.0

        def publish(self, events):
            pass

    async def serve(lockstep):
        engine = AsyncEngine(config(), lockstep=lockstep)
        await engine.start()
        try:
            async for _ in engine.generate(
                    prompt_token_ids=prompt(3, 30), request_id="r",
                    sampling_params=SamplingParams(
                        max_tokens=40, ignore_eos=True)):
                pass
        finally:
            await engine.close()
        return engine.engine

    led = await serve(Channel())
    assert not led.plan_from_clocks
    assert led.scheduler.pace.samples == 0 and led.scheduler.pace.cover() is None
    cuts = {w["cut"] for w in led.obs.recorder.snapshot() if "cut" in w}
    assert cuts and cuts <= {"cap", "finish"}
    alone = await serve(None)
    assert alone.plan_from_clocks and alone.scheduler.pace.samples > 0


# -- (c), (d) the engine follows the plan --------------------------------------


def requests(sampling, budgets=(37, 21, 30)):
    return [
        (f"q{i}", prompt(20 + i, 30 + 7 * i), SamplingParams(
            max_tokens=n, ignore_eos=True,
            seed=5 + i if sampling == "seeded" else None,
            **SAMPLING[sampling]))
        for i, n in enumerate(budgets)]


@pytest.mark.parametrize("sampling", list(SAMPLING))
def test_cap_eight_gives_the_tokens_of_single_steps(sampling):
    windows = LLMEngine(config())
    steps = LLMEngine(config(**{"scheduler.multi_step_window": False}))
    got = drain(windows, requests(sampling))
    assert got == drain(steps, requests(sampling))
    assert [len(got[f"q{i}"]) for i in range(3)] == [37, 21, 30]
    records = [w for w in windows.obs.recorder.snapshot() if "cut" in w]
    assert records and all(w["kind"] == "decode" for w in records)
    assert {w["cut"] for w in records} == {"cap", "finish"}
    assert all(w["k"] == 8 for w in records if w["cut"] == "cap")
    cut = [w for w in records if w["cut"] == "finish"]
    assert cut and all(1 <= w["k"] < 8 for w in cut)
    # Every window ran its steps for every row that had them: nothing was
    # computed for a token no one asked for.
    assert windows.multistep_wasted_tokens == 0
    assert all(w["tokens_emitted"] == w["tokens_delivered"] for w in records)
    # A step an ordinal: prefills and decode steps, whatever the windows.
    assert windows._step_counter == steps._step_counter
    # The histogram holds every window, tracing or not.
    hist = windows.window_steps_hist
    assert hist.count == len(records)
    assert hist.sum == sum(w["k"] for w in records)
    assert not [w for w in steps.obs.recorder.snapshot() if "cut" in w]
    assert steps.window_steps_hist.count == 0


def test_the_key_ordinals_follow_the_plan_whatever_it_is(monkeypatch):
    """Seeded streams are those of single steps under every plan: by the cap
    and the budgets alone, and with a host that asks for two steps or for
    three."""
    want = drain(LLMEngine(config(**{"scheduler.multi_step_window": False})),
                 requests("seeded"))
    ordinals = set()
    for cover in (None, 2, 3):
        monkeypatch.setattr(WindowPace, "cover", lambda self, c=cover: c)
        engine = LLMEngine(config())
        assert drain(engine, requests("seeded")) == want, cover
        ks = [w["k"] for w in engine.obs.recorder.snapshot() if "cut" in w]
        assert max(ks) == (cover or 8)
        if cover:
            assert "host" in {w.get("cut")
                              for w in engine.obs.recorder.snapshot()}
        ordinals.add(engine._step_counter)
    assert len(ordinals) == 1


def test_a_metrics_scrape_carries_the_histogram():
    from production_stack_tpu.obs.histogram import render_histogram
    from production_stack_tpu.obs.metric_registry import REGISTRY
    from production_stack_tpu.router.stats import vocabulary as vocab

    assert REGISTRY[vocab.TPU_DECODE_WINDOW_STEPS]["kind"] == "histogram"
    engine = LLMEngine(config())
    drain(engine, requests("greedy", (12,)))
    text = render_histogram(
        vocab.TPU_DECODE_WINDOW_STEPS, engine.window_steps_hist)
    assert 'tpu:decode_window_steps_bucket{le="8.0"}' in text
    assert f"tpu:decode_window_steps_count {engine.window_steps_hist.count}" in text
