"""An admission launched behind the work in flight (PR 51): the waiting
prompt's prefill goes to the device behind the decode window still running,
and the window that follows goes behind that prefill with the new row's first
token taken on the device (``LLMEngine._dispatch_behind``).

The synchronous order is the reference: the same engine with
``_dispatch_behind`` switched off plans every admission at the boundary, as
before.  What the two launch is the same programs on the same arrays in the
same order, so token streams are equal request by request, greedy or seeded.
"""

import jax
import numpy as np
import pytest

from production_stack_tpu.engine.config import config_from_preset
from production_stack_tpu.engine.core.engine import LLMEngine
from production_stack_tpu.engine.core.scheduler import Scheduler
from production_stack_tpu.engine.core.sequence import (
    FinishReason,
    SamplingParams,
    Sequence,
)
from production_stack_tpu.obs.metric_registry import REGISTRY
from production_stack_tpu.router.stats import vocabulary as vocab

MODULES = {"dense": "tiny-llama", "latent": "tiny-sarvam",
           "state-pool": "tiny-solar"}


def config(module: str, **overrides):
    return config_from_preset(MODULES[module], **{
        "model.dtype": "float32", "scheduler.prefill_buckets": (64, 128),
        "scheduler.max_num_seqs": 4, "scheduler.mixed_batch": False,
        "scheduler.max_model_len": 512, "cache.num_blocks": 256,
        **overrides})


def engines(module: str, **overrides):
    """(the engine as it serves, the same with every admission planned at
    the boundary)."""
    behind = LLMEngine(config(module, **overrides))
    sync = LLMEngine(config(module, **overrides))
    sync._dispatch_behind = lambda prev: False
    return behind, sync


@pytest.fixture(scope="module", params=list(MODULES))
def pair(request):
    return request.param, *engines(request.param)


@pytest.fixture(scope="module")
def dense():
    return engines("dense")


def prompt(seed: int, n: int):
    return np.random.default_rng(seed).integers(1, 250, n).tolist()


GREEDY = dict(temperature=0.0, ignore_eos=True)
SEEDED = dict(temperature=0.8, top_p=0.9, top_k=20, ignore_eos=True)


def greedy(n: int) -> SamplingParams:
    return SamplingParams(max_tokens=n, **GREEDY)


def drive(engine, arrivals, aborts=(), limit=400):
    """``arrivals``: {step: [(id, prompt ids, SamplingParams)]}, handed to the
    engine before that step's dispatch as the step loop does; ``aborts``:
    {step: [id]}.  Returns ({id: [token ids]}, {id: finish reason})."""
    streams, finished = {}, {}
    for step in range(limit):
        for rid in dict(aborts).get(step, ()):
            engine.abort_request(rid)
        for rid, ids, sp in arrivals.get(step, ()):
            engine.add_request(rid, prompt_token_ids=ids, sampling_params=sp)
        if step > max(arrivals) and not engine.has_unfinished():
            break
        for out in engine.step():
            streams.setdefault(out.seq_id, []).append(out.new_token_id)
            if out.finished:
                finished[out.seq_id] = out.finish_reason
    assert not engine.has_unfinished()
    assert not engine.has_pending()
    return streams, finished


def counters(engine):
    return (dict(engine.dispatch_behind), dict(engine.dispatch_behind_declined),
            engine.unchained_dispatches)


def grew(engine, before):
    behind, declined, unchained = counters(engine)
    return (
        {k: v - before[0][k] for k, v in behind.items()},
        {k: v - before[1].get(k, 0) for k, v in declined.items()
         if v - before[1].get(k, 0)},
        unchained - before[2],
    )


def pools_at_rest(engine):
    """Every block and every live state slot is back."""
    pool = engine.block_pool
    assert pool.num_free_blocks == pool.num_blocks - 1
    if engine.state_pool is not None:
        assert engine.state_pool.num_live == 0


def arrivals_mid_chain(sp_kw, tag):
    """Two requests from the start; once their windows chain, a third; later,
    while all three decode, a fourth."""
    def sp(n, seed=None):
        return SamplingParams(max_tokens=n, min_tokens=n, seed=seed, **sp_kw)
    seeded = sp_kw["temperature"] > 0
    return {
        0: [(f"{tag}0", prompt(1, 40), sp(60, 11 if seeded else None)),
            (f"{tag}1", prompt(2, 23), sp(44, 12 if seeded else None))],
        5: [(f"{tag}2", prompt(3, 50), sp(30, None))],
        8: [(f"{tag}3", prompt(4, 17), sp(25, 13 if seeded else None))],
    }


# -- streams ------------------------------------------------------------------


@pytest.mark.parametrize("kind", ["greedy", "seeded"])
def test_streams_equal_the_synchronous_order(pair, kind):
    module, behind, sync = pair
    arrivals = arrivals_mid_chain(GREEDY if kind == "greedy" else SEEDED, kind)
    before = counters(behind)
    got = drive(behind, arrivals)
    want = drive(sync, arrivals)
    assert got == want
    assert all(len(got[0][rid]) == sp.max_tokens
               for batch in arrivals.values() for rid, _, sp in batch)
    moved, declined, unchained = grew(behind, before)
    # The run's first prefill met an empty device and was left unread, so
    # the second went behind it and the first window behind that; both later
    # admissions met a window in flight: a prefill behind it, a window behind
    # the prefill.
    assert moved == {"prefill": 3, "window": 3}
    assert declined == {}
    assert unchained == sync.unchained_dispatches - (
        behind.unchained_dispatches - unchained)
    pools_at_rest(behind)
    # Both engines stepped their key ordinals alike.
    assert behind._step_counter == sync._step_counter


def test_two_prompts_waiting_prefill_behind_prefill(dense):
    behind, sync = dense
    sp = lambda n: SamplingParams(max_tokens=n, **SEEDED)  # noqa: E731
    arrivals = {
        0: [("w0", prompt(5, 30), sp(50))],
        4: [("w1", prompt(6, 20), sp(20)), ("w2", prompt(7, 60), sp(20))],
    }
    before = counters(behind)
    assert drive(behind, arrivals) == drive(sync, arrivals)
    moved, declined, _ = grew(behind, before)
    # w0's window behind its prefill; then w1 behind the window, w2 behind
    # w1's prefill, one window behind w2's.
    assert moved == {"prefill": 2, "window": 2}
    assert declined == {}


def test_chunked_long_prompt_goes_chunk_behind_chunk(pair):
    module, behind, sync = pair
    sp = greedy
    arrivals = {
        0: [("c0", prompt(8, 30), sp(60))],
        4: [("c1", prompt(9, 300), sp(12))],
    }
    before = counters(behind)
    assert drive(behind, arrivals) == drive(sync, arrivals)
    moved, declined, _ = grew(behind, before)
    assert moved["prefill"] >= 3 and moved["window"] == 2
    assert declined == {}
    pools_at_rest(behind)


# -- a first token that ends its row -------------------------------------------


@pytest.mark.parametrize("how", ["max_tokens_1", "stop_id"])
def test_first_token_that_finishes_is_an_overrun_of_the_window(pair, how):
    module, behind, sync = pair
    base = {0: [(how + "a", prompt(10, 30), SamplingParams(
        max_tokens=40, **GREEDY))]}
    if how == "max_tokens_1":
        late = SamplingParams(max_tokens=1, **GREEDY)
    else:
        # The token the prompt's first sample gives, made its stop id.
        probe = {**base, 4: [(how + "b", prompt(11, 25), SamplingParams(
            max_tokens=1, **GREEDY))]}
        first = drive(sync, probe)[0][how + "b"][0]
        late = SamplingParams(max_tokens=9, stop_token_ids=[first], **GREEDY)
    arrivals = {**base, 4: [(how + "b", prompt(11, 25), late)]}
    before = counters(behind)
    wasted = behind.multistep_wasted_tokens
    got = drive(behind, arrivals)
    assert got == drive(sync, arrivals)
    streams, finished = got
    assert finished[how + "b"] == (
        FinishReason.LENGTH if how == "max_tokens_1" else FinishReason.STOP)
    assert len(streams[how + "b"]) == 1
    moved, _, _ = grew(behind, before)
    # Its window was launched before the token was known, the row in it; the
    # row ran no step the synchronous order would not have run.  (The other
    # window: the first request's, behind its own prefill.)
    assert moved == {"prefill": 1, "window": 2}
    if how == "max_tokens_1":
        assert behind.multistep_wasted_tokens == wasted
    pools_at_rest(behind)  # blocks and the state slot freed once, not twice


def test_a_lone_prompt_of_one_token_launches_no_window(dense):
    """Its prefill is left unread (nothing in flight, nothing to read back
    for), but a window whose only row has no step to run is no window: the
    synchronous order launches none, and none is compiled for it."""
    behind, sync = dense
    arrivals = {0: [("l0", prompt(50, 20), SamplingParams(
        max_tokens=1, min_tokens=1, **GREEDY))]}
    before = counters(behind)
    launched = behind.sample_dispatches
    got = drive(behind, arrivals)
    assert got == drive(sync, arrivals)
    assert [len(v) for v in got[0].values()] == [1]
    assert grew(behind, before)[0] == {"prefill": 0, "window": 0}
    assert behind.sample_dispatches - launched == 1     # the prefill's own
    assert behind.scheduler.schedule_window_behind(None) == (None, None)
    pools_at_rest(behind)


# -- aborts and deadlines with the prefill in flight ---------------------------


@pytest.mark.parametrize("when", ["prefill_in_flight", "window_in_flight"])
def test_abort_while_the_admission_is_in_flight(pair, when):
    module, behind, sync = pair
    for engine in (behind, sync):
        engine.add_request(when + "a", prompt_token_ids=prompt(12, 30),
                           sampling_params=SamplingParams(
                               max_tokens=30, **GREEDY))
    streams = {}
    for engine in (behind, sync):
        got = streams[engine] = {}

        def pump(engine=engine, got=got):
            for out in engine.step():
                got.setdefault(out.seq_id, []).append(out.new_token_id)

        for _ in range(4):
            pump()
        engine.add_request(when + "b", prompt_token_ids=prompt(13, 40),
                           sampling_params=SamplingParams(
                               max_tokens=30, **GREEDY))
        before = counters(engine)
        engine.dispatch()           # the prefill, behind the window
        if when == "window_in_flight":
            for out in engine.collect():
                got.setdefault(out.seq_id, []).append(out.new_token_id)
            engine.dispatch()       # the window, behind the prefill
        if engine is behind:
            moved, _, _ = grew(engine, before)
            assert moved == {"prefill": 1,
                             "window": int(when == "window_in_flight")}
        engine.abort_request(when + "b")
        for out in engine.collect():
            got.setdefault(out.seq_id, []).append(out.new_token_id)
        for _ in range(100):
            if not engine.has_unfinished():
                break
            pump()
        assert not engine.has_unfinished() and not engine.has_pending()
        pools_at_rest(engine)
    assert streams[behind][when + "a"] == streams[sync][when + "a"]
    assert len(streams[behind][when + "a"]) == 30
    if when == "prefill_in_flight":
        assert when + "b" not in streams[behind]


def test_deadline_expiry_between_the_chunks_of_a_prompt_in_flight(dense):
    behind, _ = dense
    behind.add_request("d0", prompt_token_ids=prompt(14, 30),
                       sampling_params=SamplingParams(max_tokens=40, **GREEDY))
    for _ in range(4):
        behind.step()
    behind.add_request("d1", prompt_token_ids=prompt(15, 300),
                       sampling_params=SamplingParams(
                           max_tokens=8, deadline=1.0, **GREEDY))
    before = counters(behind)
    behind.dispatch()               # its first chunk, behind the window
    assert grew(behind, before)[0]["prefill"] == 1
    # Not final: the prompt still waits, so its deadline still counts.
    assert behind.scan_expired_deadlines(now=2.0) == ["d1"]
    behind.abort_request("d1")
    tokens = []
    for _ in range(100):
        tokens += [o.new_token_id for o in behind.step() if o.seq_id == "d0"]
        if not behind.has_unfinished():
            break
    assert 0 < len(tokens) <= 40 and not behind.has_unfinished()
    assert grew(behind, before)[0] == {"prefill": 1, "window": 0}
    pools_at_rest(behind)


# -- where it must not engage ---------------------------------------------------


DECLINES = {
    "prompt_logprobs": dict(max_tokens=6, echo=True, logprobs=True,
                            top_logprobs=2, **GREEDY),
    "max_tokens_0": dict(max_tokens=0, echo=True, logprobs=True, **GREEDY),
    "host_state": dict(max_tokens=6, logprobs=True, top_logprobs=2, **GREEDY),
    "penalties": dict(max_tokens=12, repetition_penalty=1.3, **GREEDY),
}


@pytest.mark.parametrize("reason", list(DECLINES))
def test_a_request_that_needs_collected_state_declines(dense, reason):
    behind, sync = dense
    late = SamplingParams(**DECLINES[reason])
    if reason == "max_tokens_0":
        # echo + logprobs comes first; a plain max_tokens 0 has its own.
        late = SamplingParams(max_tokens=0, **GREEDY)
    arrivals = {
        0: [(reason + "a", prompt(16, 30), SamplingParams(
            max_tokens=40, **GREEDY))],
        4: [(reason + "b", prompt(17, 24), late)],
    }
    before = counters(behind)
    got = drive(behind, arrivals)
    assert got == drive(sync, arrivals)
    moved, declined, _ = grew(behind, before)
    assert declined.get(reason, 0) >= 1, declined
    # (One window either way: the first request's, behind its own prefill.)
    if reason == "penalties":
        # The prefill samples from the prompt alone; the window after it
        # needs the token in its occurrence state.
        assert moved == {"prefill": 1, "window": 1}
    else:
        assert moved == {"prefill": 0, "window": 1}


def test_no_free_row_declines(dense):
    behind, sync = dense
    sp = greedy
    arrivals = {
        0: [(f"f{i}", prompt(20 + i, 20), sp(60 + 8 * i)) for i in range(4)],
        6: [("f4", prompt(24, 20), sp(10))],
    }
    before = counters(behind)
    assert drive(behind, arrivals) == drive(sync, arrivals)
    _, declined, _ = grew(behind, before)
    assert declined.get("no_free_row", 0) >= 1


def test_no_free_blocks_declines_and_takes_nothing():
    behind, sync = engines("dense", **{"cache.num_blocks": 14})
    sp = greedy
    # 16-token blocks: the first holds 7 of 13 by the time the second asks
    # for 5 and the window after it for more.
    arrivals = {
        0: [("b0", prompt(30, 60), sp(50))],
        4: [("b1", prompt(31, 100), sp(20))],
    }
    assert drive(behind, arrivals) == drive(sync, arrivals)
    assert behind.dispatch_behind_declined.get("no_free_blocks", 0) >= 1
    pools_at_rest(behind)


def fake_seq(**kw):
    return Sequence(seq_id="s", prompt_token_ids=[1, 2, 3],
                    sampling_params=SamplingParams(max_tokens=4), **kw)


@pytest.mark.parametrize("reason", ["preempted", "block_fetch", "offloaded"])
def test_the_scheduler_declines_what_needs_the_boundary(dense, reason):
    behind, _ = dense
    sched = Scheduler(
        config("dense").scheduler, behind.block_pool,
        remote_prefix_cb=(lambda *a: a[1:]) if reason == "block_fetch"
        else None)
    sched.waiting.append(fake_seq(offloaded=reason == "offloaded"))
    if reason == "preempted":
        sched.preempted.append(fake_seq())
    free = behind.block_pool.num_free_blocks
    plan, why = sched.schedule_prefill_behind()
    assert plan is None
    assert why == ("block_fetch" if reason == "offloaded" else reason)
    assert behind.block_pool.num_free_blocks == free
    assert why in vocab.TPU_STEP_DISPATCH_BEHIND_DECLINE_REASONS


@pytest.mark.parametrize("reason", ["mixed_batch", "speculative",
                                    "prefix_export"])
def test_the_engine_declines_on_what_it_serves(dense, reason, monkeypatch):
    behind, _ = dense
    behind.scheduler.waiting.append(fake_seq())
    try:
        if reason == "mixed_batch":
            monkeypatch.setattr(behind.config.scheduler, "mixed_batch", True)
            assert behind.config.scheduler.mixed_enabled
        elif reason == "speculative":
            monkeypatch.setattr(behind, "_spec_window_fn", object())
        else:
            monkeypatch.setattr(behind, "_exports", True)
        assert behind._prefill_behind_decline(behind.scheduler.waiting[-1]) == reason
    finally:
        behind.scheduler.waiting.pop()
    assert reason in vocab.TPU_STEP_DISPATCH_BEHIND_DECLINE_REASONS


def test_the_window_behind_budgets_rows_as_the_boundary_would(dense):
    """``schedule_window_behind`` before the first token is appended gives
    every row what the boundary's planner gives after: the window the one
    rule plans (it ends with the first row's last token), budgeted by
    ``_try_schedule_decode``."""
    behind, _ = dense
    sched = Scheduler(config("dense").scheduler, behind.block_pool)

    def rows():
        a = Sequence("a", prompt(1, 30), SamplingParams(max_tokens=40))
        a.output_token_ids = [5, 6, 7]
        b = Sequence("b", prompt(2, 47), SamplingParams(max_tokens=5))
        c = Sequence("c", prompt(3, 20), SamplingParams(max_tokens=1))
        for s in (a, b, c):
            s.block_table = behind.block_pool.allocate(-(-s.num_tokens // 16))
        return [a, b, c]

    plans, tables = [], []
    for first in (1, 2):
        sched.running = rows()
        plans.append(sched.schedule_window_behind(
            sched.running[first])[0].decode.steps)
        tables.append([len(s.block_table) for s in sched.running])
        for s in sched.running:
            behind.block_pool.free(s.block_table)
    # Behind b's prefill, c has one token left; behind c's, c ends with its
    # first token (an overrun row) and b's five are the first to run out.
    assert plans == [[1, 1, 1], [5, 5, 0]]

    sched.running = rows()
    sched.running[1].output_token_ids = [9]
    assert sched._window_for_pass() == (1, "finish")
    assert sched._try_schedule_decode(1).steps == plans[0]
    assert [len(s.block_table) for s in sched.running] == tables[0]
    for s in sched.running:
        behind.block_pool.free(s.block_table)


# -- programs and counters ------------------------------------------------------


def test_a_setup_compiles_what_it_compiled(pair):
    """No new program: after the same traffic both engines hold the same
    inventory, and the window's unpack program has one shape a batch
    bucket whether or not a first token came from the device."""
    module, behind, sync = pair
    assert behind.dispatch_behind["window"] >= 1
    assert behind.compile_inventory() == sync.compile_inventory()
    got = behind.obs.compile_tracker.seconds_by_executable()
    want = sync.obs.compile_tracker.seconds_by_executable()
    assert sorted(got) == sorted(want)
    assert (behind.obs.compile_tracker.events_total
            == sync.obs.compile_tracker.events_total)


def test_win_unpack_takes_the_token_from_the_device():
    from production_stack_tpu.engine.core import step_programs

    rows = step_programs.WIN_ROWS
    packed = np.arange(len(rows) * 4, dtype=np.int32).reshape(len(rows), 4)
    unpack = jax.jit(step_programs.win_unpack(rows))
    token = np.array([77], np.int32)
    plain = unpack(packed, token, np.array([-1], np.int32))
    filled = unpack(packed, token, np.array([2], np.int32))
    assert plain["tokens"].tolist() == packed[0].tolist()
    assert filled["tokens"].tolist() == [0, 1, 77, 3]
    for name in plain:
        if name != "tokens":
            np.testing.assert_array_equal(plain[name], filled[name])


def test_the_counters_are_registered_and_served(dense):
    behind, _ = dense
    s = behind.stats()
    assert s["step_dispatch_behind"] == behind.dispatch_behind
    assert set(s["step_dispatch_behind"]) == set(
        vocab.TPU_STEP_DISPATCH_BEHIND_KINDS)
    assert set(s["step_dispatch_behind_declined"]) <= set(
        vocab.TPU_STEP_DISPATCH_BEHIND_DECLINE_REASONS)
    for family, label in ((vocab.TPU_STEP_DISPATCH_BEHIND, "kind"),
                          (vocab.TPU_STEP_DISPATCH_BEHIND_DECLINED, "reason")):
        assert REGISTRY[family]["kind"] == "counter"
        assert REGISTRY[family]["labels"] == (label,)
    assert sum(s["step_dispatch_behind"].values()) <= s[
        "step_unchained_dispatches"]


def test_a_record_says_it_was_launched_behind(dense):
    behind, _ = dense
    sp = greedy
    drive(behind, {0: [("t0", prompt(40, 30), sp(40))],
                   4: [("t1", prompt(41, 30), sp(10))]})
    records = behind.obs.recorder.snapshot()
    mine = [w for w in records if "t1" in w["seq_ids"] and w["behind"]]
    kinds = sorted(w["kind"] for w in mine)
    assert kinds == ["decode", "prefill"]
    for w in mine:
        # Built and launched under the program before it, read back later:
        # spans in order, the launches inside them (a span in which a
        # program compiled is named for that).
        names = [p[0] for p in w["phases"]]
        assert names[0] in ("build", "compile")
        assert names[1] in ("launch", "compile") and "collect" in names
        assert not w["provisional"] and w["host_gap_s"] == 0
    prefill = next(w for w in mine if w["kind"] == "prefill")
    assert prefill["programs"][0] == "prefill_fn"
    assert prefill["programs"][-1] == "sample_fn"
    # t0's prefill met an empty device; its window went behind it.
    first = [w for w in records if w["seq_ids"] == ["t0"]]
    assert [w["behind"] for w in first if w["kind"] == "prefill"] == [False]
    assert any(w["behind"] for w in first if w["kind"] == "decode")
