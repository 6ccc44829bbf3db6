"""Who waits for whom on the device (PR 59): the flight recorder's telescoped
clock is the one account of it.  A decoder's span -- from the close of the
record that gave its first token to the close of the record that gave its
last -- divides exactly into the records it rode (``own_s``), the ``prefill``
records of other prompts that closed meanwhile (``prefill_s``, ``prefills``)
and the remainder (``rest_s``); a new request's first prefill record says how
long it waited behind the program in flight (``behind_s``); and
``tpu:itl_seconds`` shares a row's stretch among the tokens it brought.

First the hub alone on a made-up clock, then the tiny engine on the CPU at
K = 1 and K = 8 (no sleep anywhere), then the same behind its API server.
"""

import asyncio

import numpy as np
import pytest

from production_stack_tpu.engine.config import config_from_preset
from production_stack_tpu.engine.core.engine import LLMEngine
from production_stack_tpu.engine.core.sequence import SamplingParams
from production_stack_tpu.obs.engine import REQUEST_HISTS, EngineObs
from production_stack_tpu.obs.flight_recorder import FlightRecorder
from production_stack_tpu.obs.histogram import Histogram
from production_stack_tpu.obs.metric_registry import REGISTRY
from production_stack_tpu.router.stats import vocabulary as vocab

FAMILIES = {
    "request_prefill_behind": "tpu:request_prefill_behind_seconds",
    "request_decode_behind": "tpu:request_decode_behind_seconds",
}
NOW = 1_800_000_000.0


def approx(value):
    """Unix seconds near NOW resolve to 0.24 us in a double."""
    return pytest.approx(value, abs=2e-6)


# -- the hub alone, on a made-up clock ---------------------------------------


class _Seq:
    first_token_time = None
    first_scheduled_time = None
    submitted_time = admitted_time = None
    finish_reason = None
    num_prompt_tokens = 4

    def __init__(self, seq_id, arrival=NOW):
        self.seq_id = seq_id
        self.arrival_time = arrival
        self.num_generated = 0


class _Hub:
    """EngineObs driven as the step loop drives it: a record is dispatched,
    its tokens are replayed (first tokens, finishes) *inside* its collect,
    then it closes."""

    def __init__(self):
        self.obs = EngineObs()
        self.seqs = {}

    def seq(self, seq_id):
        return self.seqs.setdefault(seq_id, _Seq(seq_id))

    def record(self, kind, t0, t1, rows=(), chunks=(), tokens=0,
               first=(), finish=(), k=1):
        obs = self.obs
        rec = obs.recorder.on_dispatch(
            kind, k=k, rows=len(rows), seq_ids=tuple(rows) + tuple(chunks),
            chunk_prompts=len(chunks), now=t0)
        for seq_id in chunks:
            seq = self.seq(seq_id)
            if seq.first_scheduled_time is None:
                seq.first_scheduled_time = t0
                obs.on_first_scheduled(seq, t0)
        with obs.phase("sample", rec, family=False):
            for seq_id in first:
                seq = self.seq(seq_id)
                seq.num_generated = 1
                seq.first_token_time = t1
                obs.on_first_token(seq, t1)
            for seq_id in rows:
                self.seq(seq_id).num_generated += tokens
            for seq_id in finish:
                obs.on_finish(self.seq(seq_id), t1)
        obs.recorder.on_collect(rec, now=t1)
        return rec


def test_the_recorder_keeps_the_prefills_seconds_and_what_a_record_waited():
    """The running seconds and count of the ``prefill`` records, on the
    telescoped clock, overlapped dispatches and holes alike; and how long a
    record that is still open waited behind the one before it."""
    rec = FlightRecorder()
    assert (rec.prefill_s, rec.prefills) == (0.0, 0)
    a = rec.on_dispatch("prefill", now=100.0)
    b = rec.on_dispatch("decode", k=8, now=100.2)      # behind a
    rec.on_collect(a, now=100.5)
    assert rec.behind_of(b) == pytest.approx(0.3)      # while b is open
    rec.on_collect(b, now=101.0)
    c = rec.on_dispatch("decode", k=8, now=101.25)     # a hole of 0.25 s
    assert rec.behind_of(c) == 0.0   # nothing was in flight at its dispatch
    rec.on_collect(c, now=102.0)
    d = rec.on_dispatch("prefill", now=101.9)          # behind c
    rec.on_collect(d, now=102.5)
    assert [r.attributed_s for r in (a, b, c, d)] == pytest.approx(
        [0.5, 0.5, 0.75, 0.5])
    assert rec.prefill_s == pytest.approx(0.5 + 0.5) and rec.prefills == 2


def test_the_marks_are_taken_where_the_carrying_record_closes():
    """on_first_token and on_finish run inside the replay, before the record
    that carries them closes: a request does not stand behind its own
    prefill, and does not lose its last window."""
    hub = _Hub()
    hub.record("prefill", NOW, NOW + 0.05, chunks=["a"], first=["a"])
    hub.record("decode", NOW + 0.05, NOW + 0.13, rows=["a"], tokens=8, k=8)
    # b's prompt, two chunks, the first launched behind the window above
    hub.record("prefill", NOW + 0.10, NOW + 0.16, chunks=["b"])
    hub.record("prefill", NOW + 0.15, NOW + 0.20, chunks=["b"], first=["b"])
    hub.record("decode", NOW + 0.18, NOW + 0.30, rows=["a", "b"], tokens=8,
               k=8)
    # nothing in flight for 10 ms; a window b rides alone (a is at a budget)
    hub.record("decode", NOW + 0.31, NOW + 0.40, rows=["b"], tokens=8, k=8)
    last = hub.record("decode", NOW + 0.40, NOW + 0.50, rows=["a", "b"],
                      tokens=3, k=8, finish=["a"])
    (row,) = last.finished
    assert row[:2] == ["a", 20]
    span, own, prefill, prefills, rest = row[2:]
    assert span == approx(0.45)            # 0.05 -> 0.50
    assert own == approx(0.08 + 0.10 + 0.10)   # the last one inside
    assert prefill == approx(0.03 + 0.04) and prefills == 2  # b's
    assert rest == approx(0.01 + 0.09)     # the hole, b's own window
    assert own + prefill + rest == pytest.approx(span, abs=2e-9)
    h = hub.obs.request_hists
    assert h["request_decode_behind"].count == 1
    assert h["request_decode_behind"].sum == approx(0.07)
    # a token's gap is its share of the stretch that produced it
    assert h["itl"].count == 19 + 19
    assert h["itl"].sum == approx(0.45 + (0.30 - 0.20) + 0.10 + 0.10)
    assert "a" not in hub.obs._decoders and "b" in hub.obs._decoders


def test_behind_is_the_first_prefill_records_wait_and_counted_once():
    hub = _Hub()
    hub.record("prefill", NOW, NOW + 0.05, chunks=["a"], first=["a"])
    hub.record("decode", NOW + 0.05, NOW + 0.13, rows=["a"], tokens=8, k=8)
    first = hub.record("prefill", NOW + 0.10, NOW + 0.16, chunks=["b"])
    later = hub.record("prefill", NOW + 0.15, NOW + 0.20, chunks=["b"],
                       first=["b"])
    # launched 30 ms before the window in flight was collected; the second
    # chunk waited for the first, which is not "behind"
    assert first.behind_s == approx(0.03)
    assert later.behind_s is None
    assert "behind_s" not in later.to_dict() and "finished" not in (
        later.to_dict())
    assert first.to_dict()["behind_s"] == approx(0.03)
    h = hub.obs.request_hists
    assert h["request_prefill_behind"].count == h["prefill_time"].count == 2
    assert h["request_prefill_behind"].sum == approx(0.03)
    # one chunk, device empty: the record is the one being collected
    solo = hub.record("prefill", NOW + 0.30, NOW + 0.35, chunks=["c"],
                      first=["c"])
    assert solo.behind_s == 0.0
    assert hub.obs._first_prefill == {}


def test_span_attributes_ride_the_spans_that_exist():
    hub = _Hub()
    hub.obs.start_request("a", None, received=NOW)
    hub.record("decode", NOW - 0.02, NOW + 0.01, rows=["z"], k=8)
    hub.record("prefill", NOW, NOW + 0.05, chunks=["a"], first=["a"])
    hub.record("prefill", NOW + 0.04, NOW + 0.09, chunks=["b"], first=["b"])
    hub.record("decode", NOW + 0.09, NOW + 0.20, rows=["a", "b"], tokens=4,
               k=8, finish=["a"])
    spans = {s["name"]: s for s in hub.obs.request_payload("a")["spans"]}
    assert spans["engine.prefill"]["attrs"] == {"behind_s": 0.01}
    assert spans["engine.decode"]["attrs"] == {
        "own_s": 0.11, "prefill_s": 0.04, "prefills": 1, "rest_s": 0.0}
    assert {s["name"] for s in spans.values()} == {
        "engine.queue", "engine.prefill", "engine.decode"}  # no new name


@pytest.mark.parametrize("how", ["one_token", "abort", "abort_in_prefill",
                                 "scoring"])
def test_who_is_in_no_family(how):
    hub = _Hub()
    obs = hub.obs
    hub.record("prefill", NOW, NOW + 0.05, chunks=["keep"], first=["keep"])
    if how == "one_token":
        hub.record("prefill", NOW + 0.05, NOW + 0.10, chunks=["x"],
                   first=["x"], finish=["x"])
    elif how == "abort":
        hub.record("prefill", NOW + 0.05, NOW + 0.10, chunks=["x"],
                   first=["x"])
        hub.record("decode", NOW + 0.10, NOW + 0.2, rows=["keep", "x"],
                   tokens=8, k=8)
        obs.on_abort("x")
    elif how == "abort_in_prefill":
        hub.record("prefill", NOW + 0.05, NOW + 0.10, chunks=["x"])
        obs.on_abort("x")
    else:  # max_tokens: 0 finishes at its prefill and takes no first token
        seq = hub.seq("x")
        rec = obs.recorder.on_dispatch(
            "prefill", seq_ids=("x",), chunk_prompts=1, now=NOW + 0.05)
        obs.on_first_scheduled(seq, NOW + 0.05)
        seq.first_token_time = NOW + 0.10
        obs.on_finish(seq, NOW + 0.10)
        obs.recorder.on_collect(rec, now=NOW + 0.10)
    last = hub.record("decode", NOW + 0.2, NOW + 0.3, rows=["keep"],
                      tokens=8, k=8, finish=["keep"])
    assert [row[0] for row in last.finished] == ["keep"]
    h = obs.request_hists
    assert h["request_decode_behind"].count == 1
    assert h["request_prefill_behind"].count == (
        2 if how in ("one_token", "abort") else 1)
    assert not obs._decoders and not obs._first_prefill
    assert not obs._opening and not obs._closing


def test_tracing_off_keeps_no_account():
    obs = EngineObs(enabled=False)
    assert obs.recorder.on_close is None
    seq = _Seq("a")
    obs.on_first_scheduled(seq, NOW)
    obs.on_first_token(seq, NOW + 0.1)
    obs.on_finish(seq, NOW + 0.2)
    assert sum(h.count for h in obs.request_hists.values()) == 0
    assert not obs._decoders and not obs._first_prefill and not obs._opening


def test_histogram_takes_n_observations_at_once():
    h, one = Histogram(), Histogram()
    h.observe(0.004, 8)
    for _ in range(8):
        one.observe(0.004)
    assert (h.counts, h.count) == (one.counts, 8)
    assert h.sum == pytest.approx(one.sum)


def test_the_two_families_are_known_everywhere():
    text = EngineObs().render_metrics()
    for key, family in FAMILIES.items():
        assert key in REQUEST_HISTS
        assert vocab.TPU_REQUEST_HISTOGRAMS[key] == family
        assert REGISTRY[family]["kind"] == "histogram"
        assert f"# TYPE {family} histogram" in text
        assert f"{family}_count 0" in text


# -- the tiny engine on the CPU -------------------------------------------------


def config(k, **overrides):
    return config_from_preset("tiny-llama", **{
        "model.dtype": "float32", "scheduler.prefill_buckets": (64, 128),
        "scheduler.max_num_seqs": 4, "scheduler.mixed_batch": False,
        "scheduler.multi_step_window": k > 1,
        "scheduler.max_model_len": 512, "cache.num_blocks": 256,
        **overrides})


def prompt(seed, n):
    return np.random.default_rng(seed).integers(1, 250, n).tolist()


def sp(n):
    return SamplingParams(max_tokens=n, temperature=0.0, ignore_eos=True)


def arrivals(tag, k):
    """Two decoders from the start; while they decode, a prompt of several
    chunks (300 tokens over buckets of 128), a one-token request, a
    scoring-only request and one that is aborted two passes later."""
    late = 3 if k > 1 else 12
    seed = 10 * ord(tag)   # other prompts than the warm-up's: no prefix hit
    return {
        0: [(tag + "a", prompt(seed + 1, 40), sp(72)),
            (tag + "b", prompt(seed + 2, 50), sp(56))],
        late: [(tag + "c", prompt(seed + 3, 300), sp(24))],
        late + 2: [(tag + "d", prompt(seed + 4, 60), sp(1)),
                   (tag + "e", prompt(seed + 5, 30), sp(0)),
                   (tag + "f", prompt(seed + 6, 30), sp(40))],
    }, {late + (9 if k > 1 else 24): [tag + "f"]}


def drive(engine, arrive, aborts, limit=600):
    streams = {}
    for step in range(limit):
        for rid in aborts.get(step, ()):
            engine.abort_request(rid)
        for rid, ids, params in arrive.get(step, ()):
            engine.add_request(rid, prompt_token_ids=ids,
                               sampling_params=params)
        if step > max(arrive) and not engine.has_unfinished():
            break
        for out in engine.step():
            if out.new_token_id >= 0:
                streams.setdefault(out.seq_id, []).append(out.new_token_id)
    assert not engine.has_unfinished() and not engine.has_pending()
    return streams


class _Kept(Histogram):
    """tpu:itl_seconds with every observation kept."""

    def __init__(self):
        super().__init__()
        self.seen = []

    def observe(self, value, n=1):
        self.seen.append((value, n))
        super().observe(value, n)


class _Served:
    def __init__(self, k):
        self.k = k
        self.engine = engine = LLMEngine(config(k))
        drive(engine, *arrivals("w", k))            # compiles everything
        self.seqs = {}
        on_finish = engine.obs.on_finish

        def keep(seq, now=None):
            self.seqs[seq.seq_id] = seq
            on_finish(seq, now)

        engine.obs.on_finish = keep
        self.before = {n: (h.count, h.sum)
                       for n, h in engine.obs.request_hists.items()}
        self.itl = engine.obs.request_hists["itl"] = _Kept()
        self.streams = drive(engine, *arrivals("m", k))
        payload = engine.obs.windows_payload()
        self.records = [w for w in reversed(payload["windows"])
                        if any(s.startswith("m") for s in w["seq_ids"])]
        self.rows = {row[0]: (row, w) for w in self.records
                     for row in w.get("finished", ())}

    def grew(self, name):
        h = self.engine.obs.request_hists[name]
        count, total = self.before[name]
        return h.count - count, h.sum - total


@pytest.fixture(scope="module", params=[1, 8], ids=["k1", "k8"])
def served(request):
    return _Served(request.param)


def _sample_to_close(w):
    """From the start of the record's last ``sample`` span, inside which the
    replay stamps its tokens, to its close (seconds; one clock)."""
    start = [t0 for name, t0, _t1 in w["phases"] if name == "sample"][-1]
    return start / 1e9, w["collected_at"]


def test_the_parts_add_up_and_the_span_is_the_decode_phase(served):
    assert set(served.rows) == {"ma", "mb", "mc"}
    for seq_id, (row, last) in served.rows.items():
        _id, tokens, span, own, prefill, prefills, rest = row
        seq = served.seqs[seq_id]
        assert tokens == seq.num_generated == len(served.streams[seq_id])
        assert own + prefill + rest == pytest.approx(span, abs=2e-9)
        assert own > 0 and prefill >= 0 and rest >= -1e-9
        # The two ends are stamped inside the replay, a moment before their
        # records close (under 1 ms apart on an idle machine): each stamp
        # lies between its record's ``sample`` span and its close, so the
        # span and last_token_time - first_token_time differ by less than
        # those two stretches, whatever the machine's load.
        first = [w for w in served.records if w["kind"] == "prefill"
                 and seq_id in w["seq_ids"] and w["tokens_delivered"]][-1]
        slack = 0.0
        for stamp, w in ((seq.first_token_time, first),
                         (seq.last_token_time, last)):
            sampled, closed = _sample_to_close(w)
            assert sampled - 1e-6 <= stamp <= closed + 1e-6
            slack += closed - sampled
        assert span == pytest.approx(
            last["collected_at"] - first["collected_at"], abs=1e-6)
        stamped = seq.last_token_time - seq.first_token_time
        assert abs(span - stamped) <= slack + 2e-6


def test_a_prompt_admitted_meanwhile_is_the_decoders_wait_not_its_own(served):
    records = served.records

    def prefills_of(seq_id):
        return [w for w in records
                if w["kind"] == "prefill" and seq_id in w["seq_ids"]]

    (a, a_rec), (c, c_rec) = served.rows["ma"], served.rows["mc"]
    opened = a_rec["collected_at"] - a[2]
    # every chunk of c, and d's, e's and f's prefills, closed inside a's span
    others = [w for s in "cdef" for w in prefills_of("m" + s)]
    assert len(prefills_of("mc")) >= 3
    assert all(opened < w["collected_at"] <= a_rec["collected_at"]
               for w in others)
    assert a[5] == len(others) + len(prefills_of("mb"))
    assert a[4] == pytest.approx(
        sum(w["attributed_s"] for w in others + prefills_of("mb")), abs=1e-4)
    # c's own chunks lie before its opening: in none of its parts
    c_opened = c_rec["collected_at"] - c[2]
    inside = [w for w in records if w["kind"] == "prefill"
              and c_opened + 1e-7 < w["collected_at"] <= c_rec["collected_at"]]
    assert not any("mc" in w["seq_ids"] for w in inside)
    assert c[5] == len(inside)
    assert c[4] == pytest.approx(
        sum(w["attributed_s"] for w in inside), abs=1e-4)


def test_a_several_chunk_prompt_counts_once_in_behind(served):
    chunks = [w for w in served.records
              if w["kind"] == "prefill" and "mc" in w["seq_ids"]]
    assert len(chunks) >= 3
    assert "behind_s" in chunks[0]
    assert not any("behind_s" in w for w in chunks[1:])
    for seq_id in "abcdf":   # e, scoring-only, finished inside its prefill
        mine = [w for w in served.records
                if w["kind"] == "prefill" and "m" + seq_id in w["seq_ids"]]
        assert [("behind_s" in w) for w in mine] == (
            [True] + [False] * (len(mine) - 1)), seq_id
    # one observation a request that took a first token (e took none)
    count, total = served.grew("request_prefill_behind")
    assert count == served.grew("prefill_time")[0] == 5
    assert total == pytest.approx(sum(
        w.get("behind_s", 0.0) for w in served.records
        if "me" not in w["seq_ids"][w["rows"]:]), abs=1e-5)
    if served.k > 1:
        # launched behind the window in flight: it waited for the device
        assert chunks[0]["behind"] and chunks[0]["behind_s"] > 0


def test_the_finishing_window_is_inside_the_span(served):
    for seq_id, (row, last) in served.rows.items():
        assert seq_id in last["seq_ids"][:last["rows"]]
        opened = last["collected_at"] - row[2]
        rode = [w for w in served.records if seq_id in w["seq_ids"]
                and opened + 1e-7 < w["collected_at"] <= last["collected_at"]]
        assert rode[-1] is last
        assert row[3] == pytest.approx(
            sum(w["attributed_s"] for w in rode), abs=1e-4)
        assert row[3] > sum(w["attributed_s"] for w in rode[:-1]) + 1e-5


def test_one_token_abort_and_scoring_are_in_no_family(served):
    assert not {"md", "me", "mf"} & set(served.rows)
    assert len(served.streams["md"]) == 1 and "me" not in served.streams
    assert 0 < len(served.streams["mf"]) < 40
    assert served.grew("request_decode_behind")[0] == 3
    obs = served.engine.obs
    assert not obs._decoders and not obs._first_prefill
    assert not obs._opening and not obs._closing


def test_the_familys_sum_is_the_rows_and_a_part_of_decode_time(served):
    rows = [row for row, _w in served.rows.values()]
    behind = served.grew("request_decode_behind")[1]
    assert behind == pytest.approx(sum(r[4] for r in rows), abs=1e-8)
    # its _sum over tpu:decode_time_seconds_sum is the share: the spans of
    # the same requests make up decode_time (d's one token adds nothing, an
    # abort is in neither).
    decode = served.grew("decode_time")[1]
    assert 0 < behind < decode
    assert decode == pytest.approx(sum(r[2] for r in rows), abs=5e-3)


def rows_from_records(windows: list, tokens_of: dict) -> dict:
    """``{seq_id: [span_s, own_s, prefill_s, prefills, rest_s]}`` from flight
    records as ``/debug/windows`` serves them, by the account's definition
    and nothing of the program's: a request's span opens where the record
    that carried the last chunk of its prompt closes and ends where the
    record that brought its ``tokens_of[seq_id]``-th token closes (a decode
    row takes ``k`` tokens a record, the last record what is left); ``own_s``
    adds ``attributed_s`` of the records that list it, ``prefill_s`` /
    ``prefills`` of the other ``prefill`` records closed inside the span,
    ``rest_s`` is the remainder.  For traffic without preemption or
    speculation (the cells'); requests the ring no longer holds whole are
    left out."""
    records = sorted(windows, key=lambda w: w["collected_at"])
    opened, taken, own, before = {}, {}, {}, {}
    prefill_s = prefills = 0.0
    out = {}
    for w in records:
        took, now = w["attributed_s"], w["collected_at"]
        if w["kind"] == "prefill":
            prefill_s += took
            prefills += 1
        rows = w["rows"]
        for seq_id in w["seq_ids"]:
            if seq_id in opened and seq_id not in out:
                own[seq_id][0] += took
                if w["kind"] == "prefill":
                    own[seq_id][1] += took
                    own[seq_id][2] += 1
        for seq_id in w["seq_ids"][rows:]:
            if seq_id not in opened and w["tokens_delivered"]:
                opened[seq_id] = now
                taken[seq_id] = 1
                own[seq_id] = [0.0, 0.0, 0]
                before[seq_id] = (prefill_s, prefills)
        for seq_id in w["seq_ids"][:rows]:
            want = tokens_of.get(seq_id)
            if seq_id not in opened or seq_id in out or want is None:
                continue
            taken[seq_id] += min(w["k"], want - taken[seq_id])
            if taken[seq_id] >= want >= 2:
                span = now - opened[seq_id]
                mine, mine_prefill, mine_n = own[seq_id]
                behind = prefill_s - before[seq_id][0] - mine_prefill
                out[seq_id] = [
                    span, mine, behind,
                    int(prefills - before[seq_id][1] - mine_n),
                    span - mine - behind]
    return out


def test_the_rows_recomputed_offline_are_the_programs(served):
    tokens = {seq_id: row[1] for seq_id, (row, _w) in served.rows.items()}
    again = rows_from_records(served.records, tokens)
    assert set(again) == set(served.rows)
    for seq_id, (row, _w) in served.rows.items():
        span, own, prefill, prefills, rest = again[seq_id]
        # the records serve attributed_s to the microsecond
        slack = 1e-6 * len(served.records)
        assert [span, own, prefill, rest] == pytest.approx(
            [row[2], row[3], row[4], row[6]], abs=slack), seq_id
        assert prefills == row[5]


def test_itl_is_a_tokens_share_of_the_stretch_that_produced_it(served):
    seen = served.itl.seen
    # one observation a token after the first, as ever
    tokens = sum(len(s) - 1 for s in served.streams.values())
    assert sum(n for _v, n in seen) == served.itl.count == tokens
    windows = [w for w in served.records
               if w["kind"] == "decode" and w["tokens_delivered"]]
    shortest = min(w["attributed_s"] for w in windows)
    # A row's stretch between two closes is at least the later record's own
    # time, and its n tokens share it: no gap is under the shortest window's
    # step.  (The old reading: of a K = 8 window's gaps seven were the
    # microseconds between two appends of the replay.)
    assert min(v * n for v, n in seen) >= shortest - 2e-6
    assert min(v for v, _n in seen) >= (shortest - 2e-6) / served.k
    if served.k > 1:
        assert max(n for _v, n in seen) == 8
    else:
        assert {n for _v, n in seen} == {1}
    # and the gaps of a request add up to its span
    assert served.itl.sum == pytest.approx(
        sum(row[2] for row, _w in served.rows.values())
        + _aborted_span(served), abs=1e-6)


def _aborted_span(served):
    """f's gaps: from its first token's record to the last record that gave
    it tokens before the abort."""
    mine = [w for w in served.records if "mf" in w["seq_ids"]]
    opened = next(w for w in mine if w["kind"] == "prefill")
    gave = [w for w in mine if w["kind"] == "decode" and (
        w["tokens_delivered"])]
    n = len(served.streams["mf"]) - 1
    taken, last = 0, opened
    for w in gave:
        if taken >= n:
            break
        taken += min(w["k"], n - taken)
        last = w
    return last["collected_at"] - opened["collected_at"]


def test_the_records_cover_the_wall_clock(served):
    """What the account rests on: ``attributed_s`` telescopes, so the
    records and the stretches with nothing in flight add up to the wall
    clock; the recorder's prefill totals are the ``prefill`` records'."""
    payload = served.engine.obs.windows_payload()
    records = sorted(payload["windows"], key=lambda w: w["collected_at"])
    assert payload["recorded"] == len(records)   # the ring holds them all
    holes = sum(max(0.0, w["dispatched_at"] - before["collected_at"])
                for before, w in zip(records, records[1:]))
    assert sum(w["attributed_s"] for w in records) + holes == pytest.approx(
        records[-1]["collected_at"] - records[0]["dispatched_at"],
        abs=1e-6 * len(records))
    prefills = [w for w in records if w["kind"] == "prefill"]
    recorder = served.engine.obs.recorder
    assert recorder.prefills == len(prefills)
    assert recorder.prefill_s == pytest.approx(
        sum(w["attributed_s"] for w in prefills), abs=1e-6 * len(records))


def test_tracing_off_takes_no_stamp_adds_no_field_keeps_the_tokens(served):
    engine = LLMEngine(config(served.k, **{"obs.tracing": False}))
    seqs = {}
    finish = engine._finish_seq_now

    def keep(seq, reason):
        seqs[seq.seq_id] = seq
        return finish(seq, reason)

    engine._finish_seq_now = keep
    streams = drive(engine, *arrivals("m", served.k))
    assert streams == served.streams
    assert {"ma", "mb", "mc", "md"} <= set(seqs)
    for seq in seqs.values():
        assert seq.last_token_time is None
        assert seq.first_scheduled_time is None
    obs = engine.obs
    assert obs.recorder.on_close is None
    assert obs.recorder.windows_recorded == 0 and not obs.recorder.snapshot()
    assert (obs.recorder.prefill_s, obs.recorder.prefills) == (0.0, 0)
    assert sum(h.count for h in obs.request_hists.values()) == 0
    assert not obs._decoders and not obs._first_prefill and not obs._opening


# -- behind its API server --------------------------------------------------------


async def _engine_client():
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.server.api_server import build_engine_app
    from production_stack_tpu.engine.server.async_engine import AsyncEngine

    cfg = config_from_preset(
        "tiny-llama", **{"cache.num_blocks": 128, "scheduler.max_num_seqs": 4,
                         "scheduler.prefill_buckets": (16, 32),
                         "scheduler.mixed_batch": False})
    engine = AsyncEngine(cfg)
    server = TestServer(build_engine_app(engine, "tiny-llama"))
    await server.start_server()
    return engine, TestClient(server)


async def _stream(client, request_id, max_tokens):
    resp = await client.post(
        "/v1/completions",
        json={"model": "tiny-llama", "prompt": "hi " + request_id,
              "max_tokens": max_tokens, "ignore_eos": True, "stream": True,
              "temperature": 0},
        headers={"x-request-id": request_id})
    await resp.read()
    return resp.status


def _family(text, family):
    got = {}
    for line in text.splitlines():
        if line.startswith(family + "_sum ") or line.startswith(
                family + "_count "):
            got[line.split()[0][len(family) + 1:]] = float(line.split()[1])
    return got


async def test_over_http_zero_from_boot_then_the_sums_are_the_rows():
    engine, client = await _engine_client()
    try:
        boot = await (await client.get("/metrics")).text()
        for family in FAMILIES.values():
            assert f"# TYPE {family} histogram" in boot
            assert _family(boot, family) == {"sum": 0.0, "count": 0.0}
        statuses = await asyncio.gather(*[
            _stream(client, f"gap-{i}", n)
            for i, n in enumerate((24, 17, 1, 9))])
        assert statuses == [200] * 4
        text = await (await client.get("/metrics")).text()
        got = {key: _family(text, family) for key, family in FAMILIES.items()}
        assert got["request_decode_behind"]["count"] == 3.0  # not the 1-token
        assert got["request_prefill_behind"]["count"] == 4.0
        assert got["request_prefill_behind"]["count"] == _family(
            text, "tpu:prefill_time_seconds")["count"]
        windows = await (await client.get("/debug/windows")).json()
        rows = [row for w in windows["windows"]
                for row in w.get("finished", ())]
        assert sorted(r[0] for r in rows) == ["gap-0", "gap-1", "gap-3"]
        assert sorted(r[1] for r in rows) == [9, 17, 24]
        assert got["request_decode_behind"]["sum"] == pytest.approx(
            sum(max(0.0, r[4]) for r in rows), abs=1e-7)
        assert got["request_decode_behind"]["sum"] <= _family(
            text, "tpu:decode_time_seconds")["sum"]
        assert "totals" not in windows
        assert got["request_prefill_behind"]["sum"] == pytest.approx(
            sum(w.get("behind_s", 0.0) for w in windows["windows"]),
            abs=1e-5)
        # one observation a token after the first
        assert _family(text, "tpu:itl_seconds")["count"] == 23 + 16 + 8
        # the request's timeline carries the same parts
        trace = await (await client.get("/debug/requests/gap-0")).json()
        spans = {s["name"]: s for s in trace["spans"]}
        row = next(r for r in rows if r[0] == "gap-0")
        attrs = spans["engine.decode"]["attrs"]
        assert attrs["prefills"] == row[5]
        assert [attrs["own_s"], attrs["prefill_s"], attrs["rest_s"]] == (
            pytest.approx([row[3], row[4], row[6]], abs=1e-6))
        assert spans["engine.prefill"]["attrs"]["behind_s"] >= 0.0
        assert row in trace["windows"][-1]["finished"]   # where it finished
    finally:
        await client.close()
        await engine.close()
