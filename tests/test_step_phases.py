"""The step loop on one span mechanism (``EngineObs.phase``): names of the
jitted programs, phase spans on the flight records and in the profiler's
trace, per-dispatch counters.  CPU, tiny model; nothing here times anything.
"""

import glob
import json
import os
import re
import time

import jax
import pytest

from production_stack_tpu.engine.config import config_from_preset
from production_stack_tpu.engine.core.engine import LLMEngine
from production_stack_tpu.engine.core.sequence import SamplingParams
from production_stack_tpu.obs import compile_tracker as ct
from production_stack_tpu.obs.engine import PHASES, STEP_PHASES, EngineObs

# Every jitted step function, by the one name it is jitted, tracked and
# traced under (LLMEngine._jit).
PROGRAMS = (
    "prefill_fn", "decode_fn", "mixed_fn", "sample_fn", "window_fn",
    "spec_window_fn", "mixed_window_fn", "win_unpack_fn", "win_advance_fn",
    "win_occurrence_fn", "pipe_unpack_fn", "pipe_advance_fn",
    "penalties_fn", "logprobs_fn",
)

# Engine set-ups that between them run every dispatch path and program.
SETUPS = {
    # K=8 windows, chained; waiting prompts ride a mixed window; one
    # request with a penalty builds the occurrence state.
    "window": ({}, {"r2": {"presence_penalty": 0.5}}),
    # Single-token stepping: pipelined decode, the fused K=1 mixed step,
    # and the synchronous host-sampled step (logprobs, a penalty).
    "single": ({"scheduler.multi_step_window": False},
               {"r0": {"logprobs": True, "top_logprobs": 2, "max_tokens": 4},
                "r1": {"presence_penalty": 0.5, "max_tokens": 6}}),
    "spec_window": ({"scheduler.speculative_ngram": 2}, {}),
}


def drive(overrides, per_request, tracing=True):
    config = config_from_preset(
        "tiny-llama",
        **{"cache.num_blocks": 64, "scheduler.max_num_seqs": 4,
           "scheduler.prefill_buckets": (16, 32), "obs.tracing": tracing,
           **overrides},
    )
    eng = LLMEngine(config)
    prompts = {"r0": [3, 5, 7, 11], "r1": [4, 5, 7, 11, 2],
               "r2": [5, 5, 7, 11, 2, 9]}
    # All queued at once: r0 prefills alone, the other two ride the first
    # decode dispatch as chunks, then all three decode together.
    for rid, ids in prompts.items():
        eng.add_request(rid, prompt_token_ids=ids,
                        sampling_params=SamplingParams(**{
                            "max_tokens": 12, "ignore_eos": True,
                            **per_request.get(rid, {})}))
    while eng.has_unfinished():
        eng.step()
    return eng


def module_name(fn, args, kwargs) -> str:
    """The name the program ``fn`` lowers under for these arguments."""
    def spec(x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    args, kwargs = jax.tree_util.tree_map(spec, (args, kwargs))
    text = fn.lower(*args, **kwargs).as_text()
    return re.search(r"module @(\S+)", text).group(1)


@pytest.fixture(scope="module")
def runs():
    """{setup: windows payload} and {program: the name it lowered under},
    from one drive of each set-up with every tracked call's lowering
    watched."""
    lowered = {}
    call = ct._TrackedJit.__call__

    def watched(self, *args, **kwargs):
        if self._name not in lowered:
            lowered[self._name] = module_name(self._fn, args, kwargs)
        return call(self, *args, **kwargs)

    ct._TrackedJit.__call__ = watched
    try:
        payloads = {name: drive(*setup).obs.windows_payload()
                    for name, setup in SETUPS.items()}
    finally:
        ct._TrackedJit.__call__ = call
    return payloads, lowered


@pytest.mark.parametrize("program", PROGRAMS)
def test_each_step_program_lowers_under_its_tracker_name(runs, program):
    _payloads, lowered = runs
    assert lowered[program] == "jit_" + program


def test_no_tracked_program_is_left_out_of_the_list(runs):
    _payloads, lowered = runs
    assert set(lowered) == set(PROGRAMS)


# (set-up, what picks the path's records, a program they must name)
PATHS = {
    "prefill": ("window", lambda w: w["kind"] == "prefill", "prefill_fn"),
    "window": ("window", lambda w: w["kind"] == "decode" and w["k"] > 1
               and not w["provisional"], "window_fn"),
    "chained_window": ("window", lambda w: w["kind"] == "decode"
                       and w["provisional"], "win_advance_fn"),
    "mixed_window": ("window", lambda w: w["kind"] == "mixed",
                     "mixed_window_fn"),
    "pipelined_decode": ("single", lambda w: w["kind"] == "decode"
                         and w["provisional"], "pipe_advance_fn"),
    "sync_decode": ("single", lambda w: w["kind"] == "decode"
                    and "logprobs_fn" in w["programs"], "decode_fn"),
    "mixed_step": ("single", lambda w: w["kind"] == "mixed", "mixed_fn"),
    "spec_window": ("spec_window", lambda w: w["kind"] == "spec",
                    "spec_window_fn"),
}


@pytest.mark.parametrize("path", sorted(PATHS))
def test_every_dispatch_path_leaves_phases_programs_and_counts(runs, path):
    setup, pick, program = PATHS[path]
    records = [w for w in runs[0][setup]["windows"] if pick(w)]
    assert records, f"the {setup} set-up never took the {path} path"
    for w in records:
        phases = w["phases"]
        # A span in which a jit call compiled is named so; the rest keep
        # to the closed set too.
        assert phases and {p[0] for p in phases} <= set(PHASES)
        # Ordered, never overlapping.
        assert all(a[1] <= a[2] <= b[1] for a, b in zip(phases, phases[1:]))
        assert phases[0][0] in ("build", "compile")
        # The first launch opens launch_ns, after the first build began ...
        assert program in w["programs"]
        assert len(w["programs"]) == len(w["program_ns"])
        assert w["program_ns"] == sorted(w["program_ns"])
        assert w["launch_ns"] == w["program_ns"][0]
        assert phases[0][1] <= w["launch_ns"]
        assert w["program_ns"][-1] <= phases[-1][2]
        if w["collected_ns"] is not None:
            # ... and the read-back closes collected_ns: every launch and
            # collect span of the dispatch lies between the two.
            assert w["launch_ns"] < w["collected_ns"] <= phases[-1][2]
            assert w["collected_ns"] in [p[2] for p in phases]
            for name, start, end in phases:
                if name in ("launch", "collect") and start >= w["launch_ns"]:
                    assert end <= w["collected_ns"] or name == "launch"
        assert w["new_tokens"] <= w["bucket_tokens"] if w.get(
            "bucket_tokens") else "new_tokens" not in w
        if w.get("bucket_tokens"):
            # The flash prefill kernel's kv tiles for these chunks: some
            # are live, and the static grid holds more than are.
            assert 0 < w["kv_tiles_live"] < w["kv_tiles_grid"]
        else:
            assert "kv_tiles_live" not in w
        if w["rows"]:
            # Whole 16-token blocks, at least one a row.
            assert w["kv_tokens"] >= 16 * w["rows"]
            assert w["kv_tokens"] % 16 == 0
        else:
            assert "kv_tokens" not in w and w["new_tokens"] > 0
        assert w["host_gap_s"] >= 0.0


def test_loose_phases_are_the_spans_that_belong_to_no_dispatch(runs):
    payload = runs[0]["single"]
    loose = payload["phases"]
    assert loose and {p[0] for p in loose} <= {"schedule", "wait", "emit"}
    assert all(a[2] <= b[1] for a, b in zip(loose, loose[1:]))
    assert payload["profile"] == {}
    # One request's view leaves them out.
    eng = drive({}, {})
    assert "phases" not in eng.obs.windows_payload(seq="r0")


@pytest.mark.parametrize("setup", sorted(SETUPS))
def test_the_records_older_fields_read_as_before_on_a_fixed_plan(runs, setup):
    """Same requests, same plan: what the recorder gave before the records
    were opened ahead of the work (``fixtures/flight_records_before_pr25
    .json``, written by the parent commit) is what it gives now.  (PR 60
    changed the plan of the ``window`` set-up's end, and those two records
    with it: the file's note.)"""
    with open(os.path.join(os.path.dirname(__file__), "fixtures",
                           "flight_records_before_pr25.json")) as f:
        before = json.load(f)[setup]
    recs = sorted(runs[0][setup]["windows"], key=lambda w: w["window_id"])
    assert len(recs) == len(before)
    for w, old in zip(recs, before):
        zero = old.pop("host_gap_is_zero")
        assert {k: w.get(k) for k in old} == old
        assert (w["host_gap_s"] == 0.0) == zero
        assert w["dispatched_at"] <= w["collected_at"]
        assert w["host_s"] > 0.0 and w["attributed_s"] >= 0.0


def test_phase_nests_lands_on_its_record_and_feeds_every_sink():
    seen = []

    class Annotation:
        def __init__(self, name, **kw):
            self.what = (name, kw)

        def __enter__(self):
            seen.append(("enter",) + self.what)

        def __exit__(self, *exc):
            seen.append(("exit",) + self.what)

    obs = EngineObs(enabled=True, annotation=Annotation)
    a = obs.recorder.on_dispatch("decode", rows=1)
    b = obs.recorder.on_dispatch("prefill")
    with obs.phase("mixed"):                      # encloses, lands nowhere
        with obs.phase("build", a):
            pass
        with obs.phase("sample", a):
            with obs.phase("launch"):             # inherits a, nested
                obs.compile_tracker.on_launch("sample_fn")
            with obs.phase("collect", b, family=False):   # b's, nested
                obs.compile_tracker.on_launch("penalties_fn")
            obs.compile_tracker.on_launch("logprobs_fn")
    with obs.phase("collect", a):
        pass
    obs.compile_tracker.on_launch("prefill_fn")   # no span open: no record
    with obs.phase("schedule"):
        pass
    with obs.phase("wait"):
        pass
    with obs.phase("wait"):
        pass
    # A record keeps its top-level spans only: ordered and disjoint.
    assert [p[0] for p in a.phases] == ["build", "sample", "collect"]
    assert b.phases == []
    assert a.programs == ["sample_fn", "logprobs_fn"]
    assert b.programs == ["penalties_fn"]
    assert a.launch_ns == a.program_ns[0] and b.launch_ns == b.program_ns[0]
    assert a.phases[1][1] <= a.launch_ns <= b.launch_ns <= a.phases[1][2]
    assert a.collected_ns == a.phases[2][2] and b.collected_ns is None
    # The enclosing span rides neither record nor ring; consecutive waits
    # are one entry.
    loose = obs.windows_payload()["phases"]
    assert [p[0] for p in loose] == ["schedule", "wait"]
    # Every span reaches its histogram family where it has one, nested or
    # not, unless told otherwise.
    counts = {k: h.count for k, h in obs.step_hists.items()}
    assert counts == {"schedule": 1, "dispatch": 0, "collect": 1,
                      "sample": 1, "mixed": 1}
    assert set(counts) == set(STEP_PHASES)
    # ... and the profiler, with the window it belongs to.
    assert ("enter", "pstpu.build", {"window_id": a.window_id}) in seen
    assert ("enter", "pstpu.launch", {"window_id": a.window_id}) in seen
    assert ("enter", "pstpu.collect", {"window_id": b.window_id}) in seen
    assert ("enter", "pstpu.mixed", {}) in seen
    assert ("enter", "pstpu.wait", {}) in seen
    entered = [s[0] for s in seen]
    assert entered.count("enter") == entered.count("exit") == 9
    # A span inside which a jit call compiled says so on the record.
    with obs.phase("launch", a):
        obs.compile_tracker.record("window_fn", "sig", 1.0)
    assert a.phases[-1][0] == "compile"


def test_phase_is_state_free_with_tracing_off():
    obs = EngineObs(enabled=False, annotation=lambda *a, **k: 1 / 0)
    assert obs.phase("schedule") is obs.phase("wait", None, family=False)
    with obs.phase("schedule"):
        with obs.phase("build", None):
            pass
    payload = obs.windows_payload()
    assert payload["phases"] == [] and payload["windows"] == []
    assert sum(h.count for h in obs.step_hists.values()) == 0
    assert obs.compile_tracker.on_launch is None
    assert obs._depth == 0 and obs._open_rec is None
    eng = drive({}, {}, tracing=False)
    assert eng.obs.windows_payload()["phases"] == []
    assert sum(h.count for h in eng.obs.step_hists.values()) == 0


def test_a_profiler_session_holds_the_anchor_and_the_phase_spans(tmp_path):
    from jax.profiler import ProfileData

    eng = drive({}, {})          # compiled: the traced drive below is short
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    before = time.time_ns()
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation("pstpu.anchor",
                                          unix_ns=time.time_ns()):
            pass
        eng.add_request("t0", prompt_token_ids=[3, 5, 7, 11],
                        sampling_params=SamplingParams(max_tokens=10,
                                                       ignore_eos=True))
        while eng.has_unfinished():
            eng.step()
    finally:
        jax.profiler.stop_trace()
    after = time.time_ns()
    path = sorted(glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    spans = [(e.name, e.start_ns, dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name.startswith("pstpu.")]
    anchors = [s for s in spans if s[0] == "pstpu.anchor"]
    assert len(anchors) == 1 and before <= anchors[0][2]["unix_ns"] <= after
    # Trace time + offset = the host's clock: the anchor gives the offset,
    # and every record's phase then lies where its span does.
    offset = anchors[0][2]["unix_ns"] - anchors[0][1]
    names = {s[0] for s in spans}
    assert {"pstpu.schedule", "pstpu.dispatch", "pstpu.build",
            "pstpu.launch", "pstpu.collect", "pstpu.sample"} <= names
    mine = {w["window_id"]: w for w in eng.obs.windows_payload()["windows"]
            if "t0" in w["seq_ids"]}
    assert mine
    tagged = [s for s in spans if s[2].get("window_id") in mine]
    assert {s[2]["window_id"] for s in tagged} == set(mine)
    for name, start_ns, stats in tagged:
        phases = mine[stats["window_id"]]["phases"]
        inside = any(p[1] - 2_000_000 <= start_ns + offset <= p[2] + 2_000_000
                     for p in phases)
        assert inside, (name, stats)   # same instant, two clocks
