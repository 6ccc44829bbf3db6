"""The sampler's paths (engine/sampling.py: sample_tokens).

A batch in which no row samples takes the argmax and nothing else; one in
which a row samples draws, and sorts the vocabulary once, only if a sampling
row set top-k or top-p.  Whatever the path, the tokens are those of the
function this one replaced, kept here verbatim as the plain reference: two
full sorts, three softmaxes and a draw for every batch, the result thrown
away by the last line where no row samples.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine import sampling
from production_stack_tpu.engine.core import step_programs as sp
from production_stack_tpu.engine.core.sequence import SamplingParams
from production_stack_tpu.engine.sampling import sample_tokens
from tests.test_sampling_surface import drain, make_engine

NEG_INF = -1e30


# -- the plain reference: the parent's function, verbatim --------------------


def _ref_apply_top_k(logits, top_k):
    V = logits.shape[-1]
    sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]  # [S, V]
    k = jnp.clip(top_k, 1, V)
    kth = jnp.take_along_axis(sorted_desc, (k - 1)[:, None], axis=-1)  # [S,1]
    masked = jnp.where(logits < kth, NEG_INF, logits)
    return jnp.where((top_k > 0)[:, None], masked, logits)


def _ref_apply_top_p(logits, top_p):
    sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    cumulative = jnp.cumsum(probs, axis=-1)
    keep = (cumulative - probs) < top_p[:, None]
    threshold = jnp.min(
        jnp.where(keep, sorted_desc, jnp.inf), axis=-1, keepdims=True
    )
    masked = jnp.where(logits < threshold, NEG_INF, logits)
    return jnp.where((top_p < 1.0)[:, None], masked, logits)


def _ref_apply_min_p(logits, min_p):
    probs = jax.nn.softmax(logits, axis=-1)
    cut = jnp.max(probs, axis=-1, keepdims=True) * min_p[:, None]
    masked = jnp.where(probs < cut, NEG_INF, logits)
    return jnp.where((min_p > 0)[:, None], masked, logits)


def ref_sample_tokens(logits, temperature, top_p, top_k, step_key, seq_seeds,
                      min_p=None):
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)

    safe_temp = jnp.where(temperature > 0, temperature, 1.0)
    scaled = logits / safe_temp[:, None]
    scaled = _ref_apply_top_k(scaled, top_k)
    scaled = _ref_apply_top_p(scaled, top_p)
    if min_p is not None:
        scaled = _ref_apply_min_p(scaled, min_p)

    keys = jax.vmap(lambda s: jax.random.fold_in(step_key, s))(seq_seeds)
    sampled = jax.vmap(
        lambda key, row: jax.random.categorical(key, row)
    )(keys, scaled).astype(jnp.int32)

    return jnp.where(temperature > 0, sampled, greedy)


# -- batches -----------------------------------------------------------------

S, V = 8, 517  # rows; a vocabulary that is no power of two
SEEDS = np.asarray([0, 17, 3, 99, 4, 5, 2**31 - 1, 7], np.int32)
GREEDY = np.zeros((S,), np.float32)
HOT = np.asarray([0.7, 1.0, 0.3, 1.5, 0.9, 2.0, 0.05, 1.0], np.float32)
# Rows 1, 4 and 6 greedy among sampling rows; 6 and 7 stand for padding
# (temperature 0, top_p 1, top_k 0, min_p 0, seed 0) in the padded batches.
MIXED = np.asarray([0.7, 0.0, 0.3, 1.5, 0.0, 2.0, 0.0, 0.0], np.float32)
NO_P, NO_K = np.ones((S,), np.float32), np.zeros((S,), np.int32)
SOME_K = np.asarray([5, 0, 1, 40, 3, V, 2 * V, 7], np.int32)
SOME_P = np.asarray([0.9, 1.0, 0.5, 0.05, 0.99, 0.7, 0.3, 1.0], np.float32)
NO_MIN = np.zeros((S,), np.float32)
SOME_MIN = np.asarray([0.1, 0.0, 0.3, 0.0, 0.05, 0.0, 0.9, 0.0], np.float32)


def _logits(kind, seed):
    rng = np.random.default_rng(seed)
    x = (3.0 * rng.standard_normal((S, V))).astype(np.float32)
    if kind == "ties":
        # One decimal: dozens of equal values a row, so the k-th value is
        # shared by tokens on both sides of the cut, which all stay.
        x = np.round(x, 1)
    elif kind == "floor":
        # What shape_logits leaves behind: the min_tokens floor adds -1e9
        # to the stop ids, a mask hands in -inf; one row all but banned, so
        # that its k-th value is itself a banned one.
        x[:, ::7] += np.float32(-1e9)
        x[:, 3::11] = -np.inf
        x[2, 4:] = -np.inf
        x[5, 2:] += np.float32(-1e9)
    return x


CASES = {
    # (a) .. (h) of the issue, then what the branches could get wrong.
    "a-all-greedy": ("plain", GREEDY, NO_P, NO_K, NO_MIN),
    "a-all-greedy-filters-set": ("ties", GREEDY, SOME_P, SOME_K, SOME_MIN),
    "b-all-sampling-defaults": ("plain", HOT, NO_P, NO_K, NO_MIN),
    "c-top-k-only": ("plain", HOT, NO_P, SOME_K, NO_MIN),
    "d-top-p-only": ("plain", HOT, SOME_P, NO_K, NO_MIN),
    "e-both": ("plain", HOT, SOME_P, SOME_K, NO_MIN),
    "e-both-ties-at-kth": ("ties", HOT, SOME_P, SOME_K, NO_MIN),
    "f-mixed-rows-padded": ("plain", MIXED, SOME_P * (MIXED > 0) + (MIXED <= 0),
                            SOME_K * (MIXED > 0), NO_MIN),
    "f-mixed-only-greedy-rows-filter": (
        "ties", MIXED, np.where(MIXED > 0, 1.0, SOME_P).astype(np.float32),
        SOME_K * (MIXED <= 0), NO_MIN),
    "f-one-sampling-row": ("ties", np.eye(1, S, 3, dtype=np.float32)[0],
                           SOME_P, SOME_K, NO_MIN),
    "g-min-p-no-sort": ("plain", HOT, NO_P, NO_K, SOME_MIN),
    "g-min-p-sorted": ("ties", MIXED, SOME_P, SOME_K, SOME_MIN),
    "h-floor-and-inf-greedy": ("floor", GREEDY, NO_P, NO_K, NO_MIN),
    "h-floor-and-inf-defaults": ("floor", HOT, NO_P, NO_K, NO_MIN),
    "h-floor-and-inf-sorted": ("floor", MIXED, SOME_P, SOME_K, SOME_MIN),
}

NEW = jax.jit(sample_tokens)
REF = jax.jit(ref_sample_tokens)


@pytest.mark.parametrize("with_min_p", [True, False], ids=["min_p", "no-min_p"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_tokens_equal_the_parents(case, with_min_p):
    kind, temps, top_p, top_k, min_p = CASES[case]
    for seed in range(3):
        args = (
            jnp.asarray(_logits(kind, seed)), jnp.asarray(temps, jnp.float32),
            jnp.asarray(top_p, jnp.float32), jnp.asarray(top_k, jnp.int32),
            jax.random.PRNGKey(1234 + seed), jnp.asarray(SEEDS),
        )
        kw = {"min_p": jnp.asarray(min_p)} if with_min_p else {}
        got, want = np.asarray(NEW(*args, **kw)), np.asarray(REF(*args, **kw))
        np.testing.assert_array_equal(got, want, f"{case} seed {seed}")
        assert got.dtype == np.int32
        if not (temps > 0).any():
            np.testing.assert_array_equal(
                got, np.argmax(np.asarray(args[0]), axis=-1))


@pytest.mark.parametrize("kind", ["plain", "ties", "floor"])
def test_one_sort_gives_the_two_sorts_masks(kind):
    """Not only the drawn token: the filtered logits the draw reads are the
    parent's, element for element (a draw could hide a differing mask on a
    token of little mass)."""
    logits = jnp.asarray(_logits(kind, 7)) / jnp.asarray(HOT)[:, None]
    k, p = jnp.asarray(SOME_K), jnp.asarray(SOME_P)
    want = _ref_apply_top_p(_ref_apply_top_k(logits, k), p)
    got = jax.jit(sampling._apply_top_k_top_p)(logits, k, p)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("temps, top_p, top_k, want", [
    (GREEDY, SOME_P, SOME_K, False),          # nobody samples
    (HOT, NO_P, NO_K, False),                 # a draw, no filter
    (MIXED, NO_P, SOME_K * (MIXED <= 0), False),  # only greedy rows set one
    (MIXED, NO_P, np.eye(1, S, 0, dtype=np.int32)[0] * 5, True),
    (MIXED, 1.0 - 0.1 * np.eye(1, S, 3, dtype=np.float32)[0], NO_K, True),
], ids=["greedy", "defaults", "greedy-rows-only", "top-k-row", "top-p-row"])
def test_needs_sort_is_one_expression_on_host_and_device(temps, top_p, top_k,
                                                         want):
    host = sampling.needs_sort(temps, np.asarray(top_p, np.float32), top_k)
    device = jax.jit(sampling.needs_sort)(
        jnp.asarray(temps), jnp.asarray(top_p, jnp.float32),
        jnp.asarray(top_k))
    assert bool(host) is want and bool(device) is want


# -- the step program: where the sorts stand, and the key schedule -----------

BS, K, ROWS, WV = 4, 8, 4, 96
TABLE = jnp.asarray(
    np.random.default_rng(5).standard_normal((WV, WV)) * 2.0, jnp.float32
)


def stub_decode(params, *, tokens, positions, block_tables, ctx_lens,
                slot_block_ids, slot_offsets, kv_caches):
    logits = params[(tokens * 7 + positions) % WV]
    return logits, kv_caches.at[slot_block_ids, slot_offsets].set(tokens)


def _window_inputs(temps, top_ps, top_ks, k):
    return dict(
        tokens=jnp.asarray([1, 2, 3, 4], jnp.int32),
        positions=jnp.asarray([2, 5, 9, 1], jnp.int32),
        ctx_lens=jnp.asarray([3, 6, 10, 2], jnp.int32),
        done=jnp.asarray([False, False, False, True]),
        min_left=jnp.zeros((ROWS,), jnp.int32),
        block_tables=jnp.arange(1, 1 + ROWS * 6, dtype=jnp.int32).reshape(
            ROWS, 6),
        max_steps=jnp.full((ROWS,), k, jnp.int32),
        temps=jnp.asarray(temps, jnp.float32),
        top_ps=jnp.asarray(top_ps, jnp.float32),
        top_ks=jnp.asarray(top_ks, jnp.int32),
        min_ps=jnp.zeros((ROWS,), jnp.float32),
        seq_seeds=jnp.asarray([11, 17, 0, 0], jnp.int32),
        stop_ids=jnp.full((ROWS, 1), -1, jnp.int32),
        presence=jnp.zeros((ROWS,), jnp.float32),
        frequency=jnp.zeros((ROWS,), jnp.float32),
        repetition=jnp.ones((ROWS,), jnp.float32),
        counts=jnp.zeros((ROWS, 1), jnp.int16),
        seen=jnp.zeros((ROWS, 1), jnp.bool_),
        kv_caches=jnp.full((1 + ROWS * 6, BS), -1, jnp.int32),
    )


def _window(k):
    return jax.jit(
        sp.window_program(stub_decode, block_size=BS, n_steps=k, vocab=WV),
        static_argnames=("use_penalties", "use_min_floor"),
    )


STATIC = dict(use_penalties=False, use_min_floor=False)


@pytest.mark.parametrize("temps, top_ps, top_ks", [
    ([0.8, 1.2, 0.0, 0.0], [1.0] * 4, [0] * 4),
    ([0.8, 1.2, 0.0, 0.0], [0.8, 1.0, 1.0, 1.0], [0, 5, 0, 0]),
    ([0.0] * 4, [1.0] * 4, [0] * 4),
], ids=["defaults", "sorted", "greedy"])
def test_window_of_eight_equals_eight_windows_of_one(temps, top_ps, top_ks):
    """Seeded sampling is bit-identical across window sizes: iteration t of
    a window dispatched at counter c draws with PRNGKey(seed + c + t)."""
    inp = _window_inputs(temps, top_ps, top_ks, K)
    emitted, _, kv = _window(K)(
        TABLE, key_base=jnp.int32(100), **STATIC, **inp)

    one, step_inp, singles = _window(1), dict(inp), []
    step_inp["max_steps"] = jnp.ones((ROWS,), jnp.int32)
    for t in range(K):
        out, carry, step_kv = one(
            TABLE, key_base=jnp.int32(100 + t), **STATIC, **step_inp)
        step_inp.update(carry, kv_caches=step_kv)
        singles.append(np.asarray(out)[0])
    np.testing.assert_array_equal(np.asarray(emitted), np.stack(singles))
    np.testing.assert_array_equal(np.asarray(kv), np.asarray(step_kv))
    if any(t > 0 for t in temps):
        # The draw is a draw: the sampling rows left the greedy road.
        greedy, _, _ = _window(K)(
            TABLE, key_base=jnp.int32(100), **STATIC,
            **_window_inputs([0.0] * 4, top_ps, top_ks, K))
        assert (np.asarray(greedy)[:, :2] != np.asarray(emitted)[:, :2]).any()


_REFERENCE = re.compile(
    r"(to_apply|body|condition|true_computation|false_computation|calls)"
    r"=%?([\w.\-]+)"
)
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")


def _computations(hlo_text):
    """{name: (body text, is ENTRY)} of an HLO module's text."""
    out, name = {}, None
    for line in hlo_text.splitlines():
        head = re.match(r"^(ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$", line)
        if head and not line.startswith(" "):
            name = head.group(2)
            out[name] = ([], bool(head.group(1)))
        elif name is not None:
            out[name][0].append(line)
    return {n: ("\n".join(body), entry) for n, (body, entry) in out.items()}


def _reached_outside_a_branch(comps):
    """Computations the entry reaches without stepping into a branch of a
    conditional: what runs whatever the predicates say."""
    todo = [n for n, (_, entry) in comps.items() if entry]
    assert len(todo) == 1
    seen = set(todo)
    while todo:
        body, _ = comps[todo.pop()]
        branch = set()
        for names in _BRANCHES.findall(body):
            branch.update(n.strip().lstrip("%") for n in names.split(","))
        for kind, ref in _REFERENCE.findall(body):
            if kind in ("true_computation", "false_computation"):
                branch.add(ref)
                continue
            if ref in comps and ref not in seen and ref not in branch:
                seen.add(ref)
                todo.append(ref)
    return seen


def _has_sort(body):
    return re.search(r"\bsort\(", body) is not None


@pytest.mark.parametrize("program", ["window_fn", "sample_fn"])
def test_a_sort_stands_only_inside_a_conditionals_branch(program):
    """The lowered text of the step program: every ``sort`` is in a
    computation the entry reaches only through a conditional's branch, so a
    batch whose predicates are false runs none."""
    if program == "window_fn":
        inp = _window_inputs([0.0] * 4, [1.0] * 4, [0] * 4, K)
        lowered = _window(K).lower(
            TABLE, key_base=jnp.int32(100), **STATIC, **inp)
    else:
        lowered = NEW.lower(
            jnp.zeros((S, V), jnp.float32), jnp.asarray(GREEDY),
            jnp.asarray(NO_P), jnp.asarray(NO_K), jax.random.PRNGKey(0),
            jnp.asarray(SEEDS), min_p=jnp.asarray(NO_MIN))
    comps = _computations(lowered.compiler_ir(dialect="hlo").as_hlo_text())
    with_sort = {n for n, (body, _) in comps.items() if _has_sort(body)}
    assert with_sort, "the sampler's sort is gone: this test reads nothing"
    always = _reached_outside_a_branch(comps)
    assert "conditional(" in "".join(comps[n][0] for n in always)
    assert not with_sort & always, sorted(with_sort & always)
    # And the reader can tell: the parent's function keeps its sorts in the
    # open.
    ref = REF.lower(
        jnp.zeros((S, V), jnp.float32), jnp.asarray(GREEDY),
        jnp.asarray(NO_P), jnp.asarray(NO_K), jax.random.PRNGKey(0),
        jnp.asarray(SEEDS), min_p=jnp.asarray(NO_MIN))
    ref_comps = _computations(ref.compiler_ir(dialect="hlo").as_hlo_text())
    ref_always = _reached_outside_a_branch(ref_comps)
    assert any(_has_sort(ref_comps[n][0]) for n in ref_always)


# -- the counters that say how often each path engages -----------------------


@pytest.mark.parametrize("params, sorts", [
    ({}, False),
    ({"top_k": 5, "top_p": 0.5}, False),       # greedy: the filters are moot
    ({"temperature": 0.8, "seed": 3}, False),  # a draw, no filter
    ({"temperature": 0.8, "seed": 3, "top_p": 0.9}, True),
    ({"temperature": 0.8, "seed": 3, "top_k": 5}, True),
], ids=["greedy", "greedy-filters-set", "defaults", "top-p", "top-k"])
def test_dispatch_counters_follow_the_rows(params, sorts):
    """Every program that samples is counted, and counted as sorting just
    when a sampling row set a filter; the window and single steps count the
    same way, and emit the same tokens."""
    tokens = {}
    for n_steps in (1, 4):
        engine = make_engine(n_steps)
        before = engine.stats()
        assert before["sample_dispatches"] == 0
        assert before["sample_sorted_dispatches"] == 0
        tokens[n_steps], _ = drain(
            engine, SamplingParams(max_tokens=9, **params))
        assert len(tokens[n_steps]) == 9
        after = engine.stats()
        # The prefill's tail and every decode dispatch: at least two
        # programs for nine tokens, at most one a token.
        assert 2 <= after["sample_dispatches"] <= 9
        assert after["sample_sorted_dispatches"] == (
            after["sample_dispatches"] if sorts else 0)
    assert tokens[1] == tokens[4]


async def test_both_families_are_exported_from_boot():
    """``prom_ratio`` over the two reads 0, not a missing family, in a cell
    in which nothing sorts (bench/layer_metrics/sample_sorted_share.json)."""
    from aiohttp.test_utils import TestClient, TestServer

    from production_stack_tpu.engine.config import (
        CacheConfig, EngineConfig, ModelConfig, SchedulerConfig,
    )
    from production_stack_tpu.engine.server.api_server import build_engine_app
    from production_stack_tpu.engine.server.async_engine import AsyncEngine
    from production_stack_tpu.router.stats import vocabulary as vocab

    engine = AsyncEngine(EngineConfig(
        model=ModelConfig(),
        cache=CacheConfig(block_size=4, num_blocks=128),
        scheduler=SchedulerConfig(
            max_num_seqs=4, prefill_buckets=(16, 32, 64), max_model_len=128),
    ))
    client = TestClient(TestServer(build_engine_app(
        engine, served_model="tiny-llama")))
    await client.start_server()

    async def families():
        text = await (await client.get("/metrics")).text()
        found = dict(
            line.rsplit(" ", 1) for line in text.splitlines()
            if line.startswith("tpu:sample_"))
        return (float(found[vocab.TPU_SAMPLE_DISPATCH]),
                float(found[vocab.TPU_SAMPLE_SORTED_DISPATCH]))

    try:
        assert await families() == (0.0, 0.0)
        for extra in ({"temperature": 0.0}, {"temperature": 0.7, "top_p": 0.9}):
            resp = await client.post("/v1/completions", json={
                "model": "tiny-llama", "prompt": "hi", "max_tokens": 4,
                **extra})
            assert resp.status == 200, await resp.text()
            total, sorted_ = await families()
            assert total > 0 and (sorted_ > 0) == (extra["temperature"] > 0)
    finally:
        await client.close()
