"""The step programs without an engine (engine/core/step_programs.py).

The shared pieces every window program is built from — ``stop_mask``,
``shape_logits``, ``commit_token`` / ``advance_rows``, ``table_scatter`` —
checked alone against NumPy, and ``window_program`` over a stub model
against the same commits made one at a time.  The parity of the programs
with the K=1 engine is the business of test_multistep_window.py,
test_mixed_window.py and test_speculative.py.
"""

import ast
import functools
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from production_stack_tpu.engine.core import step_programs as sp
from production_stack_tpu.engine.sampling import sample_tokens

V = 12


def test_module_imports_nothing_of_the_engine():
    """The arrow points one way: the model arrives as a callable."""
    tree = ast.parse(pathlib.Path(sp.__file__).read_text())
    imported = [
        node.module if isinstance(node, ast.ImportFrom) else alias.name
        for node in ast.walk(tree)
        if isinstance(node, (ast.Import, ast.ImportFrom))
        for alias in node.names
    ]
    assert set(imported) == {
        "jax", "jax.numpy",
        "production_stack_tpu.engine",
        "production_stack_tpu.engine.sampling",
    }


@pytest.mark.parametrize("stop_ids", [
    [[3, 7, -1], [5, -1, -1]],            # ragged, -1 padded
    [[-1, -1], [0, 11]],                  # an empty set beside a full one
    [[4, 4, 4]],                          # a repeated id
    [[-1]],                               # nothing but padding
], ids=["ragged", "empty-row", "repeated", "all-padding"])
def test_stop_mask_against_numpy(stop_ids):
    ids = np.asarray(stop_ids, np.int32)
    want = np.zeros((ids.shape[0], V), bool)
    for row, row_ids in enumerate(ids):
        for tok in row_ids:
            if tok >= 0:
                want[row, tok] = True
    got = sp.stop_mask(jnp.asarray(ids), jnp.asarray(ids >= 0), V)
    np.testing.assert_array_equal(np.asarray(got), want)


def _state(rows):
    return (
        jnp.zeros((rows, V), jnp.int16), jnp.zeros((rows, V), jnp.bool_),
    )


def test_frozen_row_emits_minus_one_and_advances_nothing():
    counts, seen = _state(2)
    stop_ids = jnp.asarray([[9], [9]], jnp.int32)
    tok = jnp.asarray([4, 6], jnp.int32)
    alive = jnp.asarray([True, False])
    emitted, stop_hit, appended, counts, seen = sp.commit_token(
        tok, alive, counts, seen, stop_ids, stop_ids >= 0,
        use_penalties=True,
    )
    assert emitted.tolist() == [4, -1]
    assert appended.tolist() == [True, False]
    assert np.asarray(counts)[1].sum() == 0 and not np.asarray(seen)[1].any()
    assert np.asarray(counts)[0, 4] == 1 and np.asarray(seen)[0, 4]
    carry = sp.advance_rows(
        tok, alive, stop_hit,
        jnp.asarray([1, 2], jnp.int32), jnp.asarray([10, 20], jnp.int32),
        jnp.asarray([11, 21], jnp.int32), jnp.asarray([False, True]),
        jnp.asarray([3, 3], jnp.int32),
    )
    assert [c.tolist() for c in carry] == [
        [4, 2], [11, 20], [12, 21], [False, True], [2, 3],
    ]


def test_stop_hit_is_emitted_not_appended_and_freezes_the_row():
    counts, seen = _state(1)
    stop_ids = jnp.asarray([[7, -1]], jnp.int32)
    valid = stop_ids >= 0
    done = jnp.asarray([False])
    row = (jnp.asarray([1], jnp.int32), jnp.asarray([5], jnp.int32),
           jnp.asarray([6], jnp.int32), done, jnp.asarray([0], jnp.int32))
    emits = []
    for tok in (7, 3):  # the stop token, then whatever the model says next
        tok = jnp.asarray([tok], jnp.int32)
        alive = ~row[3]
        emitted, stop_hit, appended, counts, seen = sp.commit_token(
            tok, alive, counts, seen, stop_ids, valid, use_penalties=True,
        )
        row = sp.advance_rows(tok, alive, stop_hit, *row)
        emits.append(int(emitted[0]))
    assert emits == [7, -1]
    assert np.asarray(counts).sum() == 0 and not np.asarray(seen).any()
    # The stop token itself took its position; nothing after it did.
    assert [c.tolist() for c in row] == [[7], [6], [7], [True], [0]]


@pytest.mark.parametrize("min_left", [2, 0])
def test_floor_bans_exactly_the_stop_ids_while_unmet(min_left):
    logits = jnp.asarray(
        np.random.default_rng(0).standard_normal((2, V)), jnp.float32
    )
    stop_ids = jnp.asarray([[3, 7], [5, -1]], jnp.int32)
    banned = sp.stop_mask(stop_ids, stop_ids >= 0, V)
    counts, seen = _state(2)
    off = jnp.zeros((2,), jnp.float32)
    got = sp.shape_logits(
        logits, counts, seen, jnp.asarray([min_left, 0], jnp.int32), banned,
        off, off, off + 1.0, use_penalties=False, use_min_floor=True,
    )
    want = np.asarray(logits).copy()
    if min_left > 0:
        want[0, [3, 7]] += np.float32(-1e9)
    # Row 1's floor is met in both cases: its row is untouched, bit for bit.
    np.testing.assert_array_equal(np.asarray(got), want)


def test_penalties_read_the_carried_state():
    logits = jnp.ones((1, V), jnp.float32) * 2.0
    counts, seen = _state(1)
    tok = jnp.asarray([4], jnp.int32)
    for _ in range(2):
        counts, seen = sp.count_token(counts, seen, tok, jnp.asarray([True]))
    seen = seen.at[0, 9].set(True)  # a prompt token: seen, never generated
    got = np.asarray(sp.shape_logits(
        logits, counts, seen, jnp.zeros((1,), jnp.int32), None,
        jnp.asarray([0.5]), jnp.asarray([0.25]), jnp.asarray([2.0]),
        use_penalties=True, use_min_floor=False,
    ))[0]
    assert got[4] == pytest.approx(2.0 / 2.0 - 0.5 - 2 * 0.25)
    assert got[9] == pytest.approx(2.0 / 2.0)
    assert got[0] == 2.0


@pytest.mark.parametrize("cols, vals", [
    ([-1, -1, -1], [9, 9, 9]),                       # no growth
    ([2, -1, 0], [40, 41, 42]),                      # one column a row
    ([[1, 2], [-1, -1], [3, -1]], [[50, 51], [52, 53], [54, 55]]),
], ids=["none", "one-column", "several-columns"])
def test_table_scatter(cols, vals):
    tables = np.arange(12, dtype=np.int32).reshape(3, 4)
    cols, vals = np.asarray(cols, np.int32), np.asarray(vals, np.int32)
    want = tables.copy()
    for row in range(3):
        for col, val in zip(np.atleast_1d(cols[row]), np.atleast_1d(vals[row])):
            if col >= 0:
                want[row, col] = val
    got = sp.table_scatter(
        jnp.asarray(tables), jnp.asarray(cols), jnp.asarray(vals)
    )
    np.testing.assert_array_equal(np.asarray(got), want)


def test_pipe_unpack_and_advance():
    floats = np.asarray([0.7, 0.0], np.float32)
    packed = np.zeros((11, 2), np.int32)
    packed[0], packed[7] = [3, 4], [20, 0]
    for row in (5, 6, 8):
        packed[row] = floats.view(np.int32)
    tables = jnp.asarray([[1, 2, 0], [3, 0, 0]], jnp.int32)
    state = sp.pipe_unpack(jnp.asarray(packed), tables)
    assert state["tokens"].tolist() == [3, 4]
    np.testing.assert_array_equal(np.asarray(state["temps"]), floats)
    np.testing.assert_array_equal(np.asarray(state["min_ps"]), floats)
    # Row 0 crosses into a new block (col 2 := 7); row 1 left the batch
    # (ctx 0) and parks its write on null block 0.
    delta = jnp.asarray([[8, 5], [9, 0], [2, -1], [7, 0]], jnp.int32)
    nxt = sp.pipe_advance(4)(delta, jnp.asarray([6, 6], jnp.int32), tables)
    assert nxt["tables"].tolist() == [[1, 2, 7], [3, 0, 0]]
    assert nxt["slot_blocks"].tolist() == [7, 0]
    assert nxt["slot_offsets"].tolist() == [0, 1]
    assert nxt["tokens"].tolist() == [6, 6]


# -- window_program over a stub model ---------------------------------------

BS, K, ROWS = 4, 6, 3
TABLE = jnp.asarray(
    np.random.default_rng(1).standard_normal((V, V)), jnp.float32
)


def stub_decode(params, *, tokens, positions, block_tables, ctx_lens,
                slot_block_ids, slot_offsets, kv_caches):
    """Logits from a fixed table; the "cache" records which token was
    written to which slot, so parked writes are visible."""
    logits = params[(tokens + positions) % V]
    return logits, kv_caches.at[slot_block_ids, slot_offsets].set(tokens)


def _window_inputs(max_steps):
    return dict(
        tokens=jnp.asarray([1, 2, 3], jnp.int32),
        positions=jnp.asarray([2, 5, 9], jnp.int32),
        ctx_lens=jnp.asarray([3, 6, 10], jnp.int32),
        done=jnp.asarray([False, False, True]),
        min_left=jnp.asarray([3, 0, 0], jnp.int32),
        block_tables=jnp.asarray(
            [[1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 12]], jnp.int32
        ),
        max_steps=jnp.asarray(max_steps, jnp.int32),
        temps=jnp.asarray([0.0, 0.9, 0.0], jnp.float32),
        top_ps=jnp.ones((ROWS,), jnp.float32),
        top_ks=jnp.zeros((ROWS,), jnp.int32),
        min_ps=jnp.zeros((ROWS,), jnp.float32),
        seq_seeds=jnp.asarray([0, 17, 0], jnp.int32),
        stop_ids=jnp.asarray([[0, 5, 8], [2, -1, -1], [-1, -1, -1]], jnp.int32),
        key_base=jnp.asarray(100, jnp.int32),
        presence=jnp.asarray([0.8, 0.0, 0.0], jnp.float32),
        frequency=jnp.asarray([0.4, 0.3, 0.0], jnp.float32),
        repetition=jnp.asarray([1.3, 1.0, 1.0], jnp.float32),
    )


STATIC = ("use_penalties", "use_min_floor")
WINDOW = jax.jit(
    sp.window_program(stub_decode, block_size=BS, n_steps=K, vocab=V),
    static_argnames=STATIC,
)


@functools.partial(jax.jit, static_argnames=STATIC)
def one_step(t, row, counts, seen, kv, inp, banned, *, use_penalties,
             use_min_floor):
    """What one scan iteration must equal: the shared pieces, called in
    turn from outside any scan."""
    tokens, positions, ctx_lens, done, min_left = row
    stop_valid = inp["stop_ids"] >= 0
    alive = jnp.logical_and(~done, t < inp["max_steps"])
    blk = inp["block_tables"][jnp.arange(ROWS), positions // BS]
    logits, kv = stub_decode(
        TABLE, tokens=tokens, positions=positions,
        block_tables=inp["block_tables"], ctx_lens=ctx_lens,
        slot_block_ids=jnp.where(alive, blk, 0),
        slot_offsets=positions % BS, kv_caches=kv,
    )
    logits = sp.shape_logits(
        logits, counts, seen, min_left, banned,
        inp["presence"], inp["frequency"], inp["repetition"],
        use_penalties=use_penalties, use_min_floor=use_min_floor,
    )
    tok = sample_tokens(
        logits, inp["temps"], inp["top_ps"], inp["top_ks"],
        jax.random.PRNGKey(inp["key_base"] + t), inp["seq_seeds"],
        min_p=inp["min_ps"],
    )
    out, stop_hit, _, counts, seen = sp.commit_token(
        tok, alive, counts, seen, inp["stop_ids"], stop_valid,
        use_penalties=use_penalties,
    )
    return out, sp.advance_rows(tok, alive, stop_hit, *row), counts, seen, kv


@pytest.mark.parametrize("max_steps", [[K, K, K], [2, K, K], [0, 1, 3]],
                         ids=["full", "one-short", "all-short"])
@pytest.mark.parametrize("use_penalties, use_min_floor",
                         [(False, False), (True, False), (True, True)])
def test_window_equals_sequential_commits(max_steps, use_penalties,
                                          use_min_floor):
    inp = _window_inputs(max_steps)
    static = dict(use_penalties=use_penalties, use_min_floor=use_min_floor)
    counts, seen = _state(ROWS)
    kv = jnp.full((13, BS), -1, jnp.int32)
    emitted, state, kv_out = WINDOW(
        TABLE, kv_caches=kv, counts=counts, seen=seen, **static, **inp,
    )

    banned = sp.stop_mask(inp["stop_ids"], inp["stop_ids"] >= 0, V)
    row = tuple(inp[k] for k in sp.CARRY_KEYS[:5])
    want = []
    for t in range(K):
        out, row, counts, seen, kv = one_step(
            t, row, counts, seen, kv, inp, banned, **static
        )
        want.append(np.asarray(out))

    np.testing.assert_array_equal(np.asarray(emitted), np.stack(want))
    assert sorted(state) == sorted(sp.CARRY_KEYS)
    for key, value in zip(sp.CARRY_KEYS, row + (counts, seen)):
        np.testing.assert_array_equal(np.asarray(state[key]), value, key)
    np.testing.assert_array_equal(np.asarray(kv_out), kv)
    # A row never emits past its budget, and the done row never at all.
    n_emitted = (np.asarray(emitted) >= 0).sum(axis=0)
    assert (n_emitted <= np.asarray(max_steps)).all() and n_emitted[2] == 0
    # No block of the done row was written: its writes parked on block 0.
    assert (np.asarray(kv_out)[9:] == -1).all()
