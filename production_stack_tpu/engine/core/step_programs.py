"""The step programs: what runs on the device between two host round trips.

``LLMEngine.__init__`` jits one function per program under a fixed name
(``prefill_fn``, ``window_fn``, ``spec_window_fn``, ``mixed_window_fn``, ``win_unpack_fn``,
``win_advance_fn``, ``pipe_unpack_fn``, ``pipe_advance_fn``); this module builds those functions.
Each builder takes what the program needs of the engine as arguments — the
model arrives as a callable — and imports nothing of the engine, the
scheduler or obs, so a program can be lowered, timed and tested alone.

Every window program loops decode+sample iterations on the device and returns
all emitted tokens in one host round trip.  ``window_fn`` runs as many as its
longest row was budgeted (``max_steps``), up to the ``n_steps`` its outputs are
sized for; the speculative and the mixed window scan a static count.  Slot
targeting is on the device (one block-table lookup per iteration); penalties
and the ``min_tokens`` floor run inside the scan from carried occurrence state;
a stop-token match freezes the row (no further KV writes, position and context
frozen, -1 emitted), so a stop wastes no token of the window.  The final carry
is returned so the next window can chain from this one's still-in-flight
state.  The carry is a tuple inside the scan and a dict with fixed keys outside
it: its order is the order of the compiled program's parameters.

The pieces the programs share are written once: ``stop_mask``,
``shape_logits``, ``commit_token`` / ``advance_rows`` and ``table_scatter``.
"""

import jax
import jax.numpy as jnp

from production_stack_tpu.engine import sampling as sampling_lib
from production_stack_tpu.engine.sampling import sample_tokens


# The row state a window carries, in the order of its scan carry; the host
# keeps it between windows as a dict with these keys.
CARRY_KEYS = (
    "tokens", "positions", "ctx_lens", "done", "min_left", "counts", "seen",
)

# -- shared pieces -----------------------------------------------------------


def stop_mask(stop_ids, stop_valid, vocab):
    """Per-row stop set ``[S, n]`` (``stop_valid`` marks the ids that are not
    -1 padding) as an ``[S, V]`` bool mask.  The banned set of the
    ``min_tokens`` floor IS the stop set (vLLM ``min_tokens`` semantics)."""
    return jax.vmap(
        lambda ids, v: jnp.zeros(
            (vocab,), jnp.bool_
        ).at[jnp.where(v, ids, 0)].max(v)
    )(stop_ids, stop_valid)


def shape_logits(
    logits, counts, seen, min_left, banned,
    presence, frequency, repetition,
    *, use_penalties, use_min_floor,
):
    """Penalties from the carried occurrence state, then the ``min_tokens``
    floor: the transform every chosen token is chosen under.  The verifier
    and the model drafter's replay must apply it alike, or every token where
    it flips the target's argmax is a certain rejection.

    The floor is the same -1e9 additive bias as the host path's logit_bias
    matrix over ``banned`` (``stop_mask``), active while the row's floor is
    unmet; +0.0 elsewhere is a bit-exact identity."""
    if use_penalties:
        logits = sampling_lib.apply_penalties_state(
            logits, counts, seen, presence, frequency, repetition,
        )
    if use_min_floor:
        bias = (
            jnp.logical_and(
                banned, (min_left > 0)[:, None]
            ).astype(jnp.float32) * -1e9
        )
        logits = logits + bias
    return logits


def hits_stop(tok, stop_ids, stop_valid):
    """[S] bool: the row's chosen token is one of its stop ids."""
    return jnp.any(
        jnp.logical_and(tok[:, None] == stop_ids, stop_valid), axis=1
    )


def count_token(counts, seen, tok, appended):
    """Add ``tok`` to the occurrence state of the rows that append it."""
    rows = jnp.arange(counts.shape[0])
    counts = counts.at[rows, tok].add(appended.astype(jnp.int16))
    seen = seen.at[rows, tok].max(appended)
    return counts, seen


def commit_token(
    tok, alive, counts, seen, stop_ids, stop_valid, *, use_penalties,
):
    """Commit one chosen token per row: ``(emitted, stop_hit, appended,
    counts, seen)``.  A row that is not ``alive`` (done, or past its budget)
    emits -1 and changes nothing.  A stop token is emitted but not appended:
    it never enters the occurrence state, and the caller folds ``stop_hit``
    into ``done`` so the row stays frozen from the next call on."""
    stop_hit = jnp.logical_and(alive, hits_stop(tok, stop_ids, stop_valid))
    emitted = jnp.where(alive, tok, -1)
    appended = jnp.logical_and(alive, ~stop_hit)
    if use_penalties:
        counts, seen = count_token(counts, seen, tok, appended)
    return emitted, stop_hit, appended, counts, seen


def advance_rows(
    tok, alive, stop_hit, tokens, positions, ctx_lens, done, min_left,
):
    """One committed token further for the ``alive`` rows: the next
    ``(tokens, positions, ctx_lens, done, min_left)`` of the carry."""
    step = alive.astype(jnp.int32)
    return (
        jnp.where(alive, tok, tokens),
        positions + step,
        ctx_lens + step,
        jnp.logical_or(done, stop_hit),
        jnp.maximum(min_left - step, 0),
    )


def table_scatter(tables, cols, vals):
    """Block-table growth in place: write ``vals`` at ``cols`` of each row,
    col -1 = no growth.  ``cols`` is ``[S]`` (at most one new block a row)
    or ``[S, C]`` (up to C)."""
    rows = jnp.arange(tables.shape[0])
    if cols.ndim > 1:
        rows = rows[:, None]
    valid = cols >= 0
    safe = jnp.where(valid, cols, 0)
    keep = tables[rows, safe]
    return tables.at[rows, safe].set(jnp.where(valid, vals, keep))


def _stops(stop_ids, vocab, use_min_floor):
    """``(stop_valid, banned)``: which stop ids are not padding, and the
    floor's mask where the program applies a floor."""
    valid = stop_ids >= 0
    return valid, stop_mask(stop_ids, valid, vocab) if use_min_floor else None


def _lora_extra(lora, adapter_idx):
    if lora is None:
        return {}
    return {"lora": lora, "adapter_idx": adapter_idx}


def _state_extra(state_slots):
    """A model with a state pool (kv/state_pool.py) is told each row's live
    slot; its states ride the cache tree, which the scan carries as it is."""
    if state_slots is None:
        return {}
    return {"state_slots": state_slots}


# -- the K-step decode window ------------------------------------------------


def window_program(model_decode, *, block_size, n_steps, vocab, n_counts=0):
    """``window_fn``: up to ``n_steps`` decode+sample iterations in one loop.

    The trip count is a value: ``max(max_steps)``, the longest row's budget
    for this window, clamped to ``n_steps``, which stays the static size of
    the emitted ``[n_steps, S]`` and of a counting model's ``[n_steps,
    n_counts]`` (``n_counts``: how many int32 counts ``model_decode`` hands
    back a step beside its two results; 0: none).  Iteration ``t`` writes row
    ``t`` of the outputs; the rows past the last iteration hold what a frozen
    row emits (-1, and no counts), so the host reads a short window as it
    reads one whose rows all stopped."""
    bs = block_size

    def multi_window(
        params, tokens, positions, ctx_lens, done, min_left,
        block_tables, max_steps, kv_caches,
        temps, top_ps, top_ks, min_ps, seq_seeds,
        stop_ids, key_base, counts, seen,
        presence, frequency, repetition,
        use_penalties, use_min_floor,
        lora=None, adapter_idx=None, state_slots=None,
    ):
        stop_valid, banned = _stops(stop_ids, vocab, use_min_floor)

        def step(t, carry):
            (tokens, positions, ctx_lens, done, min_left,
             counts, seen, kv_caches) = carry
            active = jnp.logical_and(~done, t < max_steps)  # [S]
            blk = jnp.take_along_axis(
                block_tables, (positions // bs)[:, None], axis=1
            )[:, 0]
            # ``counted``: what a model that counts on the device (a routed
            # model's [n] int32 of its dispatch) hands back beside the two.
            logits, kv_caches, *counted = model_decode(
                params,
                tokens=tokens,
                positions=positions,
                block_tables=block_tables,
                ctx_lens=ctx_lens,
                # Frozen/done rows park their KV write on null block 0:
                # no cache slot past the stop position is ever written.
                slot_block_ids=jnp.where(active, blk, 0),
                slot_offsets=positions % bs,
                kv_caches=kv_caches,
                **_lora_extra(lora, adapter_idx),
                **_state_extra(state_slots),
            )
            logits = shape_logits(
                logits, counts, seen, min_left, banned,
                presence, frequency, repetition,
                use_penalties=use_penalties, use_min_floor=use_min_floor,
            )
            # The key schedule is that of single-token stepping: iteration
            # t of a window dispatched at step counter c uses
            # PRNGKey(seed + c + t), the key the K=1 path would use for
            # that token, so seeded sampling is bit-identical across
            # window sizes.
            sampled = sample_tokens(
                logits, temps, top_ps, top_ks,
                jax.random.PRNGKey(key_base + t), seq_seeds,
                min_p=min_ps,
            )
            emitted, stop_hit, _, counts, seen = commit_token(
                sampled, active, counts, seen, stop_ids, stop_valid,
                use_penalties=use_penalties,
            )
            return advance_rows(
                sampled, active, stop_hit,
                tokens, positions, ctx_lens, done, min_left,
            ) + (counts, seen, kv_caches), (emitted, *counted)

        def body(t, state):
            carry, outs = state
            carry, rows = step(t, carry)
            if len(rows) != len(outs):
                raise ValueError(
                    f"window_program: the model hands back {len(rows) - 1} "
                    f"array(s) of counts a step, n_counts={n_counts}")
            return carry, tuple(
                jax.lax.dynamic_update_index_in_dim(out, row, t, 0)
                for out, row in zip(outs, rows)
            )

        outs = (jnp.full((n_steps,) + tokens.shape, -1, jnp.int32),)
        if n_counts:
            outs += (jnp.zeros((n_steps, n_counts), jnp.int32),)
        carry, (emitted, *counted) = jax.lax.fori_loop(
            0, jnp.minimum(jnp.max(max_steps), n_steps), body,
            ((tokens, positions, ctx_lens, done, min_left,
              counts, seen, kv_caches), outs),
        )
        *row, kv_caches = carry
        # No all-finished reduction on the device: every stop is visible in
        # the emitted [K, S] tokens the host reads back anyway, so collect()
        # evaluates the predicate from host state and drops queued
        # successor windows without another device sync.  A counting
        # model's [K, n] per-step counts ride out the same way, a fourth
        # result, for the host to read with the tokens.
        return (emitted, dict(zip(CARRY_KEYS, row)), kv_caches, *counted)

    return multi_window


# -- the mixed window: prefill chunks ride the decode scan -------------------


def mixed_window_program(model_mixed, *, block_size, vocab):
    """``mixed_window_fn``: a waiting prompt's prefill chunks ride the decode
    scan.  Each iteration runs the packed ``[S_dec + chunk]`` mixed forward
    (the executable shape the K=1 mixed step compiles); decode rows advance
    one token from the carried state exactly as in ``window_program`` while
    the chunk cursor (cached_len, valid_len, new-block row) advances through
    the precomputed per-iteration schedule carried as scan xs.  The chunk's
    accumulated-prefix block table is ONE static ``[P]`` array whose validity
    the in-graph cursor masks: a block written by iteration t is attended by
    iteration t+1 with no host trip.  Every iteration's tail-row logits ride
    out as a scan output and the host samples a final chunk's first token at
    collect through the path K=1 mixed stepping uses, so first tokens are
    bit-identical by construction.  No drafting here (a pure-decode-window
    feature).  The scan length is a static argument that the dispatcher
    buckets to powers of two, so the inventory stays
    |chunk buckets| x |decode buckets| x O(log K)."""
    bs = block_size

    def mixed_window(
        params, tokens, positions, ctx_lens, done, min_left,
        block_tables, max_steps, kv_caches,
        temps, top_ps, top_ks, min_ps, seq_seeds,
        stop_ids, key_base, counts, seen,
        presence, frequency, repetition,
        pf_tokens, pf_cached, pf_valid, pf_new_blocks,
        pf_prefix_ids, pf_adapter,
        n_steps, use_penalties, use_min_floor,
        hist=None, lora=None, adapter_idx=None,
    ):
        stop_valid, banned = _stops(stop_ids, vocab, use_min_floor)
        S = tokens.shape[0]
        T = pf_tokens.shape[1]

        def body(carry, xs):
            (tokens, positions, ctx_lens, done, min_left,
             counts, seen, hist_c, kv_caches) = carry
            # Packed windows: each iteration carries its OWN prompt cursor
            # (tokens, block table and adapter slot ride the scan xs), so
            # chunks from several prompts share one static [S + T] shape.
            t, pft, pfc, pfv, pfnb, pfpid, pfad = xs
            active = jnp.logical_and(~done, t < max_steps)
            blk = jnp.take_along_axis(
                block_tables, (positions // bs)[:, None], axis=1
            )[:, 0]
            row_adapter = None
            if lora is not None:
                # Row layout: S decode rows, then T chunk rows sharing ONE
                # adapter.
                row_adapter = jnp.concatenate(
                    [adapter_idx, jnp.full((T,), pfad, jnp.int32)]
                )
            logits, kv_caches = model_mixed(
                params,
                dec_tokens=tokens,
                dec_positions=positions,
                dec_block_tables=block_tables,
                dec_ctx_lens=ctx_lens,
                # Frozen/done rows park their KV write on null block 0.
                dec_slot_block_ids=jnp.where(active, blk, 0),
                dec_slot_offsets=positions % bs,
                pf_tokens=pft,
                pf_cached_len=pfc,
                pf_prefix_block_ids=pfpid,
                pf_new_block_ids=pfnb,
                pf_valid_len=pfv,
                kv_caches=kv_caches,
                **_lora_extra(lora, row_adapter),
            )
            # logits[-1] is the chunk's tail row (its last VALID token).
            tail = logits[-1]
            dlogits = shape_logits(
                logits[:S], counts, seen, min_left, banned,
                presence, frequency, repetition,
                use_penalties=use_penalties, use_min_floor=use_min_floor,
            )
            # Key schedule: iteration t of a window dispatched at counter c
            # uses PRNGKey(seed + c + t), the ordinal the K=1 mixed step at
            # counter c+t burns.
            sampled = sample_tokens(
                dlogits, temps, top_ps, top_ks,
                jax.random.PRNGKey(key_base + t), seq_seeds,
                min_p=min_ps,
            )
            emitted, stop_hit, _, counts, seen = commit_token(
                sampled, active, counts, seen, stop_ids, stop_valid,
                use_penalties=use_penalties,
            )
            if hist_c is not None:
                # Keep the drafter's carried history warm across mixed
                # windows (one committed token per active row per
                # iteration), so a chained pure-decode window drafts from
                # fresh context.
                H = hist_c.shape[1]
                cat = jnp.concatenate(
                    [hist_c, jnp.maximum(emitted, 0)[:, None]], axis=1,
                )
                hidx = (
                    jnp.arange(H)[None, :]
                    + active.astype(jnp.int32)[:, None]
                )
                hist_c = jnp.take_along_axis(cat, hidx, axis=1)
            return advance_rows(
                sampled, active, stop_hit,
                tokens, positions, ctx_lens, done, min_left,
            ) + (counts, seen, hist_c, kv_caches), (emitted, tail)

        init = (
            tokens, positions, ctx_lens, done, min_left,
            counts, seen, hist, kv_caches,
        )
        xs = (
            jnp.arange(n_steps), pf_tokens, pf_cached, pf_valid,
            pf_new_blocks, pf_prefix_ids, pf_adapter,
        )
        carry, (emitted, tails) = jax.lax.scan(body, init, xs)
        *row, hist, kv_caches = carry
        state = dict(zip(CARRY_KEYS, row))
        if hist is not None:
            state["hist"] = hist
        return emitted, tails, state, kv_caches

    return mixed_window


# -- the speculative window: draft and verify inside the scan ----------------


def spec_window_program(
    model_decode, draft_decode, *, drafter, draft_len, hist_window,
    block_size, n_steps, vocab,
):
    """``spec_window_fn``: speculation fused into the K-step scan.  Each
    iteration proposes up to ``draft_len`` tokens on the device from one of
    two sources — ``drafter="ngram"`` (prompt lookup in a carried
    recent-history buffer) or ``drafter="model"`` (``draft_decode``, a tiny
    second model run autoregressively from its own compact device-resident
    KV cache, carried through the scan like the history) — and verifies them
    in the SAME wide forward that scores the committed token
    (W = draft_len + 1 rows per sequence), then folds acceptance into the
    carry.  A rejected draft costs a scan iteration, never a host round
    trip.  Greedy only: acceptance compares the model's own argmax, so
    streams are byte-identical by construction AND a pure function of
    weights + carried state (lockstep replicas cannot desync).  Penalties,
    the floor and stops apply to every accepted token in order through
    ``shape_logits`` / ``commit_token``.

    Model-drafter cache layout: the draft KV uses COMPACT slots (0-based
    within the row's dedicated draft blocks) but TRUE sequence positions for
    RoPE, so attention distances stay exact: draft logits match full-context
    draft logits whenever the H-token history window covers the sequence,
    and degrade gracefully (history truncation, not corruption) past it.
    The cache is (re)built by an in-graph causal PRIME (static ``do_prime``);
    chained windows skip it (``draft_pos`` rides the carry), and the host
    asks for it on batch rebuilds, after any dispatch that was not a
    model-drafter window, and at its capacity watermark."""
    bs = block_size
    D = draft_len  # drafts per iteration
    W = D + 1  # verify rows per sequence (committed + drafts)
    H = hist_window
    by_model = drafter == "model"

    def spec_window(
        params, tokens, positions, ctx_lens, done, min_left,
        block_tables, max_steps, kv_caches,
        stop_ids, counts, seen, hist,
        presence, frequency, repetition,
        use_penalties, use_min_floor,
        draft_params=None, draft_tables=None, draft_pos=None,
        draft_kv=None, do_prime=False,
        lora=None, adapter_idx=None,
    ):
        stop_valid, banned = _stops(stop_ids, vocab, use_min_floor)
        shaping = dict(
            use_penalties=use_penalties, use_min_floor=use_min_floor,
        )
        bmax = block_tables.shape[1]
        wide_adapter = (
            jnp.repeat(adapter_idx, W) if lora is not None else None
        )
        if by_model:
            dbmax = draft_tables.shape[1]
        if by_model and do_prime:
            # In-graph causal prime of the draft cache: one wide draft
            # forward over every row's history-window tokens EXCLUDING the
            # committed last token (the scan's first draft forward consumes
            # that).  hist col c of a row with `live` valid entries maps to
            # compact slot c - (H - live) at TRUE position
            # positions + 1 - H + c; invalid (left-pad) rows park on draft
            # null block 0 at ctx 0.  Write-then-attend + ctx = slot+1
            # masking gives exact causal attention in the single call.
            Hm1 = H - 1
            live = jnp.minimum(positions + 1, H)
            colsp = jnp.arange(Hm1)[None, :]
            slots = colsp - (H - live)[:, None]
            pvalid = slots >= 0
            safe_slot = jnp.where(pvalid, slots, 0)
            rope = positions[:, None] + 1 - H + colsp
            pblk = jnp.take_along_axis(
                draft_tables,
                jnp.clip(safe_slot // bs, 0, dbmax - 1),
                axis=1,
            )
            _, draft_kv = draft_decode(
                draft_params,
                tokens=jnp.maximum(hist[:, :Hm1], 0).reshape(-1),
                positions=jnp.where(pvalid, rope, 0).reshape(-1),
                block_tables=jnp.repeat(draft_tables, Hm1, axis=0),
                ctx_lens=jnp.where(pvalid, slots + 1, 0).reshape(-1),
                slot_block_ids=jnp.where(pvalid, pblk, 0).reshape(-1),
                slot_offsets=(safe_slot % bs).reshape(-1),
                kv_caches=draft_kv,
            )
            # Invariant entering the scan: the draft cache holds all
            # context up to but EXCLUDING the committed token, and
            # draft_pos counts those compact slots.
            draft_pos = live - 1

        def body(carry, t):
            if by_model:
                (tokens, positions, ctx_lens, done, min_left,
                 emitted_cnt, counts, seen, hist, draft_pos,
                 kv_caches, draft_kv) = carry
            else:
                (tokens, positions, ctx_lens, done, min_left,
                 emitted_cnt, counts, seen, hist, kv_caches) = carry
            # The budget gate is the TOKEN count, not the iteration index:
            # acceptance advances a row several tokens per iteration and
            # max_steps budgets the max-acceptance growth the scheduler
            # allocated blocks for.
            active = jnp.logical_and(~done, emitted_cnt < max_steps)

            if by_model:
                # D+1 sequential single-row draft forwards: d=0 consumes the
                # committed token (writing its KV at compact slot draft_pos,
                # TRUE RoPE position `positions`), each d < D argmaxes the
                # next proposal and feeds it forward; the final d=D forward
                # only writes the last draft's KV so the cache invariant
                # holds even at full acceptance.  The verify's rewind is
                # free: draft_pos advances by the ACCEPTED count + 1, landing
                # the next iteration's first write exactly on the first
                # stale (rejected-draft) slot, so stale slots are
                # overwritten before any row's ctx mask can attend them.
                # Inactive rows park writes on draft null block 0.
                #
                # The verifier scores sub-step j with the carried state plus
                # the tokens accepted at sub-steps < j, so the drafter
                # replays the SAME shaping on a local copy along its chain.
                # Acceptance stays a pure function of weights + carried
                # state.
                cur = tokens
                drafts = []
                dcounts, dseen, dmin = counts, seen, min_left
                for d in range(D + 1):
                    dslot = draft_pos + d
                    dblk = jnp.take_along_axis(
                        draft_tables,
                        jnp.clip(dslot // bs, 0, dbmax - 1)[:, None],
                        axis=1,
                    )[:, 0]
                    dlogits, draft_kv = draft_decode(
                        draft_params,
                        tokens=cur,
                        positions=positions + d,
                        block_tables=draft_tables,
                        ctx_lens=jnp.where(active, dslot + 1, 0),
                        slot_block_ids=jnp.where(active, dblk, 0),
                        slot_offsets=dslot % bs,
                        kv_caches=draft_kv,
                    )
                    if d < D:
                        dlogits = shape_logits(
                            dlogits, dcounts, dseen, dmin, banned,
                            presence, frequency, repetition, **shaping,
                        )
                        cur = jnp.argmax(dlogits, axis=-1).astype(jnp.int32)
                        drafts.append(cur)
                        if use_penalties:
                            # The verifier's append gate: a proposed stop
                            # token is emitted but not counted, and the
                            # chain past it is dead anyway.
                            dstop = hits_stop(cur, stop_ids, stop_valid)
                            dcounts, dseen = count_token(
                                dcounts, dseen, cur,
                                jnp.logical_and(active, ~dstop),
                            )
                        if use_min_floor:
                            dmin = jnp.maximum(
                                dmin - active.astype(jnp.int32), 0
                            )
                draft = jnp.stack(drafts, axis=1)  # [S, D]
                # Room for drafts: the bonus/correction token always takes
                # one budget slot, drafts fill the rest.
                room = jnp.maximum(max_steps - emitted_cnt - 1, 0)
                dvalid = jnp.logical_and(
                    jnp.arange(D)[None, :] < room[:, None],
                    active[:, None],
                )
            else:
                # Prompt lookup on the device: the most recent earlier
                # occurrence of the trailing bigram within the carried
                # [S, H] history (left -1-padded, hist[:, -1] the committed
                # token); the tokens that followed it are the draft.  No
                # bigram hit falls back to the most recent UNIGRAM
                # occurrence of the committed token: the verify rows are
                # computed either way (static shapes), so a proposal is
                # free and a rejected one costs nothing the empty iteration
                # did not.
                key0 = hist[:, H - 2][:, None]
                key1 = hist[:, H - 1][:, None]
                starts = jnp.arange(H - 2)
                match2 = jnp.logical_and(
                    jnp.logical_and(
                        hist[:, : H - 2] == key0,
                        hist[:, 1 : H - 1] == key1,
                    ),
                    hist[:, : H - 2] >= 0,
                )
                best2 = jnp.max(jnp.where(match2, starts[None, :], -1), axis=1)
                match1 = jnp.logical_and(
                    hist[:, 1 : H - 1] == key1,
                    hist[:, 1 : H - 1] >= 0,
                )
                best1 = jnp.max(jnp.where(match1, starts[None, :], -1), axis=1)
                best = jnp.where(best2 >= 0, best2, best1)
                dpos = best[:, None] + 2 + jnp.arange(D)[None, :]
                draft = jnp.take_along_axis(
                    hist, jnp.clip(dpos, 0, H - 1), axis=1
                )
                room = jnp.maximum(max_steps - emitted_cnt - 1, 0)
                dvalid = (
                    (best >= 0)[:, None]
                    & (dpos < H)
                    & (draft >= 0)
                    & (jnp.arange(D)[None, :] < room[:, None])
                    & active[:, None]
                )
            # Only a contiguous prefix is verifiable (already contiguous
            # for model proposals; shared so both sources feed the same
            # verify machinery).
            dvalid = jnp.cumsum(jnp.where(dvalid, 0, 1), axis=1) == 0
            draft = jnp.where(dvalid, draft, 0)
            nd = dvalid.sum(axis=1).astype(jnp.int32)

            # -- one wide verify forward -----------------------------------
            # Row j of sequence i consumes chain[j] at position pos+j with
            # ctx pos+j+1, so the decode kernel's write-then-attend order
            # makes draft rows see their predecessors' KV.  Dead rows park
            # KV on null block 0 (never corrupt a live slot).
            chain = jnp.concatenate([tokens[:, None], draft], axis=1)
            row_live = jnp.concatenate([active[:, None], dvalid], axis=1)
            offs = jnp.arange(W)[None, :]
            wpos = positions[:, None] + offs
            wctx = ctx_lens[:, None] + offs
            blk = jnp.take_along_axis(
                block_tables, jnp.clip(wpos // bs, 0, bmax - 1), axis=1,
            )
            logits, kv_caches = model_decode(
                params,
                tokens=chain.reshape(-1),
                positions=jnp.where(row_live, wpos, 0).reshape(-1),
                block_tables=jnp.repeat(block_tables, W, axis=0),
                ctx_lens=jnp.where(row_live, wctx, 0).reshape(-1),
                slot_block_ids=jnp.where(row_live, blk, 0).reshape(-1),
                slot_offsets=(wpos % bs).reshape(-1),
                kv_caches=kv_caches,
                **_lora_extra(lora, wide_adapter),
            )
            # No dtype cast: the verify rows must see EXACTLY the logits
            # the single-row path would (lm_head already emits fp32), or
            # greedy parity could drift.
            logits = logits.reshape(tokens.shape[0], W, vocab)

            # -- sequential verify: every accepted token committed in order
            alive = active
            last_tok = tokens
            adv = jnp.zeros_like(positions)
            acc_cnt = jnp.zeros_like(positions)
            new_done = done
            emits = []
            for j in range(W):
                lj = shape_logits(
                    logits[:, j, :], counts, seen, min_left, banned,
                    presence, frequency, repetition, **shaping,
                )
                tok_j = jnp.argmax(lj, axis=-1).astype(jnp.int32)
                emitted_j, stop_hit, appended, counts, seen = commit_token(
                    tok_j, alive, counts, seen, stop_ids, stop_valid,
                    use_penalties=use_penalties,
                )
                emits.append(emitted_j)
                step = alive.astype(jnp.int32)
                adv = adv + step
                min_left = jnp.maximum(min_left - step, 0)
                last_tok = jnp.where(alive, tok_j, last_tok)
                new_done = jnp.logical_or(new_done, stop_hit)
                if j < W - 1:
                    agree = jnp.logical_and(dvalid[:, j], tok_j == draft[:, j])
                    acc = jnp.logical_and(appended, agree)
                    acc_cnt = acc_cnt + acc.astype(jnp.int32)
                    alive = acc
            emitted = jnp.stack(emits, axis=0)  # [W, S]

            # -- fold acceptance into the carried state --------------------
            # The history shifts by the emitted count, so the next
            # iteration's bigram lookup sees the new tokens.
            cat = jnp.concatenate([hist, jnp.maximum(emitted.T, 0)], axis=1)
            hidx = jnp.arange(H)[None, :] + adv[:, None]
            hist = jnp.take_along_axis(cat, hidx, axis=1)
            core = (
                jnp.where(active, last_tok, tokens),
                positions + adv,
                ctx_lens + adv,
                new_done,
                min_left,
                emitted_cnt + adv,
                counts, seen, hist,
            )
            if by_model:
                # Commit the draft-cache cursor: adv = accepted + 1 slots
                # now hold exactly the tokens up to (excluding) the new
                # committed token.
                return core + (
                    draft_pos + adv, kv_caches, draft_kv,
                ), (emitted, nd, acc_cnt)
            return core + (kv_caches,), (emitted, nd, acc_cnt)

        init = (tokens, positions, ctx_lens, done, min_left,
                jnp.zeros_like(positions), counts, seen, hist)
        if by_model:
            init = init + (draft_pos, kv_caches, draft_kv)
        else:
            init = init + (kv_caches,)
        carry, ys = jax.lax.scan(body, init, jnp.arange(n_steps))
        if by_model:
            (tokens, positions, ctx_lens, done, min_left, _cnt,
             counts, seen, hist, draft_pos, kv_caches,
             draft_kv) = carry
        else:
            (tokens, positions, ctx_lens, done, min_left, _cnt,
             counts, seen, hist, kv_caches) = carry
        emitted, drafted, accepted = ys  # [K, W, S], [K, S], [K, S]
        state = dict(
            zip(CARRY_KEYS, (tokens, positions, ctx_lens, done, min_left,
                             counts, seen)),
            hist=hist,
        )
        if by_model:
            state["draft_pos"] = draft_pos
            return emitted, drafted, accepted, state, kv_caches, draft_kv
        return emitted, drafted, accepted, state, kv_caches

    return spec_window


# -- a dedicated prefill's inputs, from one packed transfer -------------------


def prefill_program(model_prefill, scalars, block_size, prefix_blocks):
    """``prefill_fn``: the model's prefill, with everything the host builds
    for a chunk in ONE int32 vector (a transfer costs the step thread some
    0.2 ms an array on a TPU's host, whatever its size, and the device is
    empty meanwhile): ``[tokens T | new_block_ids T/bs | prefix_block_ids
    | prompt_targets T, under ``prompt_topk`` alone | one entry a name of
    ``scalars``]``.  T follows from the vector's length; the slices are
    static, so the model's program is what it was behind them."""

    def prefill(params, chunk, kv_caches, prompt_topk=0, **extra):
        per_block = block_size * (2 if prompt_topk else 1) + 1
        n_blocks, rest = divmod(
            chunk.shape[0] - prefix_blocks - len(scalars), per_block
        )
        if rest or n_blocks <= 0:
            raise ValueError(
                f"no prefill bucket packs into {chunk.shape[0]} entries"
            )
        fields = [
            ("tokens", n_blocks * block_size), ("new_block_ids", n_blocks),
            ("prefix_block_ids", prefix_blocks),
        ]
        if prompt_topk:
            fields.append(("prompt_targets", n_blocks * block_size))
            extra["prompt_topk"] = prompt_topk
        kwargs, at = {}, 0
        for name, n in fields:
            kwargs[name] = chunk[at:at + n]
            at += n
        for i, name in enumerate(scalars):
            kwargs[name] = chunk[at + i]
        return model_prefill(params, kv_caches=kv_caches, **kwargs, **extra)

    return prefill


# -- a rebuilt window's batch state, from one packed transfer ----------------

# The rows of the packed [N, S] int32 array in which every per-row scalar of a
# window rebuilt from host state travels (engine.py: _window_host_state), in
# this order; a configuration appends the rows it has ("adapter",
# "state_slots", "draft_pos").  WIN_SAMPLING_ROWS are a request's own, static
# over its life: the engine keeps them as one column a sequence.
WIN_ROWS = (
    "tokens", "positions", "ctx_lens", "done", "min_left", "max_steps",
    "temps", "top_ps", "top_ks", "min_ps", "seeds",
    "presence", "frequency", "repetition",
)
WIN_SAMPLING_ROWS = slice(WIN_ROWS.index("temps"), len(WIN_ROWS))
WIN_FLOAT_ROWS = frozenset(
    ("temps", "top_ps", "min_ps", "presence", "frequency", "repetition")
)


def win_unpack(rows):
    """``win_unpack_fn``: the packed array's rows under their names (float
    rows bitcast back, ``done`` a bool again), and the empty occurrence state
    of a batch without penalties.  What ``pipe_unpack`` is to the K=1
    pipeline; the tables and stop ids ride beside it in the same transfer and
    need no program.  ``first_token`` [1] / ``first_row`` [1]: a window
    launched behind the prefill that admits one of its rows takes that row's
    token from the prefill's sampler, still on the device; ``first_row`` -1
    (every other rebuild) leaves ``tokens`` as the host packed them."""

    def unpack(packed, first_token, first_row):
        state = {}
        for i, name in enumerate(rows):
            row = packed[i]
            if name in WIN_FLOAT_ROWS:
                row = jax.lax.bitcast_convert_type(row, jnp.float32)
            elif name == "done":
                row = row != 0
            state[name] = row
        S = packed.shape[1]
        state["tokens"] = jnp.where(
            jnp.arange(S, dtype=jnp.int32) == first_row[0],
            first_token[0].astype(jnp.int32), state["tokens"],
        )
        state["counts"] = jnp.zeros((S, 1), jnp.int16)
        state["seen"] = jnp.zeros((S, 1), bool)
        return state

    return unpack


# -- the K=1 pipeline's device-resident batch state --------------------------


def pipe_unpack(packed, tables):
    """Batch-(re)build path: ONE packed [11, S] int32 transfer carries every
    per-row scalar (float rows bitcast); the block tables ride in a second
    transfer only when the batch composition changed."""
    def as_f32(row):
        return jax.lax.bitcast_convert_type(row, jnp.float32)

    return {
        "tokens": packed[0],
        "positions": packed[1],
        "ctx_lens": packed[2],
        "slot_blocks": packed[3],
        "slot_offsets": packed[4],
        "temps": as_f32(packed[5]),
        "top_ps": as_f32(packed[6]),
        "top_ks": packed[7],
        "min_ps": as_f32(packed[8]),
        "seeds": packed[9],
        "adapter": packed[10],
        "tables": tables,
    }


def pipe_advance(block_size):
    """``pipe_advance_fn``, the steady path ("same batch, +1 token"): tokens
    chain from the in-flight sample; the packed [4, S] int32 delta carries
    (positions, ctx_lens, upd_col, upd_val) and block-table growth is at
    most one new block per row."""

    def advance(packed, prev_sampled, tables):
        positions, ctx_lens = packed[0], packed[1]
        cols, vals = packed[2], packed[3]
        tables = table_scatter(tables, cols, vals)
        blk = jnp.take_along_axis(
            tables, (positions // block_size)[:, None], axis=1
        )[:, 0]
        active = ctx_lens > 0
        return {
            "tokens": prev_sampled,
            "positions": positions,
            "ctx_lens": ctx_lens,
            "slot_blocks": jnp.where(active, blk, 0),
            "slot_offsets": positions % block_size,
            "tables": tables,
        }

    return advance
