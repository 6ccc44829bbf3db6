"""Continuous-batching scheduler (TPU twist: static-shape step plans).

Each call to :meth:`schedule` emits one *step plan*: a single sequence's
prefill (bucketed length), one batched decode over all running sequences
(padded to a batch-size bucket), or — with ``mixed_batch`` on — a fused
MIXED plan packing every running sequence's decode token plus a bounded
prefill chunk of the head waiting sequence under the
``max_num_batched_tokens`` budget (chunked-prefill-integrated batching:
arriving prompts stop stalling the decoders for a full prefill bucket).
Every plan maps to a pre-compiled XLA executable — no shape escapes the
bucket set, so steady-state serving never recompiles.

Preemption: when the block pool cannot back a decode step, the youngest
running sequence is preempted.  With ``preemption_mode="offload"`` its KV
blocks are paged to host DRAM (kv/offload.py) and restored on resume —
cheaper on TPU than recompute because host<->HBM DMA overlaps compute, while
re-prefill burns MXU FLOPs (the reference reaches the same capability with
LMCache CPU offload, deployment-vllm-multi.yaml:161-166).
"""

from __future__ import annotations

import dataclasses
import functools
import logging
import math
from collections import deque
from typing import Deque, List, Optional, Tuple

from production_stack_tpu.engine.config import SchedulerConfig
from production_stack_tpu.engine.core.sequence import (
    Sequence,
    SequenceStatus,
    host_state_flags,
)
from production_stack_tpu.engine.kv.block_pool import BlockPool

logger = logging.getLogger(__name__)

# What one prefill dispatch costs besides its slots, in slots.  Measured on
# a v5e under int8 mistral-7b (PERF.md section 5, "One prefill program"): a
# ``prefill_fn`` program takes 0.086 ms a slot and, since PR 62, next to
# nothing that does not scale with its slots (22.1 ms at 256 slots, 175.9 at
# 2,048 with 1,500 valid: an intercept of 0.2 ms; it was 5.2-5.6 ms at PR 32,
# the gather of ``max_model_len`` prefix positions in every layer, which the
# kernel's walk through the block table replaced), a later chunk of a run
# 0.36 ms more for each 256 tokens written before it, and the step thread
# spends 4-5 ms building and launching each dispatch, which hides behind the
# device inside a run: what is left is ~5 ms, 56-64 slots, and six 256-slot
# chunks (138 ms on the device) beat the 2,048-slot program (176 ms) where
# this constant still sends 1,281-1,536 tokens to the latter.  It stays at
# PR 32's 128 (11 ms then; under ``256,2048`` every value from 103 to 191
# plans alike) until the engine warms every configured prefill bucket in its
# set-up: a bucket's program is compiled at its first use, and a smaller
# value moves the 2,048-slot program's first use from a prompt of 1,281
# tokens to one of 1,537, past what a warm-up of mid-length prompts reaches,
# so its compile would land on live traffic (the follow-up: ROADMAP S1 b;
# PERF.md section 7, PR 62).
PREFILL_DISPATCH_SLOTS = 128


@functools.lru_cache(maxsize=4096)
def cover_prefill(num_new: int, buckets: Tuple[int, ...]) -> Tuple[int, ...]:
    """The cheapest run of prefill programs over ``num_new`` prompt tokens:
    every chunk but the last fills its bucket, the last is padded.  A run
    costs the sum over its chunks of (bucket + PREFILL_DISPATCH_SLOTS);
    ties go to the fewer dispatches, then to the larger bucket first, so
    the cover of what a chunk leaves is the rest of the same run.  A pure
    function of its arguments: lockstep replicas plan alike."""
    step = math.gcd(*buckets)
    # best[r] = (cost, dispatches, -first bucket) of the cheapest run over r
    # tokens, for every r that a run over num_new can leave.
    best = {}
    for r in range(num_new % step or step, num_new + 1, step):
        options = []
        for b in buckets:
            cost, n = (0, 0) if b >= r else best[r - b][:2]
            options.append((cost + b + PREFILL_DISPATCH_SLOTS, n + 1, -b))
        best[r] = min(options)
    run, r = [], num_new
    while r > 0:
        run.append(-best[r][2])
        r -= run[-1]
    return tuple(run)


@dataclasses.dataclass
class PrefillPlan:
    seq: Sequence
    bucket_len: int  # padded token count (multiple of block size)
    new_block_ids: List[int]  # blocks receiving the new KV (null-padded)
    prefix_block_ids: List[int]  # cached-prefix blocks (may be empty)
    num_new_tokens: int  # valid tokens to prefill
    cached_len: int
    # False for a non-final chunk of a long prompt (chunked prefill): the
    # engine writes KV but must not sample — the logits are mid-prompt.
    is_final: bool = True
    # Dedicated prefill: the buckets of this chunk and of those still to
    # come for the prompt (``cover_prefill``); empty for a mixed-step chunk.
    cover: Tuple[int, ...] = ()
    # Under a state pool (kv/state_pool.py): the sequence's live slot; the
    # slot its linear layers start this chunk from (a snapshot's on a resumed
    # admission, the live slot on a later chunk, < 0: zeros); the slot that
    # keeps the state ``snapshot_len`` tokens into the chunk (the live slot
    # itself: no snapshot); whether the admission resumed from a snapshot.
    state_slot: int = 0
    state_from: int = -1
    snapshot_slot: int = 0
    snapshot_len: int = 0
    resumed: bool = False


# What ended a pure-decode window (StepPlan.window_cut, a flight record's
# ``cut``): the configured cap, the first row's last token, or the fewest
# steps that cover the step thread's own pass.
WINDOW_CUTS = ("cap", "finish", "host")


class WindowPace:
    """How many steps a decode window needs for the device to outlast the
    step thread's pass over it: ``ceil(COVER x pass_s / step_s)``, never
    under ``FLOOR``, from two running means the engine feeds as its chained
    decode windows close (engine.py: ``_note_window_pace``) -- a step's
    device time, and the thread's busy time a window.  None until both have
    ``MIN_SAMPLES``; the planner then keeps the cap.

    A wall-clock quantity in a plan: the engine feeds it on a single host
    only.  Replicas in lockstep must plan alike and clocks differ, so their
    engines never call ``note`` and their windows end by cap and by budget."""

    # The margin: a window lasts this many passes of the step thread.  Fixed
    # by a sweep on the chip (PERF.md section 5, PR 60).
    COVER = 3.0
    FLOOR = 2
    MIN_SAMPLES = 4
    # The means follow the last ~32 windows: the batch grows and shrinks.
    HORIZON = 32

    def __init__(self):
        self.samples = 0
        self.step_s = 0.0
        self.pass_s = 0.0

    def note(self, step_s: float, pass_s: float) -> None:
        self.samples += 1
        weight = 1.0 / min(self.samples, self.HORIZON)
        self.step_s += (step_s - self.step_s) * weight
        self.pass_s += (pass_s - self.pass_s) * weight

    def cover(self) -> Optional[int]:
        if self.samples < self.MIN_SAMPLES or self.step_s <= 0.0:
            return None
        return max(
            self.FLOOR, math.ceil(self.COVER * self.pass_s / self.step_s))


@dataclasses.dataclass
class DecodePlan:
    seqs: List[Sequence]  # <= max_num_seqs running sequences
    # Per-sequence decode TOKEN budget for this plan (aligned with
    # ``seqs``).  All 1s for classic stepping; for K-step windows each
    # entry is capped by the sequence's remaining room (max_model_len,
    # max_tokens) and its blocks are pre-allocated for the whole budget —
    # under the fused speculative window that is the MAX-ACCEPTANCE
    # growth K x (ngram + 1), not the iteration count.
    steps: List[int] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class StepPlan:
    """THE one step-plan type (unifying the former four-way plan
    taxonomy; the PR-8 compat views are retired — callers read the
    fields directly).
    Exactly one execution shape per plan, read off three fields:

      decode only                     pure decode — ``decode_window`` (K)
                                      iterations per row budgeted in
                                      ``decode.steps``; a window where
                                      ``window_cut`` says what ended it
                                      (``Scheduler._plan_window``), a
                                      single step (K = 1, no cut) where a
                                      prompt waits for an open slot
      prefill_chunk only              one prefill step (bucketed, maybe
                                      chunked)
      decode + prefill_chunk          fused mixed step (always K=1: the
                                      chunk either completes admission
                                      this step or the window machinery
                                      declined)
      decode + chunk_schedule         MIXED K-step window: each of the
                                      K = len(chunk_schedule) scan
                                      iterations runs the packed
                                      [decode + chunk] mixed forward —
                                      decode rows advance one token from
                                      the carried state while a waiting
                                      prompt's next chunk rides the same
                                      forward, chunk cursor carried
                                      in-graph.  The window always ends
                                      at an admission boundary (the
                                      schedule's last chunk is final, or
                                      the prompt continues next window).

    ``provisional`` marks plans made while the previous window is still
    in flight (optimistic no-finish assumption; the engine rolls back
    at collect).  ``window_fallback`` names the reason a pass that
    WANTED a K>1 window was forced to K=1 (``"waiting_head"`` — the
    head prompt forced per-token admission; ``"pool_pressure"`` — block
    pool / restore pressure ended chunking early); the engine folds it
    into
    ``tpu:multistep_fallback_total``."""

    decode: Optional[DecodePlan] = None
    prefill_chunk: Optional[PrefillPlan] = None
    decode_window: int = 1
    # What set a pure-decode window's length, one of WINDOW_CUTS; None on
    # every plan that is not a window (a K=1 window is one: the program's
    # trip count is a value).
    window_cut: Optional[str] = None
    provisional: bool = False
    # Mixed K-step window: one PrefillPlan per scan iteration, all at
    # ONE chunk bucket (static scan shape).  The schedule may carry
    # chunks from SEVERAL prompts: a final chunk mid-schedule admits its
    # prompt and the next iteration starts the next waiting prompt's
    # cursor (later prompts ride padded at the window's established
    # bucket — pf_valid masks identically).
    chunk_schedule: Optional[List[PrefillPlan]] = None
    window_fallback: Optional[str] = None

    @property
    def is_empty(self) -> bool:
        return self.decode is None and self.prefill_chunk is None


class Scheduler:
    def __init__(
        self,
        config: SchedulerConfig,
        block_pool: BlockPool,
        offload_cb=None,
        restore_cb=None,
        remote_prefix_cb=None,
        state_pool=None,
        state_stride: int = 0,
    ):
        self.config = config
        self.block_pool = block_pool
        # A model that keeps recurrent state beside its keys
        # (kv/state_pool.py): admission asks both pools, and a cached prefix
        # is cut back to the deepest block that has a snapshot of the state.
        # ``state_stride``: a final chunk leaves a snapshot at the deepest
        # multiple of it, counted from the chunk's start, below its last
        # token (the module's, ``snapshot_stride``).
        self.state_pool = state_pool
        self.state_stride = state_stride
        if state_pool is not None:
            if state_stride <= 0 or state_stride % block_pool.block_size:
                raise ValueError(
                    f"snapshot stride {state_stride} is not a multiple of "
                    f"the {block_pool.block_size}-token block")
            block_pool.on_evict = state_pool.drop
        # offload_cb(seq, block_ids) -> bool: snapshot blocks before they
        # are freed (engine wires offload_seq_blocks).  With the async
        # transfer plane (cache.remote_prefetch) the callback only
        # DISPATCHES a device-side gather and returns — the D2H wait and
        # any remote PUT complete on a writer thread, so schedule() never
        # blocks on DMA or the network here.
        self.offload_cb = offload_cb
        # restore_cb(seq) -> "restored" | "gone" | "retry": page an
        # offloaded sequence's KV back in; on "restored" the engine sets
        # seq.block_table/num_cached_tokens/partial_prefill so the plan
        # below resumes as a held prefix.  "retry" covers transient pool
        # pressure AND an in-flight async remote page-in — schedule again
        # next pass instead of waiting.
        self.restore_cb = restore_cb
        # remote_prefix_cb(seq, prefix_blocks, cached_len) ->
        # (prefix_blocks, cached_len): cross-engine prefix reuse through
        # the shared remote store (engine wires fetch_remote_prefix when
        # cache.disagg_role imports).  Async mode returns the inputs
        # unchanged, only ensuring a background prefetch is in flight —
        # completed fetches were already imported into the prefix cache
        # before schedule() ran, so match_prefix above saw them; legacy
        # mode (remote_prefetch=False) extends in place with blocking
        # GETs.
        self.remote_prefix_cb = remote_prefix_cb
        self.waiting: Deque[Sequence] = deque()
        self.running: List[Sequence] = []
        self.preempted: Deque[Sequence] = deque()
        self.num_preemptions = 0
        # Deterministic admission counter: priority ties break FCFS, and
        # (unlike wall-clock arrival_time) the ordering is identical on
        # every lockstep replica of a multi-host group.
        self._admit_counter = 0
        # Prompt tokens currently held by waiting+preempted sequences,
        # maintained incrementally so bounded admission can read one int
        # cross-thread instead of iterating a deque the step thread
        # mutates (a mid-iteration mutation raises RuntimeError).
        self.queued_prompt_tokens = 0
        # Decode-side chunk-budget computations (_chunk_token_budget
        # calls) — regression-tested O(1) per planning pass: packed
        # window planning over N waiters must not recompute it per
        # chunk.
        self.budget_computations = 0
        # The host's side of the window-length rule (_plan_window).
        self.pace = WindowPace()

    # -- admission ---------------------------------------------------------

    def add_seq(self, seq: Sequence) -> None:
        if seq.num_prompt_tokens >= self.config.max_model_len:
            raise ValueError(
                f"Prompt ({seq.num_prompt_tokens} tokens) exceeds max_model_len "
                f"({self.config.max_model_len})"
            )
        bs = self.block_pool.block_size
        worst_tokens = min(
            seq.num_prompt_tokens + seq.sampling_params.max_tokens,
            self.config.max_model_len,
        )
        worst_blocks = (worst_tokens + bs - 1) // bs
        if worst_blocks > self.block_pool.num_blocks - 1:
            raise ValueError(
                f"Request needs up to {worst_blocks} KV blocks but the pool "
                f"only has {self.block_pool.num_blocks - 1}; lower max_tokens "
                "or raise the KV pool size"
            )
        seq._admit_idx = self._admit_counter
        self._admit_counter += 1
        # Priority order (vLLM semantics: LOWER value runs earlier; ties
        # keep admission order).  Admission keys are monotone under FCFS,
        # so the all-default case stays a plain append.
        key = (seq.sampling_params.priority, seq._admit_idx)
        self.queued_prompt_tokens += seq.num_prompt_tokens
        for i, other in enumerate(self.waiting):
            if (other.sampling_params.priority, other._admit_idx) > key:
                self.waiting.insert(i, seq)
                return
        self.waiting.append(seq)

    def abort_seq(self, seq_id: str) -> Optional[Sequence]:
        for queue in (self.waiting, self.preempted):
            for seq in list(queue):
                if seq.seq_id == seq_id:
                    queue.remove(seq)
                    self.queued_prompt_tokens -= seq.num_prompt_tokens
                    self._release(seq)
                    return seq
        for seq in self.running:
            if seq.seq_id == seq_id:
                self.running.remove(seq)
                self._release(seq)
                return seq
        return None

    def has_unfinished(self) -> bool:
        return bool(self.waiting or self.running or self.preempted)

    @property
    def num_waiting(self) -> int:
        return len(self.waiting) + len(self.preempted)

    @property
    def num_running(self) -> int:
        return len(self.running)

    # -- planning ----------------------------------------------------------

    def _plan_window(self, budgets) -> Tuple[int, str]:
        """THE window-length rule, for every planner of a pure-decode window:
        ``(k, cut)`` with ``k = min(window_steps, first_finish, host_cover)``
        and ``cut`` the term that decided (WINDOW_CUTS).  ``budgets``: the
        steps each row could still run when the window starts (its room
        under ``max_tokens`` and ``max_model_len``, past what is in flight);
        rows with none left are not in it.

        ``first_finish``, the smallest of them: the window that holds a
        row's last token ends with it.  Windows never retire rows mid-loop
        (finish and abort land at collect), so steps past it would hold a
        finished request's last token back, and its slot, and decode a dead
        row.  Host state alone: lockstep replicas plan alike.

        ``host_cover`` (``WindowPace.cover``): shorter than that and the
        step thread's pass no longer hides behind the window in flight.

        The fused speculative window scans a static count and lands several
        tokens a step: it keeps the cap."""
        cap = self.config.window_steps
        if self.config.spec_window_enabled:
            return cap, "cap"
        k, cut = cap, "cap"
        cover = self.pace.cover()
        if cover is not None and cover < k:
            k, cut = cover, "host"
        first_finish = min(budgets, default=cap)
        if first_finish <= k and first_finish < cap:
            k, cut = max(1, first_finish), "finish"
        return k, cut

    def _room(self, seq: Sequence, ahead: int = 0) -> int:
        """Steps ``seq`` can still run once ``ahead`` tokens in flight have
        landed: its room under max_model_len and its max_tokens."""
        return min(
            self.config.max_model_len - seq.num_tokens,
            seq.sampling_params.max_tokens - seq.num_generated,
        ) - ahead

    def _window_for_pass(self) -> Tuple[int, Optional[str]]:
        """``(K, cut)`` of this pass's decode plan.  A window by the one rule
        (``_plan_window``) when no prompt waits.  A waiting head is first
        offered a MIXED K-step window (its chunks ride the decode scan — see
        ``_try_schedule_mixed_window``); only when that declines does
        the pass drop to K=1 steps (no cut: not a window) so admission —
        mixed chunk or dedicated prefill — is re-evaluated every token, not
        every K tokens (counted as ``window_fallback="waiting_head"``).

        Packed-window exception: when every batch slot is occupied, NO
        admission is possible this pass no matter how often it is
        re-evaluated — dropping to K=1 would burn K host round-trips purely
        on ceremony.  The rule's window runs: it ends no later than the
        first step a slot could FREE, which is exactly where admission
        becomes possible again."""
        if self.config.window_steps <= 1:
            return 1, None
        if self.num_waiting and (
            len(self.running) < self.config.max_num_seqs
        ):
            return 1, None
        return self._plan_window(self._room(s) for s in self.running)

    # stackcheck: root=step-thread
    def schedule(self) -> StepPlan:
        """Emit one unified :class:`StepPlan`.  With ``mixed_batch`` on
        and sequences decoding, a fused decode+chunk plan keeps arriving
        prompts from stalling the decoders — as a mixed K-step window
        when the head prompt has several chunks to go (decode keeps its
        host-cost amortization under sustained arrivals), else a K=1
        mixed step; otherwise prefer admitting a prefill when a batch
        slot is open, else decode every running sequence — as a K-step
        window when no prompt waits (the device-resident fast path),
        single-token steps otherwise."""
        window, cut = self._window_for_pass()
        if self.config.mixed_enabled and self.running:
            plan = self._try_schedule_mixed_window()
            if plan is not None:
                return plan
            plan = self._try_schedule_mixed(window, cut)
            if plan is not None:
                if (
                    self.config.window_steps > 1
                    and cut is None
                    and not (
                        plan.prefill_chunk is not None
                        and plan.prefill_chunk.is_final
                    )
                ):
                    # A waiting prompt forced single-stepping and the
                    # pass did NOT complete its admission (a final
                    # chunk IS the optimal full-service step): the
                    # window amortization was forfeited, visibly.
                    plan.window_fallback = "waiting_head"
                return plan
        plan = self._try_schedule_prefill()
        if plan is not None:
            return StepPlan(prefill_chunk=plan)
        decode = self._try_schedule_decode(window)
        if decode is not None:
            return StepPlan(
                decode=decode, decode_window=window, window_cut=cut)
        # No step possible.  Two partially-prefilled sequences can coexist
        # (one per queue, or via offload restore) and deadlock each other
        # by jointly holding the pool; roll back the youngest — freeing its
        # blocks for recompute later — until something schedules again.
        while self._rollback_youngest_partial():
            plan = self._try_schedule_prefill()
            if plan is not None:
                return StepPlan(prefill_chunk=plan)
        return StepPlan()

    def _rollback_youngest_partial(self) -> bool:
        """Free a stalled mid-prefill sequence's held blocks (its chunks
        will recompute).  Progress guarantee for the chunked-prefill path:
        admission bounds every single sequence to fit the pool alone."""
        partials = [
            s
            for s in list(self.preempted) + list(self.waiting)
            if s.partial_prefill
        ]
        if not partials:
            return False
        # Victim key mirrors _preempt_youngest: lowest priority loses,
        # youngest ADMISSION among equals.  Never wall-clock arrival_time —
        # clocks diverge across lockstep multi-host replicas, and a
        # replica-dependent victim desyncs every subsequent plan (the same
        # reason admission ordering uses _admit_idx).
        seq = max(
            partials,
            key=lambda s: (s.sampling_params.priority,
                           getattr(s, "_admit_idx", 0)),
        )
        logger.debug("Rolling back partial prefill of %s (pool pressure)", seq.seq_id)
        self._release(seq)
        seq.num_cached_tokens = 0
        seq.partial_prefill = False
        return True

    def _admission_queue(self) -> Optional[Deque[Sequence]]:
        """Pick which queue admits next.  Preempted sequences normally
        resume first (their progress is largest), but a strictly
        higher-priority waiting head (LOWER value) jumps ahead — without
        this, any preemption would starve later high-priority arrivals
        behind the whole preempted backlog.  Ties keep the preempted
        queue (progress wins).  Residual gap vs vLLM is documented in
        docs/engine.md (no priority-triggered preemption of running
        sequences)."""
        if not self.preempted:
            return self.waiting if self.waiting else None
        if not self.waiting:
            return self.preempted
        if (
            self.waiting[0].sampling_params.priority
            < self.preempted[0].sampling_params.priority
        ):
            return self.waiting
        return self.preempted

    def _try_schedule_mixed(
        self, window: int = 1, cut: Optional[str] = None,
    ) -> Optional[StepPlan]:
        """Fused step: decode every running sequence AND, when the token
        budget and a batch slot allow, a bounded prefill chunk of the
        admission head.  Returns None to fall back to the classic
        alternating path — used when the head needs the full prefill
        machinery (echo+logprobs wants per-position prompt logprobs,
        which only the dedicated prefill executable computes), so such
        requests keep today's prefill-first latency instead of waiting
        behind a decode-forever batch."""
        queue = self._admission_queue()
        head = queue[0] if queue else None
        if (
            head is not None
            and head.sampling_params.echo
            and head.sampling_params.logprobs
            and len(self.running) < self.config.max_num_seqs
        ):
            return None
        decode = self._try_schedule_decode(window)
        if decode is None:
            # Pool pressure emptied the running set: the classic path's
            # prefill-first + rollback machinery handles recovery.
            return None
        chunk = None
        if self.num_waiting and len(self.running) < self.config.max_num_seqs:
            budget = self._chunk_token_budget(len(decode.seqs))
            chunk = self._try_schedule_prefill(chunk_budget=budget)
        if chunk is None:
            return StepPlan(
                decode=decode, decode_window=window, window_cut=cut)
        return StepPlan(decode=decode, prefill_chunk=chunk)

    # -- mixed K-step windows ----------------------------------------------

    def _mixed_window_head(self) -> Optional[Sequence]:
        """The admission head a mixed K-step window could chunk, or None
        when the pass must stay on the K=1 machinery: no head / no open
        batch slot, a head needing the prompt-logprobs prefill
        executable, an offloaded head (the restore state machine lives
        on the K=1 path), or any running row using host-sampled
        features the engine would fall back out of the window for."""
        if not self.config.mixed_window_enabled or not self.running:
            return None
        if len(self.running) >= self.config.max_num_seqs:
            return None
        queue = self._admission_queue()
        head = queue[0] if queue else None
        if head is None or head.offloaded:
            return None
        sp = head.sampling_params
        if sp.echo and sp.logprobs:
            return None
        if any(host_state_flags(s)[0] for s in self.running):
            return None
        return head

    def _chunk_token_budget(self, num_decode_rows: int) -> int:
        """Per-iteration chunk token budget beside ``num_decode_rows``
        decode tokens — computed ONCE per planning pass and threaded
        through window planning.  The per-chunk recomputation this
        replaces also drifted on the packed path: a final chunk pops
        its prompt into ``running`` mid-planning, which must not
        shrink later chunks' budget (the window's decode rows are
        fixed at plan time; packed prompts only join the decode batch
        at the next boundary)."""
        self.budget_computations += 1
        return self.config.batched_tokens_budget - num_decode_rows

    def _chunk_buckets_in_budget(self, budget: int) -> List[int]:
        """Chunk buckets admissible beside the current decode batch
        under the per-iteration token budget (each scan iteration is
        one mixed step: decode tokens + one chunk <= the budget, so the
        window's total is K x (decode + chunk))."""
        return [b for b in self.config.prefill_chunk_buckets if b <= budget]

    def _next_packable_head(self) -> Optional[Sequence]:
        """The next waiting prompt a PACKED window may start chunking
        after the previous prompt's final chunk, or None to stop
        packing this window: no open batch slot left (prompts already
        popped by earlier final chunks count), empty queues, an
        offloaded head (the restore state machine lives on the K=1
        path), or a head needing the prompt-logprobs prefill
        executable."""
        if len(self.running) >= self.config.max_num_seqs:
            return None
        queue = self._admission_queue()
        head = queue[0] if queue else None
        if head is None or head.offloaded:
            return None
        sp = head.sampling_params
        if sp.echo and sp.logprobs:
            return None
        return head

    def _extend_chunk_schedule(
        self, first: PrefillPlan, k_cap: int, budget: int,
    ) -> List[PrefillPlan]:
        """Grow a window's chunk schedule past its first chunk, one
        ``_try_schedule_prefill`` chunk at a time, across prompts: a
        final chunk admits its prompt, and the next iteration starts the
        next packable head's cursor.  Stops at ``k_cap``, behind a final
        chunk with nothing packable waiting, or at pool pressure (the
        window ends non-final and the next window continues).  Every
        chunk after the first is FORCED to the window's established
        bucket T — a chunk smaller than T rides padded (pf_valid masks
        padding out of attention and the tail-logit gather reads the
        last VALID row, so the compute is bit-identical to the chunk's
        natural bucket) — which keeps the scan shape static without ever
        rolling back committed plan state when a prefix hit shrinks a
        chunk at planning time."""
        schedule = [first]
        while len(schedule) < k_cap:
            if schedule[-1].is_final and self._next_packable_head() is None:
                break
            nxt = self._try_schedule_prefill(
                chunk_budget=budget, force_bucket=first.bucket_len
            )
            if nxt is None:
                break
            schedule.append(nxt)
        return schedule

    def _mixed_window_decode_steps(self, seqs, k_eff, bases=None):
        """Per-row decode token budgets for a mixed K-step window: the
        plain iteration count (the in-window drafter never engages in a
        mixed window — drafting is a pure-decode-window feature), capped
        by each row's max_model_len / max_tokens room.  0 freezes the
        row for the whole window (its stream is length-done; the K=1
        world would have retired it, and collect() does the same)."""
        steps = []
        for i, seq in enumerate(seqs):
            base_tokens, base_gen = (
                bases[i] if bases is not None
                else (seq.num_tokens, seq.num_generated)
            )
            room_len = self.config.max_model_len - base_tokens
            room_out = seq.sampling_params.max_tokens - base_gen
            steps.append(max(0, min(k_eff, room_len, room_out)))
        return steps

    def _try_schedule_mixed_window(self) -> Optional[StepPlan]:
        """Plan a MIXED K-step window: up to window_steps scan
        iterations, each running the packed [decode + chunk] mixed
        forward.  The window always ends at an admission boundary (its
        last chunk is final, or the prompt keeps chunking next window),
        which is what keeps greedy streams byte-identical and seeded
        streams bit-identical to K=1 mixed stepping: iteration t of a
        window dispatched at step counter c IS step c+t of the K=1
        world, chunk shapes included.  Returns None to fall back to the
        K=1 machinery (which owns preemption, restore, and the
        echo+logprobs special cases); a planned single-chunk outcome is
        emitted in the K=1 shape directly (nothing to amortize).

        K is not clamped by queue depth: a packed window IS the
        admission — a final chunk mid-window admits its prompt and the
        next iteration starts the next waiter's cursor, so deep queues
        fill the window instead of shrinking it."""
        head = self._mixed_window_head()
        if head is None:
            return None
        budget = self._chunk_token_budget(len(self.running))
        buckets = self._chunk_buckets_in_budget(budget)
        if not buckets:
            return None
        k_cap = self.config.window_steps
        # Multi-chunk precheck before committing any state: a head that
        # fits one chunk bucket admits completely in one K=1 mixed step
        # (a false positive from an unknown prefix hit just ends the
        # window early at the final chunk).  The window keeps going when
        # OTHER waiters could fill the remaining iterations.
        remaining_max = head.num_prompt_tokens - (
            head.num_cached_tokens if head.partial_prefill else 0
        )
        if remaining_max <= buckets[-1] and self.num_waiting <= 1:
            return None
        decode = self._mixed_window_decode_plan(k_cap)
        if decode is None:
            return None
        first = self._try_schedule_prefill(chunk_budget=budget)
        schedule = (
            [] if first is None
            else self._extend_chunk_schedule(first, k_cap, budget)
        )
        k_eff = len(schedule)
        if k_eff < 2:
            # Nothing to amortize: emit the exact K=1 mixed shape (decode
            # blocks are over-allocated for the declined window — they
            # sit in the block tables and back later steps).  A final
            # first chunk with nothing packable behind it is a natural
            # K=1 shape, not a decline; no chunk at all, or no second one
            # behind a non-final first, is the pool (or a restore retry)
            # refusing the blocks.
            decode.steps = [1] * len(decode.seqs)
            natural = first is not None and first.is_final
            return StepPlan(
                decode=decode, prefill_chunk=first, decode_window=1,
                window_fallback=None if natural else "pool_pressure",
            )
        decode.steps = self._mixed_window_decode_steps(decode.seqs, k_eff)
        return StepPlan(
            decode=decode, chunk_schedule=schedule, decode_window=k_eff,
        )

    def _grow(self, seq: Sequence, need: int) -> None:
        """``need`` more blocks for a running row, next to its last where
        the pool can (kv/block_pool.py: a row that grows keeps its run)."""
        table = seq.block_table
        table.extend(self.block_pool.allocate(
            need, after=table[-1] if table else None))

    def _mixed_window_decode_plan(self, k_cap: int) -> Optional[DecodePlan]:
        """Decode rows for a mixed K-step window, blocks pre-allocated
        for the whole k_cap budget.  Declines instead of preempting —
        pool pressure falls back to the K=1 path, which owns the
        preemption/rollback recovery machinery (and whose victim choice
        must not depend on whether a window was attempted)."""
        if not self.running:
            return None
        bs = self.block_pool.block_size
        steps = self._mixed_window_decode_steps(self.running, k_cap)
        needs = []
        for seq, k in zip(self.running, steps):
            slots = seq.num_tokens + max(k, 1) - 1
            needs.append(max(0, -(-slots // bs) - len(seq.block_table)))
        total = sum(needs)
        if total and not self.block_pool.can_allocate(total):
            return None
        for seq, need in zip(self.running, needs):
            if need:
                self._grow(seq, need)
        return DecodePlan(seqs=list(self.running), steps=steps)

    def _try_schedule_prefill(
        self, chunk_budget: Optional[int] = None,
        force_bucket: Optional[int] = None,
    ) -> Optional[PrefillPlan]:
        """Plan one prefill step.  ``chunk_budget`` switches to mixed-step
        chunk mode: the padded length comes from ``prefill_chunk_buckets``
        (not ``prefill_buckets``) and may not exceed the budget.
        ``force_bucket`` (packed windows) pins the padded chunk shape to
        the window's established bucket — one scan has ONE static chunk
        shape, and a chunk smaller than the bucket rides padded
        (bit-identical: pf_valid masks padding and the tail-logit
        gather reads the last valid row)."""
        if len(self.running) >= self.config.max_num_seqs:
            return None
        queue = self._admission_queue()
        if not queue:
            return None
        seq = queue[0]
        cut_back = 0
        if chunk_budget is not None:
            if force_bucket is not None:
                chunk_buckets = [force_bucket]
            else:
                chunk_buckets = [
                    b for b in self.config.prefill_chunk_buckets
                    if b <= chunk_budget
                ]
            sp = seq.sampling_params
            if not chunk_buckets or (sp.echo and sp.logprobs):
                # No chunk fits the budget, or the head needs the
                # prompt-logprobs prefill executable: no chunk this step
                # (the mixed caller degrades to decode-only; the classic
                # path serves echo+logprobs heads prefill-first).
                return None

        if seq.offloaded:
            # Page the KV snapshot back in; on "restored" the engine has
            # set block_table/num_cached_tokens/partial_prefill and the
            # plan below resumes from that held prefix (no recompute).
            # "retry" (transient pool pressure, snapshot kept) leaves the
            # offloaded flag set and lets decode free blocks first;
            # "gone" falls through to a plain re-prefill.
            result = self.restore_cb(seq) if self.restore_cb is not None else "gone"
            if result == "retry":
                return None
            seq.offloaded = False

        if seq.partial_prefill:
            # Chunks already written: the sequence owns its blocks.
            prefix_blocks = list(seq.block_table)
            cached_len = seq.num_cached_tokens
        elif seq.sampling_params.echo and seq.sampling_params.logprobs:
            # echo+logprobs needs a logprob for EVERY prompt position; a
            # prefix-cache hit would skip those rows' compute, so this
            # sequence prefills from scratch (vLLM's prompt_logprobs makes
            # the same trade).
            prefix_blocks, cached_len = [], 0
        else:
            prefix_blocks, cached_len = self.block_pool.match_prefix(
                seq.prompt_token_ids, namespace=seq.cache_ns,
                chain=seq.prefix_chain,
            )
            if self.remote_prefix_cb is not None:
                prefix_blocks, cached_len = self.remote_prefix_cb(
                    seq, prefix_blocks, cached_len
                )
            if self.state_pool is not None:
                prefix_blocks, cached_len, cut_back = self._cut_back_to_state(
                    seq, prefix_blocks, cached_len
                )
        num_new = seq.num_prompt_tokens - cached_len
        cover: Tuple[int, ...] = ()
        if chunk_budget is not None:
            # Mixed-step chunk: pad to the chunk-bucket set so the fused
            # executable inventory stays |chunk_buckets| x |decode buckets|.
            fit = [b for b in chunk_buckets if b >= num_new]
            is_final = bool(fit)
            bucket = fit[0] if fit else chunk_buckets[-1]
            if not is_final:
                num_new = bucket
        else:
            # Chunked prefill: run the first chunk of the cheapest cover
            # now; unless it is the last, keep the sequence at the queue
            # head and continue next step from the accumulated prefix.
            cover = cover_prefill(num_new, tuple(self.config.prefill_buckets))
            bucket = cover[0]
            is_final = len(cover) == 1
            if not is_final:
                num_new = bucket
        bs = self.block_pool.block_size
        blocks_needed = (num_new + bs - 1) // bs
        if not self.block_pool.can_allocate(blocks_needed):
            if not seq.partial_prefill:
                self.block_pool.free(prefix_blocks)
            return None
        new_blocks = self.block_pool.allocate(
            blocks_needed, after=prefix_blocks[-1] if prefix_blocks else None)
        state = {}
        if self.state_pool is not None:
            state = self._plan_state(
                seq, cached_len, num_new, is_final, cut_back
            )
        seq.num_cached_tokens = cached_len
        seq.block_table = prefix_blocks + new_blocks
        if is_final:
            queue.popleft()
            self.queued_prompt_tokens -= seq.num_prompt_tokens
            seq.status = SequenceStatus.RUNNING
            seq.partial_prefill = False
            self.running.append(seq)
        else:
            seq.partial_prefill = True
            seq.num_cached_tokens = cached_len + num_new
        return PrefillPlan(
            seq=seq,
            bucket_len=bucket,
            new_block_ids=new_blocks,
            prefix_block_ids=prefix_blocks,
            num_new_tokens=num_new,
            cached_len=cached_len,
            is_final=is_final,
            cover=cover,
            **state,
        )

    def _cut_back_to_state(self, seq: Sequence, prefix_blocks, cached_len):
        """A linear layer cannot start from keys: of the cached blocks the
        block pool matched, keep those up to the deepest one whose boundary
        the state pool holds a snapshot of, and give the others back (their
        tokens are prefilled again, into blocks of the sequence's own).
        Returns (blocks, cached_len, tokens cut); the pool's hit count is
        what the admission skips after the cut, not before it."""
        bs = self.block_pool.block_size
        kept = self.state_pool.deepest(seq.prefix_chain, len(prefix_blocks))
        cut = prefix_blocks[kept:]
        if cut:
            self.block_pool.free(cut)
            self.block_pool.hit_tokens -= len(cut) * bs
        return prefix_blocks[:kept], kept * bs, cached_len - kept * bs

    def _plan_state(self, seq: Sequence, cached_len: int, num_new: int,
                    is_final: bool, cut_back: int) -> dict:
        """The state slots of one planned chunk (``PrefillPlan``'s fields),
        once its blocks are allocated: the first chunk of an admission takes
        a live slot and starts from the snapshot at ``cached_len`` (from zeros
        where that is 0); a later chunk goes on from the live slot; a final
        chunk leaves a snapshot at the deepest stride boundary below its last
        token, keyed by that block's digest, unless one is there, and then
        lets the snapshot the admission resumed from be the next evicted."""
        pool, bs = self.state_pool, self.block_pool.block_size
        chain = seq.prefix_chain
        resumed = False
        if seq.state_slot is None:
            seq.state_slot = pool.allocate_live(seq.seq_id)
            start = -1
            seq.state_resumed_from = None
            if cached_len:
                seq.state_resumed_from = chain[cached_len // bs - 1]
                start = pool.resume(seq.state_resumed_from)
                resumed = True
            elif cut_back:
                pool.resume_misses += 1
            pool.recomputed_tokens += cut_back
        else:
            start = seq.state_slot
        snap_slot, snap_len = seq.state_slot, 0
        if is_final and self.block_pool.enable_prefix_caching:
            at = (num_new - 1) // self.state_stride * self.state_stride
            block = (cached_len + at) // bs
            if 0 < block <= len(chain) and not pool.has_snapshot(
                chain[block - 1]
            ):
                snap_slot, snap_len = pool.take_snapshot(chain[block - 1]), at
                # The session's next round starts from this one: the one it
                # came from is the first to go when the snapshots are full.
                pool.supersede(seq.state_resumed_from)
        return dict(
            state_slot=seq.state_slot, state_from=start,
            snapshot_slot=snap_slot, snapshot_len=snap_len, resumed=resumed,
        )

    def _window_token_cap(self, window: int) -> int:
        """Per-row token ceiling for a pure-decode window plan: the
        max-acceptance growth K x (draft_len + 1) — draft_len from
        whichever drafter is configured (n-gram count or the model
        drafter's speculative_draft_len) — only when the fused drafter
        can actually engage: it drafts exclusively for all-greedy
        batches (the same temperature <= 0 predicate the engine
        dispatches on, read from broadcast SamplingParams so lockstep
        replicas agree) — and plain K otherwise, so sampled workloads
        never pre-allocate blocks for drafts that cannot happen.  A
        model-drafter window that declines to plain at dispatch time
        (draft-pool pressure) emits at most K tokens — strictly under
        this ceiling, so the pre-allocation stays sufficient."""
        if (
            window > 1
            and self.config.spec_window_enabled
            and all(
                s.sampling_params.temperature <= 0 for s in self.running
            )
        ):
            return window * (self.config.spec_draft_len + 1)
        return window

    def _step_budget(self, seq: Sequence, window: int = 1) -> int:
        """Decode TOKENS this sequence may emit in one plan (1 at K=1,
        drafters included): bounded by max_model_len and the request's
        max_tokens (stop/EOS cut shorter — the device stop-mask freezes
        the row; a mismatching host-only condition discards on readback).
        Under the fused speculative window a K-iteration plan can land
        up to K x (ngram + 1) tokens at full acceptance, so the budget —
        and the block pre-allocation derived from it — covers the
        max-acceptance growth (_window_token_cap), never just the
        iteration count."""
        n = self._window_token_cap(window)
        room_len = self.config.max_model_len - seq.num_tokens
        room_out = seq.sampling_params.max_tokens - seq.num_generated
        return max(1, min(n, room_len, room_out))

    def _try_schedule_decode(self, window: int = 1) -> Optional[DecodePlan]:
        if not self.running:
            return None
        bs = self.block_pool.block_size

        def blocks_needed(seq: Sequence) -> int:
            # Iteration i consumes the token at position num_tokens-1+i, so
            # a k-step budget writes KV through slot num_tokens+k-2 — the
            # table must cover num_tokens+k-1 slots (k=1: num_tokens).
            slots = seq.num_tokens + self._step_budget(seq, window) - 1
            return max(0, -(-slots // bs) - len(seq.block_table))

        # Ensure every running sequence has blocks for its whole budget;
        # preempt the youngest until the step fits.
        while self.running:
            need = sum(blocks_needed(seq) for seq in self.running)
            if self.block_pool.can_allocate(need):
                break
            self._preempt_youngest()
        if not self.running:
            return None
        for seq in self.running:
            need = blocks_needed(seq)
            if need:
                self._grow(seq, need)
        return DecodePlan(
            seqs=list(self.running),
            steps=[self._step_budget(seq, window) for seq in self.running],
        )

    def schedule_provisional_window(
        self, inflight_seqs: List[Sequence], inflight_steps: List[int]
    ) -> Optional[StepPlan]:
        """Plan the NEXT K-step decode window while the previous window
        is still in flight on the device, under the optimistic
        assumption that no in-flight row stops early and every row emits
        its full ``inflight_steps`` budget (the device window carry
        keeps actually-stopped rows frozen; the engine discards their
        overrun on readback).  Declines (None) whenever the pipeline
        must break and replan synchronously: the running set changed, an
        admission is pending that a MIXED window cannot serve (window
        selection must drop to K=1 mixed steps), every row's remaining
        budget is zero, or backing the window would require preemption.
        A waiting head whose chunks CAN ride the scan chains a MIXED
        window off the in-flight carry instead of breaking the pipeline
        — the sustained-arrival case that used to serialize every
        window boundary into K=1 host round-trips.  The window's length is
        ``_plan_window``'s, as the synchronous planner's."""
        if self.config.window_steps <= 1:
            return None
        if len(self.running) < len(inflight_seqs) or any(
            a is not b for a, b in zip(self.running, inflight_seqs)
        ):
            return None
        parked = len(self.running) > len(inflight_seqs)
        if parked:
            # The in-flight window itself admitted prompts (packed
            # final chunks pop into self.running at plan time).  Those
            # rows have NO slot in the device carry yet — a chained
            # MIXED window may keep streaming over the carried rows
            # while the newcomers PARK for one window (their first
            # token is already finalized at the in-flight window's
            # collect; they join the batch at the next synchronous
            # rebuild) — only when MORE packing work is waiting;
            # otherwise break the pipeline so the parked rows join
            # immediately.
            if any(
                seq.num_generated > 0
                for seq in self.running[len(inflight_seqs):]
            ):
                return None  # not a parked admission: replan sync
        if not inflight_seqs:
            return None
        if self.waiting or self.preempted:
            plan = self._provisional_mixed_window(inflight_steps)
            if plan is not None:
                return plan
            if parked or len(self.running) < self.config.max_num_seqs:
                return None
            if any(
                seq.remaining_budget <= prev_k
                for seq, prev_k in zip(inflight_seqs, inflight_steps)
            ):
                # A row exhausts its output budget INSIDE the in-flight
                # window: its slot frees at collect, so a chained pure
                # window would decode a dead row while this waiting prompt
                # could be admitted.  Break the pipeline; the synchronous
                # replan sees the freed slot.
                return None
            # A slot-full batch: no admission is possible at this boundary
            # no matter how it replans, so chain a pure-decode window off
            # the carry instead of breaking the pipeline into K=1
            # waiting_head steps (mirrors _window_for_pass's slot-full rule).
        elif parked:
            return None  # nothing left to pack: rebuild with the rows
        # The in-flight window will (optimistically) land its whole prev_k
        # token budget before this one runs (full acceptance under
        # speculation; the device carry keeps the real count and the engine
        # discards overrun on readback).
        plan, _ = self._window_ahead(
            self.running[: len(inflight_seqs)], inflight_steps)
        if plan is not None:
            plan.provisional = True
        return plan

    def _window_ahead(
        self, rows: List[Sequence], aheads: List[int],
    ) -> Tuple[Optional[StepPlan], Optional[str]]:
        """The pure-decode window for ``rows`` planned while work is in
        flight: row ``i`` stands ``aheads[i]`` tokens further along than the
        host's bookkeeping says, its room is reckoned past them, and a row
        with none left rides dead (no step).  (plan, None); (None, None)
        where no row has a step to run; (None, "no_free_blocks") where
        backing the window would take a preemption (never planned here: the
        victim choice must see collected state)."""
        rooms = [
            max(0, self._room(seq, ahead)) for seq, ahead in zip(rows, aheads)
        ]
        if not any(rooms):
            return None, None
        window, cut = self._plan_window(r for r in rooms if r)
        # Per-window per-row token ceiling: K x (ngram + 1) under the
        # fused speculative window at max acceptance (all-greedy batch),
        # K otherwise.
        max_tok = self._window_token_cap(window)
        bs = self.block_pool.block_size
        steps = [min(max_tok, room) for room in rooms]
        needs = [
            max(0, -(-(seq.num_tokens + ahead + k - 1) // bs)
                - len(seq.block_table))
            for seq, ahead, k in zip(rows, aheads, steps)
        ]
        total = sum(needs)
        if total and not self.block_pool.can_allocate(total):
            return None, "no_free_blocks"
        for seq, need in zip(rows, needs):
            if need:
                self._grow(seq, need)
        return StepPlan(
            decode=DecodePlan(seqs=list(rows), steps=steps),
            decode_window=window, window_cut=cut,
        ), None

    def _provisional_mixed_window(
        self, inflight_steps: List[int]
    ) -> Optional[StepPlan]:
        """Chain a MIXED K-step window off the in-flight carry for a
        waiting head: decode budgets are planned from the optimistic
        post-window base exactly like the pure provisional path, and the
        head's chunk schedule continues from its plan-time cursor (the
        in-flight window's chunks already advanced it).  Unlike the
        synchronous planner this EMITS single-chunk windows too — a
        1-iteration mixed scan is bit-identical to the K=1 mixed step
        and keeps the pipeline streaming through the admission.
        Declines (sync replan at the boundary) when the head cannot
        chunk at all."""
        cfg = self.config
        head = self._mixed_window_head()
        if head is None:
            return None
        # The chained scan's decode batch is the device CARRY's row set
        # (parked admissions from the in-flight window have no slot
        # yet), so the chunk budget and decode planning cover exactly
        # those rows.
        rows = self.running[: len(inflight_steps)]
        budget = self._chunk_token_budget(len(rows))
        buckets = self._chunk_buckets_in_budget(budget)
        if not buckets:
            return None
        k_cap = cfg.window_steps
        # Single-chunk heads decline (pipeline break -> the sync K=1
        # mixed step admits them whole): a 1-iteration scan would mint
        # a whole executable variant for zero amortization.  A prefix
        # hit discovered at chunk planning can still shrink a
        # multi-chunk head to one final chunk — that rare case emits
        # the 1-iteration window below rather than rolling back
        # committed plan state.  The window keeps chaining when
        # OTHER waiters could fill the remaining iterations.
        remaining_max = head.num_prompt_tokens - (
            head.num_cached_tokens if head.partial_prefill else 0
        )
        if remaining_max <= buckets[-1] and self.num_waiting <= 1:
            return None
        bs = self.block_pool.block_size
        bases = [
            (seq.num_tokens + prev_k, seq.num_generated + prev_k)
            for seq, prev_k in zip(rows, inflight_steps)
        ]
        steps = self._mixed_window_decode_steps(
            rows, k_cap, bases=bases
        )
        needs = []
        for (base_tokens, _), k, seq in zip(bases, steps, rows):
            slots = base_tokens + k - 1
            needs.append(max(0, -(-slots // bs) - len(seq.block_table)))
        total = sum(needs)
        if total and not self.block_pool.can_allocate(total):
            return None
        for seq, need in zip(rows, needs):
            if need:
                self._grow(seq, need)
        # Snapshot BEFORE chunk planning: a final chunk pops the head
        # into self.running at plan time, and the popped head has no
        # decode row in THIS window (it joins at the next boundary).
        decode_seqs = list(rows)
        first = self._try_schedule_prefill(chunk_budget=budget)
        if first is None:
            # Nothing chunkable (pool pressure / restore retry): break
            # the pipeline so the sync pass re-evaluates at K=1.  The
            # decode blocks above stay in the block tables and back the
            # replanned step.
            return None
        schedule = self._extend_chunk_schedule(first, k_cap, budget)
        k_eff = len(schedule)
        return StepPlan(
            decode=DecodePlan(
                seqs=decode_seqs,
                steps=[min(s, k_eff) for s in steps],
            ),
            chunk_schedule=schedule,
            decode_window=k_eff,
            provisional=True,
        )

    def schedule_provisional(
        self, inflight_seqs: List[Sequence]
    ) -> Optional[DecodePlan]:
        """Plan the NEXT decode step while the previous one is still in
        flight on the device, under the optimistic assumption that no
        in-flight sequence finishes (the engine rolls back appends for
        sequences that did — the same overrun argument multi-step decode
        relies on).  Returns None whenever the pipeline must break and
        replan synchronously:

        * the running set changed under us (an abort landed),
        * an admission is pending (a waiting/preempted sequence could
          prefill into an open slot — ordering must match the
          synchronous scheduler),
        * any in-flight sequence PREDICTABLY finishes this step
          (max_tokens / max_model_len — length finishes are host-known
          before the token is),
        * backing the extra token would require preemption (provisional
          planning never preempts: the victim choice must see collected
          state).

        On success every returned sequence's block table already covers
        the provisional +1 token (at most one new block per sequence)."""
        if len(self.running) != len(inflight_seqs) or any(
            a is not b for a, b in zip(self.running, inflight_seqs)
        ):
            return None
        if not self.running:
            return None
        if (self.waiting or self.preempted) and (
            len(self.running) < self.config.max_num_seqs
        ):
            return None
        for seq in self.running:
            if seq.num_generated + 1 >= seq.sampling_params.max_tokens:
                return None
            if seq.num_tokens + 1 >= self.config.max_model_len:
                return None
        bs = self.block_pool.block_size
        needs = [
            # After the in-flight token lands the sequence holds
            # num_tokens+1 tokens; the next step writes KV at slot index
            # num_tokens, so the table must cover num_tokens+1 slots.
            max(0, -(-(seq.num_tokens + 1) // bs) - len(seq.block_table))
            for seq in self.running
        ]
        total = sum(needs)
        if total and not self.block_pool.can_allocate(total):
            return None
        for seq, need in zip(self.running, needs):
            if need:
                self._grow(seq, need)
        return DecodePlan(seqs=list(self.running), steps=[1] * len(self.running))

    # -- an admission behind the work in flight -----------------------------

    def schedule_prefill_behind(
        self,
    ) -> Tuple[Optional[PrefillPlan], Optional[str]]:
        """Plan the head prompt's next dedicated prefill chunk while a
        program is still in flight, from what the host knows without that
        program's read-back: the chunk ``schedule()`` would plan once the
        pass in flight is collected, under the optimistic no-finish
        assumption the chained windows use (a row that finishes in flight
        frees its slot and blocks at collect, after this plan).  Never
        preempts, restores or fetches.  Returns (plan, None), or (None, why
        not): the synchronous path then plans at the boundary, with
        collected state.  (Under ``mixed_enabled`` the admission is the
        mixed planners'; the engine does not ask.)"""
        if self.preempted:
            return None, "preempted"
        head = self.waiting[0]
        if head.offloaded or self.remote_prefix_cb is not None:
            return None, "block_fetch"
        if len(self.running) >= self.config.max_num_seqs:
            return None, "no_free_row"
        plan = self._try_schedule_prefill()
        if plan is None:
            return None, "no_free_blocks"
        return plan, None

    def schedule_window_behind(
        self, first: Optional[Sequence]
    ) -> Tuple[Optional[StepPlan], Optional[str]]:
        """Plan the decode window that follows an admission while the
        admitting prefill is still in flight: every running row with the
        budget ``_try_schedule_decode`` gives it, and ``first`` (the row that
        prefill admits; its first token is on the device) one token further
        along than the host's bookkeeping says.  A ``first`` whose budget is
        that one token stays in the plan with no step to run: it finishes
        at the prefill's collect and is an overrun of this window.  Returns
        (plan, None); (None, None) where no row has a step to run (a window
        of nothing is not launched); (None, why) where backing the window
        would take a preemption."""
        if self.config.window_steps <= 1:
            return None, None
        return self._window_ahead(
            self.running, [1 if seq is first else 0 for seq in self.running])

    # -- preemption / release ---------------------------------------------

    def _preempt_youngest(self) -> None:
        # Victim: the lowest-priority running sequence (highest value),
        # youngest among equals — high-priority work survives pool
        # pressure at the expense of low-priority work.
        seq = max(
            self.running,
            key=lambda s: (s.sampling_params.priority,
                           getattr(s, "_admit_idx", 0)),
        )
        self.running.remove(seq)
        seq.status = SequenceStatus.PREEMPTED
        seq.preempt_count += 1
        self.num_preemptions += 1
        if self.config.preemption_mode == "offload" and self.offload_cb is not None:
            # Page the blocks to host DRAM *before* the pool can reuse them.
            seq.offloaded = bool(self.offload_cb(seq, list(seq.block_table)))
        self._release(seq)
        # Re-prefill path treats all prior tokens as the new prompt.
        seq.outputs_absorbed += len(seq.output_token_ids)
        seq.prompt_token_ids = seq.all_token_ids
        seq.output_token_ids = []
        # Emptying output_token_ids re-arms the min_tokens floor (the
        # host predicate counts post-preemption output tokens); the
        # engine's cached boundary-crossing bit must re-arm with it.
        if getattr(seq, "_min_tok_pending", None) is not None:
            seq._min_tok_pending = (
                seq.sampling_params.min_tokens > 0
            )
        self.queued_prompt_tokens += seq.num_prompt_tokens
        self.preempted.appendleft(seq)
        logger.debug("Preempted %s (mode=%s)", seq.seq_id, self.config.preemption_mode)

    def _release(self, seq: Sequence) -> None:
        if seq.block_table:
            self.block_pool.free(seq.block_table)
            seq.block_table = []
        if seq.state_slot is not None:
            self.state_pool.free_live(seq.state_slot)
            seq.state_slot = None

    def finish_seq(self, seq: Sequence) -> None:
        if seq in self.running:
            self.running.remove(seq)
        # Register the sequence's full blocks for prefix reuse BEFORE
        # freeing, so the freed blocks enter the reclaimable LRU tier.
        self.block_pool.register_prefix(
            seq.all_token_ids, seq.block_table, namespace=seq.cache_ns,
            chain=seq.prefix_chain,
        )
        self._release(seq)
        seq.status = SequenceStatus.FINISHED
