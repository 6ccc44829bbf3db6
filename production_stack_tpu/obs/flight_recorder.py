"""Window flight recorder: a bounded ring of per-dispatch records that
makes the device-resident scan engine explainable.

The K-step window engine (PR 8/11/15/16) packs multiple prompts' chunks,
speculative drafts and overlapped transfers into single opaque dispatches;
per-request spans alone cannot say *which window* a slow token rode or what
else shared it.  The recorder stamps one ``WindowRecord`` per dispatch
(plan composition, chain depth, planner fallback, inherited host gap) and
completes it at collect (tokens emitted/delivered/wasted, drafted/accepted,
chunk-token delivery, attributed wall time), serving the ring at
``GET /debug/windows`` and joining a request's records into
``/debug/requests/{id}``.

Lock discipline matches the tracer: records are created and completed on
the engine step thread; the HTTP server snapshots from the event loop, so
every ring mutation and every snapshot holds ``_lock``.  A dispatched-but-
uncollected record lives only on its ``_PendingStep`` (single-threaded
step-loop state) and enters the shared ring exactly once, at collect — so
"every dispatched window appears exactly once" holds by construction.

Attribution: collects are FIFO on the step thread, so
``attributed_s = collected_at - max(dispatched_at, previous collected_at)``
telescopes — summing a request's windows recovers its decode-phase wall
time even under the depth-2 lookahead pipeline, where raw
(collect - dispatch) intervals overlap and would double-count.

The same clock is the account of who waited for whom on the device: the
recorder keeps the running seconds and count of the ``prefill`` records, and
``on_close`` hands every record, as it closes, to the one who divides each
request's time among the records it rode and the prefills it stood behind
(obs/engine.py: ``EngineObs._on_record_close``).

Disabled (``obs.tracing=False``) the recorder is never consulted: the
engine gates every call on ``obs.enabled`` and ``on_dispatch`` returns
None, so the fast path carries zero recorder state.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import deque
from typing import Callable, Deque, Dict, List, Optional, Tuple

# The closed set of dispatch kinds.  Single-step paths record too —
# without them the ring has holes and per-request attribution cannot sum
# to decode wall time.
#   prefill - a standalone prefill chunk (no decode rows)
#   decode  - a pure decode dispatch (K=1 single step or K-step window)
#   mixed   - decode + packed prefill chunks (K=1 fused step or K-step
#             mixed window)
#   spec    - fused speculative window (draft+verify in the scan)
WINDOW_KINDS = ("prefill", "decode", "mixed", "spec")


@dataclasses.dataclass
class WindowRecord:
    """One engine dispatch, stamped at launch and completed at collect."""

    window_id: int
    kind: str                      # one of WINDOW_KINDS
    k: int                         # planned iterations (1 = single step, or a window of one)
    rows: int                      # decode rows in the batch
    seq_ids: Tuple[str, ...]       # sequences riding this dispatch
    chain_depth: int = 0           # 0 = cold dispatch; n = nth chained window
    provisional: bool = False      # planned off in-flight carry (lookahead)
    behind: bool = False           # built from host state, launched behind a program in flight
    spec_width: int = 0            # draft tokens per iteration (spec windows)
    drafter: str = ""              # proposal source ("ngram"/"model"), spec only
    chunk_prompts: int = 0         # distinct prompts whose chunks packed in
    chunk_tokens_planned: int = 0  # prompt tokens scheduled into the window
    chunk_tokens_delivered: int = 0
    fallback: Optional[str] = None  # planner decline reason, if it declined
    # What set a pure-decode window's ``k`` (scheduler.WINDOW_CUTS: "cap",
    # "finish" -- the first row's last token, "host" -- the fewest steps that
    # cover the step thread's pass); None on every other dispatch.
    cut: Optional[str] = None
    host_gap_s: float = 0.0        # host gap inherited from previous window
    transfer_overlap_s: float = 0.0  # H2D/D2H issued under in-flight window
    host_s: float = 0.0            # host-side dispatch cost
    dispatched_at: float = 0.0
    collected_at: Optional[float] = None
    attributed_s: float = 0.0      # non-overlapped wall time (telescoped)
    tokens_emitted: int = 0
    tokens_delivered: int = 0
    tokens_wasted: int = 0
    drafted: int = 0
    accepted: int = 0
    compile: bool = False          # an XLA compile fired inside this dispatch
    compile_s: float = 0.0
    # The step loop on the profiler's clock (all unix ns, time.time_ns()).
    # ``programs``/``program_ns``: every jitted program this dispatch
    # launched (its compile-tracker name) and when, in launch order — the
    # device runs them in that order, which is how a traced program finds
    # its record; ``launch_ns`` (served too) is the first.  ``collected_ns``:
    # the end of the last blocking read-back (None: nothing was read back).
    # ``phases``: [name, start_ns, end_ns] spans of the step thread's work
    # on this dispatch (EngineObs.phase), ordered and disjoint.
    programs: List[str] = dataclasses.field(default_factory=list)
    program_ns: List[int] = dataclasses.field(default_factory=list)
    collected_ns: Optional[int] = None
    phases: List[list] = dataclasses.field(default_factory=list)
    # Token counters, known on the host at dispatch.  ``kv_tokens``: KV
    # positions the decode rows attend, per row min(context, sliding
    # window) rounded up to whole blocks (what the paged kernel must
    # read on the first step), a layer of each kind summed over the kinds
    # where a model's layers differ (config.AttentionSpec);
    # ``kv_tokens_slots``: those of them that lie in slots of the state pool
    # (a window layer's rolling buffer), not in pages.  ``kv_groups`` /
    # ``kv_groups_coalesced``, on
    # a decode batch built from host state where one DMA of that kernel
    # carries several pages (paged_attention.py: blocks_per_descriptor):
    # the groups of so many table entries its rows held / those that were
    # one region of the pool.  ``new_tokens`` / ``bucket_tokens``: prompt
    # tokens really computed / token slots of the prefill or chunk program
    # that ran (the rest is padding).  ``cached_tokens``: tokens of those
    # prompts already in the KV cache and skipped.  ``kv_tiles_live`` /
    # ``kv_tiles_grid``: kv tiles, per layer, the prefill attention kernel
    # computes / its grid holds for those chunks (the flash prefill kernel's
    # liveness rule, or the module's own -- the latent prefill kernel's
    # (query tile, key stage) pairs -- evaluated on the host; the rest is
    # skipped; the flash kernel's grid is the chunk's block table, prefix
    # tiles of whole pages, and the chunk's own keys).  ``prefix_pages``:
    # pages of the cached prefix (a page's K and its V) the flash prefill
    # kernel copies out of the pools, summed over its query tiles and the
    # model's attention layers (every page of every live prefix tile: the
    # same rule on the host; 0 where the dense form runs instead, under a
    # mesh and off a TPU).  ``cover``: a dedicated
    # prefill's bucket and those of the chunks its prompt still has to run
    # (scheduler.cover_prefill): [256, 256, 256], [256, 256], [256] are
    # one 600-token prompt.
    kv_tokens: int = 0
    kv_tokens_slots: int = 0
    kv_groups: int = 0
    kv_groups_coalesced: int = 0
    new_tokens: int = 0
    bucket_tokens: int = 0
    cached_tokens: int = 0
    kv_tiles_live: int = 0
    kv_tiles_grid: int = 0
    prefix_pages: int = 0
    cover: Tuple[int, ...] = ()
    # What routing did in this dispatch, counted on the device by a model
    # that routes (models/sarvam_mla.py: ROUTING_STATS) and read back with
    # its tokens; None for a model that routes nothing.  ``moe_assigned``:
    # (row, expert) pairs its live rows chose, over routed layers and
    # steps; ``moe_assigned_here``: those that fell on experts held here;
    # ``experts_touched``: held experts with at least one row, summed over
    # routed layers and steps; ``expert_rows_max``: the fullest one's rows.
    # A router some of whose outputs are identity experts adds
    # (models/longcat.py: ZERO_STATS) ``moe_zero_assigned``: picks of live
    # rows that named one, and so computed nothing, of ``moe_assigned``.
    # A model with several residual streams adds (RESIDUAL_STATS):
    # ``mhc_clamped`` / ``mhc_entries``: entries of its mixing matrices'
    # exponents the clamp changed / seen; ``mhc_err_e6``: the largest
    # |row sum - 1| after the last normalisation, x 1e6.  A model with
    # selective state-space layers hands (models/jamba.py: SSM_STATS)
    # ``ssm_state_absmax_e3`` / ``ssm_dt_max_e3``: the largest |h| the
    # dispatch left in a slot and its largest step size, x 1000.  A model
    # with delta-rule layers under a decay a head (models/olmo_hybrid.py:
    # GDN_STATS) ``gdn_state_absmax_e3`` / ``gdn_beta_max_e3``: the largest
    # |S| the dispatch left in a slot and its largest beta, x 1000.
    routing: Optional[Dict[str, int]] = None
    # Under a model with a state pool (kv/state_pool.py).  ``state_rows``:
    # decode rows that read and write a slot of recurrent state each step.
    # ``state_resumed``, on a prefill record: whether this chunk began an
    # admission that started from a snapshot of the state (False: from
    # zeros, or a later chunk of its prompt); None without a state pool.
    state_rows: int = 0
    state_resumed: Optional[bool] = None
    # Who waited for whom, on ``attributed_s``' clock (obs/engine.py).
    # ``behind_s``, on the record that carried a request's first prefill
    # chunk: how long after ``dispatched_at`` the record before it was still
    # being collected -- the program was launched behind one in flight and
    # waited that long for the device.  ``finished``: one row for each
    # request of two tokens or more whose last token this record gave,
    # [seq_id, tokens, span_s, own_s, prefill_s, prefills, rest_s]: from the
    # close of the record that gave its first token to this close, divided
    # into the records it rode, the ``prefill`` records of other prompts
    # that closed meanwhile (and how many) and the remainder.
    behind_s: Optional[float] = None
    finished: List[list] = dataclasses.field(default_factory=list)

    @property
    def launch_ns(self) -> Optional[int]:
        return self.program_ns[0] if self.program_ns else None

    def to_dict(self) -> Dict:
        d = {
            "window_id": self.window_id,
            "kind": self.kind,
            "k": self.k,
            "rows": self.rows,
            "seq_ids": list(self.seq_ids),
            "chain_depth": self.chain_depth,
            "provisional": self.provisional,
            "behind": self.behind,
            "fallback": self.fallback,
            "host_gap_s": round(self.host_gap_s, 6),
            "host_s": round(self.host_s, 6),
            "dispatched_at": self.dispatched_at,
            "collected_at": self.collected_at,
            "attributed_s": round(self.attributed_s, 6),
            "tokens_emitted": self.tokens_emitted,
            "tokens_delivered": self.tokens_delivered,
            "tokens_wasted": self.tokens_wasted,
            "programs": list(self.programs),
            "program_ns": list(self.program_ns),
            "launch_ns": self.launch_ns,
            "collected_ns": self.collected_ns,
            "phases": [list(p) for p in self.phases],
        }
        if self.cut is not None:
            d["cut"] = self.cut
        if self.rows:
            d["kv_tokens"] = self.kv_tokens
        if self.kv_tokens_slots:
            d["kv_tokens_slots"] = self.kv_tokens_slots
        if self.kv_groups:
            d["kv_groups"] = self.kv_groups
            d["kv_groups_coalesced"] = self.kv_groups_coalesced
        if self.bucket_tokens:
            d["new_tokens"] = self.new_tokens
            d["bucket_tokens"] = self.bucket_tokens
            d["cached_tokens"] = self.cached_tokens
            d["kv_tiles_live"] = self.kv_tiles_live
            d["kv_tiles_grid"] = self.kv_tiles_grid
            d["prefix_pages"] = self.prefix_pages
        if self.cover:
            d["cover"] = list(self.cover)
        if self.routing:
            d.update(self.routing)
        if self.state_rows:
            d["state_rows"] = self.state_rows
        if self.state_resumed is not None:
            d["state_resumed"] = self.state_resumed
        if self.behind_s is not None:
            d["behind_s"] = round(self.behind_s, 6)
        if self.finished:
            d["finished"] = [list(row) for row in self.finished]
        if self.spec_width:
            d["spec_width"] = self.spec_width
            d["drafter"] = self.drafter
            d["drafted"] = self.drafted
            d["accepted"] = self.accepted
        if self.chunk_prompts:
            d["chunk_prompts"] = self.chunk_prompts
            d["chunk_tokens_planned"] = self.chunk_tokens_planned
            d["chunk_tokens_delivered"] = self.chunk_tokens_delivered
        if self.transfer_overlap_s:
            d["transfer_overlap_s"] = round(self.transfer_overlap_s, 6)
        if self.compile:
            d["compile"] = True
            d["compile_s"] = round(self.compile_s, 6)
        return d


class FlightRecorder:
    """Bounded ring of completed ``WindowRecord``s, newest first.

    All mutation happens on the engine step thread; HTTP snapshot readers
    take ``_lock``.  Records between ``on_dispatch`` and ``on_collect``
    are owned exclusively by the step loop (via ``_PendingStep.rec``) and
    are not yet visible to readers.
    """

    def __init__(self, enabled: bool = True, ring_size: int = 512):
        self.enabled = bool(enabled)
        self.ring_size = max(1, int(ring_size))
        self._completed: Deque[WindowRecord] = deque(maxlen=self.ring_size)
        self._lock = threading.Lock()
        self._next_id = 0
        self._last_collected_at: Optional[float] = None
        self.dropped = 0          # records evicted from a full ring
        self.windows_recorded = 0  # completed records since boot
        # Running totals of the telescoped clock over the ``prefill``
        # records (step thread writes): a decoder's wait behind other
        # prompts is their difference between two closes.
        self.prefill_s = 0.0
        self.prefills = 0
        # Called with every record as it closes, its ``attributed_s`` set
        # and the totals holding it, before a reader can see it.
        self.on_close: Optional[Callable[[WindowRecord], None]] = None

    # -- step-thread write path -------------------------------------------

    # stackcheck: allow=SC201 reason=flight-recorder timestamps are observability sinks; no plan state reads them (obs layer is plan-inert by contract)
    def on_dispatch(
        self,
        kind: str,
        *,
        k: int = 1,
        rows: int = 0,
        seq_ids: Tuple[str, ...] = (),
        chain_depth: int = 0,
        provisional: bool = False,
        behind: bool = False,
        spec_width: int = 0,
        drafter: str = "",
        chunk_prompts: int = 0,
        chunk_tokens_planned: int = 0,
        fallback: Optional[str] = None,
        cut: Optional[str] = None,
        host_gap_s: float = 0.0,
        transfer_overlap_s: float = 0.0,
        kv_tokens: int = 0,
        kv_tokens_slots: int = 0,
        new_tokens: int = 0,
        bucket_tokens: int = 0,
        cached_tokens: int = 0,
        kv_tiles_live: int = 0,
        kv_tiles_grid: int = 0,
        prefix_pages: int = 0,
        cover: Tuple[int, ...] = (),
        state_rows: int = 0,
        state_resumed: Optional[bool] = None,
        now: Optional[float] = None,
    ) -> Optional[WindowRecord]:
        """Stamp a new record at dispatch.  Returns None when disabled so
        gated call sites stay branch-cheap."""
        if not self.enabled:
            return None
        with self._lock:
            window_id = self._next_id
            self._next_id += 1
        return WindowRecord(
            window_id=window_id,
            kind=kind,
            k=int(k),
            rows=int(rows),
            seq_ids=tuple(seq_ids),
            chain_depth=int(chain_depth),
            provisional=bool(provisional),
            behind=bool(behind),
            spec_width=int(spec_width),
            drafter=str(drafter),
            chunk_prompts=int(chunk_prompts),
            chunk_tokens_planned=int(chunk_tokens_planned),
            fallback=fallback,
            cut=cut,
            host_gap_s=float(host_gap_s),
            transfer_overlap_s=float(transfer_overlap_s),
            kv_tokens=int(kv_tokens),
            kv_tokens_slots=int(kv_tokens_slots),
            new_tokens=int(new_tokens),
            bucket_tokens=int(bucket_tokens),
            cached_tokens=int(cached_tokens),
            kv_tiles_live=int(kv_tiles_live),
            kv_tiles_grid=int(kv_tiles_grid),
            prefix_pages=int(prefix_pages),
            cover=tuple(cover),
            state_rows=int(state_rows),
            state_resumed=state_resumed,
            dispatched_at=now if now is not None else time.time(),
        )

    # stackcheck: allow=SC201 reason=flight-recorder timestamps are observability sinks; no plan state reads them (obs layer is plan-inert by contract)
    def on_collect(
        self,
        rec: Optional[WindowRecord],
        *,
        now: Optional[float] = None,
        host_s: float = 0.0,
        tokens_emitted: int = 0,
        tokens_delivered: int = 0,
        tokens_wasted: int = 0,
        chunk_tokens_delivered: int = 0,
        drafted: int = 0,
        accepted: int = 0,
    ) -> None:
        """Complete a record and publish it to the ring (exactly once per
        dispatched record — dropped lookahead steps complete here too,
        with their emissions counted as wasted)."""
        if rec is None:
            return
        now = now if now is not None else time.time()
        rec.collected_at = now
        rec.host_s = float(host_s)
        rec.tokens_emitted = int(tokens_emitted)
        rec.tokens_delivered = int(tokens_delivered)
        rec.tokens_wasted = int(tokens_wasted)
        rec.chunk_tokens_delivered = int(chunk_tokens_delivered)
        rec.drafted = int(drafted)
        rec.accepted = int(accepted)
        # The clock is the step thread's own (readers see it on the records).
        prev = self._last_collected_at
        floor = rec.dispatched_at if prev is None else max(
            rec.dispatched_at, prev)
        rec.attributed_s = max(0.0, now - floor)
        if rec.kind == "prefill":
            self.prefill_s += rec.attributed_s
            self.prefills += 1
        if self.on_close is not None:
            self.on_close(rec)
        self._last_collected_at = now
        with self._lock:
            if len(self._completed) >= self.ring_size:
                self.dropped += 1
            self._completed.appendleft(rec)
            self.windows_recorded += 1

    def behind_of(self, rec: WindowRecord) -> float:
        """How long after its dispatch the record before ``rec`` closed
        (``on_collect``'s floor less ``dispatched_at``); 0 where nothing was
        in flight.  Step thread, while ``rec`` is still open."""
        prev = self._last_collected_at
        return 0.0 if prev is None else max(0.0, prev - rec.dispatched_at)

    def note_compile(self, rec: Optional[WindowRecord], seconds: float) -> None:
        """Mark a record compile-tainted (an XLA compile fired inside its
        dispatch/collect host work).  Called on the step thread before the
        record is published, so no lock is needed."""
        if rec is None:
            return
        rec.compile = True
        rec.compile_s += float(seconds)

    # -- HTTP snapshot read path ------------------------------------------

    def snapshot(
        self, seq: Optional[str] = None, limit: Optional[int] = None
    ) -> List[Dict]:
        """Lock-held dicts of completed records, newest first, optionally
        filtered to windows a sequence rode."""
        with self._lock:
            recs = [
                r.to_dict()
                for r in self._completed
                if seq is None or seq in r.seq_ids
            ]
        return recs if limit is None else recs[: max(0, int(limit))]

    def for_request(self, request_id: str) -> List[Dict]:
        """The windows one request rode, oldest first (timeline order) —
        the /debug/requests/{id} join payload."""
        recs = self.snapshot(seq=request_id)
        recs.reverse()
        return recs
