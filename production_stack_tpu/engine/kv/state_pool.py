"""Slots of recurrent state beside the block pool.

A linear-attention layer (``models/solar_kda.py``: the gated delta rule) or a
selective state-space layer (``models/jamba.py``: Mamba-1) keeps no keys: a
sequence owns one *slot*, its state over all such layers, whatever its length
(the module's ``init_cache``; 3 layers and 13 MB a slot for the first, 26
layers and 9.3 MB for the second at their published widths).  This pool is the host's book of those slots, as
``kv/block_pool.py`` is of the pages; it moves no bytes.

Slot 0 is the null slot, the padding rows' (block 0 of the pages): never
handed out, written only by rows that are the identity on it.

A **live** slot belongs to one admitted sequence from its first prefill chunk
until it finishes, is aborted or is preempted.  A **snapshot** slot holds the
state exactly at a block boundary and is keyed by that block's digest in the
prefix chain (``Sequence.prefix_chain``): a later prompt whose cached prefix
reaches that block can start its stateful layers there, which keys alone cannot
give it.  A snapshot dies with its block (``BlockPool.on_evict``) and by LRU
among the snapshots; a resume touches it, and an admission that then leaves a
deeper snapshot of its own makes the one it came from the first to go (a
session's next round starts from the deeper one; the ancestor serves only a
prompt that branches off between the two).  Nothing here is pinned: the device
runs the step programs in the order the step thread launched them, so a slot
handed on is overwritten only after every program launched before has read it.
"""

from __future__ import annotations

from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple


def pool_slots(max_num_seqs: int) -> Tuple[int, int]:
    """(live, snapshot) slots for a batch of ``max_num_seqs``: a live slot a
    running sequence and two for prompts held mid-prefill at the heads of the
    two admission queues; snapshots for the newest boundary of two and a half
    populations (a set-up's warm-up users, its measured users, and the rounds
    in flight between one user's prompt and that user's next)."""
    return max_num_seqs + 2, max(8, 5 * max_num_seqs // 2)


class StatePool:
    def __init__(self, live_slots: int, snapshot_slots: int):
        if live_slots < 1 or snapshot_slots < 1:
            raise ValueError("need at least one live and one snapshot slot")
        self.live_slots = live_slots
        self.snapshot_slots = snapshot_slots
        self.num_slots = 1 + live_slots + snapshot_slots   # 0 = null slot
        self._free_live: List[int] = list(range(live_slots, 0, -1))
        self._free_snap: List[int] = list(
            range(self.num_slots - 1, live_slots, -1))
        self._live: Dict[int, str] = {}                    # slot -> seq id
        # digest -> slot, least recently made or resumed first.
        self._snapshots: "OrderedDict[bytes, int]" = OrderedDict()
        # tpu:state_* (obs/metric_registry.py); step-thread-only writers.
        self.snapshots_taken = 0
        self.resumes = 0
        self.resume_misses = 0
        self.recomputed_tokens = 0

    # -- live slots ----------------------------------------------------------

    @property
    def slots_in_use(self) -> int:
        return len(self._live) + len(self._snapshots)

    @property
    def num_live(self) -> int:
        return len(self._live)

    @property
    def num_snapshots(self) -> int:
        return len(self._snapshots)

    def allocate_live(self, seq_id: str) -> int:
        if not self._free_live:
            raise RuntimeError(
                f"state pool exhausted: {self.live_slots} live slots held by "
                f"{sorted(self._live.values())}")
        slot = self._free_live.pop()
        self._live[slot] = seq_id
        return slot

    def free_live(self, slot: Optional[int]) -> None:
        if slot is None:
            return
        if self._live.pop(slot, None) is None:
            raise RuntimeError(f"state slot {slot} freed and not held")
        self._free_live.append(slot)

    # -- snapshots -----------------------------------------------------------

    def deepest(self, chain: Sequence[bytes], num_blocks: int) -> int:
        """How many of the first ``num_blocks`` blocks of ``chain`` the
        deepest snapshot among them covers: 0 where there is none."""
        for n in range(min(num_blocks, len(chain)), 0, -1):
            if chain[n - 1] in self._snapshots:
                return n
        return 0

    def has_snapshot(self, digest: bytes) -> bool:
        return digest in self._snapshots

    def resume(self, digest: bytes) -> int:
        """The slot of ``digest``'s snapshot, now the most recently used."""
        self._snapshots.move_to_end(digest)
        self.resumes += 1
        return self._snapshots[digest]

    def supersede(self, digest: Optional[bytes]) -> None:
        """The admission that resumed from ``digest`` has left a deeper
        snapshot: this one is the next to be evicted, and stays until then."""
        if digest in self._snapshots:
            self._snapshots.move_to_end(digest, last=False)

    def take_snapshot(self, digest: bytes) -> int:
        """A slot for the state at the end of ``digest``'s block: the one
        that holds it already (rewritten with the same state), else a free
        one, else the least recently used snapshot's."""
        slot = self._snapshots.pop(digest, None)
        if slot is None:
            slot = (self._free_snap.pop() if self._free_snap
                    else self._snapshots.popitem(last=False)[1])
            self.snapshots_taken += 1
        self._snapshots[digest] = slot
        return slot

    def drop(self, digest: Optional[bytes]) -> None:
        """The block of ``digest`` left the prefix cache: no prompt can reach
        its snapshot any more."""
        slot = self._snapshots.pop(digest, None) if digest else None
        if slot is not None:
            self._free_snap.append(slot)
