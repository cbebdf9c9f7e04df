"""GroupBatchState: struct-of-arrays consensus state for every hosted group.

This replaces the reference's per-division mutable objects
(FollowerInfo nextIndex/matchIndex/lastRpcTime, LeaderStateImpl's
commit bookkeeping, FollowerState's election deadline) with ``[G, P]`` numpy
arrays managed by a slot free-list, so the whole server's consensus state is
one tensor batch — the multi-Raft fan-in point (RaftServerProxy.ImplMap,
RaftServerProxy.java:89) becomes an array axis.

Times are int32 milliseconds since engine start.  Indices are int32.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

# role codes (device-friendly int8; see engine.roles — shared with kernels)
from ratis_tpu.engine.roles import (ROLE_CANDIDATE, ROLE_FOLLOWER,  # noqa: F401
                                    ROLE_LEADER, ROLE_LISTENER, ROLE_UNUSED)

NO_DEADLINE = np.iinfo(np.int32).max


class GroupBatchState:
    def __init__(self, max_groups: int = 1024, max_peers: int = 8,
                 n_slices: int = 1):
        g, p = max_groups, max_peers
        # Mesh slicing (ratis_tpu.parallel.mesh): the capacity is split into
        # ``n_slices`` contiguous row ranges, one per mesh device, and each
        # group is pinned to a slot WITHIN its owning slice so the device
        # that holds the rows also receives the group's packed events.
        # With one slice (the default) allocation is exactly the old single
        # free list.
        self.n_slices = max(1, int(n_slices))
        if g % self.n_slices:
            raise ValueError(
                f"capacity {g} not divisible by {self.n_slices} slices "
                f"(pad with parallel.mesh.pad_to_mesh)")
        self.slice_rows = g // self.n_slices
        self.capacity = g
        self.max_peers = p
        self.role = np.zeros(g, np.int8)
        self.self_slot = np.zeros(g, np.int8)
        self.self_mask = np.zeros((g, p), bool)
        self.conf_cur = np.zeros((g, p), bool)
        self.conf_old = np.zeros((g, p), bool)
        self.priority = np.zeros((g, p), np.int32)
        self.self_priority = np.zeros(g, np.int32)
        self.match_index = np.full((g, p), -1, np.int32)
        self.next_index = np.zeros((g, p), np.int32)
        self.flush_index = np.full(g, -1, np.int32)
        self.commit_index = np.full(g, -1, np.int32)
        self.first_leader_index = np.zeros(g, np.int32)
        self.last_ack_ms = np.zeros((g, p), np.int32)
        self.election_deadline_ms = np.full(g, NO_DEADLINE, np.int32)
        # Candidate vote-round state (batched elections, SURVEY §3.3 HOT
        # LOOP #2): grant/reject masks + round deadline; NO_DEADLINE means
        # no round in flight for the slot.  Tallied for every candidate in
        # one ops.quorum.tally_votes dispatch per engine tick, replacing
        # the reference's per-division waitForResults loop
        # (LeaderElection.java:498-592).
        self.vote_grants = np.zeros((g, p), bool)
        self.vote_rejects = np.zeros((g, p), bool)
        self.vote_deadline_ms = np.full(g, NO_DEADLINE, np.int32)
        # Lag-ledger inputs (engine/ledger.py): last applied index and
        # leader pending-queue depth mirrored from the division, the
        # server-wide dense peer id per [slot, column] (-1 = unmapped),
        # and a per-slot allocation generation so delta baselines from a
        # released slot never bleed into its next tenant.
        self.applied_index = np.full(g, -1, np.int32)
        self.pending_count = np.zeros(g, np.int32)
        self.peer_index = np.full((g, p), -1, np.int32)
        self.alloc_gen = np.zeros(g, np.int32)
        # One free list per slice over its contiguous row range (popped
        # low-to-high, matching the historical single-list order).
        self._free: list[list[int]] = [
            list(range((i + 1) * self.slice_rows - 1,
                       i * self.slice_rows - 1, -1))
            for i in range(self.n_slices)]
        self.active: set[int] = set()
        # Slots whose host-side state changed since the last engine tick.
        # The device-resident tick uploads ONLY these rows (plus packed ack
        # events); the scalar tick re-runs commit math only for these.
        self.dirty: set[int] = set()
        # Slots whose change the device need not hear of until it is asked
        # something anyway (a candidacy begun or given up: the kernel decides
        # nothing for a candidate, and a follower's next deadline opens the
        # sweep gate by itself).  They travel with the next dispatch, which
        # refreshes them before it decides anything, and never cause one.
        self.lazy: set[int] = set()

    def mark_dirty(self, slot: int) -> None:
        if slot >= 0:
            self.dirty.add(slot)

    def mark_lazy(self, slot: int) -> None:
        if slot >= 0:
            self.lazy.add(slot)

    def slice_of_slot(self, slot: int) -> int:
        return slot // self.slice_rows

    def allocate(self, slice_idx: int = -1) -> int:
        """Take a free slot.  ``slice_idx`` pins the slot to one mesh
        slice's row range; -1 fills the lowest slice with room first —
        sequential slot order 0,1,2,..., bit-identical to the unsliced
        engine's historical allocation (mesh-vs-single identity tests
        rely on this; production divisions always pass an explicit
        slice)."""
        if slice_idx < 0:
            slice_idx = next(
                (i for i in range(self.n_slices) if self._free[i]), 0)
        free = self._free[slice_idx]
        if not free:
            if self.n_slices == 1:
                self._grow()
            else:
                # Sliced capacity is FIXED at bring-up: the slot->slice map
                # is positional, so growing would re-home every row.  The
                # server auto-pads capacity to the mesh at construction;
                # running out means the deployment is undersized.
                raise RuntimeError(
                    f"slice {slice_idx} out of group slots "
                    f"({self.slice_rows} rows/slice, {self.n_slices} "
                    f"slices); raise raft.tpu.engine.max-groups")
        slot = free.pop()
        self.active.add(slot)
        self.alloc_gen[slot] += 1
        self.mark_dirty(slot)
        return slot

    def release(self, slot: int) -> None:
        self.active.discard(slot)
        self.role[slot] = ROLE_UNUSED
        self.conf_cur[slot] = False
        self.conf_old[slot] = False
        self.self_mask[slot] = False
        self.match_index[slot] = -1
        self.flush_index[slot] = -1
        self.commit_index[slot] = -1
        self.election_deadline_ms[slot] = NO_DEADLINE
        self.vote_grants[slot] = False
        self.vote_rejects[slot] = False
        self.vote_deadline_ms[slot] = NO_DEADLINE
        self.applied_index[slot] = -1
        self.pending_count[slot] = 0
        self.peer_index[slot] = -1
        self._free[self.slice_of_slot(slot)].append(slot)
        self.mark_dirty(slot)

    def _grow(self) -> None:
        """Double capacity (pad arrays); jit caches per shape, and doubling
        keeps the number of distinct compiled shapes logarithmic."""
        old = self.capacity
        new = old * 2
        for name in ("role", "self_slot", "flush_index", "commit_index",
                     "first_leader_index", "election_deadline_ms",
                     "self_priority", "vote_deadline_ms", "applied_index",
                     "pending_count", "alloc_gen"):
            a = getattr(self, name)
            b = np.zeros(new, a.dtype)
            b[:old] = a
            if name in ("flush_index", "commit_index", "applied_index"):
                b[old:] = -1
            if name in ("election_deadline_ms", "vote_deadline_ms"):
                b[old:] = NO_DEADLINE
            setattr(self, name, b)
        for name in ("self_mask", "conf_cur", "conf_old", "priority",
                     "match_index", "next_index", "last_ack_ms",
                     "vote_grants", "vote_rejects", "peer_index"):
            a = getattr(self, name)
            b = np.zeros((new, self.max_peers), a.dtype)
            b[:old] = a
            if name in ("match_index", "peer_index"):
                b[old:] = -1
            setattr(self, name, b)
        self._free[0].extend(range(new - 1, old - 1, -1))
        self.capacity = new
        self.slice_rows = new

    # -- per-group setters used by divisions --------------------------------

    def set_conf(self, slot: int, self_slot: int, cur_mask, old_mask,
                 priorities, self_priority: int) -> None:
        self.self_slot[slot] = self_slot
        self.self_mask[slot] = False
        self.self_mask[slot, self_slot] = True
        self.conf_cur[slot] = cur_mask
        self.conf_old[slot] = old_mask
        self.priority[slot] = priorities
        self.self_priority[slot] = self_priority
        self.mark_dirty(slot)
