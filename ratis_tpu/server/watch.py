"""Watch requests: futures resolved when an index reaches a replication level.

Capability parity with the reference WatchRequests
(ratis-server/.../impl/WatchRequests.java:42): per-level queues keyed by the
watched index, resolved when that level's frontier passes the index, failed
with NotReplicatedException on timeout (:185) and drained on step-down.

Levels (Raft.proto ReplicationLevel):
- MAJORITY:            leader commitIndex         >= watched index
- ALL:                 min over peers' matchIndex >= watched index
- MAJORITY_COMMITTED:  majority-min over peers' commitIndex >= index
- ALL_COMMITTED:       min over peers' commitIndex >= index
The frontiers are computed by the division from engine state + follower
commit infos piggybacked on AppendEntries replies.
"""

from __future__ import annotations

import asyncio
import heapq
from typing import Optional

from ratis_tpu.protocol.exceptions import NotReplicatedException
from ratis_tpu.protocol.requests import ReplicationLevel


class _Queue:
    """Min-heap of (index, future) for one replication level."""

    def __init__(self, level: ReplicationLevel):
        self.level = level
        self.heap: list[tuple[int, int, asyncio.Future]] = []
        self._seq = 0
        self.frontier = -1

    def add(self, index: int) -> asyncio.Future:
        fut = asyncio.get_running_loop().create_future()
        if index <= self.frontier:
            fut.set_result(self.frontier)
            return fut
        self._seq += 1
        heapq.heappush(self.heap, (index, self._seq, fut))
        return fut

    def update(self, new_frontier: int) -> int:
        if new_frontier <= self.frontier:
            return 0
        self.frontier = new_frontier
        n = 0
        while self.heap and self.heap[0][0] <= new_frontier:
            _, _, fut = heapq.heappop(self.heap)
            if not fut.done():
                fut.set_result(new_frontier)
                n += 1
        return n

    def drain(self, exc: Exception) -> None:
        while self.heap:
            _, _, fut = heapq.heappop(self.heap)
            if not fut.done():
                fut.set_exception(exc)


class WatchRequests:
    def __init__(self, timeout_s: float = 10.0, element_limit: int = 65536):
        self.queues = {lvl: _Queue(lvl) for lvl in ReplicationLevel}
        self._heaps = [q.heap for q in self.queues.values()]
        self.timeout_s = timeout_s
        self.element_limit = element_limit

    def pending_count(self) -> int:
        # (every heartbeat reply asks: no generator)
        n = 0
        for h in self._heaps:
            n += len(h)
        return n

    async def watch(self, index: int, level: ReplicationLevel,
                    call_id: int = 0) -> int:
        from ratis_tpu.protocol.exceptions import ResourceUnavailableException
        if self.pending_count() >= self.element_limit:
            raise ResourceUnavailableException(
                f"too many pending watch requests ({self.element_limit})")
        fut = self.queues[level].add(index)
        try:
            return await asyncio.wait_for(fut, self.timeout_s)
        except asyncio.TimeoutError:
            raise NotReplicatedException(call_id, level, index) from None

    def update(self, level: ReplicationLevel, new_frontier: int) -> int:
        return self.queues[level].update(new_frontier)

    def update_all_levels(self, majority_commit: int, all_match: int,
                          majority_committed: int, all_committed: int) -> None:
        self.update(ReplicationLevel.MAJORITY, majority_commit)
        self.update(ReplicationLevel.ALL, all_match)
        self.update(ReplicationLevel.MAJORITY_COMMITTED, majority_committed)
        self.update(ReplicationLevel.ALL_COMMITTED, all_committed)

    def drain(self, exc: Exception) -> None:
        for q in self.queues.values():
            q.drain(exc)

    def reset_frontiers(self) -> None:
        """New leadership term: stale frontiers from a previous term must not
        instantly satisfy watches the CURRENT follower set hasn't reached."""
        for q in self.queues.values():
            q.frontier = -1
