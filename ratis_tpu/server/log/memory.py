"""In-memory RaftLog (volatile), for tests and memory-mode groups.

Capability parity with the reference MemoryRaftLog
(ratis-server/.../raftlog/memory/MemoryRaftLog.java): a plain entry list,
immediately 'flushed'.
"""

from __future__ import annotations

from typing import Optional

from ratis_tpu.protocol.logentry import LogEntry
from ratis_tpu.protocol.termindex import INVALID_LOG_INDEX, TermIndex
from ratis_tpu.server.log.base import RaftLog


class MemoryRaftLog(RaftLog):
    def __init__(self, name: str = "memlog"):
        super().__init__(name)
        self._start = 0
        self._entries: list[LogEntry] = []
        # TermIndex of the entry just below start (snapshot boundary)
        self._below_start: Optional[TermIndex] = None

    async def open(self, last_index_on_snapshot: int = INVALID_LOG_INDEX) -> None:
        await super().open(last_index_on_snapshot)
        if last_index_on_snapshot != INVALID_LOG_INDEX and not self._entries:
            self._start = last_index_on_snapshot + 1

    @property
    def start_index(self) -> int:
        return self._start

    @property
    def next_index(self) -> int:
        # O(1) without TermIndex allocation: this is the single hottest log
        # accessor (appender fills, append handlers, bulk heartbeats)
        if self._entries:
            return self._start + len(self._entries)
        if self._below_start is not None:
            return self._below_start.index + 1
        return max(self._start, 0)

    @property
    def flush_index(self) -> int:
        if self._data_out:
            # an entry whose data_write is out does not count as flushed
            return min(self.next_index, self._data_out[0][0]) - 1
        return self.next_index - 1

    def _data_landed(self) -> None:
        index = self.flush_index
        if index > self._flush_index:
            self._flush_index = index
            if self._flush_cb is not None:
                self._flush_cb(index)

    def _strip(self, index: int) -> None:
        i = index - self._start
        if 0 <= i < len(self._entries):
            self._entries[i] = self._entries[i].without_sm_data()

    def get_last_entry_term_index(self) -> Optional[TermIndex]:
        if self._entries:
            return self._entries[-1].term_index()
        return self._below_start

    def get(self, index: int) -> Optional[LogEntry]:
        i = index - self._start
        if 0 <= i < len(self._entries):
            return self._entries[i]
        return None

    def get_term_index(self, index: int) -> Optional[TermIndex]:
        e = self.get(index)
        if e is not None:
            return e.term_index()
        if self._below_start is not None and index == self._below_start.index:
            return self._below_start
        return None

    async def append_entry(self, entry: LogEntry, wait_flush: bool = True) -> int:
        expected = self.next_index
        if entry.index != expected:
            raise ValueError(f"{self.name}: appending index {entry.index}, "
                             f"expected {expected}")
        self._entries.append(entry)
        smlog = entry.smlog
        if smlog is not None and smlog.sm_data is not None:
            self._start_data_write(entry)
        if wait_flush and self._data_out:
            await self._wait_data()
        return entry.index

    async def truncate(self, index: int) -> None:
        keep = max(0, index - self._start)
        del self._entries[keep:]
        self._flush_index = min(self._flush_index, index - 1)

    async def purge(self, index: int) -> int:
        if index < self._start:
            return self._start - 1
        ti = self.get_term_index(index)
        drop = min(index - self._start + 1, len(self._entries))
        if drop > 0:
            del self._entries[:drop]
            self._start = index + 1
            self._below_start = ti
        return self._start - 1

    def set_snapshot_boundary(self, ti: TermIndex) -> None:
        """After installing a snapshot: log restarts above it."""
        self._entries.clear()
        self._start = ti.index + 1
        self._below_start = ti
