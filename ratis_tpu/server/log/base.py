"""RaftLog API and shared base behavior.

Capability parity with the reference RaftLog SPI
(ratis-server-api/.../server/raftlog/RaftLog.java:38 — commit tracking,
updateCommitIndex:114, purge:132) and RaftLogBase
(ratis-server/.../raftlog/RaftLogBase.java — append validation, the
truncate-and-append conflict resolution used by followers, open/close).

asyncio-native: ``append_entry`` returns once the entry is durable (flushed);
``flush_index`` feeds the leader's own slot in the batched commit kernel.
"""

from __future__ import annotations

import asyncio
import collections
import concurrent.futures
from typing import Iterable, Optional, Sequence

from ratis_tpu.protocol.exceptions import (LogCorruptedException,
                                           RaftException,
                                           RaftLogIOException)
from ratis_tpu.protocol.logentry import LogEntry
from ratis_tpu.protocol.termindex import INVALID_LOG_INDEX, TermIndex
from ratis_tpu.trace.tracer import TRACER

LEAST_VALID_LOG_INDEX = 0

# State-machine data stays in the log's cache this many entries behind the
# applied index for a follower that has not acknowledged them yet; one
# further behind is served through StateMachine.data_read.
DATA_CACHE_LAG = 8
# Read-backs kept (and read ahead) for one catching-up follower.
DATA_READ_AHEAD = 8

_DATA_READS = TRACER.counter("sm.data_reads")
# log.loop_calls of the data writes: each completion is seen on the loop once
# (_on_data_written), whatever thread wrote; the log workers add their own
_DATA_LOOP_CALLS = TRACER.counter("log.loop_calls", "data")


class RaftLog:
    """Abstract log of one division."""

    def __init__(self, name: str):
        self.name = name
        self._commit_index = INVALID_LOG_INDEX
        self._purge_index = INVALID_LOG_INDEX
        self._open = False
        # Flush observers (set by the division): invoked when flush_index
        # advances asynchronously / when a write fails.  Durable logs call
        # these from the worker's completion path; the in-memory log flushes
        # synchronously inside append so it never needs them.
        self._flush_cb = None
        self._flush_err_cb = None
        self._flush_index = INVALID_LOG_INDEX
        # Latched on the first failed write: flush_index must never advance
        # past a hole (a later successful fsync does NOT make earlier failed
        # bytes durable), and further appends are refused — the reference's
        # log worker terminates on IO failure the same way.
        self._failed: Optional[Exception] = None
        # StateMachine.DataApi (set by the division).  ``_data_out``:
        # (index, the data_write) of entries whose data write has not
        # completed, in index order: a durable log's worker holds each one's
        # record back until it has (LogWorker.submit's gate), truncation and
        # close wait for them.  ``_data_held``: indexes whose entry in the
        # cache still holds its sm_data; ``_data_released``: the highest
        # index that let it go.
        self._data_api = None
        self._data_out: collections.deque = collections.deque()
        self._data_held: collections.deque = collections.deque()
        self._data_released = INVALID_LOG_INDEX
        self._data_readback: dict[int, LogEntry] = {}

    def set_data_api(self, state_machine) -> None:
        """The state machine whose data_write / data_read / data_truncate
        hold the ``sm_data`` of this log's entries."""
        self._data_api = state_machine

    def set_flush_callbacks(self, on_flush, on_error) -> None:
        """on_flush(flush_index) fires after flush_index advances without the
        appender having awaited it (the decoupled leader path,
        reference SegmentedRaftLogWorker.java:302,368); on_error(exc) fires
        when the backing write fails (StateMachine.notifyLogFailed)."""
        self._flush_cb = on_flush
        self._flush_err_cb = on_error

    # -- open/close ----------------------------------------------------------

    async def open(self, last_index_on_snapshot: int = INVALID_LOG_INDEX) -> None:
        self._open = True

    async def close(self) -> None:
        if self._data_out:
            # (the state machine closes after its log: let its writes land)
            await asyncio.wait([item[1] for item in self._data_out])
        self._open = False

    @property
    def is_open(self) -> bool:
        return self._open

    # -- indices -------------------------------------------------------------

    @property
    def commit_index(self) -> int:
        return self._commit_index

    def get_last_committed_index(self) -> int:
        return self._commit_index

    def update_commit_index(self, majority_index: int, current_term: int,
                            is_leader: bool) -> bool:
        """Advance commitIndex monotonically (RaftLog.updateCommitIndex:114).
        Leader-side term gating already happened in the quorum kernel; the
        follower side passes the leader's commit directly."""
        if majority_index <= self._commit_index:
            return False
        if is_leader:
            ti = self.get_term_index(majority_index)
            if ti is None or ti.term != current_term:
                return False
        self._commit_index = majority_index
        return True

    @property
    def start_index(self) -> int:
        raise NotImplementedError

    @property
    def next_index(self) -> int:
        ti = self.get_last_entry_term_index()
        return (ti.index + 1) if ti is not None else max(self.start_index, 0)

    @property
    def flush_index(self) -> int:
        raise NotImplementedError

    @property
    def failed(self) -> bool:
        """True once the log has latched dead on an IO failure: a node whose
        log cannot accept writes must not campaign or lead."""
        return self._failed is not None

    def get_last_entry_term_index(self) -> Optional[TermIndex]:
        raise NotImplementedError

    def get_term_index(self, index: int) -> Optional[TermIndex]:
        e = self.get(index)
        return e.term_index() if e is not None else None

    def get(self, index: int) -> Optional[LogEntry]:
        raise NotImplementedError

    def get_entries(self, start: int, end: int,
                    max_bytes: int = 1 << 62) -> list[LogEntry]:
        """Entries in [start, end) bounded by total serialized bytes — the
        appender batch builder (LogAppenderBase.newAppendEntriesRequest:223).
        Always returns at least one entry when available."""
        out: list[LogEntry] = []
        total = 0
        for i in range(start, min(end, self.next_index)):
            if out and not self.is_resident(i):
                # batch crossed into an evicted segment: stop here rather
                # than fault multi-MB of entries in synchronously; the
                # caller's next round prefaults off-loop
                break
            e = self.get(i)
            if e is None:
                break
            if i <= self._data_released:
                e = self._data_readback.get(i, e)
                if e.smlog is not None and e.smlog.data_let_go():
                    break   # never ship an entry without the data it names
            total += e.serialized_size()
            if out and total > max_bytes:
                break
            out.append(e)
        return out

    # -- append --------------------------------------------------------------

    async def append_entry(self, entry: LogEntry, wait_flush: bool = True) -> int:
        """Append one entry.  With ``wait_flush`` (follower path / default)
        the coroutine resolves only once the entry is durable — a follower's
        append reply must mean "on disk" (matchIndex == durable).  With
        ``wait_flush=False`` (leader hot path) it returns after the in-memory
        append: the write is queued, flush_index advances when the shared
        worker fsyncs, and the registered flush callback wakes the engine —
        the leader's commit math consumes flush_index, so correctness is
        preserved while the fsync overlaps follower RPCs (reference decouples
        identically: SegmentedRaftLog.appendEntryImpl:392 queues, flushIndex
        advances asynchronously)."""
        raise NotImplementedError

    async def append_entries_follower(self, entries: Sequence[LogEntry]) -> int:
        """Follower path: skip already-present matching entries, truncate at
        the first term conflict, then append the rest — the reference's
        truncate-and-append resolution (SegmentedRaftLog.appendEntryImpl:392,
        truncateImpl:363 and RaftLogBase.appendImpl).  Returns the new last
        index.  Raises LogCorruptedException when an existing committed entry
        conflicts."""
        if not entries:
            return self.next_index - 1
        to_append: list[LogEntry] = []
        truncate_at: Optional[int] = None
        for e in entries:
            if e.index < self.start_index:
                # Below our purge/snapshot boundary: already covered by the
                # installed snapshot (a leader rewound past our start after
                # a connection loss resends them) — skip, never re-append.
                continue
            existing = self.get_term_index(e.index)
            if existing is None:
                to_append.append(e)
            elif existing.term != e.term:
                if e.index <= self._commit_index:
                    raise LogCorruptedException(
                        f"{self.name}: conflict at committed index {e.index}: "
                        f"existing {existing}, new {e.term_index()}")
                truncate_at = e.index if truncate_at is None else min(truncate_at, e.index)
                to_append.append(e)
            # else: already have it; skip
        if truncate_at is not None:
            await self._wait_data()
            await self.truncate(truncate_at)
            if self._data_api is not None:
                await self._truncate_data(truncate_at)
        # Queue the whole batch, await durability once: the shared worker
        # fsyncs in submission order, so the last entry's flush implies the
        # rest are on disk — one fsync per batch instead of one per entry
        # (the reference's LogWorker coalesces identically).
        for e in to_append[:-1]:
            await self.append_entry(e, wait_flush=False)
        if to_append:
            await self.append_entry(to_append[-1])
        return self.next_index - 1

    async def truncate(self, index: int) -> None:
        """Remove entries >= index."""
        raise NotImplementedError

    async def purge(self, index: int) -> int:
        """Drop entries <= index (snapshot-covered); returns new start-1."""
        raise NotImplementedError

    def evict_cache(self, applied_index: int) -> int:
        """Release entry memory no longer needed by the applier (the
        segmented log overrides this; volatile logs have nothing to evict)."""
        return 0

    def is_resident(self, index: int) -> bool:
        """False when reading ``index`` would block on a file fault (evicted
        segment, or state-machine data the cache let go); async hot paths
        prefault() off-loop first."""
        return index > self._data_released or self._data_resident(index)

    def prefault(self, index: int) -> None:
        """Blocking: fault the segment covering ``index`` into memory, and
        read back the state-machine data its entries let go."""
        if index <= self._data_released:
            self._read_back(index)

    # -- state-machine data (StateMachine.DataApi) ---------------------------

    def _failure(self, exc: Exception) -> None:
        first = self._failed is None
        self._failed = self._failed or exc
        if first and self._flush_err_cb is not None:
            self._flush_err_cb(exc)

    def _on_record_flushed(self, index: int) -> None:
        """The worker's batch call-back (LogWorker._completed): this log's
        records up to ``index`` are fsynced.  It runs on the loop, batch
        after batch in submit order, whether or not the appender awaits, so
        flush_index stays contiguous (SegmentedRaftLogWorker
        flushIfNecessary:368); a failed write reaches ``_failure`` instead
        and nothing moves past it.  The record of an entry that carries
        state-machine data is written after that data, so a flushed
        record's data is written too."""
        if self._failed is None and index > self._flush_index:
            self._flush_index = index
            if self._flush_cb is not None:
                self._flush_cb(index)

    def _start_data_write(self, entry: LogEntry):
        """DataApi.write for an entry that carries ``sm_data``, started as
        the entry is appended.  Returns the write as the log worker's thread
        takes it (the gate the entry's record waits for before it goes to
        the disk: the writer thread's own future where the state machine
        gives one, which then wakes the worker without the loop), or None
        where the state machine writes nothing."""
        if self._data_api is None:
            return None
        try:
            pending = self._data_api.data_write(entry)
            if pending is None:
                return None  # nobody else has the bytes: the cache keeps them
            if isinstance(pending, concurrent.futures.Future):
                gate, fut = pending, asyncio.wrap_future(pending)
            else:
                gate = fut = asyncio.ensure_future(pending)
            self._data_held.append(entry.index)
        except Exception as e:
            gate = fut = asyncio.get_running_loop().create_future()
            fut.set_exception(e)
        self._data_out.append((entry.index, fut))
        fut.add_done_callback(self._on_data_written)
        return gate

    def _on_data_written(self, fut: "asyncio.Future") -> None:
        _DATA_LOOP_CALLS.n += 1
        if not fut.cancelled() and fut.exception() is not None:
            # a failed data write is a failed log write (and stays in
            # _data_out: nothing counts as flushed past it)
            self._failure(fut.exception())
            return
        out = self._data_out
        while out and out[0][1].done():
            out.popleft()
        if self._failed is None:
            self._data_landed()

    def _data_landed(self) -> None:
        """Data writes have completed (for a log whose flush_index waits
        for them itself: the in-memory one)."""

    async def _wait_data(self) -> None:
        """Until every data write started so far has completed (a follower's
        acknowledgement from memory, a truncation)."""
        while self._data_out:
            await asyncio.wait([item[1] for item in self._data_out])
            if self._failed is not None:
                raise RaftLogIOException(
                    f"{self.name}: state-machine data write failed"
                ) from self._failed

    async def _truncate_data(self, index: int) -> None:
        """Entries from ``index`` on are gone (their data writes have
        completed: truncate waits for them): so is their data."""
        while self._data_held and self._data_held[-1] >= index:
            self._data_held.pop()
        self._data_released = min(self._data_released, index - 1)
        self._data_readback = {i: e for i, e in self._data_readback.items()
                               if i < index}
        await self._data_api.data_truncate(index)

    def release_data(self, upto: int) -> int:
        """Let go of the ``sm_data`` of cached entries up to ``upto``
        (applied, and replicated as far as the caller says): from then on
        StateMachine.data_read has the bytes.  Returns how many let go."""
        held, n = self._data_held, 0
        while held and held[0] <= upto:
            index = held.popleft()
            self._strip(index)
            self._data_released = index
            n += 1
        return n

    @property
    def data_held(self) -> int:
        """Entries of the cache that still hold their state-machine data."""
        return len(self._data_held)

    def _strip(self, index: int) -> None:
        """Replace the cached entry at ``index`` by one without sm_data."""
        raise NotImplementedError

    def _data_resident(self, index: int) -> bool:
        """Whether the entry at ``index`` (itself in memory) has the
        state-machine data it carries, in the cache or read back."""
        e = self.get(index)
        return (e is None or e.smlog is None or not e.smlog.data_let_go()
                or index in self._data_readback)

    def _read_back(self, index: int) -> None:
        """Blocking: StateMachine.data_read for the entries from ``index``
        on that let their data go, a few ahead (a follower catching up reads
        on in order); kept in a small cache that get_entries reads."""
        got = {}
        for i in range(index, min(index + DATA_READ_AHEAD,
                                  self._data_released + 1)):
            e = self._data_readback.get(i) or self.get(i)
            if e is None:
                break
            if e.smlog is not None and e.smlog.data_let_go():
                e = e.with_sm_data(self._data_api.data_read(e))
                _DATA_READS.n += 1
            got[i] = e
        # (one assignment: a reader on the loop sees the old dict or the new)
        keep = {**self._data_readback, **got}
        for i in list(keep)[:-4 * DATA_READ_AHEAD]:
            del keep[i]
        self._data_readback = keep

    def term_at_or_before(self, index: int) -> Optional[TermIndex]:
        """TermIndex for a previous-entry check; None if purged away."""
        return self.get_term_index(index)

    def set_snapshot_boundary(self, ti: TermIndex) -> None:
        """Restart the log just above an installed/restored snapshot."""
        raise NotImplementedError
