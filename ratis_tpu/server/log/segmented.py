"""Durable segmented Raft log with shared flush-batching worker.

Capability parity with the reference segmented log stack
(ratis-server/.../raftlog/segmented/SegmentedRaftLog.java:86,
SegmentedRaftLogWorker.java, LogSegment.java, SegmentedRaftLogFormat):

- segment files ``log_<start>-<end>`` (closed) / ``log_inprogress_<start>``
  (open) under ``current/`` (LogSegmentStartEnd.java:41-58);
- CRC-checked records, corrupt-tail truncation on recovery;
- a single I/O worker per *storage device* batching fsyncs across ALL
  divisions sharing that device (the reference runs one worker thread per
  division — SegmentedRaftLogWorker.java:302 — which is exactly the
  thread-per-group scaling wall this design removes, cf. SURVEY §7 step 5);
- flush_index advances only after fsync and feeds the leader's own slot in
  the batched commit kernel.

Who owns what.  Segments, indexes, caches and flush_index belong to the event
loop of the log's division.  The way to the disk belongs to the worker's
thread (``LogWorker``): a record goes from ``submit`` to ``os.fsync`` without
the loop, and a state-machine data write that gates it is seen done by that
thread.  They meet in one place: the thread's one ``call_soon_threadsafe`` a
batch, which runs ``LogWorker._completed`` -> ``RaftLog._on_record_flushed``
on the loop.  Between a ``drain()`` that has returned and the next ``submit``
the thread touches no file of that loop's logs, which is when the loop closes,
renames and truncates them.

Record format (original to this implementation):
    file   := MAGIC record*
    record := u32_le payload_len | u32_le crc32(payload) | payload
    payload = LogEntry msgpack bytes
"""

from __future__ import annotations

import asyncio
import os
import pathlib
import re
import struct
import threading
import time
import zlib
from typing import Optional

from ratis_tpu.protocol.exceptions import (ChecksumException,
                                           RaftLogIOException)
from ratis_tpu.protocol.logentry import LogEntry
from ratis_tpu.protocol.termindex import INVALID_LOG_INDEX, TermIndex
from ratis_tpu.server.log.base import RaftLog
from ratis_tpu.trace.tracer import (STAGE_DATA_WAIT, STAGE_LOG_FSYNC,
                                    STAGE_LOG_QUEUE, STAGE_LOG_WRITE, TRACER)
from ratis_tpu.util import injection

MAGIC = b"RTPULOG\x01"
_REC_HDR = struct.Struct("<II")

_CLOSED_RE = re.compile(r"^log_(\d+)-(\d+)$")
_OPEN_RE = re.compile(r"^log_inprogress_(\d+)$")

# log.loop_calls of a data write that completes on a loop (an awaitable from
# a state machine that has no writer thread): its done-callback there wakes
# the worker's thread.  Added to on the loop; the workers' own are theirs.
_LOOP_GATE_CALLS = TRACER.counter("log.loop_calls", "gate")


def encode_record(payload: bytes) -> bytes:
    return _REC_HDR.pack(len(payload), zlib.crc32(payload)) + payload


def read_records(path: pathlib.Path) -> tuple[list[bytes], int]:
    """Read records; returns (payloads, good_byte_length).  Stops at the
    first corrupt/truncated record — recovery truncates the file there
    (reference SegmentedRaftLogReader corrupt-tail handling)."""
    data = path.read_bytes()
    if not data.startswith(MAGIC):
        return [], len(MAGIC) if not data else 0
    payloads = []
    off = len(MAGIC)
    while off + _REC_HDR.size <= len(data):
        ln, crc = _REC_HDR.unpack_from(data, off)
        start = off + _REC_HDR.size
        end = start + ln
        if end > len(data):
            break
        payload = data[start:end]
        if zlib.crc32(payload) != crc:
            break
        payloads.append(payload)
        off = end
    return payloads, off


class _Record:
    """One queued record: what ``LogWorker.submit`` hands back.  The worker's
    thread reads ``fileobj``, ``data``, ``gate`` and ``loop``, and sets
    ``t_held`` and (for a record it may not write) ``exc`` before it calls
    back; everything else belongs to the loop it was submitted from.
    Awaitable: ``await record`` returns once the record is on the disk, or
    raises what kept it off."""

    __slots__ = ("fileobj", "data", "log", "index", "gate", "loop",
                 "t_submit", "t_held", "done", "exc", "_waiters")

    def __init__(self, fileobj, data, log, index, gate, loop, t_submit):
        self.fileobj = fileobj
        self.data = data
        self.log = log          # the RaftLog whose flush_index it moves
        self.index = index
        self.gate = gate        # the write of its state-machine data
        self.loop = loop
        self.t_submit = t_submit  # of a log.queue sample, or 0
        self.t_held = 0         # when the thread first found the gate shut
        self.done = False
        self.exc: Optional[BaseException] = None
        self._waiters: Optional[list] = None

    def __await__(self):
        if not self.done:
            # a future only for a record somebody awaits (one each: an
            # awaiter that is cancelled takes no other's completion away)
            fut = self.loop.create_future()
            if self._waiters is None:
                self._waiters = [fut]
            else:
                self._waiters.append(fut)
            yield from fut
        if self.exc is not None:
            raise self.exc


class LogWorker:
    """One fsync-batching writer thread per storage device.

    The thread owns the way to the disk: it takes everything queued as one
    batch, writes each file's records with one ``write``, then flushes and
    fsyncs each distinct file ONCE -- group commit like the reference's
    flushIfNecessary/forceSyncNum (SegmentedRaftLogWorker.java:368) but
    across divisions.  A record whose state-machine data is still being
    written (``gate``) stays queued, and with it everything behind it: a
    batch is the longest prefix of the queue whose gates are done.

    The loops own the logs: ``submit`` runs on a division's loop and only
    appends to the queue under the condition; ``_completed`` runs on that
    loop again, once a batch, and moves every log's flush_index there.  The
    one place the two meet is the ``call_soon_threadsafe`` of ``_call_back``
    (counted: ``log.loop_calls``).
    """

    _instances: dict[str, "LogWorker"] = {}

    def __init__(self, name: str = "default"):
        self.name = name
        self._queue: list[_Record] = []
        self._cond = threading.Condition(threading.Lock())
        self._thread: Optional[threading.Thread] = None
        # release()'s way to hear of the thread's end (set: the thread is
        # to stop once nothing ready is queued); whether it has ended
        self._stopping = None
        self._ended = False
        self._home: Optional[asyncio.AbstractEventLoop] = None
        self._refs = 0
        # the newest record of each loop: what drain() on that loop waits for
        self._last: dict[asyncio.AbstractEventLoop, _Record] = {}
        # single metric source (reference log_worker catalog: flushTime/
        # flushCount/syncTime over the shared per-device worker)
        from ratis_tpu.metrics import LogWorkerMetrics
        self.registry_metrics = LogWorkerMetrics(f"device-{name}")
        self.registry_metrics.add_queue_gauges(lambda: len(self._queue))
        self.registry_metrics.add_sweep_gauge(lambda: self._sync_ewma)
        self._writes = self.registry_metrics.registry.counter("writeCount")
        self._batches = self.registry_metrics.registry.counter("batchCount")
        # calls this worker's thread scheduled onto a loop (one a batch and
        # loop); only the thread adds to it
        self._loop_calls = TRACER.counter("log.loop_calls", name)
        # decayed fsyncs-per-drain-sweep: ~1.0 on a shared log plane,
        # ~open-file-count with per-group segment files
        self._sync_ewma = 0.0
        # files a failed data write left with a hole, which take no record
        # any more (the thread's)
        self._dead_files: dict[object, BaseException] = {}
        # (fsyncs, groups they made durable) of a shared log plane's worker:
        # count_groups
        self._group_counts = None

    def count_groups(self, key: str) -> None:
        """Count this worker's fsyncs (``log.shared.syncs``) and the
        distinct logs whose records each one made durable
        (``log.shared.sync_groups``), under ``key``: a worker of the shared
        log plane, whose one file carries many groups."""
        self._group_counts = (TRACER.counter("log.shared.syncs", key),
                              TRACER.counter("log.shared.sync_groups", key))

    @property
    def metrics(self) -> dict:
        """Snapshot view kept for tests/tools."""
        return {"flushes": self.registry_metrics.flush_count.count,
                "writes": self._writes.count,
                "batched": self._batches.count}

    @property
    def sync_count(self) -> int:
        """Cumulative fsyncs issued by this worker."""
        return self.registry_metrics.sync_count.count

    @classmethod
    def shared(cls, device_key: str) -> "LogWorker":
        w = cls._instances.get(device_key)
        if w is None:
            w = cls(device_key)
            cls._instances[device_key] = w
        return w

    def acquire(self) -> None:
        self._refs += 1
        if self._thread is None:
            self._stopping, self._ended = None, False
            self._home = asyncio.get_running_loop()
            self._thread = threading.Thread(
                target=self._run, name=f"log-worker-{self.name}", daemon=True)
            self._thread.start()

    async def release(self) -> None:
        if self._refs <= 0:
            return  # tolerate close-without-open (failed startup cleanup)
        self._refs -= 1
        if self._refs <= 0 and self._thread is not None:
            thread, self._thread = self._thread, None
            loop = asyncio.get_running_loop()
            ended = loop.create_future()

            def tell() -> None:
                loop.call_soon_threadsafe(
                    lambda: ended.done() or ended.set_result(None))

            with self._cond:
                if self._ended:
                    ended.set_result(None)
                self._stopping = tell
                self._cond.notify()
            # the thread writes what is queued, fails what a gate still
            # holds, calls both back and ends; it may need this loop on the
            # way (an injected handler), so the loop runs on meanwhile
            await ended
            thread.join()
            self._last.clear()
            self._instances.pop(self.name, None)
            self.registry_metrics.unregister()

    def submit(self, fileobj, data: bytes, log: Optional[RaftLog] = None,
               index: int = INVALID_LOG_INDEX, gate=None) -> _Record:
        """Queue ``data`` for ``fileobj``.  Once it is fsynced the loop this
        was called on runs ``log._on_record_flushed`` with the highest
        ``index`` the batch holds of ``log`` (or ``log._failure``); the
        record handed back is awaitable for a caller that wants to wait.

        ``gate``: the write of the entry's state-machine data
        (StateMachine.data_write; a ``concurrent.futures.Future`` or an
        asyncio one of this loop).  The record is written and fsynced only
        once the data is, as upstream's log worker waits for the
        stateMachineDataFuture before its flush -- a crash never leaves a
        durable record whose data is missing.  Where the data write fails or
        is cancelled, neither this record nor a later one reaches the file."""
        loop = asyncio.get_running_loop()
        rec = _Record(fileobj, data, log, index, gate, loop,
                      TRACER.now() if TRACER.enabled
                      and TRACER.sample(STAGE_LOG_QUEUE) else 0)
        self._last[loop] = rec
        queue = self._queue
        with self._cond:
            queue.append(rec)
            # the thread waits only with nothing ready at the head of the
            # queue, and a record behind that head cannot help it
            if len(queue) == 1:
                self._cond.notify()
        if gate is not None:
            gate.add_done_callback(self._gate_done)
        return rec

    def _gate_done(self, gate) -> None:
        """A data write has completed: on its writer's thread for a
        ``concurrent.futures.Future``, which crosses no loop.  The thread is
        woken only for the gate it waits for, the head's."""
        if isinstance(gate, asyncio.Future):
            _LOOP_GATE_CALLS.n += 1
        queue = self._queue
        with self._cond:
            if queue and queue[0].gate is gate:
                self._cond.notify()

    async def drain(self) -> None:
        """Wait until the writes submitted from this loop are flushed, their
        logs have been told, and the thread has let go of their files."""
        rec = self._last.get(asyncio.get_running_loop())
        if rec is not None and not rec.done:
            await rec

    # ------------------------------------------------- the worker's thread

    def _run(self) -> None:
        cond, queue = self._cond, self._queue
        try:
            # worker-start injection point (reference
            # SegmentedRaftLogWorker.java:70 runs CodeInjectionForTesting at
            # the top of its run loop): lets the chaos suite stall a
            # device's whole log worker before it drains anything
            if injection.is_registered(injection.RUN_LOG_WORKER):
                injection.execute_from_thread(
                    self._home, injection.RUN_LOG_WORKER, self.name)
            while True:
                with cond:
                    while True:
                        n = self._ready(queue)
                        if n or self._stopping is not None:
                            break
                        cond.wait()
                    stopped = not n
                    batch = queue[:n or len(queue)]
                    del queue[:len(batch)]
                if stopped:
                    # (what a gate still holds at the end is not written)
                    self._call_back(batch, RaftLogIOException(
                        f"log worker {self.name} stopped"))
                    return
                self._write_batch(batch)
        finally:
            with cond:
                self._ended = True
                tell = self._stopping
            if tell is not None:
                tell()

    @staticmethod
    def _ready(queue: list) -> int:
        """How many records from the head of the queue can go: up to the
        first whose data write is still out (global submit order is kept,
        which drain() and flush_index rely on)."""
        n = 0
        for rec in queue:
            gate = rec.gate
            if gate is not None and not gate.done():
                if not rec.t_held and TRACER.enabled:
                    rec.t_held = TRACER.now()
                break
            n += 1
        return n

    def _write_batch(self, batch: list) -> None:
        try:
            exc = self._write(batch)
        except Exception as e:  # a failed write or fsync; a raising handler
            exc = e
        self._call_back(batch, exc)

    def _write(self, batch: list) -> None:
        """Write and fsync the batch; raises what kept it off the disk."""
        tracing = TRACER.enabled
        now = TRACER.now() if tracing else 0
        dead = self._dead_files
        by_file: dict[object, list[bytes]] = {}
        counts = self._group_counts
        synced = set() if counts is not None else None
        for rec in batch:
            if rec.t_submit:
                # log.queue: submit -> the batch holding it taken
                TRACER.record(0, STAGE_LOG_QUEUE, rec.t_submit, now)
            gate = rec.gate
            if gate is not None:
                if tracing and TRACER.sample(STAGE_DATA_WAIT):
                    # server.data_wait: from when the thread could have
                    # taken the record to its data written (nothing, where
                    # the data came first)
                    TRACER.record(0, STAGE_DATA_WAIT, rec.t_held or now, now)
                exc = (asyncio.CancelledError() if gate.cancelled()
                       else gate.exception())
                if exc is not None:
                    dead.setdefault(rec.fileobj, exc)
            if dead and rec.fileobj in dead:
                rec.exc = RaftLogIOException(
                    "state-machine data write failed: record not written")
                rec.exc.__cause__ = dead[rec.fileobj]
                continue
            chunks = by_file.get(rec.fileobj)
            if chunks is None:
                by_file[rec.fileobj] = [rec.data]
            else:
                chunks.append(rec.data)
            if synced is not None and rec.log is not None:
                synced.add((rec.fileobj, rec.log))
        if not by_file:
            return
        self._writes.inc(sum(map(len, by_file.values())))
        self._batches.inc()
        # per-flush-batch sync injection point (reference
        # RaftServerImpl.java:1620's LOG_SYNC): a registered delay here is
        # the slow-disk fault -- every group sharing this device pays it,
        # exactly like a real degraded disk.  The extra arg is the batch's
        # distinct-file count, so a handler can charge per FSYNC (per-group
        # segments pay N, the shared plane pays 1) rather than per sweep.
        if injection.is_registered(injection.LOG_SYNC):
            injection.execute_from_thread(
                batch[0].loop, injection.LOG_SYNC, self.name, None,
                len(by_file))
        with self.registry_metrics.flush_timer.time():
            self._do_io(by_file, tracing)
        self.registry_metrics.flush_count.inc()
        if counts is not None:
            counts[0].n += len(by_file)
            counts[1].n += len(synced)

    def _do_io(self, by_file: dict, tracing: bool) -> None:
        # log.write / log.fsync: work spans on this thread (tag = distinct
        # files: one fsync each)
        n = len(by_file)
        span = TRACER.begin(STAGE_LOG_WRITE) if tracing else None
        try:
            for fileobj, chunks in by_file.items():
                fileobj.write(chunks[0] if len(chunks) == 1
                              else b"".join(chunks))
        finally:
            if span is not None:
                TRACER.end(span, tag=n)
        span = TRACER.begin(STAGE_LOG_FSYNC) if tracing else None
        t_sync = time.perf_counter()
        try:
            for f in by_file:
                f.flush()
                os.fsync(f.fileno())
        finally:
            if span is not None:
                TRACER.end(span, tag=n)
        self.registry_metrics.sync_timer.update(time.perf_counter() - t_sync)
        self.registry_metrics.sync_count.inc(n)
        self._sync_ewma = (0.9 * self._sync_ewma + 0.1 * n
                           if self._sync_ewma else float(n))

    def _call_back(self, batch: list, exc: Optional[BaseException]) -> None:
        """One call a batch to the loop its records came from (with loop
        shards a batch may hold records of several: one call to each)."""
        by_loop: dict[object, list[_Record]] = {}
        for rec in batch:
            recs = by_loop.get(rec.loop)
            if recs is None:
                by_loop[rec.loop] = [rec]
            else:
                recs.append(rec)
        for loop, recs in by_loop.items():
            self._loop_calls.n += 1
            try:
                loop.call_soon_threadsafe(self._completed, recs, exc)
            except RuntimeError:
                pass    # that loop has closed: nobody is left to tell

    # ------------------------------------------------------ back on a loop

    @staticmethod
    def _completed(recs: list, exc: Optional[BaseException]) -> None:
        """A batch's records of this loop are on the disk (or ``exc`` kept
        them off): tell each log once, with its highest index of the batch
        -- within a file the batch is a contiguous run, so that implies the
        rest, in submit order -- then wake whoever awaits a record."""
        tops: dict[RaftLog, int] = {}
        try:
            for rec in recs:
                rec.data = None
                if rec.exc is None:
                    rec.exc = exc
                if rec.log is not None:
                    if rec.exc is not None:
                        rec.log._failure(rec.exc)
                    elif rec.index > tops.get(rec.log, INVALID_LOG_INDEX):
                        tops[rec.log] = rec.index
            for log, index in tops.items():
                log._on_record_flushed(index)
        finally:
            # (a log's observer that raises takes no awaiter's wake-up away)
            for rec in recs:
                rec.done = True
                if rec._waiters is not None:
                    for fut in rec._waiters:
                        if not fut.done():
                            fut.set_result(None)


class _Segment:
    """One segment: its file, per-entry (term, offset) metadata, and — while
    cached — the decoded entries.

    Mirrors the reference LogSegment (LogSegment.java): the compact LogRecord
    list (term + file position per entry) always stays in memory so
    consistency checks (get_term_index / previous-entry validation) never
    touch disk, while the entry payloads can be evicted
    (SegmentedRaftLogCache.java evictCache) and read back through the file on
    demand for lagging followers."""

    def __init__(self, start: int, path: pathlib.Path, is_open: bool):
        self.start = start
        self.path = path
        self.is_open = is_open
        # None = evicted (payloads live only in the file)
        self.entries: Optional[list[LogEntry]] = []
        # always-resident metadata: term + byte offset of each record
        self.terms: list[int] = []
        self.offsets: list[int] = []
        self.size = len(MAGIC)

    def append(self, entry: LogEntry, offset: int, record_len: int) -> None:
        assert self.entries is not None, "append to evicted segment"
        self.entries.append(entry)
        self.terms.append(entry.term)
        self.offsets.append(offset)
        self.size = offset + record_len

    @property
    def count(self) -> int:
        return len(self.terms)

    @property
    def end(self) -> int:
        return self.start + len(self.terms) - 1

    @property
    def cached(self) -> bool:
        return self.entries is not None

    def evict(self) -> None:
        assert not self.is_open
        self.entries = None

    def term_at(self, index: int) -> Optional[int]:
        i = index - self.start
        if 0 <= i < len(self.terms):
            return self.terms[i]
        return None

    def get(self, index: int) -> Optional[LogEntry]:
        i = index - self.start
        if 0 <= i < len(self.terms) and self.entries is not None:
            return self.entries[i]
        return None

    def load(self) -> list[LogEntry]:
        """Read the whole segment back from disk (read-through miss)."""
        payloads, _ = read_records(self.path)
        return [LogEntry.from_bytes(p) for p in payloads]


class SegmentedRaftLog(RaftLog):
    def __init__(self, name: str, directory: pathlib.Path,
                 worker: Optional[LogWorker] = None,
                 segment_size_max: int = 8 << 20,
                 cache_segments_max: int = 6):
        super().__init__(name)
        self.dir = pathlib.Path(directory)
        self.worker = worker or LogWorker.shared(str(self.dir.anchor or "default"))
        self.segment_size_max = segment_size_max
        # Closed segments beyond this many keep only (term, offset) metadata
        # in RAM; payloads are re-read from the file on demand (reference
        # SegmentedRaftLogCache.java default 6 cached segments).
        self.cache_segments_max = cache_segments_max
        self._segments: list[_Segment] = []
        # read-through cache: seg.start -> entries, tiny LRU (a couple of
        # lagging followers scanning different segments shouldn't thrash).
        # Guarded by a threading lock: prefault() runs in to_thread workers
        # concurrently with event-loop readers.
        self._rt_cache: "dict[int, list[LogEntry]]" = {}
        self._rt_cache_max = 3
        self._rt_version = 0  # bumped on truncate/purge/snapshot invalidation
        self._rt_lock = threading.Lock()
        self._open_file = None
        self._below_start: Optional[TermIndex] = None
        from ratis_tpu.metrics import SegmentedRaftLogMetrics
        self.metrics = SegmentedRaftLogMetrics(name)

    # ------------------------------------------------------------- recovery

    async def open(self, last_index_on_snapshot: int = INVALID_LOG_INDEX) -> None:
        await super().open(last_index_on_snapshot)
        self.worker.acquire()
        self.dir.mkdir(parents=True, exist_ok=True)
        found: list[tuple[int, Optional[int], pathlib.Path]] = []
        for f in self.dir.iterdir():
            m = _CLOSED_RE.match(f.name)
            if m:
                found.append((int(m.group(1)), int(m.group(2)), f))
                continue
            m = _OPEN_RE.match(f.name)
            if m:
                found.append((int(m.group(1)), None, f))
        found.sort(key=lambda x: x[0])

        for start, end, path in found:
            seg = _Segment(start, path, end is None)
            payloads, good_len = read_records(path)
            file_size = path.stat().st_size
            if good_len < file_size:
                if end is not None:
                    raise ChecksumException(
                        f"{self.name}: corrupt closed segment {path.name}",
                        good_len)
                # corrupt tail of the open segment: truncate it away
                with open(path, "r+b") as fh:
                    fh.truncate(good_len)
            off = len(MAGIC)
            for p in payloads:
                e = LogEntry.from_bytes(p)
                seg.append(e, off, _REC_HDR.size + len(p))
                off += _REC_HDR.size + len(p)
            if seg.count or seg.is_open:
                self._segments.append(seg)

        # Only the last segment may be open; close others defensively.
        for seg in self._segments[:-1]:
            if seg.is_open:
                self._close_segment_file(seg)
        if self._segments and self._segments[-1].is_open:
            seg = self._segments[-1]
            self._open_file = open(seg.path, "ab")
        # NOTE: when the log is empty and a snapshot exists, the caller must
        # follow open() with set_snapshot_boundary(snapshot.term_index) — the
        # term is not recoverable from the index argument alone.
        self._flush_index = self.next_index - 1
        # whatever a segment file gave back holds no state-machine data
        self._data_released = self._flush_index

    async def close(self) -> None:
        if self._open_file is not None:
            await self.worker.drain()
            self._open_file.close()
            self._open_file = None
        await self.worker.release()
        self.metrics.unregister()
        await super().close()

    def _close_segment_file(self, seg: _Segment) -> None:
        if not seg.count:
            seg.path.unlink(missing_ok=True)
            return
        new_path = seg.path.with_name(f"log_{seg.start}-{seg.end}")
        os.replace(seg.path, new_path)
        seg.path = new_path
        seg.is_open = False

    # ------------------------------------------------------------- indices

    @property
    def start_index(self) -> int:
        if self._segments:
            return self._segments[0].start
        if self._below_start is not None:
            return self._below_start.index + 1
        return 0

    @property
    def flush_index(self) -> int:
        return self._flush_index

    def get_last_entry_term_index(self) -> Optional[TermIndex]:
        for seg in reversed(self._segments):
            if seg.count:
                return TermIndex(seg.terms[-1], seg.end)
        return self._below_start

    def _fault_in(self, seg: _Segment) -> list[LogEntry]:
        with self._rt_lock:
            entries = self._rt_cache.get(seg.start)
            version = self._rt_version
        if entries is None:
            self.metrics.cache_miss_count.inc()
            entries = seg.load()  # file IO outside the lock
            with self._rt_lock:
                if self._rt_version == version:
                    # don't cache across an invalidation (a truncate may
                    # have rewritten the file while we were reading it)
                    self._rt_cache[seg.start] = entries
                    while len(self._rt_cache) > self._rt_cache_max:
                        self._rt_cache.pop(next(iter(self._rt_cache)))
        else:
            self.metrics.cache_hit_count.inc()
        return entries

    def _invalidate_rt_cache(self) -> None:
        with self._rt_lock:
            self._rt_version += 1
            self._rt_cache.clear()

    def _read_through(self, seg: _Segment, index: int) -> Optional[LogEntry]:
        """Serve an evicted segment from its file (one whole-segment read,
        held in a small LRU for the sequential scans a catching-up follower
        produces).  Synchronous: async hot paths should check is_resident()
        first and prefault() off-loop (LogAppender does)."""
        entries = self._fault_in(seg)
        i = index - seg.start
        if 0 <= i < len(entries):
            return entries[i]
        return None

    def _covering_segment(self, index: int) -> Optional[_Segment]:
        for seg in reversed(self._segments):
            if seg.start <= index:
                return seg if index <= seg.end else None
        return None

    def is_resident(self, index: int) -> bool:
        seg = self._covering_segment(index)
        if seg is None:
            return True
        if not seg.cached:
            # _rt_cache is mutated from prefault worker threads; the lock is
            # uncontended and keeps this membership check from racing an LRU
            # eviction into a synchronous whole-segment load on the event
            # loop
            with self._rt_lock:
                if seg.start not in self._rt_cache:
                    return False
        return super().is_resident(index)

    def prefault(self, index: int) -> None:
        """Blocking load of the segment covering ``index`` into the
        read-through cache, and of the state-machine data its entries let
        go; call via asyncio.to_thread from async paths."""
        seg = self._covering_segment(index)
        if seg is not None and not seg.cached:
            self._fault_in(seg)
        super().prefault(index)

    def _strip(self, index: int) -> None:
        seg = self._covering_segment(index)
        if seg is not None and seg.cached:
            i = index - seg.start
            seg.entries[i] = seg.entries[i].without_sm_data()

    def get(self, index: int) -> Optional[LogEntry]:
        for seg in reversed(self._segments):
            if seg.start <= index:
                if index > seg.end:
                    return None
                if seg.cached:
                    return seg.get(index)
                return self._read_through(seg, index)
        return None

    def get_term_index(self, index: int) -> Optional[TermIndex]:
        # metadata-only: never faults an evicted segment in
        for seg in reversed(self._segments):
            if seg.start <= index:
                t = seg.term_at(index)
                return TermIndex(t, index) if t is not None else None
        if self._below_start is not None and index == self._below_start.index:
            return self._below_start
        return None

    # ------------------------------------------------------------- eviction

    @property
    def cached_segments(self) -> int:
        return sum(1 for s in self._segments if not s.is_open and s.cached)

    def evict_cache(self, applied_index: int) -> int:
        """Bound entry memory (reference SegmentedRaftLogCache.evictCache):
        keep at most cache_segments_max closed segments' payloads resident,
        evicting oldest-first but only below the applied frontier (the
        applier reads every entry exactly once; evicting ahead of it would
        thrash).  Lagging followers are served from disk via read-through.
        Returns the number of segments evicted."""
        # cheap guard: runs on every apply batch, almost always a no-op
        if len(self._segments) <= self.cache_segments_max + 1:
            return 0
        closed_cached = [s for s in self._segments
                         if not s.is_open and s.cached]
        excess = len(closed_cached) - self.cache_segments_max
        evicted = 0
        for seg in closed_cached:
            if evicted >= excess:
                break
            if seg.end <= applied_index:
                seg.evict()
                self.metrics.cache_evict_count.inc()
                evicted += 1
        return evicted

    # ------------------------------------------------------------- append

    def _ensure_open_segment(self, start: int) -> _Segment:
        if self._segments and self._segments[-1].is_open:
            return self._segments[-1]
        seg = _Segment(start, self.dir / f"log_inprogress_{start}", True)
        seg.path.write_bytes(MAGIC)
        self._segments.append(seg)
        self._open_file = open(seg.path, "ab")
        return seg

    async def _roll_segment(self) -> None:
        await self.worker.drain()
        seg = self._segments[-1]
        self._open_file.close()
        self._open_file = None
        self._close_segment_file(seg)

    async def append_entry(self, entry: LogEntry, wait_flush: bool = True) -> int:
        with self.metrics.append_timer.time():
            return await self._append_entry_impl(entry, wait_flush)

    async def _append_entry_impl(self, entry: LogEntry,
                                 wait_flush: bool) -> int:
        if self._failed is not None:
            raise RaftLogIOException(
                f"{self.name}: log failed permanently") from self._failed
        expected = self.next_index
        if entry.index != expected:
            raise ValueError(f"{self.name}: appending index {entry.index}, "
                             f"expected {expected}")
        seg = self._ensure_open_segment(entry.index)
        if seg.size > self.segment_size_max:
            await self._roll_segment()
            seg = self._ensure_open_segment(entry.index)

        payload = entry.to_bytes(include_sm_data=False)
        record = encode_record(payload)
        seg.append(entry, seg.size, len(record))
        smlog = entry.smlog
        # StateMachine.DataApi.write starts here; the record follows it to
        # the disk
        gate = (self._start_data_write(entry)
                if smlog is not None and smlog.sm_data is not None else None)
        rec = self.worker.submit(self._open_file, record, self, entry.index,
                                 gate)
        if wait_flush:
            await rec
        return entry.index

    # ------------------------------------------------------------ truncate

    async def truncate(self, index: int) -> None:
        self.metrics.truncate_count.inc()
        self._invalidate_rt_cache()
        await self.worker.drain()
        while self._segments and self._segments[-1].start >= index:
            seg = self._segments.pop()
            if seg.is_open and self._open_file is not None:
                self._open_file.close()
                self._open_file = None
            seg.path.unlink(missing_ok=True)
        if not self._segments:
            self._flush_index = min(self._flush_index, index - 1)
            return
        seg = self._segments[-1]
        if index <= seg.end:
            if not seg.cached:
                seg.entries = seg.load()  # truncation rewrites the tail
            keep = index - seg.start
            byte_len = seg.offsets[keep] if keep < len(seg.offsets) else seg.size
            if seg.is_open and self._open_file is not None:
                self._open_file.close()
                self._open_file = None
            del seg.entries[keep:]
            del seg.terms[keep:]
            del seg.offsets[keep:]
            with open(seg.path, "r+b") as fh:
                fh.truncate(byte_len)
            seg.size = byte_len
            if not seg.is_open:
                # reopen as inprogress for future appends
                new_path = seg.path.with_name(f"log_inprogress_{seg.start}")
                os.replace(seg.path, new_path)
                seg.path = new_path
                seg.is_open = True
            self._open_file = open(seg.path, "ab")
        self._flush_index = min(self._flush_index, self.next_index - 1)

    async def purge(self, index: int) -> int:
        """Drop whole segments with end <= index (snapshot-covered); the
        reference purges at segment granularity too (purgeImpl)."""
        ti = self.get_term_index(index)
        self.metrics.purge_count.inc()
        self._invalidate_rt_cache()
        # Roll the open segment first when the snapshot fully covers it, so
        # purge can reclaim it too (otherwise a single-open-segment log would
        # never shrink after snapshotting).
        if self._segments and self._segments[-1].is_open \
                and self._segments[-1].count \
                and self._segments[-1].end <= index:
            await self._roll_segment()
        dropped = False
        while self._segments and not self._segments[0].is_open \
                and self._segments[0].end <= index:
            seg = self._segments.pop(0)
            seg.path.unlink(missing_ok=True)
            dropped = True
        if dropped and ti is not None and (not self._segments
                                           or self._segments[0].start > index):
            self._below_start = ti
        return self.start_index - 1

    def set_snapshot_boundary(self, ti: TermIndex) -> None:
        """After snapshot install: discard the local log below/at ti."""
        self._invalidate_rt_cache()
        for seg in self._segments:
            seg.path.unlink(missing_ok=True)
        self._segments.clear()
        if self._open_file is not None:
            self._open_file.close()
            self._open_file = None
        self._below_start = ti
        self._flush_index = ti.index
