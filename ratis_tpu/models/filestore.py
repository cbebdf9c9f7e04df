"""FileStore: a replicated file service over both bulk-data paths.

Capability parity with the reference filestore example
(ratis-examples/src/main/java/org/apache/ratis/examples/filestore/
FileStoreStateMachine.java:48 + FileStore.java + FileInfo.java).  A WRITE
goes round the raft log: ``start_transaction`` puts the header {path,
offset, length, close, sync} into the entry's ``log_data`` and the bytes
into its ``sm_data``; ``data_write`` (StateMachine.DataApi.write, called by
the log on the leader and on every follower as the entry is appended, and
completed before the entry's record goes to the disk) writes them into the
file under construction at ``offset`` and forces them where the request says
``sync``; ``apply_transaction`` commits the write and, on
``close``, moves the file into place; ``data_read`` gives the bytes back to
an appender whose cache let them go.  Large files can also stream peer to
peer over the DataStream path (``stream``:196 opens a channel into a temp
file, ``link``:210 renames it into place when the raft entry commits).
Queries read file bytes / list the store.

Commands (msgpack dicts in the Message body):
  write  {op, path, data, offset=0, close=true, sync=false}
                              — ``data`` at ``offset`` of the file under
                                construction; the writes of a path come in
                                offset order, ``close`` on the last
  stream {op, path, size}     — DataStream header; bytes arrive out of band
  delete {op, path}
  read   {op, path} (query)   — file bytes
  list   {op} (query)         — sorted names of the closed files

Layout under the root (``<storage>/sm/files`` of a durable group):
``<path>`` a closed file, ``.uc/<path>`` a file under construction,
``.tmp/`` streams.
"""

from __future__ import annotations

import asyncio
import concurrent.futures
import logging
import os
import pathlib
import tempfile
import threading
from typing import Dict, Optional

import msgpack

from ratis_tpu.protocol.message import Message
from ratis_tpu.server.statemachine import (BaseStateMachine, DataChannel,
                                           DataStream, TransactionContext)
from ratis_tpu.trace.tracer import (STAGE_DATA_FSYNC, STAGE_DATA_WRITE,
                                    STAGE_STREAM_FORCE, TRACER)

LOG = logging.getLogger(__name__)

# The writers of every FileStore of the process (the reference runs a
# writer executor per state machine; thousands of co-hosted groups share
# one).  Each job is one pwrite and, where the request says sync, one fsync.
IO_THREADS = 8
_IO = concurrent.futures.ThreadPoolExecutor(   # (threads start on demand)
    IO_THREADS, thread_name_prefix="filestore-io")


# The writers of every streamed file of the process: a stream's channel is
# pinned to one lane (by stream id), a thread that takes everything queued in
# one pass, writes each file's run of packets with one pwritev and calls each
# loop that queued back once (as server/log/segmented.py's LogWorker does).
STREAM_LANES = 3
_IOV_MAX = 1024     # buffers a pwritev takes (Linux's UIO_MAXIOV)

# counters (docs/tracing.md): payload bytes the leaders put into sm_data
# (start_transaction runs on the leader alone); forces behind data_write
_DATA_BYTES = TRACER.counter("sm.data_bytes", "leader")
_DATA_FSYNCS = TRACER.counter("sm.data_fsyncs")


class _WriterLane:
    """One ordered writer thread for streamed files.

    ``submit`` runs on a loop and only appends ``(channel, data, future)``
    under the condition, waking the thread if it waits.  The thread takes
    the whole queue as one batch, hands each channel its items in queue
    order (``FileChunkChannel._take``) and resolves the batch with one
    ``call_soon_threadsafe`` to each loop that queued into it.  Counted:
    ``stream.write_batches`` a pass."""

    def __init__(self, name: str) -> None:
        self.name = name
        self._queue: list = []
        self._cond = threading.Condition(threading.Lock())
        self._thread: Optional[threading.Thread] = None
        # (only this lane's thread adds to them)
        self.batches = TRACER.counter("stream.write_batches", name)

    def submit(self, channel: "FileChunkChannel",
               data: Optional[bytes]) -> asyncio.Future:
        """Queue ``data`` for the end of ``channel``'s file (None: close its
        descriptor); the future, of the running loop, holds the bytes
        written or the error that stopped them."""
        fut = asyncio.get_running_loop().create_future()
        with self._cond:
            self._queue.append((channel, data, fut))
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name=f"filestore-{self.name}",
                    daemon=True)
                self._thread.start()
            elif len(self._queue) == 1:     # (it waits only on an empty one)
                self._cond.notify()
        return fut

    def _run(self) -> None:
        cond, queue = self._cond, self._queue
        while True:
            with cond:
                while not queue:
                    cond.wait()
                batch = queue[:]
                del queue[:]
            self.batches.n += 1
            try:
                done = self._write(batch)
            except BaseException as e:  # the batch fails; the lane goes on
                LOG.exception("stream writer %s: a pass failed", self.name)
                err = e if isinstance(e, Exception) else RuntimeError(repr(e))
                done = [(fut, err) for _, _, fut in batch]
            by_loop: dict = {}
            for fut, out in done:
                by_loop.setdefault(fut.get_loop(), []).append((fut, out))
            for loop, outs in by_loop.items():
                try:
                    loop.call_soon_threadsafe(_resolve, outs)
                except RuntimeError:
                    pass    # that loop has closed: nobody is left to tell

    @staticmethod
    def _write(batch: list) -> list:
        """A pass: each channel's items in queue order; each future's
        outcome."""
        items: dict = {}
        for channel, data, fut in batch:
            items.setdefault(channel, []).append((data, fut))
        done = []
        for channel, queued in items.items():
            try:
                outcomes = channel._take([d for d, _ in queued])
            except Exception as e:      # (a close that failed)
                outcomes = [e] * len(queued)
            channel._taken += len(queued)
            done += [(fut, out) for (_, fut), out in zip(queued, outcomes)]
        return done


def _resolve(done: list) -> None:
    """Back on the loop that queued them: a lane pass's outcomes."""
    for fut, out in done:
        if fut.done():
            continue        # (its awaiter was cancelled)
        if isinstance(out, BaseException):
            fut.set_exception(out)
        else:
            fut.set_result(out)


_LANES = [_WriterLane(f"lane-{i}") for i in range(STREAM_LANES)]


def _safe_relpath(path: str) -> pathlib.PurePosixPath:
    p = pathlib.PurePosixPath(path)
    if p.is_absolute() or ".." in p.parts or not p.parts:
        raise ValueError(f"unsafe path {path!r}")
    return p


class FileChunkChannel(DataChannel):
    """Streams into ``<root>/.tmp/<stream>``; linked (renamed) at apply.
    Writes go through ``lane`` in order, and the close too while the lane
    holds some of them; the force does not (the plane forces only what it
    has seen written)."""

    def __init__(self, tmp_path: pathlib.Path, lane: _WriterLane) -> None:
        self.tmp_path = tmp_path
        self._lane = lane
        self._fd = os.open(tmp_path, os.O_WRONLY | os.O_CREAT | os.O_TRUNC,
                           0o644)
        self._end = 0           # bytes landed (the lane's thread moves it)
        self._forced = 0        # ... of them as far as the last force
        self._error: Optional[BaseException] = None   # the lane's thread's
        # items handed to the lane, and those it is done with (its thread's)
        self._submitted = self._taken = 0
        self._closed: Optional[asyncio.Future] = None

    def submit_write(self, data: bytes) -> "asyncio.Future[int]":
        """Queue ``data`` behind this channel's earlier writes: the future
        holds the bytes written (fewer: a short write) or the error."""
        self._submitted += 1
        return self._lane.submit(self, data)

    async def write(self, data: bytes) -> int:
        return await self.submit_write(data)

    async def force(self, metadata: bool = False) -> None:
        nbytes, self._forced = self._end - self._forced, self._end

        def _sync():
            span = TRACER.begin(STAGE_STREAM_FORCE) if TRACER.enabled \
                else None
            try:
                os.fsync(self._fd)
            finally:
                if span is not None:
                    TRACER.end(span, tag=nbytes)
        await asyncio.to_thread(_sync)

    async def close(self) -> None:
        if self._closed is None:
            if self._taken == self._submitted:
                self._closed = asyncio.get_running_loop().run_in_executor(
                    None, self._close_fd)
            else:   # behind them: the descriptor is not closed under one
                self._submitted += 1
                self._closed = self._lane.submit(self, None)
        if not self._closed.done():
            await self._closed

    def _close_fd(self) -> int:
        fd, self._fd = self._fd, -1     # (once: a failed close still frees it)
        if fd >= 0:
            os.close(fd)
        return 0

    # ------------------------------------------------- the lane's thread

    def _take(self, queued: list) -> list:
        """This channel's items of a lane pass, in order (None, last: the
        close); each one's outcome."""
        closing = queued[-1] is None
        out = self._write_run(queued[:-1] if closing else queued)
        if closing:
            out.append(self._close_fd())
        return out

    def _write_run(self, chunks: list) -> list:
        """A run of writes in one ``_append``.  Where it falls short, the
        file is cut back to its last whole packet and every later write
        of the channel fails."""
        start, err = self._end, None
        if self._error is None and chunks:
            try:
                self._append(chunks)
            except OSError as e:
                err = e
        landed, out = self._end - start, []
        for data in chunks:
            if self._error is None and landed >= len(data):
                landed -= len(data)
                out.append(len(data))
            elif self._error is None:
                self._error = err or IOError(
                    f"{self.tmp_path.name}: short write {landed}/{len(data)}")
                out.append(err or landed)
            else:
                out.append(IOError(f"{self.tmp_path.name}: an earlier "
                                   f"write failed: {self._error}"))
        if landed:      # part of a packet
            try:
                os.ftruncate(self._fd, self._end - landed)
                self._end -= landed
            except OSError:
                pass
        return out

    def _append(self, chunks: list) -> None:
        """``chunks`` at the end of what has landed: one ``pwritev`` (more
        only where the kernel takes less); stops at a call that takes
        nothing."""
        while chunks:
            n = os.pwritev(self._fd, chunks[:_IOV_MAX], self._end)
            if n == 0:
                return
            self._end += n
            while chunks and n >= len(chunks[0]):
                n -= len(chunks[0])
                chunks = chunks[1:]
            if n:
                chunks = [memoryview(chunks[0])[n:]] + chunks[1:]


class FileStoreDataStream(DataStream):
    def __init__(self, channel: FileChunkChannel, request,
                 target: pathlib.PurePosixPath) -> None:
        super().__init__(channel, request)
        self.target = target

    async def cleanup(self) -> None:
        await self.channel.close()
        self.channel.tmp_path.unlink(missing_ok=True)


class _UnderConstruction:
    """One file being written: its descriptor (opened by the first job
    that needs it), how far writes were appended to the log and how far
    they are committed."""

    __slots__ = ("uc_path", "fd", "appended", "committed", "lock")

    def __init__(self, uc_path: pathlib.Path) -> None:
        self.uc_path = uc_path
        self.fd: Optional[int] = None
        self.appended = 0
        self.committed = 0
        self.lock = threading.Lock()

    def descriptor(self) -> int:
        with self.lock:
            if self.fd is None:
                self.uc_path.parent.mkdir(parents=True, exist_ok=True)
                self.fd = os.open(self.uc_path, os.O_CREAT | os.O_RDWR, 0o644)
            return self.fd

    def close(self) -> None:
        with self.lock:
            if self.fd is not None:
                os.close(self.fd)
                self.fd = None

    def write(self, offset: int, data: bytes, sync: bool) -> None:
        """One data_write, on a writer thread."""
        fd = self.descriptor()
        span = TRACER.begin(STAGE_DATA_WRITE) if TRACER.enabled else None
        try:
            view, at = memoryview(data), offset
            while len(view):
                n = os.pwrite(fd, view, at)
                view, at = view[n:], at + n
        finally:
            if span is not None:
                TRACER.end(span, tag=len(data))
        if sync:
            span = TRACER.begin(STAGE_DATA_FSYNC) if TRACER.enabled else None
            try:
                os.fsync(fd)
            finally:
                if span is not None:
                    TRACER.end(span, tag=1)


class FileStoreStateMachine(BaseStateMachine):
    def __init__(self, root: Optional[str] = None) -> None:
        super().__init__()
        self._explicit_root = root
        self._root: Optional[pathlib.Path] = None
        self._tmp_holder: Optional[tempfile.TemporaryDirectory] = None
        self.files: Dict[str, int] = {}  # path -> size (committed metadata)
        self.writes_committed = 0        # WRITE transactions applied
        self.streams_committed = 0       # streams linked and applied
        self._open: Dict[str, _UnderConstruction] = {}
        # log index -> (path, offset, the write's future) of the writes
        # data_write has taken in this life and apply has not reached: what
        # apply waits for and data_truncate rolls back
        self._unapplied: Dict[int, tuple] = {}
        self._stream_seq = 0

    # ------------------------------------------------------------- layout

    @property
    def root(self) -> pathlib.Path:
        if self._root is None:
            if self._explicit_root is not None:
                self._root = pathlib.Path(self._explicit_root)
            elif self._storage.directory is not None:
                self._root = self._storage.directory / "files"
            else:  # volatile group: keep files in a temp dir for our lifetime
                self._tmp_holder = tempfile.TemporaryDirectory(
                    prefix="filestore-")
                self._root = pathlib.Path(self._tmp_holder.name)
        return self._root

    def resolve(self, path: str) -> pathlib.Path:
        return self.root / _safe_relpath(path)

    def _under_construction(self, path: str) -> _UnderConstruction:
        uc = self._open.get(path)
        if uc is None:
            uc = self._open[path] = _UnderConstruction(
                self.root / ".uc" / _safe_relpath(path))
        return uc

    async def close(self) -> None:
        for uc in self._open.values():
            uc.close()
        if self._tmp_holder is not None:
            self._tmp_holder.cleanup()
        await super().close()

    # ----------------------------------------------------------- pipeline

    async def start_transaction(self, request) -> TransactionContext:
        """Leader: the header into ``log_data``, a WRITE's bytes into
        ``sm_data``.  (Nothing here suspends, and the log's append follows
        without a suspension either, so the offset is checked against what
        data_write has taken of the write before.)"""
        trx = TransactionContext(client_request=request,
                                 log_data=request.message.content)
        try:
            cmd = msgpack.unpackb(request.message.content, raw=False)
            op = cmd["op"]
            if op not in ("write", "stream", "delete"):
                raise ValueError(f"not a transaction op: {op!r}")
            path = cmd["path"]
            _safe_relpath(path)
            if op != "delete" and path in self.files:
                # (a stream's bytes are linked over its path at apply: one
                # to a committed path would replace the file unseen)
                raise ValueError(f"{path!r} is closed")
            if op == "write":
                data = cmd["data"]
                offset = int(cmd.get("offset", 0))
                uc = self._open.get(path)
                expected = uc.appended if uc is not None else 0
                if offset != expected:
                    raise ValueError(f"{path!r}: write at offset {offset}, "
                                     f"expected {expected}")
                trx.log_data = msgpack.packb(
                    {"op": "write", "path": path, "offset": offset,
                     "length": len(data), "close": bool(cmd.get("close", True)),
                     "sync": bool(cmd.get("sync", False))}, use_bin_type=True)
                trx.sm_data = data
                _DATA_BYTES.n += len(data)
        except Exception as e:
            trx.exception = e
        return trx

    async def apply_transaction(self, trx: TransactionContext) -> Message:
        e = trx.log_entry
        payload = (e.smlog.log_data if e is not None and e.smlog is not None
                   else (trx.log_data or b""))
        cmd = msgpack.unpackb(payload, raw=False)
        op, path = cmd["op"], cmd.get("path", "")
        reply: dict
        if op == "write":
            reply = await self._commit_write(
                cmd, e.index if e is not None else -1)
        elif op == "stream":
            # bytes were linked into place just before apply (data_link);
            # a peer outside the routing table simply has no local copy
            target = self.resolve(path)
            if target.exists():
                size = target.stat().st_size
                self.files[path] = size
                self.streams_committed += 1
                reply = {"ok": True, "size": size}
            else:
                reply = {"ok": False, "error": "data not streamed here"}
        elif op == "delete":
            uc = self._open.pop(path, None)
            if uc is not None:
                uc.close()
                await asyncio.to_thread(uc.uc_path.unlink, True)
            target = self.resolve(path)
            await asyncio.to_thread(target.unlink, True)
            self.files.pop(path, None)
            reply = {"ok": True}
        else:
            reply = {"ok": False, "error": f"unknown op {op!r}"}
        if e is not None:
            self.update_last_applied_term_index(e.term, e.index)
        return Message(msgpack.packb(reply, use_bin_type=True))

    async def _commit_write(self, cmd: dict, index: int) -> dict:
        """A committed WRITE: its bytes are in the file under construction
        already (data_write ran when the entry was appended, and the entry
        did not count as flushed before it completed); commit the length
        and, on ``close``, move the file into place.  An entry that
        data_write has not seen in this life is replayed after a restart:
        its bytes are on the disk from the life before."""
        path, offset, length = cmd["path"], cmd["offset"], cmd["length"]
        taken = self._unapplied.pop(index, None)
        replayed = taken is None
        if not replayed and not taken[2].done():
            # committed by the other replicas while this one's own write is
            # still out (its failure is the log's to report, not apply's)
            done, _ = await asyncio.wait([asyncio.wrap_future(taken[2])])
            for f in done:
                f.cancelled() or f.exception()   # (seen: no warning of it)
        if path in self.files:
            raise ValueError(f"{path!r} is closed")
        uc = self._under_construction(path)
        if offset != uc.committed:
            raise ValueError(f"{path!r}: write at offset {offset}, "
                             f"committed {uc.committed}")
        uc.committed = offset + length
        uc.appended = max(uc.appended, uc.committed)
        self.writes_committed += 1
        if cmd["close"]:
            target = self.resolve(path)
            del self._open[path]
            uc.close()
            await asyncio.to_thread(self._move_into_place, uc.uc_path,
                                    target, replayed, uc.committed)
            self.files[path] = uc.committed
        return {"ok": True, "path": path, "offset": offset, "length": length,
                "size": uc.committed}

    @staticmethod
    def _move_into_place(uc_path: pathlib.Path, target: pathlib.Path,
                         replayed: bool, size: int) -> None:
        try:
            # (a life that ended in a crash may have written, past what is
            # committed, the data of an entry its log never held)
            os.truncate(uc_path, size)
            os.replace(uc_path, target)
        except FileNotFoundError:
            if uc_path.exists():        # the first file of its directory
                target.parent.mkdir(parents=True, exist_ok=True)
                os.replace(uc_path, target)
            elif not (replayed and target.exists()):
                raise

    # -------------------------------------------------------------- query

    async def query(self, request: Message) -> Message:
        cmd = msgpack.unpackb(request.content, raw=False)
        op = cmd["op"]
        if op == "read":
            target = self.resolve(cmd["path"])
            data = await asyncio.to_thread(target.read_bytes)
            return Message(msgpack.packb({"ok": True, "data": data},
                                         use_bin_type=True))
        if op == "list":
            return Message(msgpack.packb(
                {"ok": True, "files": sorted(self.files)},
                use_bin_type=True))
        raise ValueError(f"unknown query {op!r}")

    async def query_stale(self, request: Message, min_index: int) -> Message:
        return await self.query(request)

    # ----------------------------------------------------------- DataApi

    def data_write(self, entry):
        """DataApi.write: a WRITE's bytes into the file under construction
        at the header's offset, on a writer thread (forced there where the
        header says sync); the returned future (the writer thread's own:
        the log worker's thread sees it complete without the loop) is what
        the log's record of the entry waits for before it goes to the
        disk."""
        smlog = entry.smlog
        cmd = msgpack.unpackb(smlog.log_data, raw=False)
        if cmd.get("op") != "write":
            return None
        data = smlog.sm_data
        path, offset = cmd["path"], cmd["offset"]
        uc = self._under_construction(path)
        uc.appended = offset + len(data)
        if cmd["sync"]:
            _DATA_FSYNCS.n += 1
        written = _IO.submit(uc.write, offset, data, cmd["sync"])
        self._unapplied[entry.index] = (path, offset, written)
        return written

    def data_read(self, entry) -> bytes:
        """DataApi.read (blocking): the bytes of a WRITE entry, from the
        file under construction or, once closed, from the file in place."""
        cmd = msgpack.unpackb(entry.smlog.log_data, raw=False)
        path, offset, length = cmd["path"], cmd["offset"], cmd["length"]
        uc = self._open.get(path)
        if uc is not None:
            data = os.pread(uc.descriptor(), length, offset)
        else:
            with open(self.resolve(path), "rb") as f:
                data = os.pread(f.fileno(), length, offset)
        if len(data) != length:
            raise IOError(f"{path!r}: {len(data)} of {length} bytes at "
                          f"offset {offset}")
        return data

    async def data_flush(self, index: int) -> None:
        """DataApi.flush: force every file under construction (the writes
        that did not ask for sync)."""
        fds = [uc.fd for uc in self._open.values() if uc.fd is not None]
        if fds:
            _DATA_FSYNCS.n += len(fds)
            await asyncio.to_thread(lambda: [os.fsync(fd) for fd in fds])

    async def data_truncate(self, index: int) -> None:
        """DataApi.truncate: the log dropped its entries from ``index`` on;
        their writes (the log has waited for them) go from the files under
        construction, last first."""
        for i in sorted((i for i in self._unapplied if i >= index),
                        reverse=True):
            path, offset, _ = self._unapplied.pop(i)
            uc = self._open.get(path)
            if uc is None:
                continue
            uc.appended = max(offset, uc.committed)
            if uc.appended:
                await asyncio.to_thread(os.ftruncate, uc.descriptor(),
                                        uc.appended)
            else:
                del self._open[path]
                uc.close()
                await asyncio.to_thread(uc.uc_path.unlink, True)

    async def data_stream(self, request) -> DataStream:
        cmd = msgpack.unpackb(request.message.content, raw=False)
        if cmd.get("op") != "stream":
            raise ValueError("datastream header must be a stream op")
        target = _safe_relpath(cmd["path"])
        self._stream_seq += 1
        tmp = self.root / ".tmp" / \
            f"stream_{request.type.stream_id}_{self._stream_seq}"
        # (the directories are made where they are first needed, off the
        # loop: thousands of co-hosted groups make theirs at once)
        await asyncio.to_thread(tmp.parent.mkdir, parents=True, exist_ok=True)
        lane = _LANES[request.type.stream_id % STREAM_LANES]
        return FileStoreDataStream(FileChunkChannel(tmp, lane), request,
                                   target)

    async def data_link(self, stream: Optional[DataStream], entry) -> None:
        if stream is None:
            return
        await stream.channel.close()
        target = self.root / stream.target
        target.parent.mkdir(parents=True, exist_ok=True)
        await asyncio.to_thread(os.replace, stream.channel.tmp_path, target)
