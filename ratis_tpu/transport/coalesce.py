"""Send-side write coalescing for the gRPC transport's stream framing.

Round-5 tracing (BENCH_r05 host_path_decomposition + docs/perf.md) put the
north-star residual in the host wire path, not in consensus; a dominant
share of it was one flush (a task switch + flow-control check) per frame.
:class:`WriteCoalescer` replaces that with a per-connection send queue:
frames accumulate while one flush is pending, and the whole batch goes out
as ONE flush.  (The TCP transport left it in PR 26: it writes what a loop
pass queued in one socket write from a plain callback, transport/tcp.py.)
Policy (``raft.tpu.grpc.*`` keys, conf/keys.py):

- ``flush_micros`` > 0: wait at most that long for more frames before
  flushing; ``max_frames`` > 0 flushes at once when that many are pending.
- ``flush_micros`` 0 (the default): coalescing OFF — each ``send``
  performs exactly one flush of its one frame, serialized (asserted in
  tests/test_wire_fastpath.py).

Failure contract: a flush error fails every send awaiting that batch and
POISONS the coalescer — some frames of the batch may be half-written, so
the connection is unusable and later sends fail fast; the error never
escapes into the flusher task or the event loop (a partial-batch failure
poisons the connection, not the loop).
"""

from __future__ import annotations

import asyncio
from typing import Optional

__all__ = ["WriteCoalescer"]


class WriteCoalescer:
    """Batches outbound frames into single transport flushes.

    Generic over the flush primitive: subclasses implement
    :meth:`_flush_batch` (the gRPC transport packs chunks into one stream
    message).  ``max_frames`` caps frames per flush (0 = unbounded), so
    one stream message never carries an unbounded chunk list.  What goes
    out is counted where it is handed to the transport (``wire.*`` in
    transport/grpc.py, as in transport/tcp.py), not here.
    """

    def __init__(self, flush_micros: int = 0, max_frames: int = 0):
        self.flush_micros = int(flush_micros)
        self.max_frames = int(max_frames)
        self._pending: list = []
        self._waiters: list[asyncio.Future] = []
        self._flusher: Optional[asyncio.Task] = None
        self._lock = asyncio.Lock()
        self._dead: Optional[Exception] = None
        self.metrics = {"flushes": 0, "coalesced_frames": 0}

    @property
    def coalescing(self) -> bool:
        return self.flush_micros > 0

    @property
    def poisoned(self) -> bool:
        return self._dead is not None

    async def _flush_batch(self, frames: list) -> None:
        raise NotImplementedError

    async def send(self, frame) -> None:
        """Queue ``frame`` and return once the flush carrying it drained
        (backpressure: callers wait out the transport's flow control
        exactly as the per-frame path did)."""
        if self._dead is not None:
            raise self._dead
        if not self.coalescing:
            # the exact legacy path: one write+drain per frame, serialized
            async with self._lock:
                if self._dead is not None:
                    raise self._dead
                await self._flush_batch([frame])
                self.metrics["flushes"] += 1
            return
        fut = asyncio.get_running_loop().create_future()
        self._pending.append(frame)
        self._waiters.append(fut)
        if self.max_frames and len(self._pending) >= self.max_frames:
            await self._flush_now()
        elif self._flusher is None:
            self._flusher = asyncio.create_task(self._flush_after_delay())
        await fut

    async def _flush_after_delay(self) -> None:
        try:
            while self._pending and self._dead is None:
                await asyncio.sleep(self.flush_micros / 1e6)
                await self._flush_now()
        finally:
            self._flusher = None

    async def _flush_now(self) -> None:
        async with self._lock:
            if not self._pending or self._dead is not None:
                return
            frames = self._pending
            waiters = self._waiters
            self._pending, self._waiters = [], []
            try:
                await self._flush_batch(frames)
            except asyncio.CancelledError:
                self._poison(ConnectionError("flush cancelled mid-batch"),
                             waiters)
                raise
            except Exception as e:
                self._poison(e, waiters)
                return
            self.metrics["flushes"] += 1
            if len(frames) > 1:
                self.metrics["coalesced_frames"] += len(frames)
            for f in waiters:
                if not f.done():
                    f.set_result(None)

    def _poison(self, exc: Exception, waiters=()) -> None:
        if self._dead is None:
            self._dead = exc
        # abandoned waiters (caller's await was cancelled) are already done
        for f in (*waiters, *self._waiters):
            if not f.done():
                f.set_exception(exc)
        self._waiters.clear()
        self._pending.clear()

    async def aclose(self) -> None:
        """Flush anything still pending (flush-on-close), then retire the
        flusher.  Safe on a poisoned coalescer (no-op flush)."""
        try:
            await self._flush_now()
        finally:
            t = self._flusher
            if t is not None and t is not asyncio.current_task():
                t.cancel()
                try:
                    await t
                except (asyncio.CancelledError, Exception):
                    pass
