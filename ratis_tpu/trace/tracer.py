"""Host-path tracing: request->commit spans over the five-layer request path.

No reference analog — the reference leans on JVM profilers; here the host
runtime is a single asyncio loop and the question every perf round asks is
"which host-side stage eats the commit's wall-clock?" (VERDICT r5: the
1025 commits/s headline had no artifact decomposing msgpack / socket /
division-append / engine-dispatch cost).  This module answers it with
always-available, low-overhead structured spans:

- A :class:`TraceContext` is just an integer trace id minted at the client
  (``Tracer.begin_trace``), carried on :class:`RaftClientRequest` (wire
  field ``tr``) through the transport codec, server routing, the division
  write path, and apply — every stage the request crosses records a span
  against the same id.
- Span records are written to fixed-size per-stage ring buffers
  (:class:`SpanRing`): a pre-allocated int64 array, one row assignment per
  record — no allocation on the hot path, bounded memory, and a high-rate
  stage (codec) can never evict a low-rate one (client spans).
- Sampling (``raft.tpu.trace.sample-every``) bounds the recording rate;
  with tracing disabled (the default) every instrumentation site is a
  single attribute check.

Aggregation/export (Chrome trace-event JSON for Perfetto, and the
per-stage percentile decomposition table) lives in
:mod:`ratis_tpu.trace.export`.

The runtime is single-event-loop end to end, so one process-wide tracer
(``TRACER`` / :func:`get_tracer`) serves every co-hosted server and the
in-process clients; cross-process propagation rides the wire field.

A *session* is open while ``raft.tpu.trace.enabled`` is set or while a
``jax.profiler`` session is open in the process (:meth:`Tracer.poll`, called
from the engine's tick loop, sees the profiler start and stop).  While the
profiler is on, every *work span* (``W`` in :data:`STAGE_KINDS`: a
synchronous stretch on one thread) is also entered as a
``jax.profiler.TraceAnnotation("ratis:<stage>")``, so it lies in the
xplane's host plane on the profiler's own clock beside the device ops; one
``ratis:clock`` annotation at session open carries ``monotonic_ns`` and maps
the ring rows (interval spans, ``I``, cannot be ``with`` blocks) onto it.
Always-on integer counters (:meth:`Tracer.counter`) are snapshotted at the
session's two ends; :meth:`Tracer.session` gives the delta.
"""

from __future__ import annotations

import collections
import contextvars
import itertools
import os
import threading
import time
import types
from asyncio import Task as _Task
from asyncio import current_task as _current_task
from asyncio.events import _get_running_loop
from asyncio.tasks import _PyTask

import numpy as np

# Transport ingress timestamp for the in-flight request: the transport sets
# it just before handing off to the server handler, and the handler's route
# span starts there — so the task-scheduling hop between ingress and the
# handler's first instruction is ATTRIBUTED (it is real latency), not lost
# to the coverage residual.  A ContextVar propagates into the handler task
# (task creation copies the caller's context); single-use — the reader
# clears it.
INGRESS_NS: contextvars.ContextVar[int] = contextvars.ContextVar(
    "ratis_trace_ingress_ns", default=0)

# Stage ids.  The SERVER-side stages route/txn_start/append/replicate/apply
# TILE the request's server wall-clock (each starts where the previous
# ends), so their per-trace sum is directly comparable to the client span.
# CLIENT / WIRE / ENGINE overlap other stages (marked in export).
STAGE_CLIENT = 0      # client.send — full client-observed request wall
STAGE_ENCODE = 1      # codec.encode — msgpack encode (request or server rpc)
STAGE_DECODE = 2      # codec.decode — msgpack decode
STAGE_WIRE = 3        # wire.rtt — transport send + reply (overlaps server)
STAGE_ROUTE = 4       # server.route — handler entry -> division submit
STAGE_TXN = 5         # server.txn_start — SM start/pre-append hooks
STAGE_APPEND = 6      # server.append — leader log append (in-memory)
STAGE_REPLICATE = 7   # server.replicate — append done -> apply starts
                      # (quorum wait + apply-queue wait)
STAGE_APPLY = 8       # server.apply — state-machine apply
STAGE_REPLY = 9       # server.reply — apply done -> write handler resumes
                      # (reply-future resolution + event-loop scheduling)
STAGE_RESPOND = 10    # server.respond — server handler done -> reply handed
                      # back to the transport / written to the socket
STAGE_ENGINE = 11     # engine.dispatch — one quorum-engine tick dispatch
STAGE_FANOUT = 12     # server.fanout — one waterline reply fan-out pass
                      # (batch of committed requests resolved in one unit;
                      # tag = batch size; process-level like engine.dispatch)
# Parts of server.replicate, per traced request on the leader; they overlap
# each other and the tiling stage that holds them.
STAGE_FLUSH_WAIT = 13   # server.flush_wait — append done -> the leader's own
                        # log flush seen on the loop
STAGE_QUORUM_WAIT = 14  # server.quorum_wait — append done -> commit index
                        # covers the entry (tag 1 = inline at ack intake,
                        # 2 = by an engine tick)
STAGE_APPLY_QUEUE = 15  # server.apply_queue — commit covers it -> apply starts
# Process-level stages (trace id 0), sampled per stage.
STAGE_RTT = 16          # replicate.rtt — frame cut for a destination -> its
                        # reply taken in (tag = crc32 of the destination id)
STAGE_SWEEP = 17        # replicate.sweep — one drain pass (tag = frames cut)
STAGE_ACK = 18          # ack.intake — one engine ack/flush intake (tag = rows)
STAGE_FOLLOWER = 19     # follower.append — handle_append_entries entered ->
                        # its reply returned, flush included (tag = entries)
STAGE_LOG_QUEUE = 20    # log.queue — LogWorker.submit -> batch taken
STAGE_LOG_WRITE = 21    # log.write — one batch's writes, on the worker
                        # thread (tag = distinct files)
STAGE_LOG_FSYNC = 22    # log.fsync — the batch's fsyncs (tag = distinct files)
STAGE_PACK = 23         # engine.pack — events packed for the step
STAGE_LAUNCH = 24       # engine.launch — uploads + the step call returning
STAGE_FETCH = 25        # engine.fetch — outputs to the host (kernel + d2h)
STAGE_COLLECT = 26      # engine.collect — changed rows -> listener events
STAGE_TCP_READ = 27     # tcp.read — one read burst of a connection (tag =
                        # frames)
STAGE_SELECT = 28       # loop.select — one blocking selector wait
STAGE_WIRE_FLUSH = 29   # wire.flush — one buffered socket write of a
                        # connection's coalescer (tag = frames)
# State-machine data beside the log (StateMachine.DataApi).
STAGE_DATA_WAIT = 30    # server.data_wait — what the log worker's batch was
                        # held back for a record's data_write before its
                        # write and fsync (0 where the data came first)
STAGE_DATA_WRITE = 31   # sm.data_write — one entry's bytes written by the
                        # state machine, on the writing thread (tag = bytes)
STAGE_DATA_FSYNC = 32   # sm.data_fsync — the force behind such writes
                        # (tag = files)
# The gRPC transport's two (what tcp.read / wire.flush are to TCP).
STAGE_GRPC_READ = 33    # grpc.read — one inbound stream message: unpacked,
                        # its chunks handed to their handlers (tag = chunks)
STAGE_GRPC_WRITE = 34   # grpc.write — one outbound stream message: packed
                        # and given to grpc.aio, up to where that call
                        # suspends (tag = chunks)
# The stream plane (server/datastream.py): bulk bytes beside the log.  All
# process-level (trace id 0), sampled per stage.
STAGE_STREAM_HEADER = 35  # stream.header — a HEADER packet off the socket ->
                          # its ack written to the connection (the state
                          # machine's channel opened, successors connected
                          # and the header forwarded)
STAGE_STREAM_PACKET = 36  # stream.packet — a DATA packet off the socket at a
                          # peer -> its ack written to the connection (local
                          # write, sends, successors' acks; tag = bytes,
                          # negated at a successor)
STAGE_STREAM_WRITE = 37   # stream.write — the write handed to the channel
                          # -> its completion seen on the loop (tag = bytes)
STAGE_STREAM_CLOSE = 38   # stream.close — the CLOSE packet off the socket at
                          # the primary -> submit_data_stream_request called
                          # (pipeline drained, CLOSE forwarded, channel forced)
STAGE_STREAM_FORCE = 39   # stream.force — a channel's force, on the forcing
                          # thread (tag = bytes since the last force)
STAGE_STREAM_LINK = 40    # stream.link — take_link -> data_link returned, at
                          # apply
# A group's hard state on the shared log plane, and a group's bring-up.
STAGE_LOG_META = 41       # log.meta — one persist of a group's term and vote
                          # or configuration, from its call to its record's
                          # fsync seen on the loop (tag = record kind)
STAGE_GROUP_ADD = 42      # server.group_add — one group added to a server:
                          # its storage, its Division built and started
NUM_STAGES = 43

STAGE_NAMES = (
    "client.send", "codec.encode", "codec.decode", "wire.rtt",
    "server.route", "server.txn_start", "server.append",
    "server.replicate", "server.apply", "server.reply", "server.respond",
    "engine.dispatch", "server.fanout",
    "server.flush_wait", "server.quorum_wait", "server.apply_queue",
    "replicate.rtt", "replicate.sweep", "ack.intake", "follower.append",
    "log.queue", "log.write", "log.fsync",
    "engine.pack", "engine.launch", "engine.fetch", "engine.collect",
    "tcp.read", "loop.select", "wire.flush",
    "server.data_wait", "sm.data_write", "sm.data_fsync",
    "grpc.read", "grpc.write",
    "stream.header", "stream.packet", "stream.write", "stream.close",
    "stream.force", "stream.link",
    "log.meta", "server.group_add",
)

# W = work span: a stretch that is synchronous on one thread by construction
# (ring row + a ``ratis:<stage>`` profiler annotation); I = interval between
# two events (ring row only).  server.txn_start, server.append and
# server.apply await the embedder's state machine or the log: a suspension
# there would put other callbacks inside the annotation, so they are I.
STAGE_KINDS = (
    "I", "W", "W", "I",
    "W", "I", "I",
    "I", "I", "I", "I",
    "W", "W",
    "I", "I", "I",
    "I", "W", "W", "I",
    "I", "W", "W",
    "W", "W", "W", "W",
    "W", "W", "W",
    "I", "W", "W",
    "W", "W",
    "I", "I", "I", "I", "W", "I",
    "I", "W",
)

# Work spans happen once per batch, several batches per commit: their rings
# hold this many times ``ring-size`` so a 25 s window at the default
# sampling never wraps (server.route is one row per traced request and
# keeps the plain size).
WORK_RING_FACTOR = 4

# The layers a timed loop's busy time is charged to (LoopClock, below): each
# callback and each work span has exactly one.  docs/tracing.md, "Loop time
# by layer", has the table.
LAYER_NAMES = ("edge", "wire", "consensus", "log", "engine", "reads",
               "stream", "sm", "other")
(LAYER_EDGE, LAYER_WIRE, LAYER_CONSENSUS, LAYER_LOG, LAYER_ENGINE,
 LAYER_READS, LAYER_STREAM, LAYER_SM, LAYER_OTHER) = range(len(LAYER_NAMES))

# Module -> layer, by the longest matching prefix of the module's name;
# every module under ratis_tpu/ falls under one (the package's own root is
# the server's edge).  Code of no ratis_tpu module is ``other``.
MODULE_LAYERS = (
    ("ratis_tpu", "edge"),
    ("ratis_tpu.transport", "wire"),
    ("ratis_tpu.protocol", "wire"),
    ("ratis_tpu.server.division", "consensus"),
    ("ratis_tpu.server.leader", "consensus"),
    ("ratis_tpu.server.replication", "consensus"),
    ("ratis_tpu.server.election", "consensus"),
    ("ratis_tpu.server.state", "consensus"),
    ("ratis_tpu.server.upkeep", "consensus"),
    ("ratis_tpu.server.watchdog", "consensus"),
    ("ratis_tpu.conf.reconfiguration", "consensus"),
    ("ratis_tpu.server.log", "log"),
    ("ratis_tpu.server.storage", "log"),
    ("ratis_tpu.engine", "engine"),
    ("ratis_tpu.ops", "engine"),
    ("ratis_tpu.parallel", "engine"),
    ("ratis_tpu.server.read", "reads"),
    ("ratis_tpu.server.serving.readbatch", "reads"),
    ("ratis_tpu.server.datastream", "stream"),
    ("ratis_tpu.transport.datastream", "stream"),
    ("ratis_tpu.server.messagestream", "stream"),
    ("ratis_tpu.server.statemachine", "sm"),
    ("ratis_tpu.server.snapshot", "sm"),
    ("ratis_tpu.models", "sm"),
)

# Each stage's layer: a work span switches a timed loop's clock to it for
# its length.  loop.select, the selector's own time (``loop.select_ns``),
# has none.
STAGE_LAYERS = (
    "edge", "wire", "wire", "wire",
    "edge", "sm", "log",
    "consensus", "sm", "edge", "edge",
    "engine", "edge",
    "log", "consensus", "consensus",
    "consensus", "consensus", "consensus", "consensus",
    "log", "log", "log",
    "engine", "engine", "engine", "engine",
    "wire", None, "wire",
    "log", "sm", "sm",
    "wire", "wire",
    "stream", "stream", "stream", "stream", "stream", "stream",
    "log", "edge",
)
_STAGE_LAYER = tuple(-1 if n is None else LAYER_NAMES.index(n)
                     for n in STAGE_LAYERS)

# Stages whose durations tile the per-request path (no mutual overlap):
# these are the ones the decomposition's coverage fraction sums.
TILING_STAGES = (STAGE_ENCODE, STAGE_DECODE, STAGE_ROUTE, STAGE_TXN,
                 STAGE_APPEND, STAGE_REPLICATE, STAGE_APPLY, STAGE_REPLY,
                 STAGE_RESPOND)


class SpanRing:
    """Fixed-size span ring for ONE stage.

    Records are rows of a pre-allocated ``[capacity, 5]`` int64 array
    (trace_id, t0_ns, dur_ns, tag, origin_thread) — recording is one row
    assignment, no allocation, and wraparound overwrites the oldest record.
    With loop sharding (raft.tpu.server.loop-shards) stages record from
    several event-loop threads into the same ring, so the row slot is
    claimed under a lock and each span carries its origin thread id (the
    Chrome export maps it to a per-shard track)."""

    COLS = 5

    __slots__ = ("capacity", "_buf", "_n", "_lock")

    def __init__(self, capacity: int):
        self.capacity = max(1, int(capacity))
        self._buf = np.zeros((self.capacity, self.COLS), np.int64)
        self._n = 0
        self._lock = threading.Lock()

    def record(self, trace_id: int, t0_ns: int, t1_ns: int,
               tag: int = 0, origin: int = 0) -> None:
        with self._lock:
            row = self._buf[self._n % self.capacity]
            self._n += 1
        row[0] = trace_id
        row[1] = t0_ns
        row[2] = t1_ns - t0_ns
        row[3] = tag
        row[4] = origin

    @property
    def count(self) -> int:
        """Records currently held (<= capacity)."""
        return min(self._n, self.capacity)

    @property
    def recorded(self) -> int:
        """Records ever written (wraparound keeps only the last capacity)."""
        return self._n

    @property
    def dropped(self) -> int:
        return max(0, self._n - self.capacity)

    def rows(self) -> np.ndarray:
        """Held records, oldest first, as an [n, 5] array copy."""
        if self._n <= self.capacity:
            return self._buf[:self._n].copy()
        i = self._n % self.capacity
        return np.concatenate([self._buf[i:], self._buf[:i]])

    def clear(self) -> None:
        self._n = 0


class Count:
    """One always-on integer counter (:meth:`Tracer.counter`): sites add to
    ``n`` directly, the tracer snapshots it at a session's two ends."""

    __slots__ = ("n",)

    def __init__(self):
        self.n = 0


class Tiles:
    """An envelope work span and the parts that tile it, each begun where
    the one before ends; every occurrence has ring rows (make one only
    while ``tracer.enabled``).  ``close`` belongs in a ``finally``: a body
    that raises then leaves no span open."""

    __slots__ = ("_tracer", "_envelope", "_part")

    def __init__(self, tracer: "Tracer", stage: int):
        self._tracer = tracer
        self._envelope = tracer.begin(stage, always=True)
        self._part = None

    def part(self, stage: int) -> None:
        if self._part is not None:
            self._tracer.end(self._part)
        self._part = self._tracer.begin(stage, always=True)

    def close(self, tag: int = 0) -> None:
        if self._part is not None:
            self._tracer.end(self._part)
            self._part = None
        self._tracer.end(self._envelope, tag)


def _no_session() -> dict:
    return {"t_on": 0, "t_off": 0, "counters": {}, "keyed": {}}


class Tracer:
    """Process-wide span recorder.  With no session open (the default) it
    costs one attribute check per instrumentation site; in a session each
    Nth request (``sample_every``) gets a trace id and its stages record
    spans, and process-level stages sample every Nth of their own."""

    DEFAULT_RING_SIZE = 4096

    def __init__(self):
        self.enabled = False      # a session is open
        self.annotate = False     # ... and the profiler is: W spans annotate
        self.configured = False   # raft.tpu.trace.enabled holds it open
        self.sample_every = 1
        self.ring_size = self.DEFAULT_RING_SIZE
        self._rings: list[SpanRing] = [SpanRing(1) for _ in range(NUM_STAGES)]
        self._ids = itertools.count(1)
        self._req_tick = 0
        self._ticks = [-1] * NUM_STAGES   # per-stage sampling strides
        # trace_id -> server-handler-done ns (mark_egress/pop_egress): lets
        # the TRANSPORT close the respond span across the task boundary the
        # handler's return crosses (a ContextVar cannot flow back out of
        # the handler task — task creation copies the context one way).
        self._egress: dict[int, int] = {}
        self._counters: dict[tuple[str, str], Count] = {}
        self._annotation = None   # jax.profiler.TraceAnnotation, once polled
        self._profile_refs = 0    # servers holding profile-dir's session
        self._session = _no_session()
        self._counters_on: dict[tuple[str, str], int] = {}
        self._lock = threading.Lock()

    # -- configuration -------------------------------------------------------

    def configure(self, enabled: bool = True, sample_every: int = 1,
                  ring_size: int = DEFAULT_RING_SIZE) -> None:
        """(Re)configure; ``enabled`` opens a session that stays open until
        a later ``configure(enabled=False)`` (existing records drop)."""
        with self._lock:
            if self.enabled:
                self._close()
            self.sample_every = max(1, int(sample_every))
            self.ring_size = max(1, int(ring_size))
            self.configured = bool(enabled)
            self._rings = [SpanRing(1) for _ in range(NUM_STAGES)]
            if self.configured:
                self._open()

    def reset(self) -> None:
        """Drop recorded spans (and, with no session open, what is kept of
        the last one); keep configuration."""
        for ring in self._rings:
            ring.clear()
        self._egress.clear()
        if not self.enabled:
            self._session = _no_session()

    # -- sessions ------------------------------------------------------------

    def poll(self) -> None:
        """Follow the profiler: open a session when a ``jax.profiler``
        session has started in this process, close it when that has stopped
        (unless the configuration holds it open).  Called from the engine's
        tick loop, never per request."""
        ann = self._annotation
        if ann is None:
            from jax.profiler import TraceAnnotation as ann
            self._annotation = ann
        on = ann.is_enabled()
        if on == self.annotate:
            return
        with self._lock:
            if on == self.annotate:
                return
            if on:
                if not self.enabled:
                    self._open()
                self.annotate = True
                # the ring rows' clock on the profiler's
                with ann("ratis:clock", monotonic_ns=time.monotonic_ns()):
                    pass
            else:
                self.annotate = False
                if not self.configured:
                    self._close()

    def _open(self) -> None:
        cap = self.ring_size
        self._rings = [
            SpanRing(cap * (WORK_RING_FACTOR if kind == "W" and
                            stage != STAGE_ROUTE else 1))
            for stage, kind in enumerate(STAGE_KINDS)]
        self._req_tick = 0
        self._ticks = [-1] * NUM_STAGES
        self._egress = {}
        self._counters_on = {k: c.n for k, c in self._counters.items()}
        self._session = dict(_no_session(), t_on=time.monotonic_ns())
        self.enabled = True
        clock = _hooked_clock(_get_running_loop())
        if clock is not None:
            # opened on a loop that is timed by layer: from now on, not from
            # its next selector wait
            clock.start(self._session["t_on"])

    def _close(self) -> None:
        self.enabled = False
        self.annotate = False
        clock = _hooked_clock(_get_running_loop())
        if clock is not None and clock.active:
            # what this loop has run since its last mark is the session's
            clock.flush()
        self._session.update(self._deltas(), t_off=time.monotonic_ns())

    def profile_dir_session(self, directory: str, start: bool) -> None:
        """``raft.tpu.engine.profile-dir``: a ``jax.profiler`` session into
        ``directory`` from server start to server stop, which (see
        :meth:`poll`) is a trace session of the program too.  The profiler
        is a process singleton, so co-hosted servers share the first one's
        session and the last to stop closes it."""
        import jax
        with self._lock:
            self._profile_refs += 1 if start else -1
            first, last = ((start and self._profile_refs == 1),
                           (not start and self._profile_refs == 0))
        if first:
            # a server asked to profile that cannot must not start untraced
            jax.profiler.start_trace(directory)
        elif last:
            jax.profiler.stop_trace()
        self.poll()

    def _deltas(self) -> dict:
        total: dict[str, int] = {}
        keyed: dict[str, dict[str, int]] = {}
        for (name, key), c in list(self._counters.items()):
            d = c.n - self._counters_on.get((name, key), 0)
            total[name] = total.get(name, 0) + d
            keyed.setdefault(name, {})[key] = d
        return {"counters": total, "keyed": keyed}

    def session(self) -> dict:
        """The last session: ``t_on`` / ``t_off`` (``monotonic_ns``; 0 = not
        yet) and the counters' deltas between them — ``counters`` summed by
        name, ``keyed`` by name and key (a loop, a log worker).  Readable
        after the close; an open session reads its deltas up to now."""
        if self.enabled:
            return dict(self._session, **self._deltas())
        return dict(self._session)

    def counter(self, name: str, key: str = "") -> Count:
        """The always-on counter ``name`` of ``key`` (one per loop, per log
        worker, ...), made on first use.  Sites keep the object and add to
        its ``n``; nothing else is registered anywhere."""
        c = self._counters.get((name, key))
        if c is None:
            with self._lock:
                c = self._counters.setdefault((name, key), Count())
        return c

    # -- hot path ------------------------------------------------------------

    @staticmethod
    def now() -> int:
        return time.monotonic_ns()

    def begin_trace(self) -> int:
        """Mint a trace id for a new client request, or 0 when this request
        is not sampled (callers skip every record for id 0)."""
        if not self.enabled:
            return 0
        self._req_tick += 1
        if self._req_tick % self.sample_every:
            return 0
        return next(self._ids)

    def ingress(self, request) -> int:
        """A client request has reached a server: one that came untraced
        (the client's process has no session) is traced from here, under the
        same ``sample-every`` rule.  Returns the request's trace id."""
        tid = request.trace_id
        if not tid and self.enabled:
            tid = self.begin_trace()
            if tid:
                object.__setattr__(request, "trace_id", tid)  # frozen
        return tid

    def sample(self, stage: int) -> bool:
        """Sampling decision for PROCESS-level stages (codec on server
        RPCs, sweeps, log batches) that have no request trace id: every
        ``sample_every``-th occurrence of the stage, the first included."""
        if not self.enabled:
            return False
        self._ticks[stage] = n = self._ticks[stage] + 1
        return n % self.sample_every == 0

    def record(self, trace_id: int, stage: int, t0_ns: int, t1_ns: int,
               tag: int = 0) -> None:
        if not self.enabled:
            return
        self._rings[stage].record(trace_id, t0_ns, t1_ns, tag,
                                  origin=threading.get_ident())

    def interval(self, stage: int, t0_ns: int, tag: int = 0) -> None:
        """Close a process-level interval of ``stage`` that began at
        ``t0_ns`` (0: no session was open then), under the stage's own
        sampling."""
        if t0_ns and self.sample(stage):
            self._rings[stage].record(0, t0_ns, time.monotonic_ns(), tag,
                                      origin=threading.get_ident())

    def begin(self, stage: int, trace_id: int = -1, always: bool = False):
        """Open a work span (call only while ``enabled``).  ``trace_id`` -1
        is a process-level span, sampled by the stage's own stride unless
        ``always``; >= 0 a request's (0: not sampled, so no ring row).
        While the profiler is on every span is annotated, sampled or not;
        on a timed loop (:class:`LoopClock`) every span charges the loop's
        time to the stage's layer until it ends.  Returns what :meth:`end`
        takes, or None when there is nothing to record."""
        if trace_id < 0:
            row = always or self.sample(stage)
            trace_id = 0
        else:
            row = trace_id > 0
        clock = None
        layer = _STAGE_LAYER[stage]
        if layer >= 0:
            clock = loop_clock()
            if clock is not None:
                clock.push(layer)
        ann = None
        if self.annotate:
            ann = self._annotation(_ANNOTATION_NAMES[stage])
            ann.__enter__()
        elif not row and clock is None:
            return None
        # (an annotation without a ring row needs no timestamp of ours)
        return (stage, trace_id, row, ann, time.monotonic_ns() if row else 0,
                clock)

    def end(self, span, tag: int = 0, t0_ns: int = 0) -> int:
        """Close a work span; ``t0_ns`` moves the ring row's start (a route
        span starts at the transport's ingress stamp).  Returns the end of
        a span that has a ring row, else 0."""
        stage, trace_id, row, ann, t0, clock = span
        if ann is not None:
            ann.__exit__(None, None, None)
        if clock is not None:
            clock.pop()
        if not row:
            return 0
        t1 = time.monotonic_ns()
        if self.enabled:
            self._rings[stage].record(trace_id, t0_ns or t0, t1, tag,
                                      origin=threading.get_ident())
        return t1

    @types.coroutine
    def head(self, stage: int, awaitable, tag: int = 0):
        """``await awaitable`` under a work span that covers its
        synchronous head alone: from here to where the awaitable first
        suspends (or ends, if it never does).  What it awaits after that is
        other callbacks' time and lies outside the span.  Call only while
        ``enabled``; sampled as any process-level span."""
        it = awaitable.__await__()
        span = self.begin(stage)
        if span is None:
            return (yield from it)
        try:
            try:
                waits_for = next(it)
            finally:
                self.end(span, tag)
        except StopIteration as e:
            return e.value
        # the rest of the delegation, as ``yield from`` would do it
        while True:
            try:
                sent = yield waits_for
            except GeneratorExit:
                it.close()
                raise
            except BaseException as e:
                try:
                    waits_for = it.throw(e)
                except StopIteration as s:
                    return s.value
            else:
                try:
                    waits_for = it.send(sent)
                except StopIteration as s:
                    return s.value

    def dispatch(self, layer: int) -> None:
        """The server's dispatch has told what a request is: the running
        task's steps from here on, and this loop's time until it suspends,
        belong to ``layer`` (LAYER_*).  Call only while ``enabled``."""
        LOOP_LAYER.set((layer, id(_current_task())))
        clock = loop_clock()
        if clock is not None:
            clock.switch(layer)

    def enter_layer(self, layer: int):
        """The running task's time from here to :meth:`leave_layer`, up to
        its first suspension, belongs to ``layer``: for a call a task awaits
        (a state machine's apply or query), which no work span may hold.
        Returns what :meth:`leave_layer` takes, None where the running loop
        is not timed.  Call only while ``enabled``."""
        clock = loop_clock()
        if clock is None:
            return None
        entered = (clock, clock.cur)
        clock.switch(layer)
        return entered

    def leave_layer(self, entered) -> None:
        """Give the task's time back to the layer it had at
        :meth:`enter_layer` (unless the session closed meanwhile)."""
        clock, layer = entered
        if loop_clock() is clock:
            clock.switch(layer)

    def mark_egress(self, trace_id: int) -> None:
        """Server handler is done with this request NOW; the transport pops
        the mark to record the respond span (serialize + hand-back/socket
        write).  Bounded: a transport path that never pops (e.g. a direct
        division submit) must not leak entries forever."""
        if not self.enabled or not trace_id:
            return
        if len(self._egress) > 8192:
            self._egress.clear()
        self._egress[trace_id] = time.monotonic_ns()

    def pop_egress(self, trace_id: int) -> int:
        if not self._egress:
            return 0
        return self._egress.pop(trace_id, 0)

    # -- aggregation ---------------------------------------------------------

    def rows(self, stage: int) -> np.ndarray:
        """The stage's held rows ``[n, 5]`` (trace_id, t0_ns, dur_ns, tag,
        origin_thread), oldest first."""
        return self._rings[stage].rows()

    def snapshot(self) -> list[tuple[int, int, int, int, int, int]]:
        """Every held record as
        (trace_id, stage, t0_ns, dur_ns, tag, origin_thread)."""
        out: list[tuple[int, int, int, int, int, int]] = []
        for stage, ring in enumerate(self._rings):
            for tid, t0, dur, tag, origin in ring.rows().tolist():
                out.append((tid, stage, t0, dur, tag, origin))
        return out

    def stage_dropped(self) -> dict[str, int]:
        return {STAGE_NAMES[i]: r.dropped
                for i, r in enumerate(self._rings) if r.dropped}


_ANNOTATION_NAMES = tuple("ratis:" + n for n in STAGE_NAMES)

TRACER = Tracer()

_monotonic_ns = time.monotonic_ns
_popleft = collections.deque.popleft
_TASK_TYPES = frozenset((_Task, _PyTask))


profile_dir_session = TRACER.profile_dir_session


def get_tracer() -> Tracer:
    return TRACER


def configure_from_properties(p) -> None:
    """Take the sampling and ring size from the properties, and open a
    session when ``raft.tpu.trace.enabled`` is set.  Never closes one:
    co-hosted servers share ONE tracer, and a second server built without
    the key must not silence the first's tracing."""
    if p is None:
        return
    from ratis_tpu.conf.keys import RaftServerConfigKeys
    K = RaftServerConfigKeys.Trace
    if TRACER.enabled:
        return
    if K.enabled(p):
        TRACER.configure(enabled=True, sample_every=K.sample_every(p),
                         ring_size=K.ring_size(p))
    else:
        # a profiler-opened session (Tracer.poll) follows the same keys
        TRACER.sample_every = max(1, K.sample_every(p))
        TRACER.ring_size = max(1, K.ring_size(p))


# ------------------------------------------------------- the loop's occupancy

def instrument_loop(loop) -> bool:
    """Count the time ``loop`` spends in its selector: ``loop.select_ns`` and
    ``loop.iterations``, keyed by the loop, always on (two clock reads and
    two adds per loop iteration).  Installed once per loop, where the loop's
    owner starts on it (``RaftServer.start``, a shard's thread); a selector
    wait that may block is also the ``loop.select`` work span.  What is not
    selector time is the loop running callbacks: its busy share.  While a
    session is open the loop is also timed by layer (:class:`LoopClock`):
    the hook swaps the loop's ready queue for a timed one at the first
    select of a session and a plain one back at the first after it.  A loop
    without a ``_selector`` (not a selector event loop) is left alone."""
    selector = getattr(loop, "_selector", None)
    inner = getattr(selector, "select", None)
    if inner is None or getattr(inner, "ratis_timed", False):
        return False
    key = loop_key(loop)
    select_ns = TRACER.counter("loop.select_ns", key)
    iterations = TRACER.counter("loop.iterations", key)
    clock = time.monotonic_ns
    timed = LoopClock(loop, key, inner, select_ns, iterations)

    def select(timeout=None):
        t0 = clock()
        if TRACER.enabled or timed.active:
            return timed.select(timeout, t0)
        events = inner(timeout)
        select_ns.n += clock() - t0
        iterations.n += 1
        return events

    select.ratis_timed = True
    select.ratis_clock = timed
    try:
        selector.select = select
    except AttributeError:
        return False
    return True


def loop_key(loop=None) -> str:
    """The counters' key of ``loop`` (default: the running loop; "" off a
    loop)."""
    if loop is None:
        import asyncio
        try:
            loop = asyncio.get_running_loop()
        except RuntimeError:
            return ""
    return f"loop-{id(loop):x}"


# ---------------------------------------------------- the loop's time by layer

# The layer the server's dispatch named for a task (Tracer.dispatch), with
# the id of the task it was named in: a task the handler starts inherits the
# context but not the name.  (An id, not the task: the task's own context
# holding the task would make every request's task cyclic garbage.)
LOOP_LAYER: contextvars.ContextVar = contextvars.ContextVar(
    "ratis_loop_layer", default=None)


def _hooked_clock(loop) -> "LoopClock | None":
    """``loop``'s layer clock where :func:`instrument_loop` hooked it."""
    select = getattr(getattr(loop, "_selector", None), "select", None)
    return getattr(select, "ratis_clock", None)


def loop_clock() -> "LoopClock | None":
    """The running loop's layer clock while its pass is timed, else None."""
    ready = getattr(_get_running_loop(), "_ready", None)
    return ready.clock if type(ready) is _TimedReady else None


class _TimedReady(collections.deque):
    """A loop's ready queue while a session is open: handing out a callback
    charges the time since the clock's last mark to its current layer and
    makes the callback's owner current.  asyncio's ``_run_once`` takes every
    callback it runs, selector callbacks and timers included, with
    ``popleft``."""

    __slots__ = ("clock",)

    def popleft(self):
        handle = _popleft(self)
        # the owner: _owner, with the cached cases inline
        cb = handle._callback
        obj = getattr(cb, "__self__", None)
        if obj.__class__ in _TASK_TYPES:
            named = handle._context.get(LOOP_LAYER)
            if named is not None and named[1] == id(obj):
                owner = named[0]
            else:
                try:
                    owner = _BY_FILE[obj.get_coro().cr_code.co_filename]
                except (AttributeError, KeyError):
                    owner = _owner(cb, obj)
        elif obj is None:
            try:
                owner = _BY_MODULE[cb.__module__]
            except (AttributeError, KeyError):
                owner = _owner(cb, obj)
        else:
            owner = _BY_TYPE.get(obj.__class__, -1)
            if owner < 0:
                owner = _owner(cb, obj)
        c = self.clock
        if owner != c.cur:
            # (the clock is read only where the layer changes)
            t = _monotonic_ns()
            c.ns[c.cur].n += t - c.mark
            c.mark = t
            c.cur = owner
        return handle


class LoopClock:
    """Charges one loop's busy time to the layer that owns it, while a
    session is open: ``loop.layer_ns``, keyed ``<loop key>/<layer>``.  Each
    callback is charged to its owner (:func:`_owner`) from the ready queue
    handing it out to the next hand-out (the clock is read where the layer
    changes); a work span on the loop's thread charges its stage's layer for
    its length (:meth:`Tracer.begin`); the server's dispatch names a
    request's layer (:meth:`Tracer.dispatch`), and a state machine's call
    its own (:meth:`Tracer.enter_layer`); what follows a selector wait up to
    the first callback (asyncio's own pass) is ``other``.  Over a session,
    Σ ``loop.layer_ns`` + ``loop.select_ns`` is its length.  Only the loop's
    own thread touches it."""

    __slots__ = ("loop", "inner", "select_ns", "iterations", "ns", "cur",
                 "mark", "stack", "active", "_stale")

    def __init__(self, loop, key: str, inner, select_ns: Count,
                 iterations: Count):
        self.loop = loop
        self.inner = inner          # the selector's own select
        self.select_ns, self.iterations = select_ns, iterations
        self.ns = [TRACER.counter("loop.layer_ns", f"{key}/{n}")
                   for n in LAYER_NAMES]
        self.cur = LAYER_OTHER
        self.mark = 0
        self.stack: list[int] = []
        self.active = False     # timed, or a swapped-out queue to drain
        self._stale = None

    def select(self, timeout, t0: int):
        """The selector hook's path while a session is open or was."""
        inner = self.inner
        stale, self._stale = self._stale, None
        if stale:
            _drain(stale, self.loop._ready)
        if TRACER.enabled:
            if type(self.loop._ready) is _TimedReady:
                # the rest of the pass: the last callback's
                self.ns[self.cur].n += t0 - self.mark
                self.stack.clear()  # (no work span outlives its callback)
            else:
                self.start(t0)
            if timeout != 0:
                span = TRACER.begin(STAGE_SELECT)
                try:
                    events = inner(timeout)
                finally:
                    if span is not None:
                        TRACER.end(span)
            else:
                events = inner(timeout)
        else:
            if type(self.loop._ready) is _TimedReady:
                self._remove()
            else:
                self.active = False
            events = inner(timeout)
        t1 = _monotonic_ns()
        self.select_ns.n += t1 - t0
        self.iterations.n += 1
        self.mark = t1
        self.cur = LAYER_OTHER
        return events

    def start(self, t: int) -> None:
        """Time the loop from ``t``: its ready queue becomes a timed one
        (here, or mid-pass: ``_run_once`` takes each callback from
        ``loop._ready`` anew)."""
        old = self.loop._ready
        ready = _TimedReady()
        ready.clock = self
        if self._stale:     # (a queue swapped out at the last close)
            _drain(self._stale, ready)
        _drain(old, ready)
        self.loop._ready = ready
        self._stale = old
        self.stack.clear()
        self.active = True
        self.mark = t
        self.cur = LAYER_OTHER

    def _remove(self) -> None:
        old = self.loop._ready
        ready = collections.deque()
        _drain(old, ready)
        self.loop._ready = ready
        self._stale = old       # drained at the next pass, then inactive
        self.stack.clear()

    def flush(self) -> None:
        """Charge everything up to now (the session closes on this loop)."""
        if type(self.loop._ready) is _TimedReady:
            self.switch(self.cur)

    def switch(self, layer: int) -> None:
        """Charge up to now and make ``layer`` current."""
        t = _monotonic_ns()
        self.ns[self.cur].n += t - self.mark
        self.mark = t
        self.cur = layer

    def push(self, layer: int) -> None:
        """A work span of ``layer`` begins (the clock is read only where
        that changes the layer)."""
        cur = self.cur
        self.stack.append(cur)
        if layer != cur:
            t = _monotonic_ns()
            self.ns[cur].n += t - self.mark
            self.mark = t
            self.cur = layer

    def pop(self) -> None:
        """The innermost work span ends."""
        if not self.stack:
            return
        layer = self.stack.pop()
        cur = self.cur
        if layer != cur:
            t = _monotonic_ns()
            self.ns[cur].n += t - self.mark
            self.mark = t
            self.cur = layer


def _drain(src, dst) -> None:
    """Move what ``src`` holds to ``dst``'s end, in order (one at a time: a
    thread may still append to ``src``)."""
    while src:
        dst.append(_popleft(src))


_BY_MODULE: dict = {}   # module name -> layer
_BY_FILE: dict = {}     # a coroutine's source file -> layer
_BY_TYPE: dict = {}     # a bound method's class -> layer (-1: a transport)
_PACKAGE_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_MODULE_LAYERS = sorted(((m, LAYER_NAMES.index(n)) for m, n in MODULE_LAYERS),
                        key=lambda ml: -len(ml[0]))


def module_layer(name) -> int:
    """The layer of the module called ``name`` (MODULE_LAYERS)."""
    layer = _BY_MODULE.get(name)
    if layer is None:
        layer = LAYER_OTHER
        if isinstance(name, str):
            for prefix, lay in _MODULE_LAYERS:
                if name == prefix or name.startswith(prefix + "."):
                    layer = lay
                    break
        _BY_MODULE[name] = layer
    return layer


def _file_layer(path) -> int:
    """The layer of the module in source file ``path``."""
    layer = _BY_FILE.get(path)
    if layer is None:
        name = None
        if isinstance(path, str) and path.endswith(".py") and \
                path.startswith(_PACKAGE_DIR + os.sep):
            rel = path[len(_PACKAGE_DIR) + 1:-3].replace(os.sep, ".")
            name = "ratis_tpu." + rel.removesuffix(".__init__")
        layer = _BY_FILE[path] = module_layer(name)
    return layer


def _type_layer(cls) -> int:
    layer = _BY_TYPE.get(cls)
    if layer is None:
        module = getattr(cls, "__module__", None)
        layer = module_layer(module)
        if layer == LAYER_OTHER and isinstance(module, str) and \
                module.startswith("asyncio.") and \
                hasattr(cls, "get_protocol"):
            layer = -1
        _BY_TYPE[cls] = layer
    return layer


def _owner(cb, obj) -> int:
    """The layer of the callback ``cb``, bound to ``obj`` (or None): a
    task's step or wake-up is its task's (the dispatch's name, which
    ``_TimedReady.popleft`` reads, else its coroutine's module); a selector
    callback of an asyncio transport its protocol's; a bound method its
    class's module's; a function its module's."""
    if obj is None:
        return module_layer(getattr(cb, "__module__", None))
    if type(obj) in _TASK_TYPES:
        coro = obj.get_coro()
        code = getattr(coro, "cr_code", None) or getattr(coro, "gi_code", None)
        return _file_layer(getattr(code, "co_filename", None))
    layer = _type_layer(type(obj))
    if layer < 0:
        layer = _type_layer(type(getattr(obj, "_protocol", None)))
        return LAYER_OTHER if layer < 0 else layer
    return layer
