"""TCP transport: raw-socket envelope RPC (the Netty-analog backend).

Capability parity with the reference Netty transport
(ratis-netty/src/main/java/org/apache/ratis/netty/server/NettyRpcService.java
+ NettyRpcProxy + Netty.proto:31-48): a single length-prefixed
request/reply envelope union over all RPCs — server-to-server consensus
traffic and client requests share one listening port, exactly like the
reference's RaftNettyServerRequestProto union.  Connections are cached per
destination and multiplex concurrent calls by a request sequence number.

Frame: u32 length | u64 call_seq | u8 kind | msgpack body.
kind: 1=server-rpc 2=client-request 3=reply 4=error-reply.

A frame costs callbacks, not tasks: both ends of a connection are one
``asyncio.Protocol`` (:class:`_FramedProtocol`; in Netty's terms the frame
decoder and the flush on read-complete).  ``data_received`` parses every
whole frame it holds in that one callback: a reply resolves its call's
future there, a request's handler starts there (an eagerly started task, so
one that never suspends never becomes a scheduled task).  ``send`` is a
synchronous enqueue, and what a loop pass queued leaves in ONE
``transport.write`` at the end of the pass (frames are length-prefixed: the
joined bytes are the frames' bytes in order).  Only while the transport is
paused does a sender wait, on one future per connection.

Failure contract: a write error or a lost connection fails every pending
call and POISONS the connection — a batch may be half-written, so later
sends fail fast and the pool dials anew; the error never escapes into the
event loop.  Frames still queued go out on ``close()`` and at the peer's EOF.
"""

from __future__ import annotations

import asyncio
import collections
import itertools
import logging
import struct
import threading
from typing import Callable, Dict, Optional

from ratis_tpu.metrics.hops import hop
from ratis_tpu.protocol.exceptions import (RaftException, TimeoutIOException,
                                           exception_from_wire,
                                           exception_to_wire)
from ratis_tpu.protocol.ids import RaftPeerId
from ratis_tpu.protocol.raftrpc import decode_rpc, encode_rpc
from ratis_tpu.protocol.requests import (DEFERRED_REPLY, RaftClientReply,
                                         RaftClientRequest,
                                         attach_reply_sink)
from ratis_tpu.trace.tracer import (INGRESS_NS, STAGE_DECODE, STAGE_ENCODE,
                                    STAGE_RESPOND, STAGE_TCP_READ, STAGE_WIRE,
                                    STAGE_WIRE_FLUSH, TRACER, loop_clock,
                                    loop_key)
from ratis_tpu.transport.base import (ClientRequestHandler, ClientTransport,
                                      ServerRpcHandler, ServerTransport,
                                      TransportFactory)

LOG = logging.getLogger(__name__)

KIND_SERVER_RPC = 1
KIND_CLIENT_REQUEST = 2
KIND_REPLY = 3
KIND_ERROR = 4

_FRAME = struct.Struct(">IQB")
_HEADER = _FRAME.size           # 13: the length prefix and what it counts first
MAX_FRAME = 256 << 20


def _encode_frame(call_seq: int, kind: int, body: bytes) -> bytes:
    return _FRAME.pack(9 + len(body), call_seq, kind) + body


class _FramedProtocol(asyncio.Protocol):
    """One TCP connection's framing, the same for both ends; a subclass says
    what a frame is to it (:meth:`_frame`) and what dies with the connection
    (:meth:`_lost`).  Everything here runs on the connection's loop."""

    def __init__(self, label: str) -> None:
        self.label = label
        self.loop: Optional[asyncio.AbstractEventLoop] = None  # when made
        self._transport: Optional[asyncio.Transport] = None
        self._rbuf = bytearray()        # the bytes of a frame not yet whole
        self._out: list[bytes] = []     # frames queued in this loop pass
        self.flush_armed = False
        # set while the transport holds more than its high-water mark
        self._writable: Optional[asyncio.Future] = None
        self._closed: Optional[asyncio.Future] = None
        self.dead: Optional[Exception] = None

    # -- the transport's callbacks -----------------------------------------

    def connection_made(self, transport) -> None:
        self.loop = asyncio.get_running_loop()
        self._transport = transport
        self._closed = self.loop.create_future()
        # frames and bytes are the process's wire counters (ratis_tpu.trace:
        # always on, a trace session snapshots them), one pair per loop so
        # that every add comes from one thread
        key = loop_key(self.loop)
        self._n_frames = TRACER.counter("wire.frames", key)
        self._n_bytes = TRACER.counter("wire.bytes", key)

    def data_received(self, data) -> None:
        # tcp.read work span: one read wake-up of the connection, from its
        # first frame parsed to the last one handed on (tag = frames)
        span = TRACER.begin(STAGE_TCP_READ) if TRACER.enabled else None
        frames = 0
        try:
            buf = self._rbuf
            if buf:             # a frame straddles reads: parse the joined bytes
                buf += data
                data = buf
            pos, end = 0, len(data)
            while end - pos >= _HEADER:
                length, call_seq, kind = _FRAME.unpack_from(data, pos)
                if length < 9 or length > MAX_FRAME:
                    self._abort(ConnectionError(
                        f"{self.label}: bad frame length {length}"))
                    return
                nxt = pos + 4 + length
                if nxt > end:
                    break
                body = data[pos + _HEADER:nxt]
                pos = nxt
                frames += 1
                self._frame(call_seq, kind,
                            bytes(body) if data is buf else body)
            if data is buf:
                del buf[:pos]
            elif pos < end:
                buf += data[pos:]
        finally:
            if span is not None:
                TRACER.end(span, tag=frames)

    def eof_received(self):
        if self._out:
            self._flush()       # queued replies reach a half-closed peer
        return False            # and the transport closes

    def pause_writing(self) -> None:
        if self._writable is None:
            self._writable = self.loop.create_future()

    def resume_writing(self) -> None:
        w, self._writable = self._writable, None
        if w is not None and not w.done():
            w.set_result(None)

    def connection_lost(self, exc) -> None:
        self._fail(ConnectionError(f"{self.label} lost: {exc}" if exc
                                   else f"{self.label} closed"))
        if not self._closed.done():
            self._closed.set_result(None)

    # -- what a subclass is ------------------------------------------------

    def _frame(self, call_seq: int, kind: int, body: bytes) -> None:
        raise NotImplementedError

    def _lost(self, exc: Exception) -> None:
        """The connection is unusable: fail or cancel what waits on it."""

    # -- write -------------------------------------------------------------

    def send(self, frame: bytes) -> None:
        """Queue ``frame`` for this loop pass's one write, in call order;
        raises what killed the connection.  A sender then honours the
        transport's flow control: ``if conn.paused: await
        conn.wait_writable()`` (write, then drain)."""
        if self.dead is not None:
            raise self.dead
        self._out.append(frame)
        if not self.flush_armed:
            self.flush_armed = True
            self.loop.call_soon(self._flush)

    def _flush(self) -> None:
        self.flush_armed = False
        frames = self._out
        if not frames:
            return
        self._out = []
        # wire.flush work span: the one buffered write of this pass, which
        # is the socket's send while the transport's buffer is empty
        span = TRACER.begin(STAGE_WIRE_FLUSH) if TRACER.enabled else None
        try:
            data = frames[0] if len(frames) == 1 else b"".join(frames)
            self._transport.write(data)
        except Exception as e:
            # some of the batch may be on the wire: poison, never raise into
            # the loop
            err = ConnectionError(f"{self.label} write failed: {e!r}")
            err.__cause__ = e
            self._abort(err)
            return
        finally:
            if span is not None:
                TRACER.end(span, tag=len(frames))
        self._n_frames.n += len(frames)
        self._n_bytes.n += len(data)

    @property
    def paused(self) -> bool:
        return self._writable is not None

    async def wait_writable(self) -> None:
        """Wait out the transport's flow control, or the connection's end.
        (Shielded: the future is the connection's, one waiter's cancellation
        must not cancel the others'.)"""
        while self._writable is not None:
            await asyncio.shield(self._writable)

    # -- failure and close -------------------------------------------------

    def _fail(self, exc: Exception) -> None:
        if self.dead is None:
            self.dead = exc
        self._out.clear()
        self.resume_writing()   # the waiters find ``dead``
        self._lost(self.dead)

    def _abort(self, exc: Exception) -> None:
        self._fail(exc)
        self._transport.abort()

    def close_nowait(self) -> None:
        """Write what is queued (flush-on-close) and close; the transport
        sends what it has buffered, then ``connection_lost`` follows."""
        if self._transport is None:
            return
        if self._out and self.dead is None:
            self._flush()
        self._fail(ConnectionError(f"{self.label} closed"))
        self._transport.close()

    async def close(self) -> None:
        self.close_nowait()
        if self._closed is not None:
            await self._closed


def _defer_conf(properties) -> bool:
    """Whether client requests get a deferred-reply sink attached (the
    commit fan-out collapse, raft.tpu.replication.sweep/reply-fanout)."""
    if properties is None:
        return False
    from ratis_tpu.conf.keys import RaftServerConfigKeys
    K = RaftServerConfigKeys.Replication
    return K.sweep(properties) and K.reply_fanout(properties)


class _DeferredReplyFanout:
    """Per-connection deferred replies: the division's waterline fan-out
    calls :meth:`submit` synchronously at commit.  On the connection's own
    loop (one loop: every deployment without loop shards) the reply frame
    joins the connection's write of this pass at once.  From another loop
    (``raft.tpu.server.loop-shards`` > 1) replies queue here and ONE
    ``call_soon_threadsafe`` per burst carries them over.  Either way a
    burst costs one scheduled callback per connection, replacing the
    per-request handler-resume + send-wait chain the traced decomposition
    measured as ``server.reply`` / ``server.respond``."""

    __slots__ = ("_conn", "_q", "_lock", "_armed")

    def __init__(self, conn: "_Accepted") -> None:
        self._conn = conn               # made: its loop is the connection's
        self._q = collections.deque()
        self._lock = threading.Lock()
        self._armed = False

    def sink_for(self, call_seq: int, trace_id: int = 0):
        def sink(reply: RaftClientReply) -> None:
            self.submit(call_seq, reply, trace_id)
        return sink

    def submit(self, call_seq: int, reply: RaftClientReply,
               trace_id: int = 0) -> None:
        tid = trace_id if TRACER.enabled else 0
        t0 = TRACER.now() if tid else 0
        # encode on the CALLING (division) loop: serialization stays off
        # the connection's loop, which only performs the buffered write
        body = reply.to_bytes()
        frame = _encode_frame(call_seq, KIND_REPLY, body)
        try:
            same_loop = asyncio.get_running_loop() is self._conn.loop
        except RuntimeError:
            same_loop = False
        if same_loop:
            if not self._conn.flush_armed:
                hop("reply_flush")      # this reply arms the pass's write
            self._hand_over(frame, tid, t0, len(body))
            return
        with self._lock:
            self._q.append((frame, tid, t0, len(body)))
            if self._armed:
                return
            self._armed = True
        hop("reply_flush")
        try:
            self._conn.loop.call_soon_threadsafe(self._drain)
        except RuntimeError:
            pass  # connection loop closed: the client sees a torn socket

    def _drain(self) -> None:
        with self._lock:
            items = list(self._q)
            self._q.clear()
            self._armed = False
        for item in items:
            self._hand_over(*item)

    def _hand_over(self, frame: bytes, tid: int, t0: int, nbody: int) -> None:
        """No backpressure: replies are bounded by the connection's requests
        in flight; a dead connection drops the frame (its client retries or
        times out exactly as with a torn socket)."""
        conn = self._conn
        if conn.dead is not None:
            return
        conn.send(frame)
        if tid and t0:
            # respond span (deferred shape): reply ready at the division ->
            # queued on this connection for the pass's one write
            TRACER.record(tid, STAGE_RESPOND, t0, TRACER.now(), tag=nbody)


class TcpTlsConfig:
    """TLS for the raw-TCP transport (NettyConfigKeys.Tls): same parameter
    surface as the gRPC GrpcTlsConfig — cert chain + key server-side,
    optional trust root, optional mutual auth — applied as ssl contexts on
    the loop's create_server / create_connection."""

    def __init__(self, cert_chain_path=None, private_key_path=None,
                 trust_root_path=None, mutual_auth=False):
        self.cert_chain_path = cert_chain_path
        self.private_key_path = private_key_path
        self.trust_root_path = trust_root_path
        self.mutual_auth = mutual_auth

    @staticmethod
    def from_properties(p) -> "TcpTlsConfig | None":
        from ratis_tpu.conf.keys import NettyConfigKeys
        if p is None or not NettyConfigKeys.Tls.enabled(p):
            return None
        cfg = TcpTlsConfig(
            cert_chain_path=NettyConfigKeys.Tls.cert_chain(p),
            private_key_path=NettyConfigKeys.Tls.private_key(p),
            trust_root_path=NettyConfigKeys.Tls.trust_root(p),
            mutual_auth=NettyConfigKeys.Tls.mutual_auth(p))
        if not cfg.trust_root_path:
            # Once per configuration, not per connection: encryption without
            # server authentication is a silent downgrade (MITM-able); the
            # gRPC path refuses to run without explicit cert material.
            LOG.warning(
                "TLS enabled WITHOUT a trust root (*.tls.trust.root.path "
                "unset): connections are encrypted but the server is NOT "
                "authenticated — configure a trust root for production")
        return cfg

    def server_context(self):
        import ssl
        ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_SERVER)
        ctx.load_cert_chain(self.cert_chain_path, self.private_key_path)
        if self.trust_root_path:
            ctx.load_verify_locations(self.trust_root_path)
        if self.mutual_auth:
            ctx.verify_mode = ssl.CERT_REQUIRED
        return ctx

    def client_context(self):
        import ssl
        ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
        # cluster-internal trust root, not the system store; hostname
        # checks are disabled because peers dial each other by raw IP
        ctx.check_hostname = False
        if self.trust_root_path:
            ctx.load_verify_locations(self.trust_root_path)
            ctx.verify_mode = ssl.CERT_REQUIRED
        else:
            # no trust root: encrypted but unauthenticated — warned once at
            # from_properties time
            ctx.verify_mode = ssl.CERT_NONE
        if self.mutual_auth and self.cert_chain_path:
            ctx.load_cert_chain(self.cert_chain_path, self.private_key_path)
        return ctx


class _Connection(_FramedProtocol):
    """One outbound connection multiplexing calls by sequence number
    (reference NettyRpcProxy channel).  A frame read is a reply: it resolves
    its call's future in the read callback.  One deadline timer per
    connection stands over the pending calls (not one per call)."""

    def __init__(self, address: str, tls=None) -> None:
        super().__init__(address)         # the label is the peer's address
        self._tls = tls
        self._seq = itertools.count(1)
        # call_seq -> (the call's future, its deadline on the loop's clock)
        self._pending: Dict[int, tuple[asyncio.Future, float]] = {}
        self._timer: Optional[asyncio.TimerHandle] = None
        self._timer_at = 0.0

    async def connect(self) -> None:
        host, port = self.label.rsplit(":", 1)
        ssl_ctx = self._tls.client_context() if self._tls is not None else None
        await asyncio.get_running_loop().create_connection(
            lambda: self, host, int(port), ssl=ssl_ctx)

    @property
    def alive(self) -> bool:
        return self._transport is not None and self.dead is None

    def _frame(self, call_seq: int, kind: int, body: bytes) -> None:
        entry = self._pending.pop(call_seq, None)
        if entry is not None and not entry[0].done():
            entry[0].set_result((kind, body))

    def _lost(self, exc: Exception) -> None:
        pending, self._pending = self._pending, {}
        for fut, _deadline in pending.values():
            if not fut.done():
                fut.set_exception(exc)
        if self._timer is not None:
            self._timer.cancel()
            self._timer = None

    async def call(self, kind: int, body: bytes,
                   timeout_s: float) -> tuple[int, bytes]:
        if self.dead is not None:
            raise self.dead
        loop = self.loop
        seq = next(self._seq)
        fut = loop.create_future()
        deadline = loop.time() + timeout_s
        self._pending[seq] = (fut, deadline)
        self.send(_encode_frame(seq, kind, body))
        if self._timer is None or deadline < self._timer_at:
            self._arm(deadline)
        try:
            if self._writable is not None:
                await self.wait_writable()
            return await fut
        except asyncio.CancelledError:
            self._pending.pop(seq, None)
            raise

    def _arm(self, deadline: float) -> None:
        if self._timer is not None:
            self._timer.cancel()
        self._timer_at = deadline
        self._timer = self.loop.call_at(deadline, self._on_deadline)

    def _on_deadline(self) -> None:
        """Fail the calls whose deadline has passed and stand over the
        earliest one left (with one timeout for all, the oldest)."""
        self._timer = None
        now = self.loop.time()
        earliest = None
        for seq, (fut, deadline) in list(self._pending.items()):
            if deadline <= now:
                del self._pending[seq]
                if not fut.done():
                    fut.set_exception(TimeoutIOException(
                        f"rpc to {self.label} timed out"))
            elif earliest is None or deadline < earliest:
                earliest = deadline
        if earliest is not None:
            self._arm(earliest)


class _ConnectionPool:
    """(calling loop, address) -> cached connection; reconnects dead ones
    on demand.

    Keyed per loop on purpose: with loop sharding
    (raft.tpu.server.loop-shards) divisions pinned to worker loops send
    through this pool from their own threads, and a connection (its
    transport, futures and timer) is loop-affine — so each shard dials its
    own connection per destination, which also gives each shard an
    independent send pipe instead of one shared serialized writer.
    Single-loop runtimes see exactly one connection per address."""

    def __init__(self, tls=None) -> None:
        self._conns: Dict[tuple[int, str], _Connection] = {}
        self._locks: Dict[tuple[int, str], asyncio.Lock] = {}
        self._tls = tls

    async def get(self, address: str) -> _Connection:
        key = (id(asyncio.get_running_loop()), address)
        conn = self._conns.get(key)
        if conn is not None and conn.alive:
            return conn
        lock = self._locks.setdefault(key, asyncio.Lock())
        async with lock:
            conn = self._conns.get(key)
            if conn is not None and conn.alive:
                return conn
            if conn is not None:
                await conn.close()
            conn = _Connection(address, tls=self._tls)
            await conn.connect()
            self._conns[key] = conn
            return conn

    async def close(self) -> None:
        conns = list(self._conns.values())
        self._conns.clear()
        self._locks.clear()
        try:
            current = asyncio.get_running_loop()
        except RuntimeError:
            current = None
        for conn in conns:
            if conn.loop is None or conn.loop is current:
                await conn.close()
            elif conn.loop.is_running():
                # shard-owned connection: its transport and futures must be
                # unwound on the loop they live on
                try:
                    await asyncio.wrap_future(
                        asyncio.run_coroutine_threadsafe(conn.close(),
                                                         conn.loop))
                except Exception:
                    pass  # connection already broken; socket dies with it
            else:
                # owner loop gone (test teardown): close the raw transport
                # so the fd is released; callbacks on the dead loop never run
                try:
                    conn.close_nowait()
                except RuntimeError:
                    pass  # that loop is closed


class _Accepted(_FramedProtocol):
    """One accepted connection of a :class:`TcpServerTransport`.  A frame
    read is a request: its handler starts at once, inside the read callback
    (an eagerly started task), so a request that never suspends is served in
    the loop pass that read it, and concurrent ones do not head-of-line
    block the connection (gRPC gives that for free)."""

    def __init__(self, server: "TcpServerTransport") -> None:
        super().__init__(f"{server.peer_id} accepted")
        self._server = server
        self._tasks: set[asyncio.Task] = set()
        self.fanout: Optional[_DeferredReplyFanout] = None

    def connection_made(self, transport) -> None:
        super().connection_made(transport)
        self._server._accepted.add(self)
        if self._server.defer_replies:
            self.fanout = _DeferredReplyFanout(self)

    def _frame(self, call_seq: int, kind: int, body: bytes) -> None:
        clock = loop_clock() if TRACER.enabled else None
        layer = clock.cur if clock is not None else 0
        t = asyncio.Task(self._server._serve_one(call_seq, kind, body, self),
                         loop=self.loop, eager_start=True)
        if clock is not None:
            # the handler's first step named its own layer (the server's
            # dispatch); the rest of this read is the wire's again
            clock.switch(layer)
        if not t.done():        # it suspended: keep it (the loop holds weakly)
            self._tasks.add(t)
            t.add_done_callback(self._tasks.discard)

    def _lost(self, exc: Exception) -> None:
        self._server._accepted.discard(self)
        for t in self._tasks:
            t.cancel()


class TcpServerTransport(ServerTransport):
    """Single listening port serving both the consensus union and client
    requests (reference NettyRpcService envelope dispatch)."""

    def __init__(self, peer_id: RaftPeerId, address: str,
                 server_handler: ServerRpcHandler,
                 client_handler: ClientRequestHandler,
                 peer_resolver: Optional[Callable[[RaftPeerId],
                                                  Optional[str]]] = None,
                 request_timeout_s: float = 3.0,
                 tls: "TcpTlsConfig | None" = None,
                 defer_replies: bool = False, chaos: bool = False):
        self.peer_id = peer_id
        self._address = address
        self._bound_port: Optional[int] = None
        self.server_handler = server_handler
        self.client_handler = client_handler
        self.peer_resolver = peer_resolver
        self.request_timeout_s = request_timeout_s
        # chaos link-fault gate (raft.tpu.chaos.enabled): when armed,
        # server RPC sends consult the process-wide link-fault table
        # (ratis_tpu.chaos.link) — partitions/latency/drop on real sockets
        self.chaos = chaos
        self.tls = tls
        # commit fan-out collapse: attach a per-connection deferred-reply
        # sink to client requests (the division decides per request
        # whether to engage it; see _DeferredReplyFanout)
        self.defer_replies = defer_replies
        self._server: Optional[asyncio.AbstractServer] = None
        self._pool = _ConnectionPool(tls=tls)
        self._accepted: set[_Accepted] = set()

    async def start(self) -> None:
        host, port = self._address.rsplit(":", 1)
        ssl_ctx = self.tls.server_context() if self.tls is not None else None
        self._server = await asyncio.get_running_loop().create_server(
            lambda: _Accepted(self), host, int(port), ssl=ssl_ctx)
        self._bound_port = self._server.sockets[0].getsockname()[1]

    async def _serve_one(self, call_seq: int, kind: int, body: bytes,
                         conn: _Accepted) -> None:
        trace_tid = trace_egress = 0
        client_reply = False
        try:
            if kind == KIND_SERVER_RPC:
                reply = await self.server_handler(decode_rpc(body))
                out_kind, out = KIND_REPLY, encode_rpc(reply)
            elif kind == KIND_CLIENT_REQUEST:
                if TRACER.enabled:
                    # codec.decode: a work span of every request while the
                    # profiler is on, a ring row of the sampled ones (known
                    # only once decoded)
                    span = TRACER.begin(STAGE_DECODE, 0)
                    t0 = TRACER.now()
                    try:
                        request = RaftClientRequest.from_bytes(body)
                    finally:
                        if span is not None:
                            TRACER.end(span)
                    # a request that arrives untraced (its client's process
                    # has no session) is traced from here: the id is minted
                    # where the request arrives
                    tid = TRACER.ingress(request)
                    now = TRACER.now()
                    if tid:
                        TRACER.record(tid, STAGE_DECODE, t0, now,
                                      tag=len(body))
                    # the route span starts post-decode; set for an
                    # unsampled request too: the stamp tells the server's
                    # route site that the sampling decision has been made
                    INGRESS_NS.set(now)
                else:
                    request = RaftClientRequest.from_bytes(body)
                if conn.fanout is not None:
                    attach_reply_sink(
                        request, conn.fanout.sink_for(call_seq,
                                                      request.trace_id))
                reply = await self.client_handler(request)
                if reply is DEFERRED_REPLY:
                    # reply rides the per-connection fan-out at commit;
                    # this task is done at append time
                    return
                trace_tid = request.trace_id
                trace_egress = TRACER.pop_egress(trace_tid)
                client_reply = True
                out_kind, out = KIND_REPLY, reply.to_bytes()
            else:
                raise RaftException(f"unexpected frame kind {kind}")
        except asyncio.CancelledError:
            raise
        except Exception as e:
            LOG.warning("%s tcp rpc failed: %s", self.peer_id, e)
            exc = e if isinstance(e, RaftException) else RaftException(str(e))
            import msgpack
            out_kind, out = KIND_ERROR, msgpack.packb(
                exception_to_wire(exc), use_bin_type=True)
        try:
            if client_reply:
                # per-request commit->reply hop #3 (legacy path): the reply
                # is handed over per request — the deferred-reply fan-out
                # hands a burst over in one pass (metrics/hops.py
                # reply_send vs reply_flush)
                hop("reply_send")
            conn.send(_encode_frame(call_seq, out_kind, out))
            if trace_egress:
                # handler done -> reply serialized, framed and queued for
                # the connection's one write of this pass
                TRACER.record(trace_tid, STAGE_RESPOND, trace_egress,
                              TRACER.now(), tag=len(out))
            if conn.paused:
                await conn.wait_writable()
        except (ConnectionError, OSError):
            pass

    async def send_server_rpc(self, to: RaftPeerId, msg) -> object:
        address = self.peer_resolver(to) if self.peer_resolver else None
        if address is None:
            raise RaftException(f"unknown peer {to}")
        faults = None
        if self.chaos:
            from ratis_tpu.chaos.link import link_faults
            faults = link_faults()
            if faults:
                await faults.gate(self.peer_id, to)
        try:
            conn = await self._pool.get(address)
            kind, body = await conn.call(KIND_SERVER_RPC, encode_rpc(msg),
                                         self.request_timeout_s)
        except (ConnectionError, OSError) as e:
            raise TimeoutIOException(f"{self.peer_id}->{to}: {e}") from None
        if faults:
            # the reply hop can be degraded independently (asymmetric
            # partitions): the peer processed the RPC but we never hear it
            await faults.gate(to, self.peer_id)
        if kind == KIND_ERROR:
            raise _decode_error(body)
        return decode_rpc(body)

    @property
    def address(self) -> str:
        if self._bound_port and self._address.endswith(":0"):
            host = self._address.rsplit(":", 1)[0]
            return f"{host}:{self._bound_port}"
        return self._address

    async def close(self) -> None:
        await self._pool.close()
        for conn in list(self._accepted):
            conn.close_nowait()
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()


def _decode_error(body: bytes) -> RaftException:
    import msgpack
    try:
        return exception_from_wire(msgpack.unpackb(body, raw=False))
    except Exception:
        return RaftException(f"undecodable remote error ({len(body)}B)")


class TcpClientTransport(ClientTransport):
    def __init__(self, request_timeout_s: float = 30.0,
                 tls: "TcpTlsConfig | None" = None):
        self._pool = _ConnectionPool(tls=tls)
        self.request_timeout_s = request_timeout_s

    async def send_request(self, peer_address: str,
                           request: RaftClientRequest) -> RaftClientReply:
        timeout = (request.timeout_ms / 1000.0 if request.timeout_ms > 0
                   else self.request_timeout_s)
        tid = request.trace_id if TRACER.enabled else 0
        try:
            conn = await self._pool.get(peer_address)
            t0 = TRACER.now() if tid else 0
            payload = request.to_bytes()
            if tid:
                TRACER.record(tid, STAGE_ENCODE, t0, TRACER.now(),
                              tag=len(payload))
                t0 = TRACER.now()
            kind, body = await conn.call(KIND_CLIENT_REQUEST, payload,
                                         timeout)
            if tid:
                # socket write + server + reply read: overlaps the server
                # stages — the wire share is this minus the server tiling
                TRACER.record(tid, STAGE_WIRE, t0, TRACER.now(),
                              tag=len(body))
        except (ConnectionError, OSError) as e:
            raise TimeoutIOException(f"client->{peer_address}: {e}") from None
        if kind == KIND_ERROR:
            raise _decode_error(body)
        return RaftClientReply.from_bytes(body)

    async def close(self) -> None:
        await self._pool.close()


class TcpTransportFactory(TransportFactory):
    def new_server_transport(self, peer_id: RaftPeerId, address: str,
                             server_handler, client_handler, properties=None,
                             peer_resolver=None) -> ServerTransport:
        from ratis_tpu.conf.keys import RaftServerConfigKeys
        timeout_s = 3.0
        if properties is not None:
            timeout_s = RaftServerConfigKeys.Rpc.request_timeout(
                properties).seconds
        chaos = (properties is not None
                 and RaftServerConfigKeys.Chaos.enabled(properties))
        return TcpServerTransport(peer_id, address, server_handler,
                                  client_handler, peer_resolver=peer_resolver,
                                  request_timeout_s=timeout_s,
                                  tls=TcpTlsConfig.from_properties(properties),
                                  defer_replies=_defer_conf(properties),
                                  chaos=chaos)

    def new_client_transport(self, properties=None) -> ClientTransport:
        return TcpClientTransport(tls=TcpTlsConfig.from_properties(properties))


TransportFactory.register("NETTY", TcpTransportFactory())
TransportFactory.register("TCP", TcpTransportFactory())
