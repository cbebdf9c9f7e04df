"""DataStream transport: bulk byte streaming over asyncio TCP.

Capability parity with the reference Netty DataStream path
(ratis-netty/src/main/java/org/apache/ratis/netty/NettyDataStreamUtils.java
framing + NettyServerStreamRpc / NettyClientStreamRpc): a client opens one
TCP connection to the *primary* peer and sends framed packets — a HEADER
carrying the serialized RaftClientRequest (with routing table), then DATA
packets, finally a packet flagged CLOSE; each packet is acked, and the
CLOSE ack carries the final RaftClientReply of the raft write the primary
submitted.  Peers forward packets to successors over the same framing.

Frame layout (all big-endian):
    u32 total_len | u8 kind | u64 stream_id | u64 offset | u8 flags | bytes
kind: 1=HEADER 2=DATA 3=REPLY; flags bit0=SYNC bit1=CLOSE bit2=SUCCESS.

A packet costs callbacks, not tasks: every stream-plane connection — one
accepted on a stream port, a forwarding leg to a successor, a client's — is
one ``asyncio.Protocol`` (:class:`PeerConnection`; in Netty's terms the frame
decoder, the flush on read-complete and auto-read).  ``data_received``
parses every whole frame it holds and hands each to the connection's handler
in that callback.  ``send`` only queues: what a loop pass queued on a
connection leaves in ONE ``transport.writelines`` at the end of the pass (a
frame's header and its bytes as two buffers, never joined, so a forwarded
DATA frame is the frame as it came in, its bytes not copied).  Flow control:
while a connection's transport is over its high-water mark, the connections
that feed it stop reading, and read again once it drains (``send``'s
``reader``).

TPU-first note: this is pure host-side I/O — bulk bytes ride DCN between
failure domains and never enter an XLA program (SURVEY.md §2.6).
"""

from __future__ import annotations

import asyncio
import dataclasses
import logging
import struct
import weakref
from typing import Callable, Optional

from ratis_tpu.trace.tracer import TRACER

LOG = logging.getLogger(__name__)

# connections accepted on a stream port (docs/tracing.md; those a server
# opens to a successor are counted where it opens them)
_CONNECTS_ACCEPTED = TRACER.counter("stream.connects", "accepted")

KIND_HEADER = 1
KIND_DATA = 2
KIND_REPLY = 3

FLAG_SYNC = 1
FLAG_CLOSE = 2
FLAG_SUCCESS = 4
FLAG_PRIMARY = 8  # set by the client on the header it sends the primary

_HDR = struct.Struct(">IBQQB")  # total_len, kind, stream_id, offset, flags
_LEN = struct.Struct(">I")
_BODY_MIN = _HDR.size - 4       # what total_len counts before the bytes
MAX_FRAME = 64 << 20

# the stream plane's socket writes and the frames they carried, by the side
# that writes: ``server`` an accepted connection (acks), ``client`` one that
# connected (a client's packets, a server's forwarding legs); frames a
# write = frames_out / writes_out (docs/tracing.md)
_SIDES = ("server", "client")
_FRAMES_OUT = {s: TRACER.counter("stream.frames_out", s) for s in _SIDES}
_WRITES_OUT = {s: TRACER.counter("stream.writes_out", s) for s in _SIDES}


def encode_header(request, routing) -> bytes:
    """HEADER payload: the serialized RaftClientRequest + RoutingTable
    (reference DataStreamRequestHeader + RoutingTableProto)."""
    import msgpack
    return msgpack.packb({"req": request.to_bytes(), "rt": routing.to_dict()},
                         use_bin_type=True)


def decode_header(data: bytes):
    import msgpack

    from ratis_tpu.protocol.requests import RaftClientRequest
    from ratis_tpu.protocol.routing import RoutingTable
    d = msgpack.unpackb(data, raw=False)
    return (RaftClientRequest.from_bytes(d["req"]),
            RoutingTable.from_dict(d.get("rt")))


@dataclasses.dataclass(frozen=True)
class Packet:
    kind: int
    stream_id: int
    offset: int
    flags: int
    data: bytes

    @property
    def is_close(self) -> bool:
        return bool(self.flags & FLAG_CLOSE)

    @property
    def is_sync(self) -> bool:
        return bool(self.flags & FLAG_SYNC)

    @property
    def success(self) -> bool:
        return bool(self.flags & FLAG_SUCCESS)


def _frame_head(p: Packet) -> bytes:
    return _HDR.pack(_BODY_MIN + len(p.data), p.kind, p.stream_id, p.offset,
                     p.flags)


def encode_packet(p: Packet) -> bytes:
    return _frame_head(p) + p.data


PacketHandler = Callable[[Packet, "PeerConnection"], None]


class PeerConnection(asyncio.Protocol):
    """One stream-plane connection, either end.  ``on_packet(packet, conn)``
    gets every frame in read order, inside the read callback; ``on_lost``
    gets what ended the connection, once.  ``side`` is ``server`` for a
    connection accepted on a stream port, ``client`` for one that connected.
    Everything runs on the loop that made the connection; a send or a hold
    from another loop (a stream pinned to a loop shard,
    raft.tpu.replication.stream-shards) is carried there with
    ``call_soon_threadsafe``."""

    def __init__(self, label: str, on_packet: PacketHandler,
                 on_lost: Optional[Callable[[Exception], None]] = None,
                 side: str = "client") -> None:
        self.label = label
        self._on_packet = on_packet
        self._on_lost = on_lost
        self.side = side
        self._frames_out = _FRAMES_OUT[side]
        self._writes_out = _WRITES_OUT[side]
        self.loop: Optional[asyncio.AbstractEventLoop] = None  # when made
        self._transport: Optional[asyncio.Transport] = None
        self._rbuf = bytearray()        # the bytes of a frame not yet whole
        self._out: list = []            # frame heads and bytes of this pass
        self._frames = 0                # frames in ``_out``
        self._flush_armed = False
        self.paused = False             # the transport is over its high water
        self._throttled: set = set()    # readers held until this one drains
        self._holds: set = set()        # what holds this one's reading
        self._closed: Optional[asyncio.Future] = None
        self.dead: Optional[Exception] = None
        self.inbound = None             # the handler's own per-connection state

    # -- the transport's callbacks -----------------------------------------

    def connection_made(self, transport) -> None:
        self.loop = asyncio.get_running_loop()
        self._transport = transport
        self._closed = self.loop.create_future()
        if self.side == "server":
            _CONNECTS_ACCEPTED.n += 1

    def data_received(self, data) -> None:
        buf = self._rbuf
        if buf:                 # a frame straddles reads: parse the joined bytes
            buf += data
            data = buf
        pos, end = 0, len(data)
        view = memoryview(data)
        try:
            while end - pos >= 4:
                (body_len,) = _LEN.unpack_from(data, pos)
                if body_len < _BODY_MIN or body_len > MAX_FRAME:
                    self._abort(ConnectionError(
                        f"{self.label}: bad frame length {body_len}"))
                    return
                nxt = pos + 4 + body_len
                if nxt > end:
                    break
                _, kind, stream_id, offset, flags = _HDR.unpack_from(data, pos)
                packet = Packet(kind, stream_id, offset, flags,
                                bytes(view[pos + _HDR.size:nxt]))
                pos = nxt
                self._on_packet(packet, self)
                if self.dead is not None:
                    return
            if data is not buf and pos < end:
                buf += view[pos:]
        finally:
            view.release()
        if data is buf:
            del buf[:pos]

    def eof_received(self):
        if self._rbuf:
            self._fail(ConnectionError(f"{self.label}: truncated frame"))
        elif self._out:
            self._flush()       # queued replies reach a half-closed peer
        return False            # and the transport closes

    def pause_writing(self) -> None:
        self.paused = True

    def resume_writing(self) -> None:
        self.paused = False
        readers, self._throttled = self._throttled, set()
        for reader in readers:
            reader.release_reading(self)

    def connection_lost(self, exc) -> None:
        self._fail(ConnectionError(f"{self.label} lost: {exc}" if exc
                                   else f"{self.label} closed"))
        if not self._closed.done():
            self._closed.set_result(None)

    # -- write -------------------------------------------------------------

    def _owned(self, fn, *args) -> bool:
        """Run ``fn(*args)`` now when called on the connection's loop and
        return True; from another loop, carry it there and return False."""
        try:
            here = asyncio.get_running_loop() is self.loop
        except RuntimeError:
            here = False
        if here:
            fn(*args)
            return True
        try:
            self.loop.call_soon_threadsafe(fn, *args)
        except RuntimeError:
            pass                # that loop has closed: the connection with it
        return False

    def send(self, packet: Packet,
             reader: Optional["PeerConnection"] = None) -> None:
        """Queue ``packet`` for this loop pass's one write, in call order;
        raises what killed the connection.  ``reader`` is the connection
        whose packet this one answers or carries on: while this one's
        transport is over its high-water mark, ``reader`` stops reading, and
        it reads again once this one drains (or dies).  From another loop
        the packet is carried to the connection's, and dropped there if it
        has died."""
        if self.dead is not None:
            raise self.dead
        self._owned(self._queue, packet, reader)

    def _queue(self, packet: Packet,
               reader: Optional["PeerConnection"]) -> None:
        if self.dead is not None:
            return              # (carried from another loop: it died since)
        self._out.append(_frame_head(packet))
        if packet.data:
            self._out.append(packet.data)
        self._frames += 1
        if not self._flush_armed:
            self._flush_armed = True
            self.loop.call_soon(self._flush)
        if self.paused and reader is not None \
                and reader not in self._throttled:
            self._throttled.add(reader)
            reader.hold_reading(self)

    def _flush(self) -> None:
        self._flush_armed = False
        out, frames = self._out, self._frames
        if not out or self.dead is not None:
            return
        self._out, self._frames = [], 0
        if self._transport.is_closing():
            return              # connection_lost follows and fails the rest
        try:
            self._transport.writelines(out)
        except Exception as e:
            # some of the batch may be on the wire: poison, never raise into
            # the loop
            err = ConnectionError(f"{self.label} write failed: {e!r}")
            err.__cause__ = e
            self._abort(err)
            return
        self._writes_out.n += 1
        self._frames_out.n += frames

    # -- reading held by other connections ---------------------------------

    def hold_reading(self, holder) -> None:
        """Stop reading until every holder has let go."""
        self._owned(self._hold_owned, holder)

    def _hold_owned(self, holder) -> None:
        if not self._holds and self._transport is not None:
            self._transport.pause_reading()
        self._holds.add(holder)

    def release_reading(self, holder) -> None:
        self._owned(self._release_owned, holder)

    def _release_owned(self, holder) -> None:
        if holder not in self._holds:
            return
        self._holds.discard(holder)
        if not self._holds and self._transport is not None:
            self._transport.resume_reading()

    # -- failure and close -------------------------------------------------

    def _fail(self, exc: Exception) -> None:
        if self.dead is not None:
            return
        self.dead = exc
        self._out.clear()
        self._frames = 0
        self.resume_writing()   # held readers go on
        if self._on_lost is not None:
            self._on_lost(exc)

    def _abort(self, exc: Exception) -> None:
        self._fail(exc)
        self._transport.abort()

    def close_nowait(self) -> None:
        """Write what is queued and close; ``connection_lost`` follows."""
        if self._transport is None:
            return
        if self._out and self.dead is None:
            self._flush()
        self._fail(ConnectionError(f"{self.label} closed"))
        self._transport.close()

    async def close(self) -> None:
        if self.loop is None:
            return              # never made (a TLS handshake that failed)
        if self._owned(self.close_nowait):
            await self._closed


class DataStreamServer:
    """A stream port: every accepted connection hands its packets to
    ``handler`` in read order, inside its read callback
    (NettyServerStreamRpc)."""

    def __init__(self, address: str, handler: PacketHandler,
                 tls=None) -> None:
        self.address = address
        self.handler = handler
        self.tls = tls  # transport.tcp.TcpTlsConfig (same surface)
        self._server: Optional[asyncio.AbstractServer] = None
        # (weak: a connection whose TLS handshake fails is never made, and
        # is never lost either)
        self._conns: "weakref.WeakSet[PeerConnection]" = weakref.WeakSet()

    async def start(self) -> None:
        host, port = self.address.rsplit(":", 1)
        ssl_ctx = self.tls.server_context() if self.tls is not None else None
        self._server = await asyncio.get_running_loop().create_server(
            self._accept, host, int(port), ssl=ssl_ctx)

    @property
    def bound_port(self) -> Optional[int]:
        if self._server and self._server.sockets:
            return self._server.sockets[0].getsockname()[1]
        return None

    def _accept(self) -> PeerConnection:
        conn = PeerConnection(f"datastream {self.address} accepted",
                              self._on_packet, side="server")
        self._conns.add(conn)
        return conn

    def _on_packet(self, packet: Packet, conn: PeerConnection) -> None:
        try:
            self.handler(packet, conn)
        except Exception:
            LOG.exception("datastream handler failed")
            if conn.dead is None:
                conn.send(Packet(KIND_REPLY, packet.stream_id, packet.offset,
                                 packet.flags & ~FLAG_SUCCESS, b""))

    async def close(self) -> None:
        # connections first: wait_closed() (3.12+) waits for every one
        for conn in list(self._conns):
            await conn.close()
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()


class DataStreamConnection:
    """Client/forwarder side: one connection with per-packet ack futures
    keyed by (stream_id, offset, close-flag) — the sliding-window analog of
    OrderedStreamAsync.  A REPLY resolves its future inside the read
    callback; a lost connection, a clean EOF included, fails every one
    outstanding."""

    def __init__(self, address: str, tls=None) -> None:
        self.address = address
        self.tls = tls
        self.conn: Optional[PeerConnection] = None
        self._pending: dict[tuple, asyncio.Future] = {}

    async def connect(self) -> None:
        host, port = self.address.rsplit(":", 1)
        ssl_ctx = self.tls.client_context() if self.tls is not None else None
        _, self.conn = await asyncio.get_running_loop().create_connection(
            lambda: PeerConnection(f"datastream connection to {self.address}",
                                   self._on_reply, self._lost),
            host, int(port), ssl=ssl_ctx)

    def _on_reply(self, packet: Packet, conn: PeerConnection) -> None:
        fut = self._pending.pop(
            (packet.stream_id, packet.offset, packet.is_close), None)
        if fut is not None and not fut.done():
            fut.set_result(packet)

    def _lost(self, exc: Exception) -> None:
        pending, self._pending = self._pending, {}
        for fut in pending.values():
            if not fut.done():
                fut.set_exception(exc)

    def queue(self, packet: Packet, reader: Optional[PeerConnection] = None
              ) -> "asyncio.Future[Packet]":
        """Queue one packet for this loop pass's write; returns the future
        of its REPLY packet.  The caller's order is the wire order;
        ``reader`` as for :meth:`PeerConnection.send`."""
        if self.conn.dead is not None:
            raise self.conn.dead
        key = (packet.stream_id, packet.offset, packet.is_close)
        if key in self._pending:
            raise ConnectionError(
                f"duplicate in-flight packet key {key} (zero-length data?)")
        fut = self.conn.loop.create_future()
        self._pending[key] = fut
        self.conn.send(packet, reader)
        return fut

    async def send(self, packet: Packet) -> "asyncio.Future[Packet]":
        """Send one packet; returns the future of its REPLY packet
        (:meth:`queue` from a coroutine: nothing is awaited)."""
        return self.queue(packet)

    async def close(self) -> None:
        if self.conn is not None:
            await self.conn.close()
