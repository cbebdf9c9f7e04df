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
TPU-first note: this is pure host-side I/O — bulk bytes ride DCN between
failure domains and never enter an XLA program (SURVEY.md §2.6).
"""

from __future__ import annotations

import asyncio
import dataclasses
import logging
import struct
from typing import Awaitable, Callable, Optional

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
MAX_FRAME = 64 << 20


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


def encode_packet(p: Packet) -> bytes:
    body_len = _HDR.size - 4 + len(p.data)
    return _HDR.pack(body_len, p.kind, p.stream_id, p.offset,
                     p.flags) + p.data


async def read_packet(reader: asyncio.StreamReader) -> Optional[Packet]:
    """Read one frame; None on clean EOF; raises on truncation/oversize."""
    try:
        prefix = await reader.readexactly(4)
    except asyncio.IncompleteReadError as e:
        if not e.partial:
            return None
        raise ConnectionError("truncated frame prefix") from None
    (body_len,) = struct.unpack(">I", prefix)
    if body_len < _HDR.size - 4 or body_len > MAX_FRAME:
        raise ConnectionError(f"bad frame length {body_len}")
    body = await reader.readexactly(body_len)
    _, kind, stream_id, offset, flags = _HDR.unpack(prefix + body[:_HDR.size - 4])
    return Packet(kind, stream_id, offset, flags, body[_HDR.size - 4:])


PacketHandler = Callable[[Packet, "PeerConnection"], Awaitable[None]]


class PeerConnection:
    """One accepted connection; the handler replies via :meth:`send`.

    Loop-aware: with the DataStream plane pinned to division loop shards
    (raft.tpu.replication.stream-shards) the packet handlers — and their
    reply sends — run on shard loops while the accepted socket lives on
    the accept loop; a cross-loop send hops back to the owner (StreamWriter
    is loop-affine).  Single-loop servers take the direct path."""

    def __init__(self, reader: asyncio.StreamReader,
                 writer: asyncio.StreamWriter) -> None:
        self.reader = reader
        self.writer = writer
        self._send_lock = asyncio.Lock()
        self._loop = asyncio.get_running_loop()

    async def send(self, packet: Packet) -> None:
        if asyncio.get_running_loop() is not self._loop:
            await asyncio.wrap_future(asyncio.run_coroutine_threadsafe(
                self._send_owned(packet), self._loop))
            return
        await self._send_owned(packet)

    async def _send_owned(self, packet: Packet) -> None:
        async with self._send_lock:
            self.writer.write(encode_packet(packet))
            await self.writer.drain()

    async def close(self) -> None:
        self.writer.close()
        try:
            await self.writer.wait_closed()
        except (ConnectionError, OSError):
            pass


class DataStreamServer:
    """Accept loop dispatching packets to a handler (NettyServerStreamRpc)."""

    def __init__(self, address: str, handler: PacketHandler,
                 tls=None) -> None:
        self.address = address
        self.handler = handler
        self.tls = tls  # transport.tcp.TcpTlsConfig (same surface)
        self._server: Optional[asyncio.AbstractServer] = None
        self._conns: set[PeerConnection] = set()

    async def start(self) -> None:
        host, port = self.address.rsplit(":", 1)
        ssl_ctx = self.tls.server_context() if self.tls is not None else None
        self._server = await asyncio.start_server(self._on_connect, host,
                                                  int(port), ssl=ssl_ctx)

    @property
    def bound_port(self) -> Optional[int]:
        if self._server and self._server.sockets:
            return self._server.sockets[0].getsockname()[1]
        return None

    async def _on_connect(self, reader: asyncio.StreamReader,
                          writer: asyncio.StreamWriter) -> None:
        conn = PeerConnection(reader, writer)
        self._conns.add(conn)
        _CONNECTS_ACCEPTED.n += 1
        try:
            while True:
                packet = await read_packet(reader)
                if packet is None:
                    break
                try:
                    await self.handler(packet, conn)
                except Exception:
                    LOG.exception("datastream handler failed")
                    await conn.send(Packet(KIND_REPLY, packet.stream_id,
                                           packet.offset, packet.flags & ~FLAG_SUCCESS,
                                           b""))
        except (ConnectionError, OSError):
            pass
        finally:
            self._conns.discard(conn)
            await conn.close()

    async def close(self) -> None:
        # connections first: wait_closed() (3.12+) waits for every handler,
        # and handlers block in read_packet until their connection dies
        for conn in list(self._conns):
            await conn.close()
        if self._server is not None:
            self._server.close()
            await self._server.wait_closed()


class DataStreamConnection:
    """Client/forwarder side: one connection with per-packet ack futures
    keyed by (stream_id, offset, close-flag) — the sliding-window analog of
    OrderedStreamAsync."""

    def __init__(self, address: str, tls=None) -> None:
        self.address = address
        self.tls = tls
        self._reader: Optional[asyncio.StreamReader] = None
        self._writer: Optional[asyncio.StreamWriter] = None
        self._pending: dict[tuple, asyncio.Future] = {}
        self._recv_task: Optional[asyncio.Task] = None
        self._send_lock = asyncio.Lock()
        self._dead: Optional[Exception] = None

    async def connect(self) -> None:
        host, port = self.address.rsplit(":", 1)
        ssl_ctx = self.tls.client_context() if self.tls is not None else None
        self._reader, self._writer = await asyncio.open_connection(
            host, int(port), ssl=ssl_ctx)
        self._recv_task = asyncio.create_task(
            self._recv_loop(), name=f"datastream-recv-{self.address}")

    async def _recv_loop(self) -> None:
        cause: Exception = ConnectionError(
            f"datastream connection to {self.address} closed")
        try:
            while True:
                packet = await read_packet(self._reader)
                if packet is None:
                    break  # clean EOF still fails whatever is outstanding
                key = (packet.stream_id, packet.offset, packet.is_close)
                fut = self._pending.pop(key, None)
                if fut is not None and not fut.done():
                    fut.set_result(packet)
        except (ConnectionError, OSError, asyncio.CancelledError) as e:
            cause = ConnectionError(f"datastream connection lost: {e}")
        finally:
            self._dead = cause
            for fut in self._pending.values():
                if not fut.done():
                    fut.set_exception(cause)
            self._pending.clear()

    async def send(self, packet: Packet) -> "asyncio.Future[Packet]":
        """Send one packet; returns the future of its REPLY packet."""
        if self._dead is not None:
            raise self._dead
        key = (packet.stream_id, packet.offset, packet.is_close)
        if key in self._pending:
            raise ConnectionError(
                f"duplicate in-flight packet key {key} (zero-length data?)")
        fut = asyncio.get_running_loop().create_future()
        self._pending[key] = fut
        async with self._send_lock:
            self._writer.write(encode_packet(packet))
            await self._writer.drain()
        return fut

    async def close(self) -> None:
        if self._recv_task is not None:
            self._recv_task.cancel()
            try:
                await self._recv_task
            except asyncio.CancelledError:
                pass
        if self._writer is not None:
            self._writer.close()
            try:
                await self._writer.wait_closed()
            except (ConnectionError, OSError):
                pass
