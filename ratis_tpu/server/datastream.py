"""DataStream server side: receive bulk bytes, fan out, link at apply.

Capability parity with the reference DataStream server
(ratis-netty/src/main/java/org/apache/ratis/netty/server/DataStreamManagement.java:85
+ NettyServerStreamRpc): the *primary* peer (the one the client connected
to) opens a local DataChannel via ``StateMachine.data_stream``, forwards
every packet to its successors per the stream's RoutingTable
(getSuccessors:196), and on CLOSE — once the local channel is forced and
every successor acked — submits the header RaftClientRequest through the
ordinary consensus path; at apply each receiving peer ``data_link``s its
streamed bytes to the committed entry (FileStoreStateMachine.java:196-216).
"""

from __future__ import annotations

import asyncio
import collections
import logging
import time
from typing import Dict, Optional, Tuple

from ratis_tpu.metrics import DataStreamMetrics
from ratis_tpu.protocol.exceptions import DataStreamException
from ratis_tpu.protocol.ids import RaftPeerId
from ratis_tpu.protocol.requests import RaftClientRequest, RequestType
from ratis_tpu.protocol.routing import RoutingTable
from ratis_tpu.trace.tracer import (STAGE_RESPOND, STAGE_STREAM_CLOSE,
                                    STAGE_STREAM_HEADER, STAGE_STREAM_PACKET,
                                    STAGE_STREAM_WRITE, TRACER)
from ratis_tpu.transport.datastream import (FLAG_CLOSE, FLAG_PRIMARY,
                                            FLAG_SUCCESS, FLAG_SYNC,
                                            KIND_DATA, KIND_HEADER,
                                            KIND_REPLY, DataStreamConnection,
                                            DataStreamServer, Packet,
                                            PeerConnection, decode_header,
                                            encode_header)

LOG = logging.getLogger(__name__)

LinkKey = Tuple[bytes, int]  # (clientId, callId) of the header request

# counters (docs/tracing.md): streams finished at their primary; DATA packets
# and their bytes written to a local channel, by the peer's part in the
# stream; connections opened to a successor (those accepted are counted by
# the transport)
_STREAMS = TRACER.counter("stream.streams")
_PARTS = ((True, "primary"), (False, "successor"))    # StreamInfo.is_primary
_PACKETS = {p: TRACER.counter("stream.packets", key) for p, key in _PARTS}
_BYTES = {p: TRACER.counter("stream.bytes", key) for p, key in _PARTS}
_CONNECTS_OPENED = TRACER.counter("stream.connects", "opened")


def _consume_result(fut: asyncio.Future) -> None:
    """Retrieve an abandoned ack future's outcome so the loop never logs
    'exception never retrieved' for a failure path we already handled."""
    if not fut.cancelled():
        fut.exception()


def _reply(conn: PeerConnection, packet: Packet, ok: bool,
           data: bytes = b"") -> None:
    """Queue ``packet``'s ack on the connection it came by (dropped if that
    connection has died: nobody is left to tell); while the connection's
    writes are paused, it stops reading."""
    if conn.dead is not None:
        return
    flags = packet.flags | FLAG_SUCCESS if ok else packet.flags & ~FLAG_SUCCESS
    conn.send(Packet(KIND_REPLY, packet.stream_id, packet.offset, flags, data),
              conn)


class StreamInfo:
    """One receiving stream on one peer (reference StreamInfo:88-193)."""

    def __init__(self, request: RaftClientRequest, is_primary: bool,
                 local, remotes: "list[_RemoteStream]") -> None:
        self.request = request
        self.is_primary = is_primary
        self.local = local            # StateMachine DataStream | None
        self.remotes = remotes
        self.next_offset = 0
        self.bytes_written = 0
        self.closed = False
        self.touched_s = time.monotonic()
        # DATA packets whose ack is not yet sent (_PacketAck: the local write
        # or a successor's ack outstanding — the pipeline); CLOSE drains
        # them through ``answered``, which the last one to finish resolves
        self.open_acks = 0
        self._drained: Optional[asyncio.Future] = None
        self.failed: Optional[Exception] = None
        # loop shard owning this stream's handling (the owning division's
        # shard when the plane is shard-pinned; None = primary loop) —
        # cleanup must unwind the stream's connections on this loop
        self.shard: Optional[int] = None

    def ack_done(self) -> None:
        self.open_acks -= 1
        if self.open_acks == 0 and self._drained is not None:
            drained, self._drained = self._drained, None
            if not drained.done():
                drained.set_result(None)

    async def answered(self) -> None:
        """Wait until every DATA packet so far has been answered."""
        if self.open_acks > 0:
            self._drained = asyncio.get_running_loop().create_future()
            await self._drained

    def abandon(self) -> None:
        """Cleanup: a CLOSE that waits for the pipeline waits no more."""
        drained, self._drained = self._drained, None
        if drained is not None:
            drained.cancel()


class _PacketAck:
    """One DATA packet in the pipeline: its parts — the local write, one ack
    a successor, and for a SYNC packet the force — each count one down as
    they come in (done-callbacks, no task); at zero the success ack is
    queued on the client's connection.  The first part to fail poisons the
    stream and queues the failure reply; what comes in after only has its
    outcome consumed."""

    __slots__ = ("mgmt", "info", "packet", "conn", "t0", "left", "write",
                 "acks")

    def __init__(self, mgmt: "DataStreamManagement", info: StreamInfo,
                 packet: Packet, conn: PeerConnection, t0: int,
                 write: asyncio.Future, acks: list, extra: int) -> None:
        self.mgmt, self.info, self.packet = mgmt, info, packet
        self.conn, self.t0 = conn, t0
        self.left = 1 + len(acks) + extra
        self.write, self.acks = write, acks
        info.open_acks += 1
        write.add_done_callback(self._write_done)
        for fut in acks:
            fut.add_done_callback(self._ack_done)

    def _write_done(self, fut: asyncio.Future) -> None:
        if self.left <= 0:
            _consume_result(fut)
            return
        try:
            self.mgmt._written(self.info, self.packet.data, fut.result())
        except (Exception, asyncio.CancelledError) as e:
            self.fail(e)
            return
        self.part_done()

    def _ack_done(self, fut: asyncio.Future) -> None:
        if self.left <= 0:
            _consume_result(fut)
            return
        try:
            reply = fut.result()
            if not reply.success:
                peer = next(r.peer_id for r, f in
                            zip(self.info.remotes, self.acks) if f is fut)
                raise DataStreamException(
                    f"successor {peer} rejected stream "
                    f"{self.packet.stream_id} offset {self.packet.offset}")
        except (Exception, asyncio.CancelledError) as e:
            self.fail(e)
            return
        self.part_done()

    def part_done(self) -> None:
        if self.left <= 0:
            return              # (failed already)
        self.left -= 1
        if self.left:
            return
        packet, info = self.packet, self.info
        self.mgmt.metrics.bytes_written.inc(len(packet.data))
        _reply(self.conn, packet, True)
        TRACER.interval(STAGE_STREAM_PACKET, self.t0, len(packet.data)
                        if info.is_primary else -len(packet.data))
        info.ack_done()

    def fail(self, e: BaseException) -> None:
        if self.left <= 0:
            return
        self.left = 0
        # poison the stream: later packets and the CLOSE must fail
        self.info.failed = e if isinstance(e, Exception) \
            else DataStreamException(f"stream {self.packet.stream_id}: {e!r}")
        LOG.warning("datastream packet failed: %s", e)
        self.mgmt.metrics.num_failed.inc()
        _reply(self.conn, self.packet, False)
        self.info.ack_done()


class _RemoteStream:
    """Forwarding leg to one successor (reference RemoteStream)."""

    def __init__(self, peer_id: RaftPeerId, address: str, tls=None) -> None:
        self.peer_id = peer_id
        self.address = address
        self.conn = DataStreamConnection(address, tls=tls)

    async def connect(self) -> None:
        await self.conn.connect()
        _CONNECTS_OPENED.n += 1

    async def forward(self, packet: Packet) -> Packet:
        """Forward and await the successor's ack."""
        reply = await self.conn.queue(packet)
        if not reply.success:
            raise DataStreamException(
                f"successor {self.peer_id} rejected stream "
                f"{packet.stream_id} offset {packet.offset}")
        return reply

    async def close(self) -> None:
        await self.conn.close()


class _Inbound:
    """One accepted connection's packets in read order: while a HEADER,
    CLOSE or SYNC packet's task is suspended, the packets read behind it
    wait here, and the connection stops reading once more than a client's
    window of them wait."""

    __slots__ = ("task", "backlog", "holding")

    def __init__(self) -> None:
        self.task: Optional[asyncio.Task] = None
        self.backlog: collections.deque = collections.deque()
        self.holding = False


# packets waiting behind a task before the connection stops reading: the
# client's window (client.py DataStreamOutput)
_BACKLOG_PACKETS = 16


class DataStreamManagement:
    """Per-server packet handler + the apply-time link registry."""

    def __init__(self, server, address: str,
                 expiry_s: float = 300.0) -> None:
        self.server = server  # RaftServer
        from ratis_tpu.conf.keys import NettyConfigKeys
        self.tls = NettyConfigKeys.DataStreamTls.tls_config(
            server.properties)
        self.transport = DataStreamServer(address, self._on_packet,
                                          tls=self.tls)
        # streamId -> StreamInfo while streaming (ids are client-random
        # 64-bit, collision-free in practice)
        self._streams: Dict[int, StreamInfo] = {}
        # (clientId, callId) -> (StreamInfo, retired-at) awaiting apply-time
        # link; swept together with idle streams so an aborted submit can't
        # pin temp files/FDs on followers forever
        self._links: Dict[LinkKey, Tuple[StreamInfo, float]] = {}
        self._expiry_s = expiry_s
        self._last_sweep_s = time.monotonic()
        self.metrics = DataStreamMetrics(str(server.peer_id))
        # Shard-pinned stream plane (raft.tpu.replication.stream-shards):
        # with loop sharding, each stream's packet handling — channel
        # writes, successor forwards, ack collection — runs on its OWNING
        # DIVISION's loop shard instead of the primary loop (the primary
        # loop's zero-sum cycle share was the attributed cause of
        # mixed-rung stream starvation, docs/perf.md).  streamId -> shard,
        # registered at HEADER routing time on the accept loop.
        self._pin_shards = (server.shards is not None
                            and getattr(server, "stream_shards", True))
        self._stream_shards: Dict[int, int] = {}
        self._sweeps: set[asyncio.Task] = set()   # expiry sweeps under way
        self._sweep_timer: Optional[asyncio.TimerHandle] = None

    async def start(self) -> None:
        await self.transport.start()
        self._arm_sweep()

    def _arm_sweep(self) -> None:
        """The expiry sweep's timer: every tenth of the expiry, besides the
        one a HEADER runs."""
        if self._expiry_s > 0:
            self._sweep_timer = asyncio.get_running_loop().call_later(
                self._expiry_s / 10, self._on_sweep_timer)

    def _on_sweep_timer(self) -> None:
        self._expire_idle()
        self._arm_sweep()

    async def close(self) -> None:
        if self._sweep_timer is not None:
            self._sweep_timer.cancel()
            self._sweep_timer = None
        self.metrics.unregister()
        await self.transport.close()
        for info in list(self._streams.values()):
            await self._cleanup(info)
        for info, _ in list(self._links.values()):
            await self._cleanup(info)
        self._streams.clear()
        self._links.clear()
        self._stream_shards.clear()

    # ------------------------------------------------------------- packets

    def _expire_idle(self) -> None:
        """Reclaim streams whose client vanished mid-stream and links whose
        raft entry never applied (lazy sweep, cf. MessageStreamRequests):
        run by a HEADER and by a timer, never by a DATA packet; the cleanup
        a task only when something is due."""
        if self._expiry_s <= 0:
            return
        now = time.monotonic()
        if now - self._last_sweep_s < self._expiry_s / 10:
            return  # at most one sweep a tenth of the expiry
        self._last_sweep_s = now
        deadline = now - self._expiry_s
        expired = []
        for sid in [s for s, i in self._streams.items()
                    if i.touched_s < deadline]:
            expired.append(self._streams.pop(sid))
            self._stream_shards.pop(sid, None)
            LOG.warning("expiring abandoned datastream %s", sid)
        for key in [k for k, (_, t) in self._links.items() if t < deadline]:
            expired.append(self._links.pop(key)[0])
        if expired:
            t = asyncio.get_running_loop().create_task(
                self._cleanup_all(expired))
            self._sweeps.add(t)
            t.add_done_callback(self._sweeps.discard)

    async def _cleanup_all(self, infos: "list[StreamInfo]") -> None:
        for info in infos:
            await self._cleanup(info)

    def _on_packet(self, packet: Packet, conn: PeerConnection) -> None:
        """Accept-loop entry, inside the connection's read callback: route
        the packet to its stream's pinned loop shard (the owning division's
        shard) and handle it there; unsharded servers — or packets for
        unknown streams, whose handling is just an error reply — stay on
        the accept loop.  A hop to a shard is one ``call_soon_threadsafe``
        a packet, in read order."""
        t0 = TRACER.now() if TRACER.enabled else 0   # off the socket
        if packet.kind == KIND_HEADER:
            self._expire_idle()
        if self._pin_shards:
            shard = self._route_shard(packet)
            if shard is not None:
                self.server.shards.call_soon(shard, self._in_order, packet,
                                             conn, t0)
                return
        self._in_order(packet, conn, t0)

    def _route_shard(self, packet: Packet) -> Optional[int]:
        """Loop shard owning ``packet``'s stream: registered at HEADER
        time from the header's group id (one extra header decode, paid
        once per stream), looked up for DATA/CLOSE.  None = handle on the
        accept loop (undecodable header / unknown stream error paths)."""
        if packet.kind == KIND_HEADER:
            try:
                request, _ = decode_header(packet.data)
            except Exception:
                return None  # the handler produces the failure reply
            shard = self.server.shard_of_group(request.group_id)
            self._stream_shards[packet.stream_id] = shard
            return shard
        return self._stream_shards.get(packet.stream_id)

    def _in_order(self, packet: Packet, conn: PeerConnection,
                  t0: int) -> None:
        """A connection's packets are handled in read order: one that comes
        while a task of the connection (HEADER, CLOSE, SYNC) is suspended
        waits for it."""
        inbound = conn.inbound
        if inbound is None:
            inbound = conn.inbound = _Inbound()
        if inbound.task is not None:
            inbound.backlog.append((packet, t0))
            if len(inbound.backlog) > _BACKLOG_PACKETS \
                    and not inbound.holding:
                inbound.holding = True
                conn.hold_reading(inbound)
            return
        self._handle(packet, conn, t0, inbound)

    def _handle(self, packet: Packet, conn: PeerConnection, t0: int,
                inbound: _Inbound) -> None:
        """``t0`` is when the packet came off the socket, on the tracer's
        clock (0: no session).  DATA is handled here, in the read callback:
        the offset checked, the local write queued, the copies put on the
        successors' connections, and a :class:`_PacketAck` left to answer
        the client once all of them are in.  Serialized per-packet
        round-trips through the fan-out chain were the measured ceiling
        (~0.7 MB/s at 64KB packets); the reference pipelines the same way
        (DataStreamManagement.java:85 writeTo/thenCombine chains).  HEADER,
        CLOSE and SYNC (once a stream, or rare) await: they run as a task,
        and the connection's later packets wait for it."""
        self.metrics.num_requests.inc()
        if packet.kind == KIND_DATA \
                and not packet.flags & (FLAG_CLOSE | FLAG_SYNC):
            with self.metrics.request_timer.time():
                try:
                    self._on_data(packet, conn, t0)
                except Exception as e:
                    self._failed(packet, conn, e)
            return
        inbound.task = asyncio.get_running_loop().create_task(
            self._handle_once(packet, conn, t0, inbound))

    def _resume(self, conn: PeerConnection, inbound: _Inbound) -> None:
        """The connection's task has ended: handle what waited, in order,
        until one starts a task of its own."""
        inbound.task = None
        backlog = inbound.backlog
        while backlog and inbound.task is None:
            packet, t0 = backlog.popleft()
            self._handle(packet, conn, t0, inbound)
        if inbound.holding and len(backlog) <= _BACKLOG_PACKETS:
            inbound.holding = False
            conn.release_reading(inbound)

    def _failed(self, packet: Packet, conn: PeerConnection,
                e: Exception) -> None:
        LOG.warning("datastream packet failed: %s", e)
        self.metrics.num_failed.inc()
        _reply(conn, packet, False)

    async def _handle_once(self, packet: Packet, conn: PeerConnection,
                           t0: int, inbound: _Inbound) -> None:
        """A HEADER, CLOSE or SYNC packet, in the connection's task; the
        packets that waited behind it are handled when it ends."""
        try:
            with self.metrics.request_timer.time():
                await self._answer_once(packet, conn, t0)
        finally:
            self._resume(conn, inbound)

    async def _answer_once(self, packet: Packet, conn: PeerConnection,
                           t0: int) -> None:
        reply_data, tid = b"", 0
        try:
            if packet.kind == KIND_HEADER:
                is_new = packet.stream_id not in self._streams
                await self._on_header(packet)
                if is_new:  # count only opens that actually succeeded
                    self.metrics.streams_started.inc()
            elif packet.kind == KIND_DATA:
                if not packet.is_close:
                    await self._on_sync(packet, conn, t0)
                    return  # its _PacketAck acks the client
                await self._on_close_data(packet)
            else:
                raise DataStreamException(f"unexpected kind {packet.kind}")
            if packet.is_close:
                # inside the try: a failing close must still answer the
                # client (failure reply) and count as failed
                reply_data, tid = await self._finish(packet, t0)
                self.metrics.streams_closed.inc()
        except Exception as e:
            self._failed(packet, conn, e)
            return
        _reply(conn, packet, True, reply_data)
        if packet.kind == KIND_HEADER:
            TRACER.interval(STAGE_STREAM_HEADER, t0)
        elif tid:
            # the stream's raft request, answered in the CLOSE's ack:
            # what a transport's respond span is to a client request
            egress = TRACER.pop_egress(tid)
            if egress:
                TRACER.record(tid, STAGE_RESPOND, egress, TRACER.now())

    async def _on_header(self, packet: Packet) -> None:
        request, routing = decode_header(packet.data)
        if packet.stream_id in self._streams:
            return  # idempotent header retry
        is_primary = bool(packet.flags & FLAG_PRIMARY)

        division = self.server.get_division(request.group_id)
        local = await division.state_machine.data_stream(request)

        remotes: list[_RemoteStream] = []
        successors = routing.get_successors(self.server.peer_id)
        if routing.is_empty() and is_primary:
            # documented default: an empty table means the primary fans out
            # to every other peer that serves a datastream address
            successors = tuple(
                p.id for p in division.state.configuration.all_peers()
                if p.id != self.server.peer_id and p.datastream_address)
        for pid in successors:
            peer = division.state.configuration.get_peer(pid)
            if peer is None or not peer.datastream_address:
                raise DataStreamException(
                    f"successor {pid} has no datastream address")
            remotes.append(_RemoteStream(pid, peer.datastream_address,
                             tls=self.tls))

        info = StreamInfo(request, is_primary, local, remotes)
        info.shard = self._stream_shards.get(packet.stream_id)
        self._streams[packet.stream_id] = info
        try:
            forwarded = Packet(KIND_HEADER, packet.stream_id, packet.offset,
                               packet.flags & ~FLAG_PRIMARY, packet.data)
            await asyncio.gather(*(r.connect() for r in remotes))
            await asyncio.gather(*(r.forward(forwarded) for r in remotes))
        except Exception:
            self._streams.pop(packet.stream_id, None)
            await self._cleanup(info)
            raise

    def _info_for(self, packet: Packet) -> StreamInfo:
        info = self._streams.get(packet.stream_id)
        if info is None:
            raise DataStreamException(f"unknown stream {packet.stream_id}")
        return info

    async def _write_local(self, info: StreamInfo, data: bytes) -> None:
        """A packet's bytes into the stream's local channel, awaited and
        counted."""
        self._written(info, data, await self._queue_local(info, data))

    @staticmethod
    def _queue_local(info: StreamInfo, data: bytes) -> asyncio.Future:
        """A packet's bytes queued to the stream's local channel
        (``DataChannel.submit_write``): the offset moves now, the future
        holds the bytes written."""
        t0 = TRACER.now() if TRACER.enabled else 0
        fut = info.local.channel.submit_write(data)
        info.next_offset += len(data)
        if t0:
            fut.add_done_callback(lambda _: TRACER.interval(
                STAGE_STREAM_WRITE, t0, len(data)))
        return fut

    @staticmethod
    def _written(info: StreamInfo, data: bytes, written: int) -> None:
        if written != len(data):
            raise DataStreamException(f"short write {written}/{len(data)}")
        info.bytes_written += len(data)
        _PACKETS[info.is_primary].n += 1
        _BYTES[info.is_primary].n += len(data)

    def _on_data(self, packet: Packet, conn: PeerConnection, t0: int,
                 extra: int = 0) -> _PacketAck:
        """A (non-close) DATA packet, in read order: validate, queue the
        local write, put the copies on the successors' connections, and
        leave the ack to a :class:`_PacketAck` (the reference's writeTo
        combines the local write and the remote ones the same way)."""
        info = self._info_for(packet)
        info.touched_s = time.monotonic()
        if info.failed is not None:
            raise info.failed
        if packet.offset != info.next_offset:
            raise DataStreamException(
                f"stream {packet.stream_id}: out-of-order offset "
                f"{packet.offset}, expected {info.next_offset}")
        acks: list = []
        write: Optional[asyncio.Future] = None
        try:
            write = self._queue_local(info, packet.data)
            # sends happen NOW, in read order (per-successor FIFO)
            for r in info.remotes:
                acks.append(r.conn.queue(packet, conn))
        except Exception as e:
            # Poison the stream OURSELVES (later packets and the CLOSE fail
            # fast server-side instead of relying on the client reacting to
            # the failure reply), and consume/cancel the earlier
            # successors' ack futures — abandoned, their eventual
            # set_exception would surface as 'exception never retrieved'
            # noise with no handler (ADVICE r5).
            info.failed = e if isinstance(e, DataStreamException) \
                else DataStreamException(str(e))
            for fut in acks if write is None else [write, *acks]:
                fut.add_done_callback(_consume_result)
                fut.cancel()
            raise
        return _PacketAck(self, info, packet, conn, t0, write, acks, extra)

    async def _on_sync(self, packet: Packet, conn: PeerConnection,
                       t0: int) -> None:
        """A SYNC packet: as any DATA packet, and its ack waits for a force
        that runs once its queued write has landed (so it covers the
        packet's bytes)."""
        ack = self._on_data(packet, conn, t0, extra=1)
        try:
            await asyncio.wait((ack.write,))
            await ack.info.local.channel.force()
        except Exception as e:
            ack.fail(e)
            return
        ack.part_done()

    async def _on_close_data(self, packet: Packet) -> None:
        """The CLOSE packet's data phase: drain the pipeline first (each
        packet's ack waits for its local write too, so the force covers
        every byte acknowledged), then the fully-awaited ordered path
        (forwarding the close to successors and forcing the local
        channel)."""
        info = self._info_for(packet)
        info.touched_s = time.monotonic()
        await info.answered()
        if info.failed is not None:
            raise info.failed
        if packet.offset != info.next_offset:
            raise DataStreamException(
                f"stream {packet.stream_id}: out-of-order close offset "
                f"{packet.offset}, expected {info.next_offset}")
        if packet.data:
            await self._write_local(info, packet.data)
            self.metrics.bytes_written.inc(len(packet.data))
        await asyncio.gather(*(r.forward(packet) for r in info.remotes))
        await info.local.channel.force()

    async def _finish(self, packet: Packet, t0: int = 0
                      ) -> Tuple[bytes, int]:
        """CLOSE handling after the data landed everywhere: primary submits
        the raft write; reply bytes ride back in the CLOSE ack.  Returns
        them and the raft request's trace id (0: not traced)."""
        info = self._info_for(packet)
        info.closed = True
        self._streams.pop(packet.stream_id, None)
        self._stream_shards.pop(packet.stream_id, None)
        await info.local.channel.close()
        for r in info.remotes:  # successors acked the CLOSE already
            await r.close()
        link_key = (info.request.client_id.to_bytes(), info.request.call_id)
        self._links[link_key] = (info, time.monotonic())
        if not info.is_primary:
            return b"", 0
        TRACER.interval(STAGE_STREAM_CLOSE, t0)
        # the raft request a stream ends in arrives here: traced from here
        # like a client request from its transport's ingress
        tid = TRACER.ingress(info.request) if TRACER.enabled else 0
        reply = await self.server.submit_data_stream_request(info.request)
        if reply.success:
            _STREAMS.n += 1
        else:
            self._links.pop(link_key, None)
            await self._cleanup(info)
        return reply.to_bytes(), tid

    async def _cleanup(self, info: StreamInfo) -> None:
        # a shard-pinned stream's tasks and successor connections are
        # loop-affine: unwind them on the loop they live on
        if info.shard is not None and self.server.shards is not None:
            await self.server.shards.run_on(info.shard,
                                            self._cleanup_owned(info))
            return
        await self._cleanup_owned(info)

    async def _cleanup_owned(self, info: StreamInfo) -> None:
        info.abandon()
        if info.local is not None:
            try:
                await info.local.cleanup()
            except Exception:
                LOG.exception("stream cleanup failed")
        for r in info.remotes:
            await r.close()

    # ----------------------------------------------------- apply-time link

    def take_link(self, client_id: bytes, call_id: int
                  ) -> Optional[StreamInfo]:
        entry = self._links.pop((client_id, call_id), None)
        return entry[0] if entry is not None else None

    @property
    def bound_port(self) -> Optional[int]:
        return self.transport.bound_port
