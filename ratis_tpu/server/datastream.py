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
        # in-flight packet completions (successor acks being awaited while
        # later packets already write — the pipeline); CLOSE drains these
        self.pending: set[asyncio.Task] = set()
        self.failed: Optional[Exception] = None
        # loop shard owning this stream's handling (the owning division's
        # shard when the plane is shard-pinned; None = primary loop) —
        # cleanup must unwind the stream's tasks/connections on this loop
        self.shard: Optional[int] = None


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
        reply = await (await self.send(packet))
        if not reply.success:
            raise DataStreamException(
                f"successor {self.peer_id} rejected stream "
                f"{packet.stream_id} offset {packet.offset}")
        return reply

    async def send(self, packet: Packet) -> "asyncio.Future[Packet]":
        """Put the packet on the successor's socket NOW (ordered per
        connection) and return the ack future — the pipelined half of
        :meth:`forward`."""
        return await self.conn.send(packet)

    async def close(self) -> None:
        await self.conn.close()


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

    async def start(self) -> None:
        await self.transport.start()

    async def close(self) -> None:
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

    async def _expire_idle(self) -> None:
        """Reclaim streams whose client vanished mid-stream and links whose
        raft entry never applied (lazy sweep, cf. MessageStreamRequests)."""
        if self._expiry_s <= 0:
            return
        now = time.monotonic()
        if now - self._last_sweep_s < self._expiry_s / 10:
            return  # keep the per-packet hot path O(1)
        self._last_sweep_s = now
        deadline = now - self._expiry_s
        for sid in [s for s, i in self._streams.items()
                    if i.touched_s < deadline]:
            info = self._streams.pop(sid)
            self._stream_shards.pop(sid, None)
            LOG.warning("expiring abandoned datastream %s", sid)
            await self._cleanup(info)
        for key in [k for k, (_, t) in self._links.items() if t < deadline]:
            info, _ = self._links.pop(key)
            await self._cleanup(info)

    async def _on_packet(self, packet: Packet, conn: PeerConnection) -> None:
        """Accept-loop entry: route the packet to its stream's pinned loop
        shard (the owning division's shard) and run the real handler
        there; unsharded servers — or packets for unknown streams, whose
        handling is just an error reply — stay on the accept loop.  The
        read loop awaits this per packet, so per-stream packet order is
        preserved across the hop."""
        t0 = TRACER.now() if TRACER.enabled else 0   # off the socket
        await self._expire_idle()
        if self._pin_shards:
            shard = self._route_shard(packet)
            if shard is not None:
                await self.server.shards.run_on(
                    shard, self._handle_packet(packet, conn, t0))
                return
        await self._handle_packet(packet, conn, t0)

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

    async def _handle_packet(self, packet: Packet, conn: PeerConnection,
                             t0: int = 0) -> None:
        """The real packet handler (on the stream's pinned loop when
        sharded); ``t0`` is when the packet came off the socket, on the
        tracer's clock (0: no session).  HEADER and CLOSE are handled fully
        inline (once per stream).  DATA is PIPELINED: the ordered work — offset check,
        local channel write, putting the forward copies on the successor
        sockets — happens inline (so stream order is the read-loop
        order), but awaiting the successor acks and answering the client
        moves to a completion task, letting the read loop pull the next
        packet immediately.  Serialized per-packet round-trips through the
        whole fan-out chain were the measured throughput ceiling
        (~0.7 MB/s aggregate at 64KB packets); the reference pipelines
        exactly this way by chaining per-stream futures
        (DataStreamManagement.java:85 writeTo/thenCombine chains)."""
        self.metrics.num_requests.inc()
        with self.metrics.request_timer.time():
            reply_data, tid = b"", 0
            try:
                if packet.kind == KIND_HEADER:
                    is_new = packet.stream_id not in self._streams
                    await self._on_header(packet)
                    if is_new:  # count only opens that actually succeeded
                        self.metrics.streams_started.inc()
                elif packet.kind == KIND_DATA:
                    if not packet.is_close:
                        await self._on_data_pipelined(packet, conn, t0)
                        return  # completion task acks the client
                    await self._on_close_data(packet)
                else:
                    raise DataStreamException(f"unexpected kind {packet.kind}")
                if packet.is_close:
                    # inside the try: a failing close must still answer the
                    # client (failure reply) and count as failed
                    reply_data, tid = await self._finish(packet, t0)
                    self.metrics.streams_closed.inc()
            except Exception as e:
                LOG.warning("datastream packet failed: %s", e)
                self.metrics.num_failed.inc()
                await conn.send(Packet(KIND_REPLY, packet.stream_id,
                                       packet.offset,
                                       packet.flags & ~FLAG_SUCCESS, b""))
                return
            await conn.send(Packet(KIND_REPLY, packet.stream_id, packet.offset,
                                   packet.flags | FLAG_SUCCESS, reply_data))
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

    async def _on_data_pipelined(self, packet: Packet, conn: PeerConnection,
                                 t0: int = 0) -> None:
        """Ordered phase of a (non-close) DATA packet: validate, queue the
        local write, put the forward copies on the wire; then hand the local
        write and the ack-collection to a completion task so the read loop
        pipelines (the reference's writeTo combines the local write and the
        remote ones the same way)."""
        info = self._info_for(packet)
        info.touched_s = time.monotonic()
        if info.failed is not None:
            raise info.failed
        if packet.offset != info.next_offset:
            raise DataStreamException(
                f"stream {packet.stream_id}: out-of-order offset "
                f"{packet.offset}, expected {info.next_offset}")
        ack_futs: list = []
        write: Optional[asyncio.Future] = None
        try:
            write = self._queue_local(info, packet.data)
            # sends happen NOW, in read-loop order (per-successor FIFO);
            # only the ack futures move to the completion task
            for r in info.remotes:
                ack_futs.append(await r.send(packet))
            if packet.is_sync:
                await asyncio.wait((write,))    # (the force below covers it)
        except asyncio.CancelledError:
            raise
        except Exception as e:
            # Poison the stream OURSELVES (later packets and the CLOSE fail
            # fast server-side instead of relying on the client reacting to
            # the failure reply), and consume/cancel the earlier
            # successors' ack futures — abandoned, their eventual
            # set_exception would surface as 'exception never retrieved'
            # noise with no handler (ADVICE r5).
            info.failed = e if isinstance(e, DataStreamException) \
                else DataStreamException(str(e))
            for fut in ack_futs if write is None else [write, *ack_futs]:
                fut.add_done_callback(_consume_result)
                fut.cancel()
            raise
        if packet.is_sync:
            await info.local.channel.force()

        async def complete() -> None:
            try:
                self._written(info, packet.data, await write)
                replies = await asyncio.gather(*ack_futs)
                for r, reply in zip(info.remotes, replies):
                    if not reply.success:
                        raise DataStreamException(
                            f"successor {r.peer_id} rejected stream "
                            f"{packet.stream_id} offset {packet.offset}")
            except asyncio.CancelledError:
                raise
            except Exception as e:
                # poison the stream: later packets and the CLOSE must fail
                info.failed = e
                for fut in ack_futs:    # (where the write failed first)
                    fut.add_done_callback(_consume_result)
                LOG.warning("datastream packet failed: %s", e)
                self.metrics.num_failed.inc()
                await conn.send(Packet(KIND_REPLY, packet.stream_id,
                                       packet.offset,
                                       packet.flags & ~FLAG_SUCCESS, b""))
                return
            self.metrics.bytes_written.inc(len(packet.data))
            await conn.send(Packet(KIND_REPLY, packet.stream_id,
                                   packet.offset,
                                   packet.flags | FLAG_SUCCESS, b""))
            TRACER.interval(STAGE_STREAM_PACKET, t0, len(packet.data)
                            if info.is_primary else -len(packet.data))

        t = asyncio.create_task(complete())
        info.pending.add(t)
        t.add_done_callback(info.pending.discard)

    async def _on_close_data(self, packet: Packet) -> None:
        """The CLOSE packet's data phase: drain the pipeline first (each
        packet's completion task waits for its local write too, so the
        force covers every byte acknowledged), then the fully-awaited
        ordered path (forwarding the close to successors and forcing the
        local channel)."""
        info = self._info_for(packet)
        info.touched_s = time.monotonic()
        # (a task done but not yet discarded is drained: a gather of done
        # tasks never yields, so its discard would never run)
        while any(not t.done() for t in info.pending):
            await asyncio.gather(*list(info.pending),
                                 return_exceptions=True)
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
        for t in list(info.pending):
            t.cancel()
        info.pending.clear()
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
