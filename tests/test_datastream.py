"""DataStream tests (reference ratis-test datastream suites +
TestNettyDataStream*: framing, routing, stream-write-link end to end)."""

import asyncio
import errno
import random
import threading
import time

import msgpack
import pytest

from ratis_tpu.models import filestore
from ratis_tpu.models.filestore import (FileChunkChannel,
                                        FileStoreStateMachine, _WriterLane)
from ratis_tpu.server.statemachine import DataChannel, DataStream
from ratis_tpu.protocol.ids import RaftPeerId
from ratis_tpu.protocol.routing import RoutingTable
from ratis_tpu.trace.tracer import TRACER
from ratis_tpu.transport.datastream import (FLAG_CLOSE, FLAG_PRIMARY,
                                            FLAG_SUCCESS, FLAG_SYNC,
                                            KIND_DATA, KIND_HEADER,
                                            KIND_REPLY, MAX_FRAME,
                                            DataStreamConnection, Packet,
                                            PeerConnection, encode_packet)
from tests.minicluster import run_with_new_cluster


def _pid(s):
    return RaftPeerId.value_of(s)


class _Wire(asyncio.Transport):
    """A stand-in socket for one :class:`PeerConnection`: keeps what each
    write carried and whether the connection reads."""

    def __init__(self) -> None:
        super().__init__()
        self.writes: list[bytes] = []
        self.reading = True
        self.closed = self.aborted = False

    def writelines(self, data) -> None:
        self.writes.append(b"".join(data))

    def is_closing(self) -> bool:
        return self.closed or self.aborted

    def pause_reading(self) -> None:
        self.reading = False

    def resume_reading(self) -> None:
        self.reading = True

    def close(self) -> None:
        self.closed = True

    def abort(self) -> None:
        self.aborted = True


def _made(on_packet=None, on_lost=None, side="client"):
    """A connection on the running loop over a :class:`_Wire`, with the
    packets it read."""
    got: list = []
    conn = PeerConnection("test", on_packet or (lambda p, c: got.append(p)),
                          on_lost, side)
    wire = _Wire()
    conn.connection_made(wire)
    return conn, wire, got


def test_packet_roundtrip():
    async def _run():
        p = Packet(KIND_DATA, 12345, 678, FLAG_SYNC | FLAG_CLOSE, b"payload")
        conn, wire, got = _made()
        conn.data_received(encode_packet(p))
        assert got == [p]
        assert got[0].is_sync and got[0].is_close
        assert conn.eof_received() is False     # clean EOF: the transport closes
        assert conn.dead is None and not wire.aborted

    asyncio.run(_run())


def test_packet_truncation_raises():
    async def _run():
        p = Packet(KIND_HEADER, 1, 0, FLAG_PRIMARY, b"x" * 100)
        raw = encode_packet(p)
        conn, _, got = _made()
        conn.data_received(raw[:len(raw) - 5])
        conn.eof_received()
        assert got == []
        assert isinstance(conn.dead, ConnectionError)
        assert "truncated" in str(conn.dead)

    asyncio.run(_run())


def _split_at_every_byte(data):
    """Two frames, cut in two reads at every byte: each read hands on every
    whole frame it completes, once, in order."""
    frames = [Packet(KIND_DATA, 7, 0, 0, data),
              Packet(KIND_REPLY, 7, len(data), FLAG_SUCCESS, b"")]
    raw = b"".join(encode_packet(p) for p in frames)
    first = len(encode_packet(frames[0]))
    for cut in range(len(raw) + 1):
        conn, _, got = _made()
        conn.data_received(raw[:cut])
        assert got == frames[:(cut >= first) + (cut == len(raw))], cut
        conn.data_received(raw[cut:])
        assert got == frames and not conn._rbuf, cut


def _several_in_one_read(_):
    """Five whole frames and the start of a sixth in one read: the five in
    order, the rest kept; the sixth whole once its bytes come."""
    frames = [Packet(KIND_DATA, 3, k * 10, 0, bytes([k]) * 10)
              for k in range(6)]
    raw = [encode_packet(p) for p in frames]
    conn, _, got = _made()
    conn.data_received(b"".join(raw[:5]) + raw[5][:9])
    assert got == frames[:5]
    assert bytes(conn._rbuf) == raw[5][:9]
    conn.data_received(raw[5][9:])
    assert got == frames and not conn._rbuf


def _bad_length(length):
    """A length below the frame's head or above MAX_FRAME aborts the
    connection, fails it once, and a later send raises."""
    lost: list = []
    conn, wire, got = _made(on_lost=lost.append)
    conn.data_received(length.to_bytes(4, "big") + bytes(30))
    assert got == [] and wire.aborted
    assert isinstance(conn.dead, ConnectionError) and lost == [conn.dead]
    with pytest.raises(ConnectionError):
        conn.send(Packet(KIND_REPLY, 1, 0, 0, b""))


@pytest.mark.parametrize("case, arg", [
    (_split_at_every_byte, b""),
    (_split_at_every_byte, b"q" * 37),
    (_several_in_one_read, None),
    (_bad_length, 0),
    (_bad_length, 17),
    (_bad_length, MAX_FRAME + 1),
], ids=["split-empty", "split-37", "several", "bad-0", "bad-17", "bad-max"])
def test_the_frame_parser(case, arg):
    async def _run():
        case(arg)

    asyncio.run(_run())


def test_a_clean_eof_fails_what_is_outstanding_on_a_connection():
    """The stream port reads one whole packet and closes cleanly: both
    packets' futures fail, and a later send raises."""
    async def _run():
        first = Packet(KIND_DATA, 9, 0, 0, b"a" * 100)

        async def serve(reader, writer):
            await reader.readexactly(len(encode_packet(first)))
            writer.close()

        srv = await asyncio.start_server(serve, "127.0.0.1", 0)
        port = srv.sockets[0].getsockname()[1]
        conn = DataStreamConnection(f"127.0.0.1:{port}")
        await conn.connect()
        futs = [await conn.send(first),
                conn.queue(Packet(KIND_DATA, 9, 100, 0, b"b" * 100))]
        for fut in futs:
            with pytest.raises(ConnectionError):
                await asyncio.wait_for(fut, 10)
        with pytest.raises(ConnectionError):
            conn.queue(Packet(KIND_DATA, 9, 200, 0, b"c"))
        await conn.close()
        srv.close()
        await srv.wait_closed()

    asyncio.run(_run())


def test_what_one_pass_queues_leaves_in_one_write():
    """Three packets sent in one loop pass: one ``writelines`` carrying the
    three frames byte for byte, counted as one write of three frames."""
    async def _run():
        conn, wire, _ = _made()
        writes = TRACER.counter("stream.writes_out", "client").n
        frames = TRACER.counter("stream.frames_out", "client").n
        packets = [Packet(KIND_REPLY, 5, k * 4, FLAG_SUCCESS, b"") for k in
                   range(2)] + [Packet(KIND_DATA, 5, 8, 0, b"four")]
        for p in packets:
            conn.send(p)
        assert wire.writes == []
        await asyncio.sleep(0)
        assert wire.writes == [b"".join(encode_packet(p) for p in packets)]
        assert TRACER.counter("stream.writes_out", "client").n == writes + 1
        assert TRACER.counter("stream.frames_out", "client").n == frames + 3

    asyncio.run(_run())


def test_routing_table_shapes():
    a, b, c = _pid("a"), _pid("b"), _pid("c")
    chain = RoutingTable.chain([a, b, c])
    assert chain.get_successors(a) == (b,)
    assert chain.get_successors(b) == (c,)
    assert chain.get_successors(c) == ()
    star = RoutingTable.star(a, [b, c])
    assert set(star.get_successors(a)) == {b, c}
    rt = (RoutingTable.Builder().add_successor(a, b)
          .add_successor(a, c).build())
    assert rt.get_successors(a) == (b, c)
    # wire round trip
    assert RoutingTable.from_dict(rt.to_dict()) == rt


def _stream_cmd(path):
    return msgpack.packb({"op": "stream", "path": path}, use_bin_type=True)


def test_filestore_stream_end_to_end():
    """1MB streamed in 64KB packets lands identically on every peer."""

    async def _test(cluster):
        await cluster.wait_for_leader()
        payload = bytes((i * 31) % 256 for i in range(1 << 20))
        async with cluster.new_client() as client:
            out = await client.data_stream().stream(_stream_cmd("big.bin"))
            for i in range(0, len(payload), 64 << 10):
                await out.write_async(payload[i:i + (64 << 10)])
            reply = await out.close_async()
            assert reply.success, reply.exception
            result = msgpack.unpackb(reply.message.content, raw=False)
            assert result == {"ok": True, "size": len(payload)}

            # read back through a linearizable query
            read = await client.io().send_read_only(
                msgpack.packb({"op": "read", "path": "big.bin"},
                              use_bin_type=True))
            data = msgpack.unpackb(read.message.content, raw=False)["data"]
            assert data == payload

            await cluster.wait_applied(reply.log_index)
        # every peer that received the stream has the identical file
        found = 0
        for div in cluster.divisions():
            sm = div.state_machine
            target = sm.resolve("big.bin")
            if target.exists():
                assert target.read_bytes() == payload
                found += 1
        assert found == len(cluster.divisions())  # star routing reaches all
        # stream metrics observed the traffic (NettyServerStreamRpcMetrics
        # analog): bytes counted on the primary, stream opened and closed
        m = [s.datastream.metrics for s in cluster.servers.values()
             if s.datastream is not None]
        assert sum(x.bytes_written.count for x in m) >= len(payload)
        assert sum(x.streams_started.count for x in m) >= 1
        assert sum(x.streams_closed.count for x in m) >= 1
        assert all(x.num_failed.count == 0 for x in m)

    run_with_new_cluster(3, _test, sm_factory=FileStoreStateMachine)


def test_filestore_stream_via_follower_primary():
    """Streaming to a non-leader primary still commits (forward to leader)."""

    async def _test(cluster):
        leader = await cluster.wait_for_leader()
        follower = next(d for d in cluster.divisions() if d.is_follower())
        follower_peer = cluster.group.get_peer(follower.member_id.peer_id)
        async with cluster.new_client() as client:
            out = await client.data_stream().stream(
                _stream_cmd("via-follower.bin"), primary=follower_peer)
            await out.write_async(b"hello " * 1000)
            reply = await out.close_async()
            assert reply.success, reply.exception

    run_with_new_cluster(3, _test, sm_factory=FileStoreStateMachine)


def test_filestore_chain_routing():
    """Chain topology: primary -> f1 -> f2; all peers get the bytes."""

    async def _test(cluster):
        leader = await cluster.wait_for_leader()
        order = [leader.member_id.peer_id] + \
            [d.member_id.peer_id for d in cluster.divisions()
             if d.member_id.peer_id != leader.member_id.peer_id]
        rt = RoutingTable.chain(order)
        leader_peer = cluster.group.get_peer(order[0])
        payload = b"chained-data" * 5000
        async with cluster.new_client() as client:
            out = await client.data_stream().stream(
                _stream_cmd("chain.bin"), routing_table=rt,
                primary=leader_peer)
            await out.write_async(payload)
            reply = await out.close_async()
            assert reply.success, reply.exception
            await cluster.wait_applied(reply.log_index)
        for div in cluster.divisions():
            target = div.state_machine.resolve("chain.bin")
            assert target.exists()
            assert target.read_bytes() == payload

    run_with_new_cluster(3, _test, sm_factory=FileStoreStateMachine)


def test_filestore_empty_routing_defaults_to_fanout():
    """An explicitly empty RoutingTable means 'primary fans out to all'."""

    async def _test(cluster):
        leader = await cluster.wait_for_leader()
        leader_peer = cluster.group.get_peer(leader.member_id.peer_id)
        payload = b"fanout" * 10000
        async with cluster.new_client() as client:
            out = await client.data_stream().stream(
                _stream_cmd("fanout.bin"), routing_table=RoutingTable(),
                primary=leader_peer)
            await out.write_async(payload)
            reply = await out.close_async()
            assert reply.success, reply.exception
            await cluster.wait_applied(reply.log_index)
        for div in cluster.divisions():
            assert div.state_machine.resolve("fanout.bin").read_bytes() \
                == payload

    run_with_new_cluster(3, _test, sm_factory=FileStoreStateMachine)


def test_filestore_write_read_delete():
    """Small files through the ordinary log path."""

    async def _test(cluster):
        await cluster.wait_for_leader()
        async with cluster.new_client() as client:
            w = await client.io().send(msgpack.packb(
                {"op": "write", "path": "small.txt", "data": b"contents"},
                use_bin_type=True))
            assert w.success
            ls = await client.io().send_read_only(
                msgpack.packb({"op": "list"}, use_bin_type=True))
            assert msgpack.unpackb(ls.message.content,
                                   raw=False)["files"] == ["small.txt"]
            d = await client.io().send(msgpack.packb(
                {"op": "delete", "path": "small.txt"}, use_bin_type=True))
            assert d.success
            ls = await client.io().send_read_only(
                msgpack.packb({"op": "list"}, use_bin_type=True))
            assert msgpack.unpackb(ls.message.content,
                                   raw=False)["files"] == []

    run_with_new_cluster(3, _test, sm_factory=FileStoreStateMachine)


def test_filestore_rejects_unsafe_paths():
    async def _test(cluster):
        await cluster.wait_for_leader()
        async with cluster.new_client() as client:
            for bad in ("../escape", "/abs/path", ""):
                reply = await client.io().send(msgpack.packb(
                    {"op": "write", "path": bad, "data": b"x"},
                    use_bin_type=True))
                assert not reply.success

    run_with_new_cluster(3, _test, sm_factory=FileStoreStateMachine)


class LinkRecordingFileStore(FileStoreStateMachine):
    """Records data_link(None, ...) calls — the missing-stream repair hook."""

    def __init__(self):
        super().__init__()
        self.null_link_indices: list[int] = []

    async def data_link(self, stream, entry):
        if stream is None:
            self.null_link_indices.append(entry.index)
        await super().data_link(stream, entry)


def test_peer_outside_routing_table_gets_null_link():
    """A replica that never received the stream still gets
    data_link(None, entry) at apply so it can detect/repair the miss
    (reference DataStreamManagement passes a null stream)."""

    async def _test(cluster):
        leader = await cluster.wait_for_leader()
        others = [d.member_id.peer_id for d in cluster.divisions()
                  if d.member_id.peer_id != leader.member_id.peer_id]
        # route only leader -> others[0]; others[1] is outside the table
        rt = RoutingTable.chain([leader.member_id.peer_id, others[0]])
        leader_peer = cluster.group.get_peer(leader.member_id.peer_id)
        async with cluster.new_client() as client:
            out = await client.data_stream().stream(
                _stream_cmd("partial.bin"), routing_table=rt,
                primary=leader_peer)
            await out.write_async(b"x" * 4096)
            reply = await out.close_async()
            assert reply.success, reply.exception
            await cluster.wait_applied(reply.log_index)
        for div in cluster.divisions():
            sm = div.state_machine
            if div.member_id.peer_id == others[1]:
                assert reply.log_index in sm.null_link_indices
            else:
                assert reply.log_index not in sm.null_link_indices

    run_with_new_cluster(3, _test, sm_factory=LinkRecordingFileStore)


def test_datastream_tls_end_to_end(tmp_path):
    """DataStream over TLS (NettyConfigKeys.DataStreamTls; the reference's
    NettyServerStreamRpc takes its own TlsConfig): a streamed file lands on
    every peer with all stream legs (client->primary, primary->successor)
    riding TLS sockets, and a plaintext stream client cannot connect."""
    import subprocess

    cert = tmp_path / "cert.pem"
    key = tmp_path / "key.pem"
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", str(key), "-out", str(cert), "-days", "1",
         "-subj", "/CN=localhost",
         "-addext", "subjectAltName=IP:127.0.0.1,DNS:localhost"],
        check=True, capture_output=True)

    from ratis_tpu.conf.keys import NettyConfigKeys
    from tests.minicluster import fast_properties

    p = fast_properties()
    p.set(NettyConfigKeys.DataStreamTls.ENABLED_KEY, "true")
    p.set(NettyConfigKeys.DataStreamTls.CERT_CHAIN_KEY, str(cert))
    p.set(NettyConfigKeys.DataStreamTls.PRIVATE_KEY_KEY, str(key))
    p.set(NettyConfigKeys.DataStreamTls.TRUST_ROOT_KEY, str(cert))

    async def _test(cluster):
        leader = await cluster.wait_for_leader()
        payload = bytes((i * 7) % 256 for i in range(1 << 16))
        async with cluster.new_client() as client:
            out = await client.data_stream().stream(_stream_cmd("tls.bin"))
            await out.write_async(payload)
            reply = await out.close_async()
            assert reply.success, reply.exception
            await cluster.wait_applied(reply.log_index)
        for div in cluster.divisions():
            target = div.state_machine.resolve("tls.bin")
            assert target.exists() and target.read_bytes() == payload

        # plaintext connection against the TLS stream port must fail
        from ratis_tpu.transport.datastream import DataStreamConnection
        srv = cluster.servers[leader.member_id.peer_id]
        addr = srv.datastream.transport.address
        plain = DataStreamConnection(addr)
        try:
            await plain.connect()
            # TLS handshake failure may surface on first send instead
            from ratis_tpu.transport.datastream import (FLAG_PRIMARY,
                                                        KIND_HEADER, Packet)
            fut = await plain.send(Packet(KIND_HEADER, 1, 0, FLAG_PRIMARY,
                                          b""))
            await asyncio.wait_for(fut, 2.0)
            raise AssertionError("plaintext stream spoke to TLS endpoint")
        except (ConnectionError, OSError, asyncio.TimeoutError,
                asyncio.IncompleteReadError):
            pass
        finally:
            try:
                await plain.close()
            except Exception:
                pass

    run_with_new_cluster(3, _test, sm_factory=FileStoreStateMachine,
                         properties=p)


# ------------------------------------------- the writer lane of streamed files

def test_a_lane_writes_interleaved_streams_each_in_order(tmp_path):
    """Four streams' packets interleaved through one lane land byte for
    byte as a plain sequential write of each stream's packets."""

    async def _run():
        lane = _WriterLane("test-order")
        rnd = random.Random(37)
        chans = [FileChunkChannel(tmp_path / f"s{i}", lane) for i in range(4)]
        packets: list = [[] for _ in chans]
        futs = []
        for k in range(200):
            i = rnd.randrange(len(chans))
            data = rnd.randbytes(rnd.randrange(1, 5000))
            packets[i].append(data)
            futs.append((chans[i].submit_write(data), len(data)))
            if k % 17 == 0:
                await asyncio.sleep(0)      # let a pass take what is queued
        for fut, n in futs:
            assert await fut == n
        for i, chan in enumerate(chans):
            await chan.close()
            plain = tmp_path / f"plain{i}"
            with open(plain, "wb") as f:
                for data in packets[i]:
                    f.write(data)
            assert chan.tmp_path.read_bytes() == plain.read_bytes()

    asyncio.run(_run())


def test_a_lane_pass_resolves_what_it_met_with_one_loop_callback(
        tmp_path, monkeypatch):
    held, entered = threading.Event(), threading.Event()
    pwritev, writes = filestore.os.pwritev, []

    def counted_pwritev(*args):
        if threading.current_thread().name == "filestore-test-batch":
            writes.append(1)
        return pwritev(*args)

    monkeypatch.setattr(filestore.os, "pwritev", counted_pwritev)

    class HeldChannel(FileChunkChannel):
        def _append(self, chunks):
            entered.set()
            assert held.wait(10)
            super()._append(chunks)

    async def _run():
        loop = asyncio.get_running_loop()
        lane = _WriterLane("test-batch")
        callbacks = []
        call_soon_threadsafe = loop.call_soon_threadsafe

        def counted(cb, *args, **kwargs):
            if threading.current_thread().name == "filestore-test-batch":
                callbacks.append(cb)
            return call_soon_threadsafe(cb, *args, **kwargs)

        loop.call_soon_threadsafe = counted
        gate = HeldChannel(tmp_path / "gate", lane)
        first = gate.submit_write(b"g")
        try:
            assert entered.wait(10)     # the lane's first pass holds it
            chans = [FileChunkChannel(tmp_path / f"s{i}", lane)
                     for i in range(3)]
            futs = [chans[k % 3].submit_write(bytes([k]) * 1000)
                    for k in range(12)]
            # a close while the lane holds the channel's writes: behind them
            closing = asyncio.create_task(chans[0].close())
            await asyncio.sleep(0)
            assert not closing.done() and chans[0]._fd >= 0
            batches, calls = lane.batches.n, len(writes)
        finally:
            held.set()
        assert await first == 1
        assert [await f for f in futs] == [1000] * 12
        await closing
        assert chans[0]._fd == -1
        # the twelve queued packets and the close: one pass, one pwritev a
        # file, and one call back to the loop (the held pass adds its
        # pwritev and its call)
        assert lane.batches.n - batches == 1
        assert len(writes) - calls == 3 + 1
        assert len(callbacks) == 2
        for k, chan in enumerate(chans):
            await chan.close()
            assert chan.tmp_path.read_bytes() == b"".join(
                bytes([j]) * 1000 for j in range(k, 12, 3))
        await gate.close()

    asyncio.run(_run())


def test_a_lane_outlives_a_fault_of_its_own_pass(tmp_path, monkeypatch):
    """A pass that raises fails its batch's futures with the error; the
    lane's thread goes on and takes the next writes."""
    write = _WriterLane._write
    faults = [RuntimeError("a fault of the pass")]

    def faulty(batch):
        if faults:
            raise faults.pop()
        return write(batch)

    monkeypatch.setattr(_WriterLane, "_write", staticmethod(faulty))

    async def _run():
        lane = _WriterLane("test-fault")
        chan = FileChunkChannel(tmp_path / "f", lane)
        with pytest.raises(RuntimeError, match="a fault of the pass"):
            await chan.submit_write(b"lost")
        assert await asyncio.wait_for(chan.submit_write(b"kept"), 10) == 4
        await chan.close()
        assert chan.tmp_path.read_bytes() == b"kept"

    asyncio.run(_run())


P = 4096            # a packet of the stream tests below
LIMIT = 2 * P + 100  # what a failing channel takes: two packets and a bit


class _FailingChannel(FileChunkChannel):
    how = "short"

    def _append(self, chunks):
        data = b"".join(bytes(c) for c in chunks)
        room = max(0, LIMIT - self._end)
        if room:
            super()._append([data[:room]])
        if len(data) > room and self.how == "error":
            raise OSError(errno.ENOSPC, "no space left on device")


def _failing_store(how):
    class FailingFileStore(FileStoreStateMachine):
        async def data_stream(self, request):
            stream = await super().data_stream(request)
            stream.channel.__class__ = _FailingChannel
            stream.channel.how = how
            return stream
    return FailingFileStore


def _chain_from_leader(cluster, leader):
    order = [leader.member_id.peer_id] + \
        [d.member_id.peer_id for d in cluster.divisions()
         if d.member_id.peer_id != leader.member_id.peer_id]
    return RoutingTable.chain(order), cluster.group.get_peer(order[0])


@pytest.mark.parametrize("how", ["short", "error"])
def test_a_failed_local_write_poisons_the_stream(how):
    """Packet 2 of 6 falls short on every peer: packets 0 and 1 are
    acknowledged, 2 and later get failure replies, the CLOSE fails, and
    each peer's file holds exactly the acknowledged packets."""

    async def _test(cluster):
        leader = await cluster.wait_for_leader()
        rt, primary = _chain_from_leader(cluster, leader)
        async with cluster.new_client() as client:
            out = await client.data_stream().stream(
                _stream_cmd("poisoned.bin"), routing_table=rt,
                primary=primary)
            for k in range(6):
                await out.write_async(bytes([k]) * P)
            replies = await asyncio.gather(*out._acks)
            assert [r.success for r in replies] == [True] * 2 + [False] * 4
            close = await (await out._conn.send(Packet(
                KIND_DATA, out._stream_id, 6 * P, FLAG_CLOSE, b"")))
            assert not close.success
            await out._conn.close()
        held = [info for s in cluster.servers.values()
                for info in s.datastream._streams.values()]
        assert len(held) == 3 and all(i.failed is not None for i in held)
        for info in held:
            channel = info.local.channel
            assert channel.tmp_path.read_bytes() == bytes([0]) * P \
                + bytes([1]) * P

    run_with_new_cluster(3, _test, sm_factory=_failing_store(how))


class _HeldChannel(DataChannel):
    """A channel whose writes the test completes: each ``submit_write`` is a
    future of the running loop, kept in ``writes``."""

    def __init__(self) -> None:
        self.writes: list = []
        self.forced = 0

    def submit_write(self, data):
        fut = asyncio.get_running_loop().create_future()
        self.writes.append((fut, len(data)))
        return fut

    async def force(self, metadata=False):
        self.forced += 1


def _bare_plane(n_successors=0):
    """A stream server's packet handler with no server behind it: stream 7
    open at offset 0 on a :class:`_HeldChannel`, with ``n_successors``
    forwarding legs over stand-in sockets; the accepted connection that
    feeds it and the legs' sockets."""
    from ratis_tpu.metrics import DataStreamMetrics
    from ratis_tpu.server import datastream

    mgmt = datastream.DataStreamManagement.__new__(
        datastream.DataStreamManagement)
    mgmt._links, mgmt._stream_shards, mgmt._sweeps = {}, {}, set()
    mgmt._expiry_s, mgmt._pin_shards = 0, False
    mgmt.metrics = DataStreamMetrics(f"test-{random.random()}")
    remotes, legs = [], []
    for k in range(n_successors):
        r = datastream._RemoteStream(_pid(f"s{k}"), "127.0.0.1:1")
        r.conn.conn, wire, _ = _made(r.conn._on_reply, r.conn._lost)
        remotes.append(r)
        legs.append(wire)
    info = datastream.StreamInfo(None, True, DataStream(_HeldChannel()),
                                 remotes)
    mgmt._streams = {7: info}
    upstream, wire, _ = _made(mgmt._on_packet, side="server")
    return mgmt, info, upstream, wire, legs


def _replies(wire):
    """The packets a stand-in socket carried."""
    conn, _, got = _made()
    for data in wire.writes:
        conn.data_received(data)
    return got


def test_a_close_drains_a_packet_done_but_not_yet_discarded():
    """The CLOSE may run in the loop pass in which the last packet's write
    has landed and before its completion record has counted it: the drain
    waits for that record, and for nothing else (no future left that
    nobody resolves)."""
    async def _run():
        mgmt, info, upstream, wire, _ = _bare_plane()
        upstream.data_received(encode_packet(Packet(KIND_DATA, 7, 0, 0,
                                                    b"x" * 10)))
        (write, n), = info.local.channel.writes
        assert info.open_acks == 1
        write.set_result(n)          # landed; its record not yet called back
        await asyncio.wait_for(mgmt._on_close_data(
            Packet(KIND_DATA, 7, 10, FLAG_CLOSE, b"")), 5)
        assert info.open_acks == 0 and info.local.channel.forced == 1
        assert [p.success for p in _replies(wire)] == [True]
        # nothing open: the drain does not wait at all
        await asyncio.wait_for(info.answered(), 0.5)
        mgmt.metrics.unregister()

    asyncio.run(_run())


@pytest.mark.parametrize("last", ["write", "successor"])
def test_a_packets_ack_waits_for_its_write_and_every_successor(last):
    """Two successors: the copy leaves at once, and the client's ack leaves
    only once the local write and both successors' acks are in, whichever
    comes last."""
    async def _run():
        mgmt, info, upstream, wire, legs = _bare_plane(2)
        p = Packet(KIND_DATA, 7, 0, 0, b"y" * 64)
        upstream.data_received(encode_packet(p))
        await asyncio.sleep(0)
        # each copy is the frame as it came in, byte for byte
        assert [leg.writes for leg in legs] == [[encode_packet(p)]] * 2
        (write, n), = info.local.channel.writes
        ack = encode_packet(Packet(KIND_REPLY, 7, 0, FLAG_SUCCESS, b""))
        steps = [lambda: write.set_result(n)] + [
            lambda r=r: r.conn.conn.data_received(ack) for r in info.remotes]
        if last == "write":
            steps.append(steps.pop(0))
        for step in steps:
            assert _replies(wire) == []
            step()
            for _ in range(3):
                await asyncio.sleep(0)
        assert [(q.offset, q.success) for q in _replies(wire)] == [(0, True)]
        assert info.open_acks == 0 and info.bytes_written == 64
        mgmt.metrics.unregister()

    asyncio.run(_run())


def test_acks_of_one_pass_leave_in_one_write():
    """Five packets' writes land in one lane pass: their five acks go to
    the client in one socket write, counted as one write of five frames on
    the server's side."""
    async def _run():
        mgmt, info, upstream, wire, _ = _bare_plane()
        writes = TRACER.counter("stream.writes_out", "server").n
        frames = TRACER.counter("stream.frames_out", "server").n
        upstream.data_received(b"".join(
            encode_packet(Packet(KIND_DATA, 7, k * 8, 0, b"z" * 8))
            for k in range(5)))
        for write, n in info.local.channel.writes:
            write.set_result(n)
        for _ in range(3):
            await asyncio.sleep(0)
        assert len(wire.writes) == 1
        assert [q.offset for q in _replies(wire)] == [0, 8, 16, 24, 32]
        assert TRACER.counter("stream.writes_out", "server").n == writes + 1
        assert TRACER.counter("stream.frames_out", "server").n == frames + 5
        mgmt.metrics.unregister()

    asyncio.run(_run())


def test_packets_behind_a_suspended_header_are_handled_after_it_in_order():
    """A HEADER whose handling is suspended holds the packets read behind it
    on its connection; once it ends they are handled in read order, and
    the connection, which stopped reading while more than a window's worth
    waited, reads again."""
    from ratis_tpu.server import datastream

    async def _run():
        mgmt, info, upstream, wire, _ = _bare_plane()
        mgmt._streams = {}
        go = asyncio.Event()

        async def on_header(packet):
            await go.wait()
            mgmt._streams[packet.stream_id] = info

        mgmt._on_header = on_header
        n = datastream._BACKLOG_PACKETS + 1
        upstream.data_received(
            encode_packet(Packet(KIND_HEADER, 7, 0, FLAG_PRIMARY, b"h"))
            + b"".join(encode_packet(Packet(KIND_DATA, 7, k, 0, b"d"))
                       for k in range(n)))
        await asyncio.sleep(0)
        assert info.local.channel.writes == [] and _replies(wire) == []
        assert not wire.reading         # more than a window waits
        go.set()
        for _ in range(3):
            await asyncio.sleep(0)
        assert wire.reading
        assert [q.kind for q in _replies(wire)] == [KIND_REPLY]   # HEADER's
        assert info.next_offset == n and len(info.local.channel.writes) == n
        for write, k in info.local.channel.writes:
            write.set_result(k)
        for _ in range(3):
            await asyncio.sleep(0)
        assert [(q.offset, q.success) for q in _replies(wire)] == \
            [(0, True)] + [(k, True) for k in range(n)]
        mgmt.metrics.unregister()

    asyncio.run(_run())


def test_a_paused_successor_leg_pauses_reading_upstream():
    """While a successor's socket is over its high-water mark, the
    connection whose packets it carries stops reading; it reads again when
    that socket drains."""
    async def _run():
        mgmt, info, upstream, wire, legs = _bare_plane(1)
        leg = info.remotes[0].conn.conn
        leg.pause_writing()
        upstream.data_received(encode_packet(Packet(KIND_DATA, 7, 0, 0, b"a")))
        assert not wire.reading
        upstream.data_received(encode_packet(Packet(KIND_DATA, 7, 1, 0, b"b")))
        assert info.next_offset == 2 and not wire.reading
        leg.resume_writing()
        assert wire.reading
        # a dying leg lets its readers go too
        leg.pause_writing()
        upstream.data_received(encode_packet(Packet(KIND_DATA, 7, 2, 0, b"c")))
        assert not wire.reading
        leg.connection_lost(None)
        assert wire.reading
        mgmt.metrics.unregister()

    asyncio.run(_run())


def test_a_successor_lost_mid_stream_fails_its_acks_and_poisons_the_stream():
    """Two packets out to the one successor, their writes landed, the first
    one acknowledged: the successor's connection dies.  The second packet
    gets a failure reply, the stream is poisoned, and a later packet fails
    at once without a write or a copy."""
    async def _run():
        mgmt, info, upstream, wire, legs = _bare_plane(1)
        leg = info.remotes[0].conn.conn
        upstream.data_received(b"".join(
            encode_packet(Packet(KIND_DATA, 7, k * 4, 0, b"w" * 4))
            for k in range(2)))
        for write, n in info.local.channel.writes:
            write.set_result(n)
        leg.data_received(encode_packet(
            Packet(KIND_REPLY, 7, 0, FLAG_SUCCESS, b"")))
        await asyncio.sleep(0)
        leg.connection_lost(ConnectionResetError("reset by peer"))
        for _ in range(3):
            await asyncio.sleep(0)
        assert [(q.offset, q.success) for q in _replies(wire)] == \
            [(0, True), (4, False)]
        assert isinstance(info.failed, ConnectionError)
        assert info.open_acks == 0
        upstream.data_received(encode_packet(Packet(KIND_DATA, 7, 8, 0, b"x")))
        await asyncio.sleep(0)
        assert [(q.offset, q.success) for q in _replies(wire)][2:] == \
            [(8, False)]
        assert len(info.local.channel.writes) == 2
        assert len(legs[0].writes) == 1         # the two copies, one write
        assert mgmt.metrics.num_failed.count == 2
        mgmt.metrics.unregister()

    asyncio.run(_run())


def test_the_expiry_sweep_runs_on_a_header_and_a_timer_never_on_data():
    """An abandoned stream is reclaimed by the sweep: DATA packets never
    run it; a HEADER does, and so does the timer a started plane arms."""
    from ratis_tpu.server import datastream

    async def _run():
        mgmt, info, upstream, wire, _ = _bare_plane()
        mgmt._expiry_s, mgmt._sweep_timer = 10.0, None
        cleaned: list = []

        async def cleanup(i):
            cleaned.append(i)

        mgmt._cleanup = cleanup
        info.touched_s = mgmt._last_sweep_s = time.monotonic() - 20
        upstream.data_received(encode_packet(Packet(KIND_DATA, 8, 0, 0, b"d")))
        assert 7 in mgmt._streams and not mgmt._sweeps
        mgmt._on_packet(Packet(KIND_HEADER, 9, 0, 0, b"not a header"),
                        upstream)
        assert 7 not in mgmt._streams and len(mgmt._sweeps) == 1
        await asyncio.gather(*mgmt._sweeps)
        assert cleaned == [info]
        # the timer: armed by start, and again by each sweep it runs
        mgmt._expiry_s = 0.05
        mgmt._streams[7] = info
        info.touched_s = mgmt._last_sweep_s = time.monotonic() - 1
        mgmt._arm_sweep()
        for _ in range(100):
            await asyncio.sleep(0.01)
            if len(cleaned) == 2:
                break
        assert cleaned == [info, info]
        assert mgmt._sweep_timer is not None \
            and not mgmt._sweep_timer.cancelled()
        mgmt._sweep_timer.cancel()
        mgmt.metrics.unregister()

    asyncio.run(_run())


class _MemoryChannel(DataChannel):
    """A channel with only a ``write``: the plane queues each packet through
    ``DataChannel.submit_write``'s default, one write behind the other."""

    gate: asyncio.Event      # (set per test: holds every write)

    def __init__(self) -> None:
        self.data = bytearray()

    async def write(self, data: bytes) -> int:
        await self.gate.wait()
        self.data += data
        return len(data)


class MemoryChannelFileStore(FileStoreStateMachine):
    async def data_stream(self, request):
        stream = DataStream(_MemoryChannel(), request)
        stream.path = msgpack.unpackb(request.message.content,
                                      raw=False)["path"]
        return stream

    async def data_link(self, stream, entry):
        if stream is not None:
            self.resolve(stream.path).write_bytes(bytes(stream.channel.data))


def test_the_default_submit_writes_in_order_and_fails_what_follows_a_failure():
    """``DataChannel.submit_write`` runs each ``write`` behind the one before
    it, whatever each takes; one that fails fails every later one."""

    class Jittery(DataChannel):
        def __init__(self):
            self.data, self.rnd = bytearray(), random.Random(37)

        async def write(self, data):
            for _ in range(self.rnd.randrange(4)):
                await asyncio.sleep(0)
            if data == b"bad":
                raise OSError(errno.EIO, "write failed")
            self.data += data
            return len(data)

    async def _run():
        chan = Jittery()
        futs = [chan.submit_write(bytes([k]) * (k + 1)) for k in range(40)]
        assert [await f for f in futs] == [k + 1 for k in range(40)]
        assert chan.data == b"".join(bytes([k]) * (k + 1) for k in range(40))
        bad, after = chan.submit_write(b"bad"), chan.submit_write(b"late")
        for fut in (bad, after):
            with pytest.raises(OSError):
                await fut
        assert not chan.data.endswith(b"late")

    asyncio.run(_run())


def test_a_channel_with_only_a_write_streams_through_the_default_submit():
    """Its writes held shut, all four packets still reach both successors
    (the copy does not wait for the write) and the client has no ack; let
    go, every peer's bytes are whole and no FileStore lane ran."""
    lanes = sum(lane.batches.n for lane in filestore._LANES)

    async def _test(cluster):
        _MemoryChannel.gate = asyncio.Event()
        leader = await cluster.wait_for_leader()
        rt, primary = _chain_from_leader(cluster, leader)
        payload = random.Random(4).randbytes(4 * P)
        async with cluster.new_client() as client:
            out = await client.data_stream().stream(
                _stream_cmd("memory.bin"), routing_table=rt, primary=primary)
            for i in range(0, len(payload), P):
                await out.write_async(payload[i:i + P])
            infos: list = []
            deadline = time.monotonic() + 10
            while not (len(infos) == 3 and all(
                    i.next_offset == len(payload) for i in infos)):
                assert time.monotonic() < deadline
                await asyncio.sleep(0.01)
                infos = [info for s in cluster.servers.values()
                         for info in s.datastream._streams.values()]
            assert not any(i.local.channel.data for i in infos)
            assert not any(f.done() for f in out._acks)
            _MemoryChannel.gate.set()
            reply = await out.close_async()
            assert reply.success, reply.exception
            assert msgpack.unpackb(reply.message.content, raw=False) == \
                {"ok": True, "size": len(payload)}
            await cluster.wait_applied(reply.log_index)
        for div in cluster.divisions():
            assert div.state_machine.resolve("memory.bin").read_bytes() \
                == payload

    run_with_new_cluster(3, _test, sm_factory=MemoryChannelFileStore)
    assert sum(lane.batches.n for lane in filestore._LANES) == lanes


def test_the_copy_leaves_before_the_local_write_completes(monkeypatch):
    """With every lane held shut, all four packets reach both successors
    (their offsets move) while no peer's write has landed, each packet's
    completion record waits on every peer and the client has no ack; let
    go, the stream completes and every file is whole."""
    held = threading.Event()
    append = FileChunkChannel._append

    def held_append(self, chunks):
        assert held.wait(10)
        append(self, chunks)

    monkeypatch.setattr(FileChunkChannel, "_append", held_append)

    async def _test(cluster):
        leader = await cluster.wait_for_leader()
        rt, primary = _chain_from_leader(cluster, leader)
        payload = random.Random(5).randbytes(4 * P)
        async with cluster.new_client() as client:
            out = await client.data_stream().stream(
                _stream_cmd("held.bin"), routing_table=rt, primary=primary)
            try:
                for i in range(0, len(payload), P):
                    await out.write_async(payload[i:i + P])
                infos = [info for s in cluster.servers.values()
                         for info in s.datastream._streams.values()]
                deadline = time.monotonic() + 10
                while not (len(infos) == 3 and all(
                        i.next_offset == len(payload) for i in infos)):
                    assert time.monotonic() < deadline
                    await asyncio.sleep(0.01)
                    infos = [info for s in cluster.servers.values()
                             for info in s.datastream._streams.values()]
                assert sum(not i.is_primary for i in infos) == 2
                assert all(i.local.channel._end == 0 for i in infos)
                # every packet's completion record is open on every peer,
                # its write the part still out
                assert all(i.open_acks == 4 for i in infos)
                assert not any(f.done() for f in out._acks)
            finally:
                held.set()
            reply = await out.close_async()
            assert reply.success, reply.exception
            await cluster.wait_applied(reply.log_index)
        for div in cluster.divisions():
            assert div.state_machine.resolve("held.bin").read_bytes() \
                == payload

    try:
        run_with_new_cluster(3, _test, sm_factory=FileStoreStateMachine)
    finally:
        held.set()


def test_a_stream_pinned_to_a_loop_shard_is_called_back_on_that_loop(
        monkeypatch):
    """raft.tpu.replication.stream-shards with two loop shards, the group
    on the second: every peer queues its writes from its shard's loop, and
    the lanes call back there, not to the servers' first loop."""
    from ratis_tpu.server.shards import LoopShardPool
    from tests.minicluster import fast_properties

    monkeypatch.setattr(LoopShardPool, "shard_of", lambda self, key: 1)
    loops = set()
    submit = _WriterLane.submit

    def seen(self, channel, data):
        fut = submit(self, channel, data)
        loops.add(fut.get_loop())
        return fut

    monkeypatch.setattr(_WriterLane, "submit", seen)
    p = fast_properties()
    p.set("raft.tpu.server.loop-shards", "2")

    async def _test(cluster):
        await cluster.wait_for_leader()
        payload = random.Random(6).randbytes(1 << 19)
        async with cluster.new_client() as client:
            out = await client.data_stream().stream(_stream_cmd("shard.bin"))
            for i in range(0, len(payload), 64 << 10):
                await out.write_async(payload[i:i + (64 << 10)])
            reply = await out.close_async()
            assert reply.success, reply.exception
            await cluster.wait_applied(reply.log_index)
        for div in cluster.divisions():
            assert div.state_machine.resolve("shard.bin").read_bytes() \
                == payload
        shard_loops = {s.shards.loop(1) for s in cluster.servers.values()}
        assert loops == shard_loops
        assert asyncio.get_running_loop() not in loops

    run_with_new_cluster(3, _test, sm_factory=FileStoreStateMachine,
                         properties=p)


def test_a_sync_packet_waits_for_its_queued_write_before_the_force(
        monkeypatch):
    """A SYNC packet's write is queued like any other; the force behind it
    runs once that write has landed, so it covers the packet's bytes."""
    forced = []
    force = FileChunkChannel.force

    async def seen(self, metadata=False):
        forced.append(self._end)
        await force(self, metadata)

    monkeypatch.setattr(FileChunkChannel, "force", seen)

    async def _test(cluster):
        leader = await cluster.wait_for_leader()
        rt, primary = _chain_from_leader(cluster, leader)
        async with cluster.new_client() as client:
            out = await client.data_stream().stream(
                _stream_cmd("sync.bin"), routing_table=rt, primary=primary)
            await out.write_async(b"a" * P)
            await out.write_async(b"b" * P, sync=True)
            await out.write_async(b"c" * P)
            reply = await out.close_async()
            assert reply.success, reply.exception
            await cluster.wait_applied(reply.log_index)
        for div in cluster.divisions():
            assert div.state_machine.resolve("sync.bin").read_bytes() == \
                b"a" * P + b"b" * P + b"c" * P
        # each peer: the SYNC packet's force saw both packets landed, then
        # the CLOSE's saw all three
        assert sorted(forced) == [2 * P] * 3 + [3 * P] * 3

    run_with_new_cluster(3, _test, sm_factory=FileStoreStateMachine)
