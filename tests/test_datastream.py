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
from ratis_tpu.transport.datastream import (FLAG_CLOSE, FLAG_PRIMARY,
                                            FLAG_SYNC, KIND_DATA,
                                            KIND_HEADER, Packet,
                                            encode_packet, read_packet)
from tests.minicluster import run_with_new_cluster


def _pid(s):
    return RaftPeerId.value_of(s)


def test_packet_roundtrip():
    async def _run():
        p = Packet(KIND_DATA, 12345, 678, FLAG_SYNC | FLAG_CLOSE, b"payload")
        reader = asyncio.StreamReader()
        reader.feed_data(encode_packet(p))
        reader.feed_eof()
        q = await read_packet(reader)
        assert q == p
        assert q.is_sync and q.is_close
        assert await read_packet(reader) is None  # clean EOF

    asyncio.run(_run())


def test_packet_truncation_raises():
    async def _run():
        p = Packet(KIND_HEADER, 1, 0, FLAG_PRIMARY, b"x" * 100)
        raw = encode_packet(p)
        reader = asyncio.StreamReader()
        reader.feed_data(raw[:len(raw) - 5])
        reader.feed_eof()
        with pytest.raises((ConnectionError, asyncio.IncompleteReadError)):
            await read_packet(reader)

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


def test_a_close_drains_a_packet_done_but_not_yet_discarded(monkeypatch):
    """The CLOSE may run in the loop pass after a packet's completion task
    finished and before its done-callback took it out of ``pending``: the
    drain takes it as done, and does not spin on a gather that never
    yields (a hang, seen beside a shard-pinned stream)."""
    from ratis_tpu.server import datastream

    gather, calls = asyncio.gather, []

    def counted(*futs, **kwargs):
        calls.append(1)
        if len(calls) > 100:
            raise AssertionError("the drain spins")
        return gather(*futs, **kwargs)

    async def _run():
        mgmt = datastream.DataStreamManagement.__new__(
            datastream.DataStreamManagement)
        info = datastream.StreamInfo(None, True, DataStream(DataChannel()),
                                     [])
        done = asyncio.get_running_loop().create_future()
        done.set_result(None)
        info.pending.add(done)      # finished; its discard not yet run
        mgmt._streams = {7: info}
        monkeypatch.setattr(datastream.asyncio, "gather", counted)
        await mgmt._on_close_data(Packet(KIND_DATA, 7, 0, FLAG_CLOSE, b""))

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
    (their offsets move) while no peer's write has landed and the client
    has no ack; let go, the stream completes and every file is whole."""
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
