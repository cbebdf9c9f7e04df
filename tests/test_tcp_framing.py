"""The TCP transport's framed connection (transport/tcp.py:_FramedProtocol):
one ``asyncio.Protocol`` for both ends.  Every whole frame a read holds is
parsed in that read's callback; what one loop pass queues leaves in one
``transport.write``; flow control, failure and close keep the contract the
module's docstring states.  Driven over a recording transport, and once over
real sockets."""

import asyncio
import threading

import pytest

from ratis_tpu.protocol.exceptions import RaftException, TimeoutIOException
from ratis_tpu.protocol.ids import RaftPeerId
from ratis_tpu.transport import tcp
from ratis_tpu.transport.tcp import (KIND_CLIENT_REQUEST, KIND_ERROR,
                                     KIND_REPLY, KIND_SERVER_RPC,
                                     _encode_frame)


class _Transport:
    """Stands where the socket transport does: keeps what was written."""

    def __init__(self, fail_writes: bool = False):
        self.writes: list[bytes] = []
        self.fail_writes = fail_writes
        self.closed = self.aborted = False

    def write(self, data) -> None:
        if self.fail_writes:
            raise ConnectionResetError("peer went away mid-batch")
        self.writes.append(bytes(data))

    def close(self) -> None:
        self.closed = True

    def abort(self) -> None:
        self.aborted = True


class _Recorder(tcp._FramedProtocol):
    def __init__(self):
        super().__init__("recorder")
        self.frames: list = []
        self.lost: list = []

    def _frame(self, call_seq, kind, body):
        self.frames.append((call_seq, kind, body))

    def _lost(self, exc):
        self.lost.append(exc)


def _made(protocol, **kw):
    transport = _Transport(**kw)
    protocol.connection_made(transport)
    return transport


def _run(main, loop_errors=None):
    """Run ``main()``; whatever reaches the loop's exception handler lands in
    ``loop_errors`` (the loop must survive a connection's failure)."""
    async def wrapped():
        if loop_errors is not None:
            asyncio.get_running_loop().set_exception_handler(
                lambda loop, ctx: loop_errors.append(ctx))
        return await main()
    return asyncio.run(wrapped())


async def _passes(n: int = 3) -> None:
    for _ in range(n):
        await asyncio.sleep(0)


FRAMES = [(1, KIND_SERVER_RPC, b""),                  # the shortest frame
          (2, KIND_CLIENT_REQUEST, b"INCREMENT"),
          (2 ** 63 + 5, KIND_REPLY, bytes(range(256)) * 3),
          (4, KIND_ERROR, b"x")]
STREAM = b"".join(_encode_frame(*f) for f in FRAMES)


# ------------------------------------------------------------------- read

@pytest.mark.parametrize("chunk", [1, 2, 3, 4, 5, 12, 13, 14, 64, len(STREAM)])
def test_reads_of_any_size_parse_to_the_same_frames(chunk):
    async def main():
        p = _Recorder()
        _made(p)
        for i in range(0, len(STREAM), chunk):
            p.data_received(STREAM[i:i + chunk])
        assert p.frames == FRAMES
        assert all(type(body) is bytes for _, _, body in p.frames)
        assert not p._rbuf and p.dead is None

    _run(main)


def test_a_stream_cut_at_every_byte_boundary_parses_to_the_same_frames():
    async def main():
        for cut in range(1, len(STREAM)):
            p = _Recorder()
            _made(p)
            p.data_received(STREAM[:cut])
            whole = [f for f in FRAMES
                     if STREAM.index(_encode_frame(*f))
                     + len(_encode_frame(*f)) <= cut]
            assert p.frames == whole, cut    # every whole frame, at once
            p.data_received(STREAM[cut:])
            assert p.frames == FRAMES, cut

    _run(main)


@pytest.mark.parametrize("length", [0, 8, tcp.MAX_FRAME + 1])
def test_a_bad_frame_length_kills_the_connection_not_the_loop(length):
    errors = []

    async def main():
        p = _Recorder()
        t = _made(p)
        good = _encode_frame(7, KIND_REPLY, b"ok")
        p.data_received(good + tcp._FRAME.pack(length, 1, 1) + b"rest")
        assert p.frames == [(7, KIND_REPLY, b"ok")]
        assert isinstance(p.dead, ConnectionError) and t.aborted
        assert p.lost == [p.dead]
        with pytest.raises(ConnectionError):
            p.send(b"later")
        await _passes()

    _run(main, errors)
    assert errors == []


# ------------------------------------------------------------------ write

@pytest.mark.parametrize("n", [1, 2, 8, 200])
def test_frames_queued_in_one_pass_leave_in_one_write(n):
    async def main():
        p = _Recorder()
        t = _made(p)
        frames = [_encode_frame(i, KIND_REPLY, b"frame-%d" % i)
                  for i in range(n)]
        for f in frames:
            p.send(f)
        assert t.writes == [] and p.flush_armed   # nothing before the pass ends
        await _passes()
        assert t.writes == [b"".join(frames)]     # ONE write, frames in order
        second = [_encode_frame(n + i, KIND_REPLY, b"next") for i in range(n)]
        for f in second:
            p.send(f)
        await _passes()
        assert t.writes == [b"".join(frames), b"".join(second)]
        # and the peer parses the same frames back
        q = _Recorder()
        _made(q)
        q.data_received(b"".join(t.writes))
        assert [_encode_frame(*f) for f in q.frames] == frames + second

    _run(main)


def test_the_wire_counters_count_at_the_write():
    from ratis_tpu.trace import get_tracer
    from ratis_tpu.trace.tracer import loop_key

    async def main():
        key = loop_key()
        frames = get_tracer().counter("wire.frames", key)
        nbytes = get_tracer().counter("wire.bytes", key)
        # (a loop of an earlier test may have had this loop's id, and key)
        before = (frames.n, nbytes.n)
        p = _Recorder()
        _made(p)
        p.send(b"12345")
        p.send(b"678")
        assert (frames.n - before[0], nbytes.n - before[1]) == (0, 0)
        await _passes()
        assert (frames.n - before[0], nbytes.n - before[1]) == (2, 8)

    _run(main)


@pytest.mark.parametrize("how", ["close", "peer-eof"])
def test_queued_frames_go_out_before_the_connection_goes(how):
    async def main():
        p = _Recorder()
        t = _made(p)
        p.send(b"queued-1")
        p.send(b"queued-2")
        if how == "close":
            p.close_nowait()
            assert t.closed
            with pytest.raises(ConnectionError):
                p.send(b"after close")
        else:
            assert p.eof_received() is False      # the transport then closes
        assert t.writes == [b"queued-1queued-2"]
        p.connection_lost(None)
        await p.close()                           # returns: already lost
        assert t.writes == [b"queued-1queued-2"]

    _run(main)


# -------------------------------------------------- calls on a connection

def _reply(conn, seq, body=b"pong", kind=KIND_REPLY):
    conn.data_received(_encode_frame(seq, kind, body))


def test_replies_resolve_their_calls_in_the_read_callback():
    async def main():
        c = tcp._Connection("peer:1")
        t = _made(c)
        calls = [asyncio.create_task(c.call(KIND_SERVER_RPC, b"ping-%d" % i,
                                            5.0)) for i in range(3)]
        await _passes()
        assert t.writes == [b"".join(
            _encode_frame(i + 1, KIND_SERVER_RPC, b"ping-%d" % i)
            for i in range(3))]
        # all three replies in one read, out of order
        c.data_received(_encode_frame(3, KIND_REPLY, b"c")
                        + _encode_frame(1, KIND_ERROR, b"a")
                        + _encode_frame(2, KIND_REPLY, b"b"))
        assert not c._pending     # resolved there, not a pass later
        assert await asyncio.gather(*calls) == [
            (KIND_ERROR, b"a"), (KIND_REPLY, b"b"), (KIND_REPLY, b"c")]
        _reply(c, 99)       # a reply nobody waits for is dropped

    _run(main)


def test_pause_writing_holds_senders_and_resume_releases_them():
    """Write, then drain: frames queue in call order whatever the transport
    says; while it is paused each sender waits, on the connection's ONE
    future, and goes on when the transport resumes."""
    async def main():
        c = tcp._Connection("peer:1")
        t = _made(c)
        c.pause_writing()
        assert c.paused
        calls = [asyncio.create_task(c.call(KIND_SERVER_RPC, b"held", 5.0))
                 for _ in range(4)]
        await _passes()
        assert t.writes == [b"".join(
            _encode_frame(seq, KIND_SERVER_RPC, b"held")
            for seq in (1, 2, 3, 4))]
        for seq in (1, 2, 3, 4):
            _reply(c, seq)          # answered, and still held
        held = c._writable
        calls[3].cancel()           # one waiter gives up: the others stay
        await _passes(5)
        assert calls[3].cancelled() and not held.done()
        assert not any(call.done() for call in calls[:3])
        c.resume_writing()
        assert held.done() and not c.paused
        assert await asyncio.wait_for(asyncio.gather(*calls[:3]), 2.0) == [
            (KIND_REPLY, b"pong")] * 3
        # not paused: a call never touches the future
        assert await asyncio.gather(
            c.call(KIND_SERVER_RPC, b"free", 5.0), _answer(c, 5)) == [
                (KIND_REPLY, b"pong"), None]

    _run(main)


async def _answer(conn, seq):
    await _passes()
    _reply(conn, seq)


@pytest.mark.parametrize("how", ["connection-lost", "write-fails",
                                 "lost-while-paused"])
def test_a_dead_connection_fails_every_pending_call_and_is_poisoned(how):
    errors = []

    async def main():
        c = tcp._Connection("peer:1")
        t = _made(c, fail_writes=(how == "write-fails"))
        if how == "lost-while-paused":
            c.pause_writing()
        calls = [asyncio.create_task(c.call(KIND_SERVER_RPC, b"x", 5.0))
                 for _ in range(3)]
        await _passes()
        if how != "write-fails":
            c.connection_lost(ConnectionResetError("reset by peer"))
        results = await asyncio.wait_for(
            asyncio.gather(*calls, return_exceptions=True), 2.0)
        assert all(isinstance(r, ConnectionError) for r in results), results
        assert not c.alive and not c._pending and c._timer is None
        assert t.aborted == (how == "write-fails")
        with pytest.raises(ConnectionError):       # later sends fail fast
            await c.call(KIND_SERVER_RPC, b"y", 5.0)
        await _passes()

    _run(main, errors)
    assert errors == []         # nothing escaped into the loop


def test_a_call_times_out_with_timeout_io_exception_under_one_timer():
    async def main():
        c = tcp._Connection("peer:1")
        _made(c)
        slow = asyncio.create_task(c.call(KIND_SERVER_RPC, b"slow", 30.0))
        await _passes()
        timer = c._timer
        quick = asyncio.create_task(c.call(KIND_SERVER_RPC, b"quick", 0.05))
        other = asyncio.create_task(c.call(KIND_SERVER_RPC, b"other", 0.05))
        await _passes()
        assert c._timer is not timer and timer.cancelled()  # moved, not added
        t0 = asyncio.get_running_loop().time()
        for call in (quick, other):
            with pytest.raises(TimeoutIOException, match="peer:1"):
                await call
        assert asyncio.get_running_loop().time() - t0 < 1.0
        assert list(c._pending) == [1] and c._timer is not None  # over `slow`
        _reply(c, 1)
        assert await slow == (KIND_REPLY, b"pong")
        _reply(c, 2)            # the late reply of a timed-out call: dropped
        slow2 = asyncio.create_task(c.call(KIND_SERVER_RPC, b"z", 30.0))
        await _passes()
        slow2.cancel()          # a cancelled caller leaves nothing pending
        await _passes()
        assert not c._pending

    _run(main)


# ------------------------------------------------------ the accepted side

class _Server:
    """What an accepted connection needs of its TcpServerTransport."""
    peer_id = "s0"
    defer_replies = False

    def __init__(self, serve_one):
        self._accepted = set()
        self._serve_one = serve_one


def test_a_request_that_never_suspends_is_served_inside_the_read():
    async def main():
        seen = []
        gate = asyncio.Event()

        async def serve_one(call_seq, kind, body, conn):
            seen.append(call_seq)
            if body == b"wait":
                await gate.wait()
            conn.send(_encode_frame(call_seq, KIND_REPLY, body))

        srv = _Server(serve_one)
        a = tcp._Accepted(srv)
        t = _made(a)
        assert srv._accepted == {a}
        a.data_received(_encode_frame(1, KIND_SERVER_RPC, b"now")
                        + _encode_frame(2, KIND_SERVER_RPC, b"wait")
                        + _encode_frame(3, KIND_SERVER_RPC, b"now"))
        # all three started in frame order inside the callback; the two that
        # never suspended are done and their replies queued for this pass
        assert seen == [1, 2, 3]
        assert a._out == [_encode_frame(1, KIND_REPLY, b"now"),
                          _encode_frame(3, KIND_REPLY, b"now")]
        assert len(a._tasks) == 1       # only the suspended one is a task
        await _passes()
        assert t.writes == [_encode_frame(1, KIND_REPLY, b"now")
                            + _encode_frame(3, KIND_REPLY, b"now")]
        gate.set()
        await _passes()
        assert not a._tasks and len(t.writes) == 2
        # a lost connection cancels what still runs and leaves the server
        a.data_received(_encode_frame(4, KIND_SERVER_RPC, b"wait"))
        gate.clear()
        a.data_received(_encode_frame(5, KIND_SERVER_RPC, b"wait"))
        (task,) = a._tasks
        a.connection_lost(None)
        await _passes()
        assert task.cancelled() and srv._accepted == set()

    _run(main)


class _ReplyStub:
    def __init__(self, body):
        self.body = body

    def to_bytes(self):
        return self.body


@pytest.mark.parametrize("caller", ["same-loop", "another-thread"])
def test_deferred_replies_join_the_pass_or_cross_loops_once(caller):
    async def main():
        loop = asyncio.get_running_loop()
        a = tcp._Accepted(_Server(None))
        t = _made(a)
        fanout = tcp._DeferredReplyFanout(a)
        threadsafe = []
        real = loop.call_soon_threadsafe
        loop.call_soon_threadsafe = lambda *args: (threadsafe.append(args),
                                                   real(*args))[1]
        replies = [(seq, _ReplyStub(b"reply-%d" % seq)) for seq in (5, 6, 7)]

        def submit_all():
            for seq, reply in replies:
                fanout.sink_for(seq)(reply)

        if caller == "same-loop":
            submit_all()
            assert len(a._out) == 3 and threadsafe == []   # no hop, no pipe
        else:
            th = threading.Thread(target=submit_all)
            th.start()
            th.join(5.0)
            assert not th.is_alive()
            assert a._out == [] and len(threadsafe) == 1   # one per burst
        await _passes(4)
        assert t.writes == [b"".join(
            _encode_frame(seq, KIND_REPLY, r.body) for seq, r in replies)]
        a.connection_lost(None)
        fanout.submit(8, _ReplyStub(b"late"))       # dropped, not raised
        await _passes()

    _run(main)


# ------------------------------------------------------- over real sockets

@pytest.mark.parametrize("size", [0, 150, 70_000, 1_500_000])
def test_round_trips_over_real_sockets(size, monkeypatch):
    """Two transports on one loop as in every cell; bodies from empty to
    well over one read (a frame then straddles many ``data_received``)."""
    monkeypatch.setattr(tcp, "encode_rpc", lambda msg: msg)
    monkeypatch.setattr(tcp, "decode_rpc", lambda body: body)

    async def main():
        order = []

        async def handler(msg: bytes):
            order.append(msg[:8])
            if msg.startswith(b"refuse"):
                raise RaftException("refused " + msg[:8].decode())
            if len(order) % 2:
                await asyncio.sleep(0)
            return msg[::-1]

        srv = tcp.TcpServerTransport(RaftPeerId.value_of("s0"),
                                     "127.0.0.1:0", handler, None)
        await srv.start()
        cli = tcp.TcpServerTransport(RaftPeerId.value_of("s1"),
                                     "127.0.0.1:0", None, None,
                                     peer_resolver=lambda to: srv.address)
        try:
            msgs = [b"msg-%04d" % i + bytes([i]) * size for i in range(24)]
            got = await asyncio.wait_for(asyncio.gather(
                *(cli.send_server_rpc("s0", m) for m in msgs)), 30.0)
            assert got == [m[::-1] for m in msgs]
            assert order == [m[:8] for m in msgs]   # frames keep their order
            with pytest.raises(RaftException, match="refused refuse-1"):
                await cli.send_server_rpc("s0", b"refuse-1")
            assert len(cli._pool._conns) == 1       # all on one connection
            (conn,) = cli._pool._conns.values()
            # the server goes: calls fail as timeouts of the RPC layer, and
            # the pool dials anew once there is a server again
            await srv.close()
            assert srv._accepted == set()
            with pytest.raises(TimeoutIOException):
                await cli.send_server_rpc("s0", b"anyone?")
            assert not conn.alive
        finally:
            await cli.close()
            await srv.close()

    _run(main)
