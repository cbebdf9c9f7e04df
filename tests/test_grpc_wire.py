"""The gRPC transport as a deployment's wire (``ratis-3x1k-grpc``): the
registry finds it by its name, its counters count every message on every
path, its two work spans are on the servers' loop, the TCP counters are what
they were, and the cell holds against the plain reference on the CPU."""

import asyncio
import json
import os
import subprocess
import sys

import pytest

from minicluster import MiniCluster, run_with_new_cluster
from ratis_tpu.trace import get_tracer
from ratis_tpu.trace.tracer import STAGE_GRPC_WRITE, STAGE_NAMES

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "ratis-3x1k-grpc.write-closed"
WRITES = 12
GRPC_COUNTERS = ("grpc.messages_out", "grpc.messages_in", "grpc.chunks_out")


@pytest.fixture(autouse=True)
def _tracer_sandbox():
    tracer = get_tracer()
    yield
    tracer.configure(enabled=False)


def _rows(tracer, name):
    return tracer.rows(STAGE_NAMES.index(name)).tolist()


# ----------------------------------------------------------- the registry

def _fresh_interpreter(code: str) -> subprocess.CompletedProcess:
    return subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=120)


@pytest.mark.parametrize("rpc_type, factory", [
    ("GRPC", "GrpcTransportFactory"), ("grpc", "GrpcTransportFactory"),
    ("TCP", "TcpTransportFactory"), ("NETTY", "TcpTransportFactory")])
def test_the_registry_finds_a_transport_nobody_imported(rpc_type, factory):
    p = _fresh_interpreter(
        "import sys\n"
        "from ratis_tpu.transport.base import TransportFactory\n"
        "assert not [m for m in sys.modules if m.startswith("
        "'ratis_tpu.transport.') and m != 'ratis_tpu.transport.base'], "
        "sys.modules\n"
        f"print(type(TransportFactory.get({rpc_type!r})).__name__)\n")
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stdout.split() == [factory]


def test_an_unknown_rpc_type_still_raises_with_the_known_list():
    p = _fresh_interpreter(
        "from ratis_tpu.transport.base import TransportFactory\n"
        "TransportFactory.get('tcp')\n"
        "for name in ('CARRIER_PIGEON', 'no.such', '../tcp', 'base'):\n"
        "    try:\n"
        "        TransportFactory.get(name)\n"
        "    except ValueError as e:\n"
        "        print(e)\n")
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    assert len(lines) == 4
    for line, name in zip(lines, ("CARRIER_PIGEON", "no.such", "../tcp",
                                  "base")):
        assert line == (f"unsupported rpc type {name!r}; known: "
                        "['NETTY', 'TCP']")


# ------------------------------------------------- counters and work spans

def _writes_one_after_another(rpc_type: str, n: int = WRITES) -> dict:
    """``n`` INCREMENTs to one group of a 3-peer cluster over ``rpc_type``,
    each sent when the last is acknowledged, inside one trace session whose
    counters' deltas come back (servers and client share the process), with
    the wires' rows taken at the same moment (``rows``: what the cluster
    sends as it closes counts in neither)."""
    tracer = get_tracer()
    out = {}

    async def body(cluster: MiniCluster):
        await cluster.wait_for_leader()
        async with cluster.new_client() as client:
            assert (await client.io().send(b"INCREMENT")).success
            tracer.configure(enabled=True, sample_every=1, ring_size=4096)
            for _ in range(n):
                assert (await client.io().send(b"INCREMENT")).success
            out.update(tracer.session()["counters"])
            out["rows"] = {name: _rows(tracer, name) for name in
                           ("grpc.read", "grpc.write", "wire.flush")}

    run_with_new_cluster(3, body, rpc_type=rpc_type)
    return out


def test_the_wire_counters_count_every_grpc_message_on_every_path():
    n = WRITES
    c = _writes_one_after_another("GRPC")
    # a write: 2 appends out, 2 acks back, the client's reply (and here its
    # request, the client being in the process); each a message of its own
    assert c["wire.frames"] >= 5 * n
    assert c["wire.bytes"] >= 5 * n * len(b"INCREMENT")
    assert c["grpc.messages_out"] >= 2.5 * n
    assert c["grpc.messages_in"] >= 2.5 * n
    assert c["grpc.chunks_out"] >= c["grpc.messages_out"]
    # nothing batches at flush-micros 0, and every written message is read
    # by somebody in this process
    assert c["grpc.chunks_out"] == c["grpc.messages_out"] == c["wire.frames"]
    assert abs(c["grpc.messages_in"] - c["grpc.messages_out"]) <= 12


def test_a_trace_session_holds_grpc_read_and_write_rows_on_the_loop():
    import threading
    c = _writes_one_after_another("GRPC")
    reads, writes = c["rows"]["grpc.read"], c["rows"]["grpc.write"]
    # every stream message has a row at sample-every 1 (unary ones have none)
    assert len(writes) >= 5 * WRITES and len(reads) >= 5 * WRITES
    assert len(writes) <= c["grpc.messages_out"]
    assert len(reads) <= c["grpc.messages_in"]
    for r in reads + writes:
        assert r[3] >= 1                        # tag = chunks
        assert r[4] == threading.get_ident()    # the servers' loop's thread
    # a write's span is the call into grpc.aio, not the wait behind it: far
    # shorter than the writes' round trips
    assert sorted(r[2] for r in writes)[len(writes) // 2] < 5_000_000


def test_the_tcp_counters_of_the_same_writes_are_what_they_were():
    n = WRITES
    c = _writes_one_after_another("TCP")
    assert c["wire.frames"] >= 5 * n
    assert c["wire.bytes"] >= 5 * n * len(b"INCREMENT")
    # counted at the socket write: every frame went out in a wire.flush span
    assert sum(r[3] for r in c["rows"]["wire.flush"]) == c["wire.frames"]
    assert not any(c.get(name) for name in GRPC_COUNTERS)
    assert not c["rows"]["grpc.read"] and not c["rows"]["grpc.write"]


def test_a_head_span_covers_the_synchronous_head_of_an_awaitable():
    import time
    tracer = get_tracer()
    tracer.configure(enabled=True, sample_every=1, ring_size=64)

    async def slow(answer):
        time.sleep(0.02)                # the head: synchronous
        await asyncio.sleep(0.2)        # the rest: other callbacks' time
        if answer is None:
            raise KeyError("raised behind the head")
        return answer

    async def at_once():
        return "no suspension"

    async def main():
        assert await tracer.head(STAGE_GRPC_WRITE, slow(7), tag=3) == 7
        assert await tracer.head(STAGE_GRPC_WRITE, at_once()) \
            == "no suspension"
        with pytest.raises(KeyError):
            await tracer.head(STAGE_GRPC_WRITE, slow(None))
        with pytest.raises(asyncio.TimeoutError):
            await asyncio.wait_for(tracer.head(STAGE_GRPC_WRITE, slow(1)),
                                   0.05)

    asyncio.run(main())
    rows = _rows(tracer, "grpc.write")
    assert [r[3] for r in rows] == [3, 0, 0, 0]
    for r in (rows[0], rows[2], rows[3]):
        assert 15_000_000 <= r[2] < 150_000_000     # 20 ms, never 220
    assert rows[1][2] < 15_000_000


# --------------------------- the cell against the plain reference, on the CPU

def _rehearse(*extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "2147483999", "--seconds", "2",
         "--trace", "0", "--rehearse-cpu", "--groups", "16", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_the_cell_rehearsed_on_the_cpu_agrees_with_the_plain_reference():
    result = _rehearse()
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    assert set(result["compared"]) == {
        "never_answered", "answers_wrong", "groups_short_of_replicas",
        "device_rows_differing", "device_quorum_rows_wrong",
        "device_commit_advance_wrong", "groups_short_of_durable"}
    for c in result["compared"].values():
        assert c["value"] == c["limit"] == 0
    assert set(result["metrics"]) == {"commits_per_s", "commit_p50_ms",
                                      "commit_p75_ms", "setup_s"}


def test_the_memory_log_control_is_not_correct_by_durability_alone():
    result = _rehearse("--control", "memory-log")
    assert result["correct"] is False and result["failed"] == 0
    wrong = {k for k, c in result["compared"].items()
             if c["value"] > c["limit"]}
    assert wrong == {"groups_short_of_durable"}


# ------------------------------ a stream outlives its queued senders' deadlines

class _SlowCall:
    """A bidi call whose ``write`` takes ``write_s`` and whose peer never
    answers: what a stream looks like behind a busy loop."""

    def __init__(self, write_s: float):
        self.write_s, self.written, self.cancelled = write_s, [], False

    async def write(self, data: bytes) -> None:
        await asyncio.sleep(self.write_s)
        self.written.append(data)

    def __aiter__(self):
        return self

    async def __anext__(self):
        await asyncio.sleep(3600)

    def cancel(self):
        self.cancelled = True


def test_a_sender_timing_out_in_the_queue_does_not_kill_the_shared_stream():
    """Writes of one stream go one at a time.  A send whose deadline runs out
    while it waits its turn never reached the call, so the stream stays up
    for every other append; one cancelled MID-write leaves an abandoned
    write in the core, and that stream is done."""
    from ratis_tpu.transport.grpc import _AppendStreamClient

    async def main():
        call = _SlowCall(0.2)
        stream = _AppendStreamClient(lambda: call)
        first = asyncio.ensure_future(stream.send(b"first", 5.0))
        await asyncio.sleep(0.01)               # its write is under way
        with pytest.raises(asyncio.TimeoutError):
            await stream.send(b"queued", 0.05)  # times out behind it
        assert not stream.closed and not stream._out.poisoned
        await asyncio.sleep(0.3)
        assert len(call.written) == 1           # the queued chunk never went
        third = asyncio.ensure_future(stream.send(b"third", 5.0))
        await asyncio.sleep(0.3)
        assert len(call.written) == 2 and not stream.closed
        # now a deadline that falls inside the write itself
        with pytest.raises(asyncio.TimeoutError):
            await stream.send(b"mid-write", 0.05)
        assert stream.closed and stream._out.poisoned
        for pending in (first, third):          # failed with the stream
            with pytest.raises(Exception):
                await asyncio.wait_for(pending, 1.0)
        with pytest.raises(Exception):
            await stream.send(b"after", 1.0)
        await stream.close()
        assert call.cancelled

    asyncio.run(main())
