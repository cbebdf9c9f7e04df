"""The round-6 wire hot path: write coalescing + encode-once codec.

Covers the ISSUE 2 satellite test checklist:

- frame-coalescing unit tests of WriteCoalescer, which the gRPC transport
  uses (frame-count/latency threshold boundaries, flush-on-close,
  partial-batch failure poisons the connection not the loop, and the
  threshold-at-0 path is bit-identical to per-frame); the TCP transport's
  own framing is tests/test_tcp_framing.py;
- encode-once fan-out bit-identity vs the slow (generic msgpack) path;
- trace attribution across coalesced frames (per-stage spans survive);
- keyed-FIFO gRPC stream dispatch (same-group chunks keep arrival order).
"""

import asyncio

import msgpack
import pytest

from minicluster import MiniCluster, fast_properties, run_with_new_cluster
from ratis_tpu.conf.keys import WireConfigKeys
from ratis_tpu.transport.coalesce import WriteCoalescer

RPC = "NETTY"


# ------------------------------------------------------------- coalescer

class _FakeWriter:
    """StreamWriter stand-in recording write()/drain() activity."""

    def __init__(self, fail_after_drains: int = -1):
        self.chunks: list[bytes] = []
        self.drains = 0
        self.fail_after_drains = fail_after_drains

    def write(self, b: bytes) -> None:
        self.chunks.append(bytes(b))

    async def drain(self) -> None:
        if self.fail_after_drains >= 0 \
                and self.drains >= self.fail_after_drains:
            raise ConnectionResetError("peer went away mid-batch")
        self.drains += 1


class _WriterCoalescer(WriteCoalescer):
    """WriteCoalescer over a writer: the batch goes out as ONE buffered
    write followed by ONE drain (the generic class as the gRPC transport
    uses it, with bytes for chunks)."""

    def __init__(self, writer, **kw):
        super().__init__(**kw)
        self._writer = writer

    async def _flush_batch(self, frames: list) -> None:
        self._writer.write(b"".join(frames))
        await self._writer.drain()


def _coalescer(writer, **kw):
    return _WriterCoalescer(writer, **kw)


def test_thresholds_zero_is_per_frame_bit_identical():
    """The off-by-default-safe contract: flush thresholds at 0 produce one
    write + one drain per frame, and the byte stream equals the frame
    concatenation — exactly the pre-coalescing path."""

    async def main():
        w = _FakeWriter()
        c = _coalescer(w, flush_micros=0)
        frames = [b"frame-%d" % i for i in range(5)]
        for f in frames:
            await c.send(f)
        assert not c.coalescing
        assert w.chunks == frames          # one write per frame, in order
        assert w.drains == len(frames)     # one drain per frame
        assert b"".join(w.chunks) == b"".join(frames)
        assert c.metrics["flushes"] == 5
        assert c.metrics["coalesced_frames"] == 0

    asyncio.run(main())


def test_coalescing_batches_but_stream_is_identical():
    """Concurrent sends under coalescing fold into fewer flushes; the byte
    STREAM stays identical to the per-frame path."""

    async def main():
        w = _FakeWriter()
        c = _coalescer(w, flush_micros=100)
        frames = [b"frame-%d" % i for i in range(8)]
        await asyncio.gather(*(c.send(f) for f in frames))
        await c.aclose()
        assert b"".join(w.chunks) == b"".join(frames)  # bit-identical
        assert w.drains < len(frames)                  # actually coalesced
        assert c.metrics["coalesced_frames"] > 0

    asyncio.run(main())


def test_frame_threshold_boundary_flushes_immediately():
    """Reaching max_frames flushes inline (no latency wait): queue two
    frames, which reaches the threshold, with a huge flush_micros — the
    flush must not wait for the timer."""

    async def main():
        w = _FakeWriter()
        c = _coalescer(w, max_frames=2, flush_micros=10_000_000)
        t0 = asyncio.get_running_loop().time()
        await asyncio.gather(c.send(b"12345"), c.send(b"67890"))
        took = asyncio.get_running_loop().time() - t0
        assert took < 1.0, "frame-threshold flush waited on the timer"
        assert b"".join(w.chunks) == b"1234567890"
        await c.aclose()

    asyncio.run(main())


def test_latency_threshold_flushes_single_frame():
    """A lone sub-threshold frame flushes after flush_micros, not never."""

    async def main():
        w = _FakeWriter()
        c = _coalescer(w, flush_micros=5_000)
        await asyncio.wait_for(c.send(b"lonely"), 2.0)
        assert w.chunks == [b"lonely"]
        await c.aclose()

    asyncio.run(main())


def test_flush_on_close():
    """aclose() drains queued frames before the connection goes away."""

    async def main():
        w = _FakeWriter()
        c = _coalescer(w, flush_micros=5_000_000)
        t = asyncio.create_task(c.send(b"queued"))
        await asyncio.sleep(0)  # frame is pending, timer far away
        assert w.chunks == []
        await c.aclose()
        await t
        assert w.chunks == [b"queued"]

    asyncio.run(main())


def test_partial_batch_failure_poisons_connection_not_loop():
    """A drain failure mid-batch fails every send awaiting that batch and
    poisons the coalescer; later sends fail fast; nothing leaks into the
    event loop (the flusher task ends cleanly)."""

    async def main():
        w = _FakeWriter(fail_after_drains=0)
        c = _coalescer(w, flush_micros=100)
        results = await asyncio.gather(
            c.send(b"a"), c.send(b"b"), return_exceptions=True)
        assert all(isinstance(r, ConnectionResetError) for r in results)
        assert c.poisoned
        with pytest.raises(ConnectionResetError):
            await c.send(b"c")
        # the flusher died CLEANLY (no exception escaped to the loop)
        await asyncio.sleep(0.01)
        assert c._flusher is None

    asyncio.run(main())


# -------------------------------------------------- encode-once fast path

def _fanout_case():
    from ratis_tpu.protocol.ids import RaftGroupId, RaftPeerId
    from ratis_tpu.protocol.logentry import (make_config_entry,
                                             make_metadata_entry,
                                             make_transaction_entry)
    from ratis_tpu.protocol.peer import RaftPeer
    from ratis_tpu.protocol.raftrpc import (AppendEntriesRequest,
                                            RaftRpcHeader)
    from ratis_tpu.protocol.termindex import TermIndex
    gid = RaftGroupId.random_id()
    entries = (
        make_transaction_entry(3, 10, b"c" * 16, 42, b"x" * 300),
        make_transaction_entry(3, 11, b"c" * 16, 43, b"y" * 70_000,
                               sm_data=b"z" * 10),
        make_transaction_entry(3, 12, b"c" * 16, 44, b"",
                               is_datastream=True),
        make_config_entry(3, 13, [RaftPeer(RaftPeerId.value_of("s1"),
                                           address="10.0.0.1:5")]),
        make_metadata_entry(2 ** 40, 14, 9),
    )
    reqs = tuple(
        AppendEntriesRequest(
            RaftRpcHeader(RaftPeerId.value_of("s0"),
                          RaftPeerId.value_of(rp), gid, 7),
            3, TermIndex(2, 9), entries, 8, False,
            (("s1", 5), ("s2", -1)))
        for rp in ("s1", "s2", "s3", "s4"))
    return entries, reqs


def _slow_encode(msg):
    from ratis_tpu.protocol.raftrpc import _TYPE_TAGS
    return msgpack.packb({"_": _TYPE_TAGS[type(msg)], "b": msg.to_dict()},
                         use_bin_type=True)


def test_encode_once_fanout_bit_identity():
    """The spliced fast path is byte-identical to the generic packer for
    the whole per-follower fan-out, envelopes included, and round-trips
    through decode_rpc."""
    from ratis_tpu.protocol.raftrpc import (AppendEntriesRequest,
                                            AppendEnvelope, FANOUT_STATS,
                                            _encode, decode_rpc)
    from ratis_tpu.protocol.ids import RaftGroupId, RaftPeerId
    from ratis_tpu.protocol.raftrpc import RaftRpcHeader
    _entries, reqs = _fanout_case()
    fallback0 = FANOUT_STATS["fallback"]
    for msg in (*reqs, AppendEnvelope(reqs),
                # sequenced lane frame (round-9 append windows)
                AppendEnvelope(reqs, lane=(123 << 32) | 45, seq=6),
                # heartbeat: no entries, no previous
                AppendEntriesRequest(
                    RaftRpcHeader(RaftPeerId.value_of("s0"),
                                  RaftPeerId.value_of("s1"),
                                  RaftGroupId.random_id(), 0),
                    2 ** 35, None, (), -1, True, ())):
        fast = _encode(msg)
        assert fast == _slow_encode(msg)
        assert decode_rpc(fast).to_dict() == msg.to_dict()
    assert FANOUT_STATS["fallback"] == fallback0, \
        "fast path silently fell back"


def test_encode_once_reuses_suffix_across_followers():
    """Fanning one batch to N followers packs the suffix once: followers
    2..N hit the suffix cache (the encode-once contract, observable)."""
    from ratis_tpu.protocol.raftrpc import FANOUT_STATS, _encode
    _entries, reqs = _fanout_case()
    hits0 = FANOUT_STATS["suffix_hits"]
    for r in reqs:
        _encode(r)
    assert FANOUT_STATS["suffix_hits"] - hits0 >= len(reqs) - 1


def test_entry_wire_bytes_memoized_on_entry():
    from ratis_tpu.protocol.logentry import make_transaction_entry
    from ratis_tpu.protocol.raftrpc import entry_wire_bytes
    e = make_transaction_entry(1, 2, b"c" * 16, 3, b"payload")
    w1 = entry_wire_bytes(e)
    assert entry_wire_bytes(e) is w1  # second call returns the memo
    assert w1 == msgpack.packb(e.to_dict(), use_bin_type=True)


# ---------------------------------------------- keyed gRPC stream dispatch

def test_grpc_stream_keyed_fifo_dispatch():
    """Same-key chunks dispatch in strict arrival order even when the
    first suspends longer (ADVICE r5: differing await points reordered
    same-group appends); distinct keys stay concurrent."""
    from ratis_tpu.protocol.ids import RaftPeerId
    from ratis_tpu.transport.grpc import GrpcServerTransport

    async def main():
        t = GrpcServerTransport(RaftPeerId.value_of("s0"), "127.0.0.1:0",
                                None, None, flush_micros=0)
        order: list[str] = []

        def classify(payload: bytes):
            name = payload.decode()
            return name, ("k", name[0])  # key by first letter

        async def dispatch(name: str) -> bytes:
            # the FIRST chunk of each key suspends longest: unordered
            # dispatch would finish a1/b1 AFTER a2/b2
            await asyncio.sleep(0.05 if name.endswith("1") else 0.0)
            order.append(name)
            return name.encode()

        async def chunks():
            for i, name in enumerate(("a1", "a2", "b1", "b2")):
                yield msgpack.packb([i, name.encode()])

        replies = []

        async def write(item: bytes) -> None:
            replies.append(msgpack.unpackb(item))

        await t._serve_stream(chunks(), write, dispatch, classify=classify)
        assert order.index("a1") < order.index("a2")
        assert order.index("b1") < order.index("b2")
        assert {r[0] for r in replies} == {0, 1, 2, 3}
        assert t.dispatch_metrics["keyed_chunks"] == 4
        assert t.dispatch_metrics["ordered_waits"] >= 2

    asyncio.run(main())


def test_grpc_stream_accepts_coalesced_chunk_batches():
    """One inbound stream message carrying a BATCH of chunks dispatches
    each chunk and answers every call id (the raft.tpu.grpc framing)."""
    from ratis_tpu.protocol.ids import RaftPeerId
    from ratis_tpu.transport.grpc import GrpcServerTransport

    async def main():
        t = GrpcServerTransport(RaftPeerId.value_of("s0"), "127.0.0.1:0",
                                None, None, flush_micros=100)

        async def dispatch(payload: bytes) -> bytes:
            return b"ok-" + payload

        async def chunks():
            yield msgpack.packb([[0, b"a"], [1, b"b"], [2, b"c"]])

        got = {}

        async def write(item: bytes) -> None:
            decoded = msgpack.unpackb(item)
            triples = (decoded if decoded
                       and isinstance(decoded[0], (list, tuple))
                       else [decoded])
            for call_id, status, payload in triples:
                got[call_id] = (status, payload)

        await t._serve_stream(chunks(), write, dispatch)
        assert got == {0: (0, b"ok-a"), 1: (0, b"ok-b"), 2: (0, b"ok-c")}
        assert t.dispatch_metrics["batched_messages"] == 1

    asyncio.run(main())


# ------------------------------------------- end-to-end over real sockets

def _coalescing_properties():
    p = fast_properties()
    p.set(WireConfigKeys.Grpc.FLUSH_MICROS_KEY, "100")
    return p


def test_tcp_cluster_with_the_batched_wire():
    """Full consensus over real TCP sockets (one write per loop pass, which
    the TCP transport always does): writes commit, reads see them."""

    async def t(cluster: MiniCluster):
        async with cluster.new_client() as client:
            for _ in range(8):
                assert (await client.io().send(b"INCREMENT")).success
            r = await client.io().send_read_only(b"GET")
            assert r.message.content == b"8"

    run_with_new_cluster(3, t, rpc_type=RPC,
                         properties=_coalescing_properties())


def test_grpc_cluster_with_coalescing_on():
    """Same over the gRPC transport: batched stream framing end to end."""

    async def t(cluster: MiniCluster):
        async with cluster.new_client() as client:
            for _ in range(8):
                assert (await client.io().send(b"INCREMENT")).success
            r = await client.io().send_read_only(b"GET")
            assert r.message.content == b"8"

    run_with_new_cluster(3, t, rpc_type="GRPC",
                         properties=_coalescing_properties())


def test_trace_attribution_survives_coalescing():
    """Coalesced frames still produce per-stage spans: with tracing on, a
    traced request over TCP records decode, the full server tiling, and
    the respond span (the reply queued for its pass's one write)."""
    from ratis_tpu.trace import get_tracer
    from ratis_tpu.trace.tracer import (STAGE_APPEND, STAGE_APPLY,
                                        STAGE_CLIENT, STAGE_DECODE,
                                        STAGE_REPLICATE, STAGE_RESPOND,
                                        STAGE_ROUTE)
    tracer = get_tracer()
    tracer.configure(enabled=True, sample_every=1, ring_size=1024)
    try:
        async def t(cluster: MiniCluster):
            async with cluster.new_client() as client:
                for _ in range(4):
                    assert (await client.io().send(b"INCREMENT")).success

        run_with_new_cluster(3, t, rpc_type=RPC,
                             properties=_coalescing_properties())
        by_stage: dict[int, set[int]] = {}
        for tid, stage, _t0, _dur, _tag, _origin in tracer.snapshot():
            if tid:
                by_stage.setdefault(stage, set()).add(tid)
        full = (by_stage.get(STAGE_CLIENT, set())
                & by_stage.get(STAGE_DECODE, set())
                & by_stage.get(STAGE_ROUTE, set())
                & by_stage.get(STAGE_APPEND, set())
                & by_stage.get(STAGE_REPLICATE, set())
                & by_stage.get(STAGE_APPLY, set())
                & by_stage.get(STAGE_RESPOND, set()))
        assert full, ("coalescing lost span attribution: "
                      f"{ {k: len(v) for k, v in by_stage.items()} }")
    finally:
        tracer.configure(enabled=False)
