"""The round-6 wire hot path: write coalescing + encode-once codec.

Covers the ISSUE 2 satellite test checklist:

- frame-coalescing unit tests of WriteCoalescer, which the gRPC transport
  uses (frame-count/latency threshold boundaries, flush-on-close,
  partial-batch failure poisons the connection not the loop, and the
  threshold-at-0 path is bit-identical to per-frame); the TCP transport's
  own framing is tests/test_tcp_framing.py;
- encode-once fan-out bit-identity vs the slow (generic msgpack) path;
- trace attribution across coalesced frames (per-stage spans survive);
- keyed-FIFO gRPC stream dispatch (same-group chunks keep arrival order);
- the bench's one-line JSON stays inside the driver's 2000-char window.
"""

import asyncio
import json

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
            await c.send(f, len(f))
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
        await asyncio.gather(*(c.send(f, len(f)) for f in frames))
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
        await asyncio.gather(c.send(b"12345", 5), c.send(b"67890", 5))
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
        await asyncio.wait_for(c.send(b"lonely", 6), 2.0)
        assert w.chunks == [b"lonely"]
        await c.aclose()

    asyncio.run(main())


def test_flush_on_close():
    """aclose() drains queued frames before the connection goes away."""

    async def main():
        w = _FakeWriter()
        c = _coalescer(w, flush_micros=5_000_000)
        t = asyncio.create_task(c.send(b"queued", 6))
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
            c.send(b"a", 1), c.send(b"b", 1), return_exceptions=True)
        assert all(isinstance(r, ConnectionResetError) for r in results)
        assert c.poisoned
        with pytest.raises(ConnectionResetError):
            await c.send(b"c", 1)
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
        async for item in t._serve_stream(chunks(), dispatch,
                                          classify=classify):
            replies.append(msgpack.unpackb(item))
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
        async for item in t._serve_stream(chunks(), dispatch):
            decoded = msgpack.unpackb(item)
            triples = (decoded if decoded
                       and isinstance(decoded[0], (list, tuple))
                       else [decoded])
            for call_id, status, payload in triples:
                got[call_id] = (status, payload)
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


# ------------------------------------------------- bench line stays small

def test_bench_summary_line_fits_driver_window():
    """The one-line bench JSON must parse from the driver's 2000-char tail
    capture (BENCH_r05.json overflowed it: parsed null).  Fill every rung
    with worst-case-width synthetic numbers and assert the line fits."""
    import bench

    def rung(**extra):
        out = {"commits_per_sec": 123456.8, "p50_ms": 99999.99,
               "p99_ms": 99999.99, "election_convergence_s": 9999.99,
               "write_failures": 0, "engine_occupancy": 0.9999,
               "watchdog_events": 99999, "reply_hops_per_commit": 99.999,
               "window_occupancy": 0.9999}
        out.update(extra)
        return out

    decomp = {"coverage": 0.975, "stages": {
        name: {"p50_us": 123456.7}
        for name in ("server.route", "server.txn_start", "server.append",
                     "server.replicate", "server.apply", "server.reply",
                     "server.respond")}}
    trials = [rung() for _ in range(5)]
    summary = bench._summarize(
        headline=trials, scalar=trials,
        ladder={1: trials[:2], 64: trials[:2], 1024: trials[:3],
                10_240: trials[:2]},
        mesh_trials=trials[:2],
        peer5=rung(host_path_decomposition=decomp,
                   mp={"server_procs": 5, "client_procs": 4,
                       "loop_shards": 3}),
        peer5_sp=rung(), peer5_mp=rung(),
        peer5_scalar=rung(),
        peer5_grpc=rung(), peer5_grpc_scalar=rung(),
        peer7=rung(host_path_decomposition=decomp),
        sparse_hib=rung(hibernated_groups=10240), sparse_plain=rung(),
        churn=rung(transfers_ok=64, transfers_failed=64),
        mixed=rung(streams_ok=32, stream_mb_per_s=99999.99),
        mixed_fs={"pergroup": rung(stream_mb_per_s=99999.99,
                                   fsyncs_per_commit=99.9999),
                  "shared": rung(stream_mb_per_s=99999.99,
                                 fsyncs_per_commit=99.9999),
                  "pergroup_5ms": rung(stream_mb_per_s=99999.99,
                                       fsyncs_per_commit=99.9999),
                  "shared_5ms": rung(stream_mb_per_s=99999.99,
                                     fsyncs_per_commit=99.9999)},
        stream=rung(stream_mb_per_s=99999.99),
        grpc_b=trials[:3], grpc_s_1024=rung(), grpc_s_256=rung(),
        kernel={"group_updates_per_sec": 1330708656.5,
                "vs_scalar_loop": 99126.85, "platform": "TPU v5 lite0"},
        kernel_100k={"group_updates_per_sec_100k": 1333027867.0},
        mesh100k={"groups": 102400, "devices": 8,
                  "updates_per_s": 1333027867.9, "tick_ms": 99999.99,
                  "efficiency_frac": 0.999},
        tpu_e2e=rung(device={"platform": "tpu", "kind": "TPU v5 lite",
                             "count": 1}),
        traced=rung(host_path_decomposition=decomp),
        filestore5=rung(streams_ok=32, stream_mb_per_s=99999.99),
        readmix=rung(reads_per_sec=123456.8, read_p99_ms=99999.99,
                     reads_lease_leader=99999,
                     reads_follower_linearizable=99999,
                     reads_stale=99999),
        snapcatch=rung(catchup_s=9999.99, installs=10240,
                       cps_before=123456.8),
        zipf=rung(writes_per_sec=123456.8, reads_per_sec=123456.8,
                  shed_frac=0.9999),
        placement={"hotspot_p99_before_ms": 99999.99,
                   "hotspot_p99_after_ms": 99999.99,
                   "transfers": 99999, "grey_steer_frac": 0.9999},
        win_sweep={str(d): [123456.8, 99999.99, 0.9999]
                   for d in (1, 4, 16)},
        chaos={"passed": 9, "total": 9, "worst_reelect_s": 9999.999,
               "recovery_frac": 99.999, "fault_events": 99999},
        tel_on=rung(telemetry={"samples": 99999,
                               "sample_cost_p99_ms": 9999.999,
                               "hot_share": 0.9999,
                               "hot_group": "group-aabbccdd",
                               "sampler_pass_ms": 9999.999,
                               "ledger_fetch_ms": 9999.999,
                               "walk_pass_ms": 9999.999}),
        tel_off=rung(),
        # realistic-worst width: the idle scan measures in MICROseconds
        # (tests/test_upkeep.py); 9.999ms is already a 1000x degradation
        upkeep=[9.999, 9.999, 0.99])
    line = json.dumps(summary, separators=(",", ":"))
    assert len(line) < 2000, f"bench line would overflow: {len(line)} chars"
    parsed = json.loads(line)
    assert parsed["value"] == 123456.8
    assert parsed["vs_baseline"] == 1.0
    assert parsed["secondary"]["p5_10240"]["vs_scalar"] == 1.0
    assert parsed["secondary"]["p5_10240"]["mp"] == [5, 3, 4]
    assert parsed["secondary"]["p5_fs"][2] == 32
    # durable mixed rung: [pg c/s, pg f/c, shared c/s, shared MB/s,
    # shared f/c, speedup] + the modeled-disk pair [pg, shared, speedup]
    assert parsed["secondary"]["mix_fs"][5] == 1.0
    assert parsed["secondary"]["mix_5ms"][2] == 1.0
    assert parsed["secondary"]["readmix"][1] == 123456.8
    assert parsed["secondary"]["snap_1024"][1] == 10240
    # round-12 zipf fleet rung: [writes/s, reads/s, shed frac, p99 ms]
    assert parsed["secondary"]["zipf"] == [
        123456.8, 123456.8, 0.9999, 99999.99]
    # round-16 placement closed loop: [hot p99 OFF, ON, transfers,
    # grey steer fraction]
    assert parsed["secondary"]["placement"] == [
        99999.99, 99999.99, 99999, 0.9999]
    # observability keys: [engine occupancy, watchdog event count,
    # reply-plane scheduling hops per commit (round-8 fan-out collapse),
    # append-window occupancy (round-9 pipelined windows), the round-11
    # telemetry-on/off overhead pair, the headline hot-group skew, and
    # the round-14 lag-ledger cost pair [sampler pass p50 ms, device
    # ledger fetch p50 ms]]
    assert parsed["secondary"]["obs"] == [
        0.9999, 99999 * 6, 99.999, 0.9999,
        [123457, 123457, 0.0], 0.9999, [9999.999, 9999.999]]
    assert parsed["secondary"]["win_sweep"]["16"] == [123456.8, 99999.99,
                                                      0.9999]
    # chaos campaign rung: [passed, total, worst reelect s,
    # recovery-throughput fraction, injected-fault event records]
    assert parsed["secondary"]["chaos"] == [9, 9, 9999.999, 99.999,
                                                 99999]
    # round-15 upkeep plane: [sweep ms @64 slots, @1024, sim dip frac]
    assert parsed["secondary"]["upkeep"] == [9.999, 9.999, 0.99]
    # kernel throughputs are COUNTS: emitted rounded to the integer
    assert parsed["secondary"]["kernel"][0] == 1330708656
    assert parsed["secondary"]["kernel_100k"] == 1333027867
    # PR-18 flagship mesh rung: [groups, devices, updates/s, tick ms,
    # efficiency vs the mesh-devices=0 control]
    assert parsed["secondary"]["mesh100k"] == [
        102400, 8, 1333027868, 99999.99, 0.999]
    # compact list forms: grpc_1024 = [cps, p99, scalar cps, s256 cps],
    # mesh_10240 = [cps, spread, sim cps, sim spread]
    assert parsed["secondary"]["grpc_1024"][0] == 123456.8
    assert len(parsed["secondary"]["mesh_10240"]) == 4
