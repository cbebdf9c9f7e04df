"""Host-path tracing subsystem (ratis_tpu.trace): span propagation across
the simulated transport end to end, ring-buffer wraparound, disabled-mode
zero cost, decomposition coverage + Perfetto export validity, and the
traced-vs-untraced overhead guard."""

import asyncio
import json

import pytest

from minicluster import (MiniCluster, batched_properties, fast_properties,
                         run_with_new_cluster)
from ratis_tpu.trace import get_tracer
from ratis_tpu.trace.export import (host_path_decomposition, to_chrome_trace,
                                    write_chrome_trace)
from ratis_tpu.trace.tracer import (STAGE_APPEND, STAGE_APPLY, STAGE_CLIENT,
                                    STAGE_NAMES, STAGE_REPLICATE, STAGE_REPLY,
                                    STAGE_ROUTE, STAGE_TXN, SpanRing)


@pytest.fixture(autouse=True)
def _tracer_sandbox():
    """Tests share ONE process-wide tracer: restore the disabled default so
    a tracing test never bleeds spans (or enablement) into its neighbors."""
    tracer = get_tracer()
    yield
    tracer.configure(enabled=False)


# --------------------------------------------------------------- ring buffer

def test_ring_wraparound_keeps_latest_records():
    ring = SpanRing(8)
    for i in range(20):
        ring.record(trace_id=i, t0_ns=i * 100, t1_ns=i * 100 + 10, tag=i)
    assert ring.count == 8
    assert ring.recorded == 20
    assert ring.dropped == 12
    rows = ring.rows()
    # oldest-first snapshot of the LAST capacity records (12..19)
    assert [r[0] for r in rows.tolist()] == list(range(12, 20))
    assert all(r[2] == 10 for r in rows.tolist())  # durations survive wrap


def test_tracer_sampling_every_n():
    tracer = get_tracer()
    tracer.configure(enabled=True, sample_every=4, ring_size=64)
    ids = [tracer.begin_trace() for _ in range(16)]
    assert sum(1 for i in ids if i) == 4  # one in four sampled
    assert len({i for i in ids if i}) == 4  # sampled ids are distinct


# ------------------------------------------------------- disabled-mode cost

def test_disabled_tracer_records_nothing():
    tracer = get_tracer()
    tracer.configure(enabled=False)

    async def body(cluster: MiniCluster):
        for _ in range(4):
            assert (await cluster.send_write()).success

    run_with_new_cluster(3, body, properties=fast_properties())
    assert tracer.snapshot() == []
    assert tracer.begin_trace() == 0


# -------------------------------------------------- end-to-end propagation

def test_span_propagation_sim_transport_end_to_end():
    """Client send -> leader append -> commit -> apply all share ONE trace
    id, recorded through the full RaftClient stack over the simulated
    transport."""
    tracer = get_tracer()
    tracer.configure(enabled=True, sample_every=1, ring_size=1024)

    async def body(cluster: MiniCluster):
        await cluster.wait_for_leader()
        client = cluster.new_client()
        try:
            reply = await client.io().send(b"INCREMENT")
            assert reply.success
        finally:
            await client.close()

    run_with_new_cluster(3, body, properties=fast_properties())

    by_stage: dict[int, set[int]] = {}
    for tid, stage, _t0, _dur, _tag, _origin in tracer.snapshot():
        if tid:
            by_stage.setdefault(stage, set()).add(tid)
    client_ids = by_stage.get(STAGE_CLIENT, set())
    assert client_ids, "no client span recorded"
    # at least one request crossed every layer under a single id
    full_path = (client_ids & by_stage.get(STAGE_ROUTE, set())
                 & by_stage.get(STAGE_TXN, set())
                 & by_stage.get(STAGE_APPEND, set())
                 & by_stage.get(STAGE_REPLICATE, set())
                 & by_stage.get(STAGE_APPLY, set())
                 & by_stage.get(STAGE_REPLY, set()))
    assert full_path, f"no trace id crossed all stages: {by_stage}"


def test_trace_id_rides_the_wire_encoding():
    from ratis_tpu.protocol.ids import ClientId, RaftGroupId, RaftPeerId
    from ratis_tpu.protocol.message import Message
    from ratis_tpu.protocol.requests import RaftClientRequest
    req = RaftClientRequest(ClientId.random_id(), RaftPeerId.value_of("s0"),
                            RaftGroupId.random_id(), 7,
                            Message(b"x"), trace_id=12345)
    assert RaftClientRequest.from_bytes(req.to_bytes()).trace_id == 12345
    # untraced requests pay zero wire bytes for the field
    bare = RaftClientRequest(req.client_id, req.server_id, req.group_id, 8,
                             Message(b"x"))
    assert b"tr" not in bare.to_bytes() or \
        RaftClientRequest.from_bytes(bare.to_bytes()).trace_id == 0


# ---------------------------------------- decomposition + Perfetto export

def test_decomposition_coverage_and_perfetto_export(tmp_path):
    """A sim-transport bench rung with tracing on: the per-stage totals
    account for >= 80% of the client-observed wall-clock, and the Chrome
    trace-event export is valid JSON with >= 5 distinct stage names."""
    from ratis_tpu.tools.bench_cluster import run_bench
    tracer = get_tracer()
    tracer.configure(enabled=False)  # run_bench's properties re-enable it
    out_path = str(tmp_path / "trace.json")

    async def main():
        return await run_bench(4, 16, batched=False, concurrency=8,
                               transport="sim", warmup_writes=1,
                               trace=True, trace_sample=1,
                               trace_out=out_path)

    result = asyncio.run(main())
    decomp = result["host_path_decomposition"]
    assert decomp["traced_requests"] > 0
    assert decomp["coverage"] >= 0.8, decomp
    # the tiling stages are all present in the table
    for name in ("server.route", "server.txn_start", "server.append",
                 "server.replicate", "server.apply", "server.reply",
                 "server.respond"):
        assert name in decomp["stages"], decomp["stages"].keys()
    # non-overlap sanity: covered never exceeds the measured wall
    assert decomp["covered_ms_total"] <= decomp["wall_ms_total"] * 1.001

    with open(out_path) as f:
        chrome = json.load(f)  # valid JSON or this raises
    events = chrome["traceEvents"]
    assert len(events) > 0
    names = {e["name"] for e in events}
    assert len(names) >= 5, names
    assert names <= set(STAGE_NAMES)
    for e in events[:50]:
        assert e["ph"] == "X" and e["dur"] > 0 and "ts" in e


def test_export_helpers_on_synthetic_records():
    records = [
        (1, STAGE_CLIENT, 1000, 1000, 0),
        (1, STAGE_APPEND, 1100, 200, 0),
        (1, STAGE_REPLICATE, 1300, 500, 0),
        (1, STAGE_APPLY, 1800, 100, 0),
    ]
    d = host_path_decomposition(records)
    assert d["traced_requests"] == 1
    assert d["coverage"] == 0.8  # (200+500+100)/1000
    chrome = to_chrome_trace(records)
    assert len(chrome["traceEvents"]) == 4
    assert json.loads(json.dumps(chrome)) == chrome


# ------------------------------------------------------------ overhead guard

# alternating untraced/traced rung pairs of the overhead guard
OVERHEAD_PAIRS = 7


def test_tracing_overhead_within_tolerance():
    """Traced (sample-every=4) vs untraced throughput on the same small sim
    rung.  The bound is deliberately loose (50%) — the point is catching a
    pathological regression (e.g. tracing work on the untraced path), not
    benchmarking.  Single small rungs on a shared machine scatter widely, so
    untraced and traced rungs alternate and their medians are compared."""
    import statistics

    from ratis_tpu.tools.bench_cluster import run_bench
    tracer = get_tracer()

    async def rung(trace: bool):
        return await run_bench(2, 48, batched=False, concurrency=16,
                               transport="sim", warmup_writes=4,
                               trace=trace, trace_sample=4)

    rates = {False: [], True: []}
    for _ in range(OVERHEAD_PAIRS):
        for trace in (False, True):
            tracer.configure(enabled=False)  # fresh state for each rung
            rates[trace].append(asyncio.run(rung(trace))["commits_per_sec"])
    untraced, traced = (statistics.median(rates[False]),
                        statistics.median(rates[True]))
    assert traced >= untraced * 0.5, rates


# ------------------------------------------- one session, where the work is

def _rows(tracer, name):
    from ratis_tpu.trace.tracer import STAGE_NAMES as names
    return tracer.rows(names.index(name)).tolist()


def _by_tid(tracer, name):
    return {r[0]: r for r in _rows(tracer, name) if r[0]}


def _tcp_properties(sample_every=1):
    p = batched_properties()
    p.set("raft.tpu.trace.sample-every", str(sample_every))
    return p


def _run_tcp_writes(n, properties, tmp_path=None, after=None):
    """``n`` writes over real TCP through the minimal client, which sends
    every request with trace_id 0; ``after()`` runs once they have settled,
    before the cluster closes."""
    async def body(cluster: MiniCluster):
        await cluster.wait_for_leader()
        for _ in range(n):
            assert (await cluster.send_write()).success
        await asyncio.sleep(0.05)
        if after is not None:
            after()

    run_with_new_cluster(3, body, properties=properties, rpc_type="TCP",
                         storage_root=str(tmp_path) if tmp_path else None)


def test_untraced_request_over_tcp_is_traced_at_the_servers_ingress():
    """The id is minted where the request arrives, once: every second
    request is traced, and each traced one has its decode, route and
    respond rows (a second sampling at the route site would lose the
    transport's rows)."""
    tracer = get_tracer()
    tracer.configure(enabled=True, sample_every=2, ring_size=1024)
    _run_tcp_writes(8, _tcp_properties())
    route = _by_tid(tracer, "server.route")
    # every second arrival (a write refused as not-ready arrives again)
    assert len(route) == tracer._req_tick // 2 >= 4
    # minted by the transport, so its decode row has the id too
    assert set(_by_tid(tracer, "codec.decode")) == set(route)
    appended = _by_tid(tracer, "server.append")
    assert len(appended) >= 3 and set(appended) <= set(route)
    for name in ("server.txn_start", "server.replicate", "server.apply",
                 "server.reply", "server.respond"):
        assert set(_by_tid(tracer, name)) == set(appended), name
    # the client-minted id keeps working beside it (other tests here)


def test_wire_rows_carry_their_frame_counts_and_the_counters_count_the_write():
    """``wire.flush``: one row per socket write, tag = the frames of the pass
    joined in it; ``tcp.read``: one row per ``data_received``, tag = the
    whole frames parsed in it; ``wire.frames`` / ``wire.bytes`` count at the
    write."""
    from ratis_tpu.transport import tcp

    class Sink(tcp._FramedProtocol):
        def _frame(self, call_seq, kind, body):
            pass

    class Pipe:
        """``write`` is the peer's read, as a loopback socket's is."""

        def __init__(self, peer):
            self.peer = peer

        def write(self, data):
            self.peer.data_received(data)

    tracer = get_tracer()
    frames = [tcp._encode_frame(i, tcp.KIND_REPLY, b"x" * i) for i in range(5)]

    async def main():
        a, b = Sink("a"), Sink("b")
        a.connection_made(Pipe(b))
        b.connection_made(Pipe(a))
        tracer.configure(enabled=True, sample_every=1, ring_size=64)
        for f in frames[:3]:
            a.send(f)               # one pass: one write of three frames
        await asyncio.sleep(0)
        await asyncio.sleep(0)
        for f in frames[3:]:
            a.send(f)               # the next: one write of two
        b.data_received(frames[4][:5])      # no whole frame in this read
        b.data_received(frames[4][5:])      # its rest: one
        await asyncio.sleep(0)
        await asyncio.sleep(0)

    asyncio.run(main())
    assert [r[3] for r in _rows(tracer, "wire.flush")] == [3, 2]
    assert [r[3] for r in _rows(tracer, "tcp.read")] == [3, 0, 1, 2]
    # each read lies inside the write that caused it (the pipe is synchronous)
    (w0, w1), (r0, _, _, r1) = _rows(tracer, "wire.flush"), \
        _rows(tracer, "tcp.read")
    assert w0[1] <= r0[1] and r0[1] + r0[2] <= w0[1] + w0[2]
    assert w1[1] <= r1[1] and r1[1] + r1[2] <= w1[1] + w1[2]
    counters = tracer.session()["counters"]
    assert counters["wire.frames"] == 5
    assert counters["wire.bytes"] == sum(len(f) for f in frames)


def test_parts_of_replicate_lie_inside_it_and_the_queue_ends_at_apply(
        tmp_path):
    tracer = get_tracer()
    tracer.configure(enabled=True, sample_every=1, ring_size=1024)
    # (durable: a memory log has flushed before the append returns)
    _run_tcp_writes(6, _tcp_properties(), tmp_path=tmp_path)
    replicate = _by_tid(tracer, "server.replicate")
    apply_ = _by_tid(tracer, "server.apply")
    assert len(replicate) == 6
    parts = {n: _by_tid(tracer, n) for n in
             ("server.flush_wait", "server.quorum_wait", "server.apply_queue")}
    assert set(parts["server.quorum_wait"]) == set(replicate)
    assert set(parts["server.apply_queue"]) == set(replicate)
    assert parts["server.flush_wait"]       # (a late own flush has no row)
    for name, rows in parts.items():
        for tid, (_, t0, dur, tag, _origin) in rows.items():
            _, r0, rdur, _, _ = replicate[tid]
            assert r0 <= t0 and t0 + dur <= r0 + rdur, (name, tid)
            if name == "server.quorum_wait":
                assert t0 == r0 and tag in (1, 2)   # inline or by a tick
            if name == "server.apply_queue":
                assert t0 + dur == apply_[tid][1] == r0 + rdur
    quorum, queue = parts["server.quorum_wait"], parts["server.apply_queue"]
    for tid in replicate:       # commit covered it, then the apply queue
        assert quorum[tid][1] + quorum[tid][2] == queue[tid][1]


def test_engine_parts_tile_the_dispatch():
    tracer = get_tracer()
    tracer.configure(enabled=True, sample_every=16, ring_size=1024)
    _run_tcp_writes(6, _tcp_properties(sample_every=16))
    dispatch = _rows(tracer, "engine.dispatch")
    assert len(dispatch) >= 3          # every dispatch, whatever the stride
    total = sum(r[2] for r in dispatch)
    parts = {n: _rows(tracer, n) for n in ("engine.pack", "engine.launch",
                                           "engine.fetch", "engine.collect")}
    for name in ("engine.pack", "engine.fetch", "engine.collect"):
        assert len(parts[name]) == len(dispatch), name
    assert len(parts["engine.launch"]) >= len(dispatch)   # + full uploads
    tiled = sum(r[2] for rows in parts.values() for r in rows)
    assert 0.95 * total <= tiled <= total, (tiled, total)
    for d in dispatch:                  # and each part inside its dispatch
        inside = [r for rows in parts.values() for r in rows
                  if d[1] <= r[1] and r[1] + r[2] <= d[1] + d[2]]
        assert len(inside) >= 4


def test_log_fsync_rows_carry_the_worker_thread_and_sum_to_the_fsync_count(
        tmp_path):
    import threading
    from ratis_tpu.server.log.segmented import LogWorker

    seen = {}

    def fsyncs():       # the workers' own count, which fsyncs_per_commit reads
        return sum(w.sync_count for w in LogWorker._instances.values())

    def settled():
        seen["fsyncs"] = fsyncs() - before
        seen["tags"] = sum(r[3] for r in _rows(tracer, "log.fsync"))

    tracer = get_tracer()
    before = fsyncs()   # (this cluster's workers are new and start at 0)
    tracer.configure(enabled=True, sample_every=1, ring_size=4096)
    _run_tcp_writes(5, _tcp_properties(), tmp_path=tmp_path, after=settled)
    fsync, write = _rows(tracer, "log.fsync"), _rows(tracer, "log.write")
    assert fsync and len(write) == len(fsync)
    assert all(r[4] != threading.get_ident() for r in fsync)
    assert seen["tags"] == seen["fsyncs"] > 0
    assert len(_rows(tracer, "log.queue")) >= 5 * 3   # a write, 3 replicas
    # and the wire and loop counters moved with it: every frame counted
    # went out in a wire.flush span
    counters = tracer.session()["counters"]
    flushed = sum(r[3] for r in _rows(tracer, "wire.flush"))
    assert flushed >= counters["wire.frames"] > 0
    assert counters["wire.bytes"] > 0 and counters["loop.iterations"] > 0


def test_session_opens_and_closes_with_the_profiler(tmp_path):
    import time
    import jax
    tracer = get_tracer()
    tracer.configure(enabled=False)
    hand = tracer.counter("test.hand_count")
    hand.n += 3                         # before: not the session's
    seen = {}

    async def body(cluster: MiniCluster):
        await cluster.wait_for_leader()
        assert not tracer.enabled
        jax.profiler.start_trace(str(tmp_path))
        try:
            t0 = time.monotonic()
            while not tracer.enabled and time.monotonic() - t0 < 2.0:
                await asyncio.sleep(0.001)
            seen["opened_after_s"] = time.monotonic() - t0
            seen["annotate"] = tracer.annotate
            hand.n += 7
            assert (await cluster.send_write()).success
        finally:
            jax.profiler.stop_trace()
        t0 = time.monotonic()
        while tracer.enabled and time.monotonic() - t0 < 2.0:
            await asyncio.sleep(0.001)
        seen["closed_after_s"] = time.monotonic() - t0
        hand.n += 5                     # after: not the session's either
        assert (await cluster.send_write()).success

    run_with_new_cluster(3, body, properties=_tcp_properties(),
                         rpc_type="TCP")
    assert seen["annotate"] is True
    assert seen["opened_after_s"] < 0.05 and seen["closed_after_s"] < 0.05
    sess = tracer.session()
    assert 0 < sess["t_on"] < sess["t_off"]
    assert sess["counters"]["test.hand_count"] == 7
    # one write's rows, and they stay readable after the close
    assert len(_by_tid(tracer, "server.replicate")) == 1
    assert all(sess["t_on"] <= r[1] <= sess["t_off"]
               for r in _rows(tracer, "server.route"))


def test_no_session_no_row_no_annotation():
    """Off, every site is an attribute check: nothing is written and no
    TraceAnnotation is even constructed (a planted class counts)."""
    tracer = get_tracer()
    tracer.configure(enabled=False)

    class Planted:
        made = 0

        def __init__(self, *a, **kw):
            Planted.made += 1

        @staticmethod
        def is_enabled():
            return False

    tracer._annotation = Planted
    try:
        _run_tcp_writes(4, _tcp_properties())
    finally:
        tracer._annotation = None
    assert Planted.made == 0
    assert tracer.snapshot() == []
    assert tracer.session()["t_on"] == 0 or not tracer.enabled


def test_loop_counters_split_selector_time_from_callbacks():
    import time
    from ratis_tpu.trace import instrument_loop
    tracer = get_tracer()

    async def main():
        loop = asyncio.get_running_loop()
        assert instrument_loop(loop) and not instrument_loop(loop)  # once
        tracer.configure(enabled=True, sample_every=1, ring_size=64)
        await asyncio.sleep(0.05)       # in the selector
        t = time.monotonic()
        while time.monotonic() - t < 0.05:
            pass                        # busy on the loop
        await asyncio.sleep(0)
        return tracer.session()

    sess = asyncio.run(main())
    (key, waited), = [(k, v) for k, v in
                      sess["keyed"]["loop.select_ns"].items() if v]
    assert 0.04e9 < waited < 0.09e9
    assert sess["keyed"]["loop.iterations"][key] >= 2
    assert any(r[2] > 0.04e9 for r in _rows(tracer, "loop.select"))


# ------------------------------------ work spans: synchronous, always closed

class _PlantedProfiler:
    """Stands where ``jax.profiler.TraceAnnotation`` does: on, and keeps the
    names entered and the count left."""
    entered: list = []
    left = 0

    def __init__(self, name, **kw):
        self.name = name

    def __enter__(self):
        _PlantedProfiler.entered.append(self.name)
        return self

    def __exit__(self, *exc):
        _PlantedProfiler.left += 1
        return False

    @staticmethod
    def is_enabled():
        return True


@pytest.fixture
def annotating():
    """A session with the (planted) profiler on: work spans annotate."""
    tracer = get_tracer()
    tracer.configure(enabled=False)
    _PlantedProfiler.entered, _PlantedProfiler.left = [], 0
    tracer._annotation = _PlantedProfiler
    tracer.poll()
    assert tracer.enabled and tracer.annotate
    yield _PlantedProfiler
    tracer._annotation = None
    tracer.annotate = False


def _raising_dispatch():
    from ratis_tpu.engine.engine import QuorumEngine
    from ratis_tpu.trace.tracer import STAGE_LAUNCH, STAGE_PACK
    engine = QuorumEngine(max_groups=8, max_peers=4)

    def refused(acks, now, part):
        part(STAGE_PACK)
        part(STAGE_LAUNCH)
        raise RuntimeError("the step raised")

    engine._tick_batched_dispatch = refused
    engine._tick_batched_pass([], 0)


def _raising_decode():
    from ratis_tpu.protocol.raftrpc import decode_rpc
    decode_rpc(b"\xc1 not msgpack")


def _raising_log_write():
    from ratis_tpu.server.log.segmented import LogWorker

    class FullDisk:
        def write(self, data):
            raise OSError("no space left on device")

    async def main():
        worker = LogWorker("test-full-disk")
        worker.acquire()
        try:
            await worker.submit(FullDisk(), b"entry")
        finally:
            await worker.release()

    asyncio.run(main())


class _RefusingTransport:
    """A socket transport whose ``write`` fails."""

    def write(self, data):
        raise ConnectionResetError("peer went away mid-batch")

    def abort(self):
        pass


def _failing_wire_write():
    from ratis_tpu.transport import tcp

    async def main():
        conn = tcp._Connection("peer:1")
        conn.connection_made(_RefusingTransport())
        conn.send(b"frame")
        conn._flush()           # the pass's write fails: swallowed, poisoned
        conn.send(b"next")      # so this raises

    asyncio.run(main())


def _raising_frame_handler():
    from ratis_tpu.transport import tcp

    class Raising(tcp._FramedProtocol):
        def _frame(self, call_seq, kind, body):
            raise RuntimeError("the frame's handler raised")

    async def main():
        p = Raising("raising")
        p.connection_made(_RefusingTransport())
        p.data_received(tcp._encode_frame(1, tcp.KIND_REPLY, b"body"))

    asyncio.run(main())


@pytest.mark.parametrize("body, stages", [
    (_raising_dispatch, ["engine.dispatch", "engine.pack", "engine.launch"]),
    (_raising_decode, ["codec.decode"]),
    (_raising_log_write, ["log.write"]),
    (_failing_wire_write, ["wire.flush"]),
    (_raising_frame_handler, ["tcp.read"]),
], ids=["engine-step", "decode", "log-write", "wire-write", "frame-handler"])
def test_a_body_that_raises_leaves_no_work_span_open(annotating, body, stages):
    before = len(annotating.entered)
    with pytest.raises(Exception):
        body()
    assert annotating.entered[before:] == ["ratis:" + s for s in stages]
    assert annotating.left == len(annotating.entered)
    tracer = get_tracer()
    for s in stages:    # and each has its ring row
        assert len(_rows(tracer, s)) == 1, s


def test_only_synchronous_stretches_are_annotated(annotating, tmp_path):
    """A ``ratis:`` annotation never spans an await: the stages that time an
    await of the state machine or the log (txn_start, append, apply) are
    intervals, ring rows only; every annotation entered is left."""
    from ratis_tpu.trace.tracer import STAGE_KINDS
    kinds = dict(zip(STAGE_NAMES, STAGE_KINDS))
    for name in ("server.txn_start", "server.append", "server.apply"):
        assert kinds[name] == "I"
    _run_tcp_writes(4, _tcp_properties(), tmp_path=tmp_path)
    names = {n[len("ratis:"):] for n in annotating.entered} - {"clock"}
    assert names <= {n for n, k in kinds.items() if k == "W"}, names
    assert {"server.route", "wire.flush", "tcp.read", "codec.decode",
            "log.fsync", "ack.intake", "replicate.sweep"} <= names
    assert annotating.left == len(annotating.entered)
    tracer = get_tracer()
    assert len(_by_tid(tracer, "server.apply")) >= 4   # the rows are there
