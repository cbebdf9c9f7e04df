"""The cluster that tier-1, ``chip_smoke.py`` and the chaos campaign's large
shapes stand on.  Not a benchmark: ``benchmarks/run.py`` is (BENCHMARK.json).

In-process: :class:`BenchCluster` assembles ``num_servers`` ``RaftServer``s
hosting N sibling groups over the simulated or the TCP transport, brings
every group up with a ready leader (appointed in waves, or a first wave by
ordinary election), and drives counter writes through the full
client->leader->log->appender->quorum->apply->reply path;
:func:`run_bench` is build, warm, load and, with ``trace``, decompose the
trace.  :func:`bench_properties` is the density-scaled property set both
use, which ``ratis_tpu/chaos/cluster.py`` borrows for its large shapes.

Multi-process: :func:`run_multiproc_bench` starts one process per peer and
one per load-generator shard (``python -m ratis_tpu.tools.bench_cluster
--mp-server|--mp-client``) and merges what the children's introspection
endpoints serve.

The module keeps its name for its importers; ROADMAP.md (Design 2) has what
is still owed to ``bench_properties(batched=False)``.
"""

from __future__ import annotations

import asyncio
import gc
import itertools
import json
import os
import sys
import time
from typing import Optional

from ratis_tpu.conf import RaftProperties, RaftServerConfigKeys
from ratis_tpu.models.counter import CounterStateMachine
from ratis_tpu.protocol.exceptions import (LeaderNotReadyException,
                                           NotLeaderException, RaftException)
from ratis_tpu.protocol.group import RaftGroup
from ratis_tpu.protocol.ids import ClientId, RaftGroupId, RaftPeerId
from ratis_tpu.protocol.message import Message
from ratis_tpu.protocol.peer import RaftPeer
from ratis_tpu.protocol.requests import RaftClientRequest, write_request_type
from ratis_tpu.server.server import RaftServer
from ratis_tpu.transport.simulated import (SimulatedNetwork,
                                           SimulatedTransportFactory)

# one write's retry budget, and the deadline of each attempt within it: one
# stuck call must cost one attempt, not the write's whole budget
WRITE_BUDGET_S = 60.0
ATTEMPT_TIMEOUT_MS = 10_000.0


def _ephemeral_port() -> int:
    """Ask the kernel for a currently-free localhost port."""
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _tcp_factory():
    """The registered factory of the framed TCP transport."""
    from ratis_tpu.transport.base import TransportFactory
    return TransportFactory.get("TCP")


def _prewarm_grid(num_groups: int) -> tuple[list[int], list[int]]:
    """(group counts, event counts) of every pad bucket a cluster of
    ``num_groups`` can dispatch."""
    from ratis_tpu.engine.engine import QuorumEngine
    top = max(QuorumEngine._bucket(num_groups), 64)
    buckets, b = [], 64
    while b <= max(top, 4096):
        buckets.append(b)
        b *= 4
    return [x for x in buckets if x <= top], buckets


def bench_properties(batched: bool, num_groups: int = 1,
                     num_servers: int = 3,
                     trace: bool = False,
                     trace_sample: int = 16,
                     loop_shards: int = 1) -> RaftProperties:
    from ratis_tpu.engine.engine import QuorumEngine
    p = RaftProperties()
    if loop_shards > 1:
        # host-runtime loop sharding: N worker event loops per server with
        # divisions (and their transport connections) hash-pinned to one
        p.set(RaftServerConfigKeys.LOOP_SHARDS_KEY, str(loop_shards))
    # Timeouts scale with CHANNEL density (groups x followers): background
    # heartbeat volume is O(channels / interval) — one appender item per
    # follower per group, like the reference — so a fixed 1s/2s that is
    # fine at 64 groups makes thousands of co-hosted channels spend the
    # whole host on idle upkeep (measured: 5-peer x 10240 = 40960 channels
    # at an 8s/16s-derived 4s sweep saturated the loop on heartbeat item
    # build+handle alone).  Multi-raft deployments tune exactly this knob
    # as density grows; both engine modes get the same setting.
    channels = num_groups * max(num_servers - 1, 1)
    if channels >= 2048:
        # the per-call rpc deadline scales with density too: at thousands
        # of channels a legitimately-busy handler on a loaded loop blows a
        # 3s deadline, and mass timeouts amplify into retry storms
        p.set(RaftServerConfigKeys.Rpc.REQUEST_TIMEOUT_KEY, "8s")
    if channels >= 32768:
        # margin over the sweep period matters as much as volume here: a
        # loaded sweep delivers late, and the election timeout must
        # tolerate a couple of late sweeps without deposing the leader
        RaftServerConfigKeys.Rpc.set_timeout(p, "24s", "48s")
    elif channels >= 16384:
        RaftServerConfigKeys.Rpc.set_timeout(p, "8s", "16s")
    elif channels >= 4096:
        # 1s/2s is metastable at this density: one hiccup tips thousands
        # of divisions into concurrent elections and the storm sustains
        # itself (measured through the costlier grpc.aio transport at
        # 2048 channels already: 3072 live candidacies, 4k in-flight vote
        # RPCs; TCP's cheap framing holds 1s/2s there).  One tier of
        # margin removes the basin.
        RaftServerConfigKeys.Rpc.set_timeout(p, "4s", "8s")
    else:
        # 1s/2s at <=1024 3-peer groups: already ~7x the reference's
        # default election timeouts (150-300ms, RaftServerConfigKeys.java)
        RaftServerConfigKeys.Rpc.set_timeout(p, "1s", "2s")
    # Pre-size the engine so adding N groups never regrows the batch arrays
    # (each regrow is a new kernel shape -> a compile stall mid-run).
    p.set(RaftServerConfigKeys.Engine.MAX_GROUPS_KEY,
          str(max(QuorumEngine._bucket(num_groups), 64)))
    RaftServerConfigKeys.Log.set_use_memory(p, True)
    # server-level heap discipline (tuned thresholds + idle-janitor seal;
    # run_bench and chip_smoke.py call seal_heap() right after bring-up
    # instead of waiting out the idle window)
    p.set(RaftServerConfigKeys.Gc.DISCIPLINE_KEY, "true")
    # steady-state re-freeze: the in-memory logs accrete live entries
    # under load and collector passes over them were measured at 0.3-0.5s
    # (gen1, 40k channels) up to 13.8s (gen2 over a retry-storm-bloated
    # young heap at 1024 gRPC groups) — collecting ZERO every time.  The
    # memory log never purges, so the refreeze leak trade is moot here.
    p.set(RaftServerConfigKeys.Gc.REFREEZE_INTERVAL_KEY, "15s")
    if trace:
        # host-path tracing (ratis_tpu.trace): every trace_sample-th write
        # records request->commit stage spans
        p.set(RaftServerConfigKeys.Trace.ENABLED_KEY, "true")
        p.set(RaftServerConfigKeys.Trace.SAMPLE_EVERY_KEY, str(trace_sample))
    if batched:
        # Commits advance inline at ack intake (QuorumEngine.on_ack), so
        # the device tick only drives election timeouts (1-2s here) and
        # staleness sweeps: a 20ms cadence loses nothing while cutting the
        # per-dispatch overhead 10x, and each dispatch carries a 10x larger
        # packed event batch.
        p.set("raft.tpu.engine.tick-interval", "20ms")
        # Every tick runs the jitted kernel over all groups, and append
        # traffic toward each destination server is folded into
        # multi-group envelopes (data-path + heartbeat coalescing —
        # O(server pairs) RPCs instead of O(groups)).
        p.set("raft.tpu.engine.scalar-fallback-threshold", "0")
        p.set(RaftServerConfigKeys.Log.Appender.COALESCING_ENABLED_KEY, "true")
        p.set(RaftServerConfigKeys.Heartbeat.COALESCING_ENABLED_KEY, "true")
        # gRPC stream-message coalescing (raft.tpu.grpc.*), for the chaos
        # campaign's gRPC shapes, which borrow these properties: 100µs of
        # latency budget is noise against ~100ms commit p50.  (TCP needs
        # no key: one socket write per loop pass.)
        from ratis_tpu.conf.keys import WireConfigKeys
        p.set(WireConfigKeys.Grpc.FLUSH_MICROS_KEY, "100")
        p.set(WireConfigKeys.Grpc.FLUSH_CHUNKS_KEY, "64")
    else:
        # Tier-1's cheap cluster without a jitted program, which selects
        # the old side of every replication and heartbeat toggle at once:
        # the scalar engine (one Python pass per group per event), one RPC
        # per (group, follower) batch, per-appender heartbeats and flush
        # loops, scalar on_ack per reply, per-request reply chains, gRPC
        # flush keys at 0.  ROADMAP.md (Design 2) lists the tests that
        # stand on this before those sides can go.
        p.set("raft.tpu.engine.tick-interval", "2ms")
        p.set("raft.tpu.engine.scalar-fallback-threshold", "1000000000")
        p.set(RaftServerConfigKeys.Log.Appender.COALESCING_ENABLED_KEY, "false")
        p.set(RaftServerConfigKeys.Heartbeat.COALESCING_ENABLED_KEY, "false")
        p.set(RaftServerConfigKeys.Replication.SWEEP_KEY, "0")
    return p


class BenchCluster:
    """An in-process ``num_servers``-server cluster (default 3) hosting
    ``num_groups`` sibling counter groups."""

    def __init__(self, num_groups: int, num_servers: int = 3,
                 batched: bool = True, transport: str = "sim",
                 trace: bool = False, trace_sample: int = 16,
                 loop_shards: int = 1, extra_props: Optional[dict] = None):
        self.num_groups = num_groups
        self.batched = batched
        self.transport = transport
        if transport == "tcp":
            # real localhost sockets: every RPC pays framing + syscalls
            self.network = None
            self.factory = _tcp_factory()
            addresses = [f"127.0.0.1:{_ephemeral_port()}"
                         for _ in range(num_servers)]
        elif transport == "sim":
            self.network = SimulatedNetwork()
            self.factory = SimulatedTransportFactory(self.network)
            addresses = [f"sim:s{i}" for i in range(num_servers)]
        else:
            raise ValueError(f"unknown bench transport {transport!r}")
        peers = [RaftPeer(RaftPeerId.value_of(f"s{i}"), address=a)
                 for i, a in enumerate(addresses)]
        self.properties = bench_properties(batched, num_groups,
                                           num_servers=num_servers,
                                           trace=trace,
                                           trace_sample=trace_sample,
                                           loop_shards=loop_shards)
        for k, v in (extra_props or {}).items():
            self.properties.set(k, str(v))
        if self.network is not None:
            # the sim's default 3s rpc deadline models a small cluster; a
            # legitimately-busy handler at thousands of co-hosted groups
            # (coalesced envelope / bulk chunk on a saturated loop) gets
            # the same density-scaled deadline the real transports get
            self.network.request_timeout_s = max(
                3.0, RaftServerConfigKeys.Rpc.timeout_min(
                    self.properties).seconds)
        self.groups = [RaftGroup.value_of(RaftGroupId.random_id(), peers)
                       for _ in range(num_groups)]
        self.servers: list[RaftServer] = [
            RaftServer(p.id, p.address,
                       state_machine_registry=lambda gid:
                           CounterStateMachine(),
                       properties=self.properties,
                       transport_factory=self.factory,
                       group=self.groups[0])
            for p in peers]
        self._call_ids = itertools.count(1)
        self.election_convergence_s: float = 0.0
        self.prewarm_s: float = 0.0
        self._peers = [(s.peer_id, s.address) for s in self.servers]
        self._leader_hint: dict[RaftGroupId, int] = {}  # index of _peers

    def prewarm(self) -> None:
        """Compile every pad bucket before elections begin: a mid-run
        compile stall is long enough to fire election timeouts.  The
        jitted steps are process-shared, so one engine warms every server.
        Compilation is NOT part of election convergence (it is paid once
        per process, not once per bring-up) — timed separately."""
        tw = time.monotonic()
        group_counts, event_counts = _prewarm_grid(self.num_groups)
        self.servers[0].engine.prewarm(group_counts=group_counts,
                                       event_counts=event_counts)
        self.prewarm_s = time.monotonic() - tw

    async def start(self, elect_first: int = 0) -> None:
        """Bring every group up with a ready leader.  The first
        ``elect_first`` groups get NO appointment: they are added alone and
        elect by ordinary randomized timeout (vote rounds through the
        engine's tally) before the mass bring-up starts."""
        if self.batched and not self.prewarm_s:
            self.prewarm()
        t0 = time.monotonic()
        await asyncio.gather(*(s.start() for s in self.servers))
        elected = self.groups[:max(elect_first, 1)]
        await asyncio.gather(*(s.group_add(g) for g in elected[1:]
                               for s in self.servers))
        if not elect_first:
            await self._appoint_leaders(elected)
        # a randomized timeout, a pre-vote and a vote round, each up to one
        # election timeout long
        await self._wait_all_leaders(
            elected, timeout=120.0 + 4 * RaftServerConfigKeys.Rpc
            .timeout_max(self.properties).seconds)
        # Wave-wise group bring-up with APPOINTED-LEADER bootstrap: after
        # each wave's group-add, server 0's fresh divisions install
        # leadership directly (Division.bootstrap_as_leader — the
        # deployment mode where the operator chose the initial leader) —
        # no vote rounds at all.  At 10k 5-peer groups the per-group
        # election machinery (vote RPC fan-out + reply handling x 51200
        # divisions) was the dominant bring-up cost; randomized-timeout
        # elections remain as the fallback for any division the bootstrap
        # cannot claim (non-fresh state).
        wave = 128
        # Pipelined waves: wave k's leader-READY wait (startup entries
        # committing through real replication) overlaps wave k+1's
        # group-add + bootstrap — the two touch disjoint groups, and with
        # appointed leaders there are no elections to storm.
        pending_wait: list[RaftGroup] = []
        for i in range(len(elected), len(self.groups), wave):
            batch = self.groups[i:i + wave]
            await asyncio.gather(*(s.group_add(g) for g in batch
                                   for s in self.servers))
            await self._appoint_leaders(batch)
            if pending_wait:
                await self._wait_all_leaders(pending_wait)
            pending_wait = batch
        if pending_wait:
            await self._wait_all_leaders(pending_wait)
        self.election_convergence_s = time.monotonic() - t0

    async def _appoint_leaders(self, groups: list[RaftGroup]) -> None:
        boots = []
        for g in groups:
            d = self.servers[0].divisions.get(g.group_id)
            if d is not None and d.is_follower():
                # via the server so a loop-sharded division bootstraps on
                # its own pinned loop
                boots.append(self.servers[0].bootstrap_division(g.group_id))
        if boots:
            results = await asyncio.gather(*boots, return_exceptions=True)
            for r in results:
                if isinstance(r, BaseException):
                    print(f"bench: bootstrap fell back to election: {r}",
                          file=sys.stderr, flush=True)

    async def _wait_all_leaders(self, groups: list[RaftGroup],
                                timeout: float = 120.0) -> None:
        deadline = time.monotonic() + timeout
        pending = {g.group_id for g in groups}
        while pending and time.monotonic() < deadline:
            done = set()
            for gid in pending:
                for i, s in enumerate(self.servers):
                    d = s.divisions.get(gid)
                    if d is not None and d.is_leader() \
                            and d.leader_ctx is not None \
                            and d.leader_ctx.leader_ready.done():
                        self._leader_hint[gid] = i
                        done.add(gid)
                        break
            pending -= done
            if pending:
                await asyncio.sleep(0.05)
        if pending:
            raise TimeoutError(
                f"{len(pending)}/{len(groups)} groups in this wave have no "
                f"ready leader after {timeout}s")

    async def close(self) -> None:
        await asyncio.gather(*(s.close() for s in self.servers),
                             return_exceptions=True)

    # ------------------------------------------------------------- workload

    async def _drive(self, groups: list[RaftGroup], writes_per_group: int,
                     concurrency: int) -> tuple[int, list[str]]:
        """``writes_per_group`` sequential writes to each of ``groups`` on
        the running loop through one client transport of its own, at most
        ``concurrency`` in flight; returns (acknowledged, failed groups)."""
        client = self.factory.new_client_transport(self.properties)
        try:
            return await _drive_groups(
                client, self._peers, [g.group_id for g in groups],
                writes_per_group, concurrency, self._call_ids,
                self._leader_hint, "bench")
        finally:
            await client.close()

    async def run_load(self, writes_per_group: int,
                       concurrency: int = 256,
                       client_shards: int = 1) -> dict:
        """Drive writes_per_group sequential writes per group, groups
        concurrent under a global in-flight bound.  ``client_shards`` > 1
        splits the driver across that many threads, each with its own
        event loop, its own client connections (real-socket transports
        only) and a round-robin slice of the groups; the in-flight budget
        is split evenly.  The leader-hint map and the tracer are shared
        (both thread-safe)."""
        t_start = time.monotonic()
        if client_shards > 1:
            if self.transport != "tcp":
                raise ValueError(
                    "client_shards needs the TCP transport (the sim hub "
                    "is single-loop by construction)")
            parts = [pt for pt in (self.groups[i::client_shards]
                                   for i in range(client_shards)) if pt]
            per_shard = max(1, concurrency // len(parts))
            outs = await asyncio.gather(*(
                asyncio.to_thread(asyncio.run, self._drive(
                    pt, writes_per_group, per_shard)) for pt in parts))
        else:
            outs = [await self._drive(self.groups, writes_per_group,
                                      concurrency)]
        failures = [gid for _, failed in outs for gid in failed]
        result = _load_result(sum(acked for acked, _ in outs),
                              len(failures), failures,
                              self.num_groups * writes_per_group,
                              time.monotonic() - t_start)
        if client_shards > 1:
            result["client_shards"] = len(parts)
        return result


async def _write(client, peers: list, client_id: ClientId, gid: RaftGroupId,
                 call_ids, leader_hint: dict) -> None:
    """One counter INCREMENT to ``gid``: sent to the peer ``leader_hint``
    holds for it (an index of ``peers`` = [(peer id, address)]; the first
    peer if none), then following NotLeader suggestions and else going
    round the peers, until it is acknowledged (the hint then names who
    did) or WRITE_BUDGET_S has gone (TimeoutError)."""
    from ratis_tpu.trace.tracer import STAGE_CLIENT, TRACER
    ids = [pid for pid, _ in peers]
    i = leader_hint.get(gid, 0)
    deadline = time.monotonic() + WRITE_BUDGET_S
    while True:
        pid, addr = peers[i]
        trace_id = TRACER.begin_trace()
        req = RaftClientRequest(client_id, pid, gid, next(call_ids),
                                Message.value_of(b"INCREMENT"),
                                type=write_request_type(),
                                timeout_ms=ATTEMPT_TIMEOUT_MS,
                                trace_id=trace_id)
        t0 = TRACER.now() if trace_id else 0
        try:
            reply = await client.send_request(addr, req)
        except (RaftException, asyncio.TimeoutError, OSError):
            reply = None
        finally:
            if trace_id:
                TRACER.record(trace_id, STAGE_CLIENT, t0, TRACER.now())
        if reply is not None and reply.success:
            leader_hint[gid] = i
            return
        if time.monotonic() > deadline:
            raise TimeoutError(f"write to {gid} kept failing")
        exc = reply.exception if reply is not None else None
        if isinstance(exc, NotLeaderException) \
                and exc.suggested_leader is not None \
                and exc.suggested_leader.id in ids:
            i = ids.index(exc.suggested_leader.id)
        elif isinstance(exc, LeaderNotReadyException):
            await asyncio.sleep(0.01)
        else:
            i = (i + 1) % len(peers)
            await asyncio.sleep(0.01)


async def _drive_groups(client, peers: list, gids: list,
                        writes_per_group: int, concurrency: int, call_ids,
                        leader_hint: dict, who: str) -> tuple[int, list[str]]:
    """``writes_per_group`` sequential writes a group, each group under a
    client id of its own, the groups concurrent with at most
    ``concurrency`` in flight; returns (acknowledged, the groups of the
    writes that gave up).  ONE write exhausting its retry budget is
    REPORTED, not raised: the caller fails loudly past a 1% fraction."""
    sem = asyncio.Semaphore(concurrency)
    acked = 0
    failures: list[str] = []

    async def group_load(gid) -> None:
        nonlocal acked
        client_id = ClientId.random_id()
        for _ in range(writes_per_group):
            async with sem:
                try:
                    await _write(client, peers, client_id, gid, call_ids,
                                 leader_hint)
                except TimeoutError as e:
                    failures.append(str(gid))
                    print(f"{who}: WRITE FAILED {gid}: {e}",
                          file=sys.stderr, flush=True)
                    continue
                acked += 1

    await asyncio.gather(*(group_load(g) for g in gids))
    return acked, failures


def _load_result(commits: int, failed: int, failed_groups: list,
                 total: int, elapsed: float) -> dict:
    """The load's result from its drivers' sums; ``failed_groups`` names
    some of the ``failed`` writes' groups."""
    if not commits or failed > max(8, total // 100):
        raise TimeoutError(
            f"{failed}/{total} writes failed — not a tail "
            f"event, the cluster is broken: {failed_groups[:5]}")
    return {
        "commits": commits,
        "write_failures": failed,
        "elapsed_s": round(elapsed, 3),
        "commits_per_sec": round(commits / elapsed, 1),
    }


async def run_bench(num_groups: int, writes_per_group: int,
                    batched: bool = True, concurrency: int = 256,
                    warmup_writes: int = 1, transport: str = "sim",
                    trace: bool = False, trace_sample: int = 16,
                    trace_out: Optional[str] = None,
                    loop_shards: int = 1,
                    client_shards: int = 1) -> dict:
    """Build the three-server cluster, bring it up, warm up, load, tear
    down; returns :meth:`BenchCluster.run_load`'s result.  ``trace``
    enables host-path tracing (ratis_tpu.trace) over the loaded window and
    attaches the ``host_path_decomposition`` block; ``trace_out``
    additionally writes the Chrome trace-event JSON (Perfetto-loadable) to
    that path."""
    # Bring-up allocates a few million long-lived objects; automatic gen-2
    # passes over that growing heap measured 0.5-1.25s pauses at 4096
    # 5-peer groups (they fire election timeouts -> storms) and tens of
    # seconds at 10k+.  Nothing allocated during bring-up is garbage, so
    # the collector is OFF while building, then the server runtime takes
    # its one deliberate seal (raft.tpu.gc.discipline supplies the
    # thresholds; RaftServer.seal_heap is the production knob — a server
    # without this harness gets the same seal from its idle janitor).
    gc.disable()
    cluster = None
    try:
        cluster = BenchCluster(num_groups, batched=batched,
                               transport=transport, trace=trace,
                               trace_sample=trace_sample,
                               loop_shards=loop_shards)
        await cluster.start()
        cluster.servers[0].seal_heap()
        gc.enable()
        if warmup_writes:
            await cluster.run_load(warmup_writes, concurrency)
        if trace:
            # decompose the loaded window only, not warmup/bring-up
            from ratis_tpu.trace import get_tracer
            get_tracer().reset()
        result = await cluster.run_load(writes_per_group, concurrency,
                                        client_shards=client_shards)
        if trace:
            from ratis_tpu.trace.export import (host_path_decomposition,
                                                write_chrome_trace)
            records = get_tracer().snapshot()
            result["host_path_decomposition"] = \
                host_path_decomposition(records)
            dropped = get_tracer().stage_dropped()
            if dropped:
                # never a silent cap: wraparound means the table covers the
                # tail of the window, not all of it
                result["host_path_decomposition"]["rings_dropped"] = dropped
            if trace_out:
                write_chrome_trace(trace_out, records)
                result["trace_out"] = os.path.abspath(trace_out)
        if loop_shards > 1:
            result["loop_shards"] = loop_shards
        return result
    finally:
        gc.enable()
        if cluster is not None:
            await cluster.close()


# ------------------------------------------------- multi-process cluster
#
# The in-process BenchCluster time-slices its servers and the client
# drivers through ONE GIL.  This harness starts each peer as its own
# subprocess (own engine, own GC discipline, real-socket transports only)
# and shards the load generator across client subprocesses: the deployment
# shape, which is what the cross-process aggregation (metrics/aggregate.py,
# trace/export.py) has to be tested on.
#
# Protocol (newline-delimited over the child's stdin/stdout):
#   parent -> server child:  one JSON spec line, then ADDGROUPS / APPOINT /
#                            SEAL / RESET_TRACE / TRACEDUMP <path> / EXIT
#   server child -> parent:  MPSTARTED <metrics port>, MPADDED, MPREADY,
#                            MPSEALED, MPTRACED, MPTRACEDUMPED
#   parent -> client child:  one JSON spec line
#   client child -> parent:  MPRESULT <json>

def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _mp_peers(spec: dict) -> list:
    return [(RaftPeerId.value_of(pid), addr) for pid, addr in spec["peers"]]


def _mp_group_ids(spec: dict) -> list:
    return [RaftGroupId.value_of(bytes.fromhex(h)) for h in spec["groups"]]


def _mp_server_main() -> None:
    """One cluster peer as its own process (``--mp-server``)."""
    # The server processes cannot share one chip (it belongs to one
    # process), so this harness is CPU-only by construction: pin or fail.
    from ratis_tpu.util.jaxenv import pin_cpu
    pin_cpu()
    spec = json.loads(sys.stdin.readline())
    gc.disable()  # bring-up heap discipline, same as run_bench

    async def main() -> None:
        peers = [RaftPeer(pid, address=addr) for pid, addr in _mp_peers(spec)]
        groups = [RaftGroup.value_of(gid, peers)
                  for gid in _mp_group_ids(spec)]
        properties = bench_properties(
            True, len(groups), num_servers=len(peers),
            trace=spec["trace"], trace_sample=spec["trace_sample"],
            loop_shards=spec["loop_shards"])
        # Observability plane: every child serves the introspection
        # endpoint on an ephemeral port and reports the bound port on the
        # MPSTARTED line, so the parent can scrape and merge the
        # per-process registries and the pid-keyed /timeseries +
        # /hotgroups series once the load is done.
        properties.set("raft.tpu.metrics.http-port", "0")
        properties.set("raft.tpu.telemetry.enabled", "true")
        if spec["telemetry_interval"]:
            properties.set("raft.tpu.telemetry.interval",
                           spec["telemetry_interval"])
        me = peers[spec["peer_index"]]
        server = RaftServer(me.id, me.address,
                            state_machine_registry=lambda gid:
                                CounterStateMachine(),
                            properties=properties,
                            transport_factory=_tcp_factory(),
                            group=groups[0])
        group_counts, event_counts = _prewarm_grid(len(groups))
        server.engine.prewarm(group_counts=group_counts,
                              event_counts=event_counts)
        await server.start()
        # Phase handshake: report STARTED (imports + prewarm + transport
        # up) and only add groups when the parent says every peer is
        # there.  Without the barrier, the slowest child's jax import
        # lands inside its siblings' election timeouts and fresh
        # followers self-elect against the not-yet-sent appointments.
        print(f"MPSTARTED {server.metrics_http.bound_port}", flush=True)

        loop = asyncio.get_running_loop()
        while True:
            line = await loop.run_in_executor(None, sys.stdin.readline)
            cmd = line.strip()
            if not line or cmd == "EXIT":
                # no graceful unwind of the divisions: the OS reclaims the
                # process
                os._exit(0)
            elif cmd == "ADDGROUPS":
                wave = 512
                for i in range(1, len(groups), wave):
                    await asyncio.gather(*(server.group_add(g)
                                           for g in groups[i:i + wave]))
                print("MPADDED", flush=True)
            elif cmd == "APPOINT":
                bw = 256
                for i in range(0, len(groups), bw):
                    batch = groups[i:i + bw]
                    res = await asyncio.gather(
                        *(server.bootstrap_division(g.group_id)
                          for g in batch), return_exceptions=True)
                    for r in res:
                        if isinstance(r, BaseException):
                            print(f"mp-server: bootstrap fell back: {r}",
                                  file=sys.stderr, flush=True)
                    deadline = time.monotonic() + 300.0
                    pending = {g.group_id for g in batch}
                    while pending and time.monotonic() < deadline:
                        done = set()
                        for gid in pending:
                            d = server.divisions.get(gid)
                            if d is None:
                                continue
                            if d.is_leader() and d.leader_ctx is not None \
                                    and d.leader_ctx.leader_ready.done():
                                done.add(gid)
                            elif not d.is_leader() \
                                    and d.state.leader_id is not None:
                                # a follower election won the group before
                                # our appointment's first heartbeat landed
                                # (slow multi-process bring-up): a leader
                                # EXISTS, clients fail over to it — ready
                                done.add(gid)
                        pending -= done
                        if pending:
                            await asyncio.sleep(0.05)
                    if pending:
                        print(f"mp-server: {len(pending)} groups not "
                              "ready after 300s", file=sys.stderr,
                              flush=True)
                        os._exit(3)
                print("MPREADY", flush=True)
            elif cmd == "SEAL":
                server.seal_heap()
                gc.enable()
                print("MPSEALED", flush=True)
            elif cmd == "RESET_TRACE":
                from ratis_tpu.trace import get_tracer
                get_tracer().reset()
                print("MPTRACED", flush=True)
            elif cmd.startswith("TRACEDUMP "):
                # write this process's Chrome trace so the parent can
                # concatenate every child's into one cluster trace
                from ratis_tpu.trace import get_tracer
                from ratis_tpu.trace.export import write_chrome_trace
                try:
                    write_chrome_trace(cmd[len("TRACEDUMP "):],
                                       get_tracer().snapshot())
                except OSError as e:
                    print(f"mp-server: trace dump failed: {e}",
                          file=sys.stderr, flush=True)
                print("MPTRACEDUMPED", flush=True)

    asyncio.run(main())


def _mp_client_main() -> None:
    """One load-generator shard as its own process (``--mp-client``)."""
    spec = json.loads(sys.stdin.readline())

    async def main() -> None:
        peers = _mp_peers(spec)
        gids = _mp_group_ids(spec)
        # same trace conf as the servers (sampling)
        properties = bench_properties(
            True, len(gids), num_servers=len(peers),
            trace=spec["trace"], trace_sample=spec["trace_sample"])
        # a client child builds no RaftServer, so the process tracer must
        # be enabled explicitly or begin_trace() stays 0 and the whole
        # cluster's per-request spans vanish
        from ratis_tpu.trace import configure_from_properties
        configure_from_properties(properties)
        client = _tcp_factory().new_client_transport(properties)
        wall_start = time.time()
        acked, failures = await _drive_groups(
            client, peers, gids, spec["writes"], spec["concurrency"],
            itertools.count(1), {}, "mp-client")
        # the count and the first few: a broken large cluster must not
        # outgrow the parent's line buffer before _load_result can say so
        print("MPRESULT " + json.dumps({
            "commits": acked, "failed": len(failures),
            "failures": failures[:5],
            "wall_start": wall_start, "wall_end": time.time()}), flush=True)
        os._exit(0)

    asyncio.run(main())


async def _mp_wait_line(proc, prefix: str, timeout_s: float, who: str) -> str:
    """Read the child's stdout until a ``prefix`` line (stray lines pass
    through to stderr so child diagnostics stay visible)."""
    deadline = time.monotonic() + timeout_s
    while True:
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            raise TimeoutError(f"{who}: no {prefix} within {timeout_s}s")
        line = await asyncio.wait_for(proc.stdout.readline(), remaining)
        if not line:
            raise RuntimeError(f"{who} exited before {prefix} "
                               f"(rc={proc.returncode})")
        text = line.decode(errors="replace").rstrip()
        if text.startswith(prefix):
            return text
        print(f"bench[{who}]: {text}", file=sys.stderr, flush=True)


async def run_multiproc_bench(num_groups: int, writes_per_group: int, *,
                              num_servers: int = 5,
                              transport: str = "tcp",
                              loop_shards: int = 1,
                              client_procs: int = 4,
                              concurrency: int = 128,
                              trace: bool = False,
                              trace_sample: int = 32,
                              trace_out: Optional[str] = None,
                              bringup_timeout_s: float = 900.0,
                              load_timeout_s: float = 1200.0,
                              telemetry_interval: Optional[str] = None
                              ) -> dict:
    """The cluster as N server processes + M client processes over real
    sockets; returns :meth:`BenchCluster.run_load`'s keys plus an ``mp``
    block, a ``cluster_metrics`` block (every child's introspection
    endpoint scraped once the load is done and merged into one snapshot —
    metrics/aggregate.py) and a ``cluster_timeseries`` block (the
    pid-keyed telemetry series and the merged hot-group sketch).  With
    ``trace`` on and ``trace_out`` set, each server child dumps its
    Perfetto export and the parent concatenates them into one merged
    chrome-trace keyed by pid at ``trace_out``."""
    if transport != "tcp":
        raise ValueError("the multi-process cluster needs the TCP "
                         "transport")
    peer_list = [[f"s{i}", f"127.0.0.1:{_ephemeral_port()}"]
                 for i in range(num_servers)]
    gids_hex = [RaftGroupId.random_id().to_bytes().hex()
                for _ in range(num_groups)]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _repo_root() + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    common = {"peers": peer_list, "trace": trace,
              "trace_sample": trace_sample}

    async def spawn(arg: str, spec: dict):
        proc = await asyncio.create_subprocess_exec(
            sys.executable, "-m", "ratis_tpu.tools.bench_cluster", arg,
            stdin=asyncio.subprocess.PIPE, stdout=asyncio.subprocess.PIPE,
            stderr=None, env=env, cwd=_repo_root())
        proc.stdin.write((json.dumps({**common, **spec}) + "\n").encode())
        await proc.stdin.drain()
        return proc

    async def tell(i: int, cmd: str, answer: str, timeout_s: float) -> str:
        servers[i].stdin.write(f"{cmd}\n".encode())
        await servers[i].stdin.drain()
        return await _mp_wait_line(servers[i], answer, timeout_s,
                                   f"server{i}")

    servers: list = []
    clients: list = []
    try:
        for i in range(num_servers):
            servers.append(await spawn("--mp-server", {
                "peer_index": i, "groups": gids_hex,
                "loop_shards": loop_shards,
                "telemetry_interval": telemetry_interval}))
        addresses = []
        for i, proc in enumerate(servers):
            started = await _mp_wait_line(proc, "MPSTARTED",
                                          bringup_timeout_s, f"server{i}")
            addresses.append(f"127.0.0.1:{int(started.split()[1])}")
        for proc in servers:
            proc.stdin.write(b"ADDGROUPS\n")
            await proc.stdin.drain()
        for i, proc in enumerate(servers):
            await _mp_wait_line(proc, "MPADDED", bringup_timeout_s,
                                f"server{i}")
        await tell(0, "APPOINT", "MPREADY", bringup_timeout_s)
        for i in range(num_servers):
            await tell(i, "SEAL", "MPSEALED", 120.0)
        if trace:
            for i in range(num_servers):
                await tell(i, "RESET_TRACE", "MPTRACED", 60.0)

        parts = [gids_hex[i::client_procs] for i in range(client_procs)]
        parts = [pt for pt in parts if pt]
        for part in parts:
            clients.append(await spawn("--mp-client", {
                "groups": part, "writes": writes_per_group,
                "concurrency": max(1, concurrency // len(parts))}))
        outs = []
        for i, proc in enumerate(clients):
            line = await _mp_wait_line(proc, "MPRESULT", load_timeout_s,
                                       f"client{i}")
            outs.append(json.loads(line[len("MPRESULT "):]))

        # wall-clock over the union of the client windows (time.time() is
        # process-shared; each child's import/startup cost stays outside)
        load_end = max(o["wall_end"] for o in outs)
        result = _load_result(
            sum(o["commits"] for o in outs), sum(o["failed"] for o in outs),
            [gid for o in outs for gid in o["failures"]],
            num_groups * writes_per_group,
            load_end - min(o["wall_start"] for o in outs))
        result["mp"] = {"server_procs": num_servers,
                        "client_procs": len(parts),
                        "loop_shards": loop_shards}

        # Merge every child's registries/health/events into ONE snapshot,
        # and the pid-keyed telemetry series + hot-group sketch (per-pid
        # latest sample, not the whole ring), while the servers are alive.
        from ratis_tpu.metrics.aggregate import (scrape_cluster,
                                                 scrape_cluster_timeseries)
        result["cluster_metrics"] = await scrape_cluster(addresses)
        # A child samples on its own cadence, and a scrape makes it sample
        # only once a whole interval has gone since its last pass: ask
        # until every child's newest sample is from after the load, so
        # that the series and the sketch hold the load's commits.
        deadline = time.monotonic() + 60.0
        while True:
            series = await scrape_cluster_timeseries(addresses)
            stale = [pid for pid, p in series["procs"].items()
                     if p["last"].get("t", 0.0) <= load_end]
            if not stale:
                break
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"no telemetry sample from after the load within 60s "
                    f"from pids {stale}")
            await asyncio.sleep(0.02)
        result["cluster_timeseries"] = series

        # Merged Perfetto artifact: each server child dumps its chrome
        # trace, the parent concatenates them keyed by pid.
        if trace and trace_out:
            import tempfile
            tdir = tempfile.mkdtemp(prefix="ratis-mp-trace-")
            paths = []
            for i in range(num_servers):
                path = os.path.join(tdir, f"trace_s{i}.json")
                try:
                    await tell(i, f"TRACEDUMP {path}", "MPTRACEDUMPED",
                               120.0)
                    paths.append(path)
                except (TimeoutError, RuntimeError) as e:
                    print(f"bench: server{i} trace dump unavailable: {e}",
                          file=sys.stderr, flush=True)
            from ratis_tpu.trace.export import merge_chrome_trace_files
            merged = merge_chrome_trace_files(paths, trace_out)
            result["trace_out"] = os.path.abspath(trace_out)
            result["trace_pids"] = len({e.get("pid")
                                        for e in merged["traceEvents"]})
        return result
    finally:
        for proc in (*servers, *clients):
            try:
                proc.kill()
            except ProcessLookupError:
                pass
        for proc in (*servers, *clients):
            try:
                await proc.wait()
            except Exception:
                pass


if __name__ == "__main__":
    if "--mp-server" in sys.argv:
        _mp_server_main()
    elif "--mp-client" in sys.argv:
        _mp_client_main()
    else:
        print("usage: python -m ratis_tpu.tools.bench_cluster "
              "--mp-server|--mp-client  (spec JSON on stdin)",
              file=sys.stderr)
        sys.exit(2)
