"""End-to-end multi-raft benchmark harness: the framework's own load
generator (reference analog: ratis-examples filestore LoadGen,
ratis-examples/src/main/java/org/apache/ratis/examples/filestore/cli/LoadGen.java,
driven against an in-process MiniRaftCluster-style trio).

Spins one in-process server trio over the simulated transport (direct
function-call RPC — measures the framework, not socket syscalls), hosts N
sibling RaftGroups on it (the multi-raft axis, RaftServerProxy.java:89-188),
elects all leaders, then drives concurrent counter writes through the full
client->leader->log->appender->quorum->apply->reply path, with the batched
quorum engine ticking every group on each server as ONE fused dispatch.

Reports aggregate commits/sec + p50/p99 commit latency — the north-star
metrics from BASELINE.md.
"""

from __future__ import annotations

import asyncio
import contextlib
import itertools
import sys
import time
from typing import Optional


def _ephemeral_port() -> int:
    """Ask the kernel for a currently-free localhost port."""
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]

from ratis_tpu.conf import RaftProperties, RaftServerConfigKeys
from ratis_tpu.models.counter import CounterStateMachine
from ratis_tpu.protocol.exceptions import (LeaderNotReadyException,
                                           NotLeaderException, RaftException,
                                           ResourceUnavailableException)
from ratis_tpu.protocol.group import RaftGroup
from ratis_tpu.protocol.ids import ClientId, RaftGroupId, RaftPeerId
from ratis_tpu.protocol.message import Message
from ratis_tpu.protocol.peer import RaftPeer
from ratis_tpu.protocol.requests import RaftClientRequest, write_request_type
from ratis_tpu.server.server import RaftServer
from ratis_tpu.transport.simulated import (SimulatedNetwork,
                                           SimulatedTransportFactory)


def bench_properties(batched: bool, num_groups: int = 1,
                     hibernate: bool = False,
                     mesh_devices: int = 0,
                     num_servers: int = 3,
                     transport: str = "sim",
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
    # as density grows; both engine modes get the same setting, so the
    # batched/scalar comparison is unaffected.
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
    elif channels >= (2048 if transport == "grpc" else 4096):
        # 2048 channels at 1s/2s was metastable through the costlier
        # grpc.aio transport: one hiccup tipped ~3000 divisions into
        # concurrent elections (measured: 3072 live candidacies, 4k
        # in-flight vote RPCs, multi-GB of pending call objects) and the
        # storm sustained itself.  One tier of margin removes the basin —
        # a deployment tunes this knob to its transport's per-op cost
        # (TCP's cheap framing holds 1s/2s at the same density).
        RaftServerConfigKeys.Rpc.set_timeout(p, "4s", "8s")
    else:
        # 1s/2s at <=1024 3-peer groups: already ~7x the reference's
        # default election timeouts (150-300ms, RaftServerConfigKeys.java)
        # — the baseline's per-(group,follower) heartbeat channels get a
        # generous but realistic idle cadence.
        RaftServerConfigKeys.Rpc.set_timeout(p, "1s", "2s")
    if batched:
        # Commits advance inline at ack intake (QuorumEngine.on_ack), so
        # the device tick only drives election timeouts (1-2s here) and
        # staleness sweeps: a 20ms cadence loses nothing while cutting the
        # per-dispatch overhead 10x — and each dispatch carries a 10x
        # larger packed event batch, which is exactly the shape the TPU
        # kernel wants.
        p.set("raft.tpu.engine.tick-interval", "20ms")
    else:
        p.set("raft.tpu.engine.tick-interval", "2ms")
    # Pre-size the engine so adding N groups never regrows the batch arrays
    # (each regrow is a new kernel shape -> a compile stall mid-run).
    p.set(RaftServerConfigKeys.Engine.MAX_GROUPS_KEY,
          str(max(QuorumEngine._bucket(num_groups), 64)))
    RaftServerConfigKeys.Log.set_use_memory(p, True)
    # server-level heap discipline (tuned thresholds + idle-janitor seal;
    # the harness calls seal_heap() right after bring-up instead of waiting
    # out the idle window)
    p.set(RaftServerConfigKeys.Gc.DISCIPLINE_KEY, "true")
    # steady-state re-freeze on every rung: the in-memory logs accrete
    # live entries under load and collector passes over them were
    # measured at 0.3-0.5s (gen1, 40k channels) up to 13.8s (gen2 over a
    # retry-storm-bloated young heap at 1024 gRPC groups) — collecting
    # ZERO every time.  The memory log never purges, so the refreeze
    # leak trade is moot here.
    p.set(RaftServerConfigKeys.Gc.REFREEZE_INTERVAL_KEY, "15s")
    if mesh_devices:
        # shard the resident engine state over the group axis of an
        # n-device mesh (parallel/mesh.py): each device owns one
        # contiguous slice of the group batch, divisions are crc32-pinned
        # to slots inside their owning slice, and the fast tick ships
        # slice-routed [7, S, E] event planes instead of replicating the
        # pack to every device (the rung that gives sharding a measured
        # e2e number, not just dryrun bit-identity).  Capacity is
        # auto-padded to the mesh, so num_groups needs no alignment.
        p.set(RaftServerConfigKeys.Engine.MESH_DEVICES_KEY,
              str(mesh_devices))
    if trace:
        # host-path tracing (ratis_tpu.trace): every trace_sample-th write
        # records request->commit stage spans; exported by run_bench as the
        # host_path_decomposition block + Chrome trace-event JSON
        p.set(RaftServerConfigKeys.Trace.ENABLED_KEY, "true")
        p.set(RaftServerConfigKeys.Trace.SAMPLE_EVERY_KEY, str(trace_sample))
    if batched:
        # TPU-native execution mode: every tick runs the jitted kernel over
        # all groups, and append traffic toward each destination server is
        # folded into multi-group envelopes (data-path + heartbeat
        # coalescing — O(server pairs) RPCs instead of O(groups)).
        p.set("raft.tpu.engine.scalar-fallback-threshold", "0")
        p.set(RaftServerConfigKeys.Log.Appender.COALESCING_ENABLED_KEY, "true")
        p.set(RaftServerConfigKeys.Heartbeat.COALESCING_ENABLED_KEY, "true")
        # gRPC stream-message coalescing (raft.tpu.grpc.*, round 6): 100µs
        # of latency budget is noise against ~100ms commit p50.  Scalar
        # mode keeps the reference's per-message shape (these stay 0
        # there).  (TCP needs no key: one socket write per loop pass.)
        from ratis_tpu.conf.keys import WireConfigKeys
        p.set(WireConfigKeys.Grpc.FLUSH_MICROS_KEY, "100")
        p.set(WireConfigKeys.Grpc.FLUSH_CHUNKS_KEY, "64")
        if hibernate:
            # idle-group quiescence (requires the coalesced heartbeat
            # channel): idle groups cost zero background traffic
            p.set(RaftServerConfigKeys.Hibernate.ENABLED_KEY, "true")
    else:
        # the reference's cost shape: one Python pass per group per event
        # (thread-per-division EventProcessor analog) and one RPC per
        # (group, follower) batch (GrpcLogAppender.java:356 stream-per-pair)
        # — and per-request replication scheduling (per-appender flush-loop
        # wakes, scalar on_ack per reply, per-request reply chains): the
        # round-8 sweep discipline is a batched-mode optimization, so the
        # baseline keeps the pre-sweep paths.
        p.set("raft.tpu.engine.scalar-fallback-threshold", "1000000000")
        p.set(RaftServerConfigKeys.Log.Appender.COALESCING_ENABLED_KEY, "false")
        p.set(RaftServerConfigKeys.Heartbeat.COALESCING_ENABLED_KEY, "false")
        p.set(RaftServerConfigKeys.Replication.SWEEP_KEY, "0")
    return p


class BenchCluster:
    """An in-process ``num_servers``-server cluster (default 3) hosting
    ``num_groups`` sibling groups."""

    def __init__(self, num_groups: int, num_servers: int = 3,
                 batched: bool = True, transport: str = "sim",
                 sm: str = "counter", datastream: bool = False,
                 hibernate: bool = False, mesh_devices: int = 0,
                 trace: bool = False, trace_sample: int = 16,
                 loop_shards: int = 1, extra_props: Optional[dict] = None,
                 sm_storage_root: Optional[str] = None):
        self.num_groups = num_groups
        self.batched = batched
        self.transport = transport
        self.sm = sm
        self.datastream = datastream
        self.hibernate = hibernate
        self.mesh_devices = mesh_devices
        self.trace = trace
        self.loop_shards = loop_shards
        if transport in ("tcp", "grpc"):
            # Real localhost sockets: every RPC pays framing + syscalls, so
            # the per-(group,follower) stream shape costs what it costs the
            # reference — the rungs that prove the coalesced paths
            # (AppendEnvelope / BulkHeartbeat) survive a real transport.
            # "tcp" is the netty-analog framed transport; "grpc" is the
            # grpc.aio transport (reference's primary RPC stack analog).
            from ratis_tpu.transport.base import TransportFactory
            import ratis_tpu.transport.grpc  # noqa: F401  (registers GRPC)
            import ratis_tpu.transport.tcp  # noqa: F401  (registers TCP)
            self.network = None
            self.factory = TransportFactory.get(
                "GRPC" if transport == "grpc" else "TCP")
            peers = [RaftPeer(RaftPeerId.value_of(f"s{i}"),
                              address=f"127.0.0.1:{_ephemeral_port()}",
                              datastream_address=(
                                  f"127.0.0.1:{_ephemeral_port()}"
                                  if datastream else None))
                     for i in range(num_servers)]
        elif transport == "sim":
            self.network = SimulatedNetwork()
            self.factory = SimulatedTransportFactory(self.network)
            peers = [RaftPeer(RaftPeerId.value_of(f"s{i}"),
                              address=f"sim:s{i}",
                              datastream_address=(
                                  f"127.0.0.1:{_ephemeral_port()}"
                                  if datastream else None))
                     for i in range(num_servers)]
        else:
            raise ValueError(f"unknown bench transport {transport!r}")
        self.properties = bench_properties(batched, num_groups,
                                           hibernate=hibernate,
                                           mesh_devices=mesh_devices,
                                           num_servers=num_servers,
                                           transport=transport,
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
        if sm == "filestore":
            from ratis_tpu.models.filestore import FileStoreStateMachine

            def _sm_factory():
                return FileStoreStateMachine()
        elif sm == "arithmetic":
            from ratis_tpu.models.arithmetic import ArithmeticStateMachine

            def _sm_factory():
                return ArithmeticStateMachine()
        else:
            def _sm_factory():
                return CounterStateMachine()
        def _registry_for(peer_id):
            if sm_storage_root is None:
                return lambda gid: _sm_factory()

            def _reg(gid):
                # real snapshot storage even with the in-memory log: the
                # snapshot rungs (take/purge/chunked-install) need a place
                # for SM snapshot files, exactly like the reference's
                # SimpleStateMachineStorage under the raft storage dir
                m = _sm_factory()
                m.get_state_machine_storage().init(
                    f"{sm_storage_root}/{peer_id}/{gid}")
                return m
            return _reg

        self.servers: list[RaftServer] = [
            RaftServer(p.id, p.address,
                       state_machine_registry=_registry_for(p.id),
                       properties=self.properties,
                       transport_factory=self.factory,
                       group=self.groups[0])
            for p in peers]
        self._call_ids = itertools.count(1)
        self.election_convergence_s: float = 0.0
        self.prewarm_s: float = 0.0
        self._leader_hint: dict[RaftGroupId, RaftServer] = {}

    def prewarm(self) -> None:
        """Compile every pad bucket before elections begin: a mid-run
        compile stall is long enough to fire election timeouts.  The
        jitted steps are process-shared, so one engine warms every server.
        Compilation is NOT part of election convergence (it is paid once
        per process, not once per bring-up) — timed separately."""
        tw = time.monotonic()
        buckets, b = [], 64
        from ratis_tpu.engine.engine import QuorumEngine
        top = max(QuorumEngine._bucket(self.num_groups), 64)
        while b <= max(top, 4096):
            buckets.append(b)
            b *= 4
        self.servers[0].engine.prewarm(
            group_counts=[x for x in buckets if x <= top],
            event_counts=buckets)
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
        import os
        trace = os.environ.get("RATIS_BENCH_TRACE")
        wave = 128
        # Pipelined waves: wave k's leader-READY wait (startup entries
        # committing through real replication) overlaps wave k+1's
        # group-add + bootstrap — the two touch disjoint groups, and with
        # appointed leaders there are no elections to storm, so the old
        # add->elect->wait serialization was pure idle time.
        pending_wait: list[RaftGroup] = []
        for i in range(len(elected), len(self.groups), wave):
            batch = self.groups[i:i + wave]
            tw = time.monotonic()
            await asyncio.gather(*(s.group_add(g) for g in batch
                                   for s in self.servers))
            t_add = time.monotonic() - tw
            await self._appoint_leaders(batch)
            if pending_wait:
                await self._wait_all_leaders(pending_wait)
            pending_wait = batch
            if trace:
                print(f"bench: wave@{i} add={t_add:.2f}s "
                      f"total={time.monotonic() - tw:.2f}s",
                      file=sys.stderr, flush=True)
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
                for s in self.servers:
                    d = s.divisions.get(gid)
                    if d is not None and d.is_leader() \
                            and d.leader_ctx is not None \
                            and d.leader_ctx.leader_ready.done():
                        self._leader_hint[gid] = s
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

    async def _write(self, client, client_id: ClientId, gid: RaftGroupId,
                     timeout: float = 0.0, message: bytes = b"INCREMENT"):
        """One write with leader-hint failover."""
        if not timeout:
            # a saturated 10k-group loop can starve one write past a fixed
            # 60s while the aggregate is perfectly healthy
            timeout = 60.0 if self.num_groups < 8192 else 240.0
        server = self._leader_hint.get(gid, self.servers[0])
        deadline = time.monotonic() + timeout
        from ratis_tpu.trace.tracer import STAGE_CLIENT, TRACER
        while True:
            # bounded per-attempt deadline: one stuck call must cost one
            # attempt, not the write's whole retry budget (the client
            # transport's 30s default ate 2 of the 60s budget per hang)
            trace_id = TRACER.begin_trace()
            req = RaftClientRequest(client_id, server.peer_id, gid,
                                    next(self._call_ids),
                                    Message.value_of(message),
                                    type=write_request_type(),
                                    timeout_ms=10_000.0,
                                    trace_id=trace_id)
            t0 = TRACER.now() if trace_id else 0
            try:
                reply = await client.send_request(server.address, req)
            except (RaftException, asyncio.TimeoutError):
                reply = None
            finally:
                if trace_id:
                    TRACER.record(trace_id, STAGE_CLIENT, t0, TRACER.now())
            if reply is not None and reply.success:
                self._leader_hint[gid] = server
                return reply
            if time.monotonic() > deadline:
                raise TimeoutError(f"write to {gid} kept failing")
            exc = reply.exception if reply is not None else None
            if isinstance(exc, NotLeaderException) \
                    and exc.suggested_leader is not None:
                by_id = {s.peer_id: s for s in self.servers}
                server = by_id.get(exc.suggested_leader.id, server)
            elif isinstance(exc, LeaderNotReadyException):
                await asyncio.sleep(0.01)
            else:
                idx = self.servers.index(server)
                server = self.servers[(idx + 1) % len(self.servers)]
                await asyncio.sleep(0.01)

    async def run_load(self, writes_per_group: int,
                       concurrency: int = 256,
                       message_factory=None,
                       active_groups: Optional[int] = None,
                       client_shards: int = 1) -> dict:
        """Drive writes_per_group sequential writes per group, groups
        concurrent under a global in-flight bound; returns throughput and
        latency percentiles.  ``message_factory`` builds per-write payloads
        (default: the counter INCREMENT).  ``active_groups`` restricts the
        load to the first N groups — the sparse multi-tenant shape where
        most hosted groups are cold.  ``client_shards`` > 1 splits the
        driver across that many threads, each with its own event loop and
        its own client connections (real-socket transports only): the
        client-side half of the measured event-loop queueing residual
        (docs/perf.md) scales with in-flight writes per loop, and this is
        the knob that divides it."""
        if client_shards > 1:
            if self.transport not in ("tcp", "grpc"):
                raise ValueError(
                    "client_shards needs a real-socket transport (the sim "
                    "hub is single-loop by construction)")
            return await self._run_load_sharded(
                writes_per_group, concurrency, message_factory,
                active_groups, client_shards)
        # properties matter here: the client plane gets the same wire
        # coalescing conf as the servers (raft.tpu.tcp/grpc flush keys)
        client = self.factory.new_client_transport(self.properties)
        sem = asyncio.Semaphore(concurrency)
        latencies: list[float] = []
        target_groups = (self.groups if active_groups is None
                         else self.groups[:active_groups])

        import os
        trace = os.environ.get("RATIS_BENCH_TRACE")
        failures: list[str] = []

        async def group_load(g: RaftGroup):
            client_id = ClientId.random_id()
            for _ in range(writes_per_group):
                async with sem:
                    msg = (message_factory() if message_factory is not None
                           else b"INCREMENT")
                    t0 = time.monotonic()
                    try:
                        await self._write(client, client_id, g.group_id,
                                          message=msg)
                    except TimeoutError as e:
                        # ONE write exhausting its retry budget must be
                        # REPORTED, not abort a multi-thousand-write rung
                        # (observed ~1/20k over grpc under load); the rung
                        # still fails loudly past a 1% fraction below
                        failures.append(str(g.group_id))
                        print(f"bench: WRITE FAILED {g.group_id}: {e}",
                              file=sys.stderr, flush=True)
                        continue
                    latencies.append(time.monotonic() - t0)
                    if trace and len(latencies) % 4096 == 0:
                        print(f"bench: {len(latencies)} writes done "
                              f"({len(latencies) / (time.monotonic() - t_start):.0f}/s)",
                              file=sys.stderr, flush=True)

        t_start = time.monotonic()
        await asyncio.gather(*(group_load(g) for g in target_groups))
        elapsed = time.monotonic() - t_start

        total = len(target_groups) * writes_per_group
        if not latencies or len(failures) > max(8, total // 100):
            raise TimeoutError(
                f"{len(failures)}/{total} writes failed — not a tail "
                f"event, the rung is broken: {failures[:5]}")
        latencies.sort()
        n = len(latencies)
        return {
            "commits": total - len(failures),
            "write_failures": len(failures),
            "elapsed_s": round(elapsed, 3),
            "commits_per_sec": round((total - len(failures)) / elapsed, 1),
            "p50_ms": round(latencies[n // 2] * 1e3, 2),
            "p99_ms": round(latencies[min(n - 1, (n * 99) // 100)] * 1e3, 2),
            "election_convergence_s": round(self.election_convergence_s, 2),
            "prewarm_s": round(self.prewarm_s, 2),
        }

    async def _run_load_sharded(self, writes_per_group: int,
                                concurrency: int, message_factory,
                                active_groups: Optional[int],
                                client_shards: int) -> dict:
        """Client-sharded load: each shard is a thread with its own event
        loop, its own client transport (own sockets), and a round-robin
        slice of the groups; the in-flight budget is split evenly.  The
        leader-hint map and tracer are shared (both thread-safe)."""
        target_groups = (self.groups if active_groups is None
                         else self.groups[:active_groups])
        parts = [target_groups[i::client_shards]
                 for i in range(client_shards)]
        parts = [pt for pt in parts if pt]
        per_shard_conc = max(1, concurrency // len(parts))

        def drive(part):
            async def run():
                client = self.factory.new_client_transport(self.properties)
                sem = asyncio.Semaphore(per_shard_conc)
                lat: list[float] = []
                failures: list[str] = []

                async def group_load(g: RaftGroup):
                    client_id = ClientId.random_id()
                    for _ in range(writes_per_group):
                        async with sem:
                            msg = (message_factory()
                                   if message_factory is not None
                                   else b"INCREMENT")
                            t0 = time.monotonic()
                            try:
                                await self._write(client, client_id,
                                                  g.group_id, message=msg)
                            except TimeoutError as e:
                                failures.append(str(g.group_id))
                                print(f"bench: WRITE FAILED {g.group_id}: "
                                      f"{e}", file=sys.stderr, flush=True)
                                continue
                            lat.append(time.monotonic() - t0)

                await asyncio.gather(*(group_load(g) for g in part))
                try:
                    await client.close()
                except Exception:
                    pass
                return lat, failures

            return asyncio.run(run())

        t_start = time.monotonic()
        outs = await asyncio.gather(
            *(asyncio.to_thread(drive, pt) for pt in parts))
        elapsed = time.monotonic() - t_start
        latencies = sorted(x for lat, _ in outs for x in lat)
        failures = [x for _, f in outs for x in f]
        total = len(target_groups) * writes_per_group
        if not latencies or len(failures) > max(8, total // 100):
            raise TimeoutError(
                f"{len(failures)}/{total} writes failed — not a tail "
                f"event, the rung is broken: {failures[:5]}")
        n = len(latencies)
        return {
            "commits": total - len(failures),
            "write_failures": len(failures),
            "elapsed_s": round(elapsed, 3),
            "commits_per_sec": round((total - len(failures)) / elapsed, 1),
            "p50_ms": round(latencies[n // 2] * 1e3, 2),
            "p99_ms": round(latencies[min(n - 1, (n * 99) // 100)] * 1e3, 2),
            "election_convergence_s": round(self.election_convergence_s, 2),
            "prewarm_s": round(self.prewarm_s, 2),
            "client_shards": len(parts),
        }




# ------------------------------------------------- multi-process cluster
#
# The in-process BenchCluster time-slices 5 servers + the client drivers
# through ONE GIL — which is exactly the single-event-loop queueing the
# traced decomposition blames for the north-star residual (docs/perf.md).
# This harness spawns each peer as its own subprocess (own engine, own GC
# discipline, real-socket transports only) and shards the load generator
# across client subprocesses, so the bench measures the DEPLOYMENT shape
# instead of a one-GIL approximation of it.
#
# Protocol (newline-delimited over the child's stdin/stdout):
#   parent -> server child:  one JSON spec line, then APPOINT / SEAL /
#                            RESET_TRACE / REPORT / EXIT commands
#   server child -> parent:  MPADDED, MPREADY <s>, MPSEALED, MPTRACED,
#                            MPREPORT <json>
#   parent -> client child:  one JSON spec line
#   client child -> parent:  MPRESULT <json>

def _repo_root() -> str:
    import os
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def _mp_sm_factory(sm: str):
    if sm == "filestore":
        from ratis_tpu.models.filestore import FileStoreStateMachine
        return lambda: FileStoreStateMachine()
    if sm == "arithmetic":
        from ratis_tpu.models.arithmetic import ArithmeticStateMachine
        return lambda: ArithmeticStateMachine()
    return lambda: CounterStateMachine()


def _mp_build_groups(spec: dict):
    peers = [RaftPeer(RaftPeerId.value_of(pid), address=addr)
             for pid, addr in spec["peers"]]
    groups = [RaftGroup.value_of(
        RaftGroupId.value_of(bytes.fromhex(h)), peers)
        for h in spec["groups"]]
    return peers, groups


def _mp_server_main() -> None:
    """One cluster peer as its own process (``--mp-server``)."""
    import gc
    import json
    import os

    # Five server processes cannot share one chip (it belongs to one
    # process), so this harness is CPU-only by construction: pin or fail.
    from ratis_tpu.util.jaxenv import pin_cpu
    pin_cpu()
    spec = json.loads(sys.stdin.readline())
    gc.disable()  # bring-up heap discipline, same as _started_cluster

    async def main() -> None:
        import ratis_tpu.transport.tcp  # noqa: F401 (registers TCP)
        from ratis_tpu.transport.base import TransportFactory
        peers, groups = _mp_build_groups(spec)
        num_groups = len(groups)
        batched = spec.get("batched", True)
        transport = spec.get("transport", "tcp")
        if transport == "grpc":
            import ratis_tpu.transport.grpc  # noqa: F401
        factory = TransportFactory.get(
            "GRPC" if transport == "grpc" else "TCP")
        properties = bench_properties(
            batched, num_groups, num_servers=len(peers),
            transport=transport, trace=spec.get("trace", False),
            trace_sample=spec.get("trace_sample", 32),
            loop_shards=spec.get("loop_shards", 1))
        # Observability plane: every measurement child serves the
        # introspection endpoint on an ephemeral port and reports the
        # bound port on the MPSTARTED handshake line so the parent can
        # scrape and merge the per-process registries at rung end.
        properties.set("raft.tpu.metrics.http-port",
                       str(spec.get("metrics_port", 0)))
        # Continuous telemetry in every measurement child (cheap: one
        # 1s-cadence sampler): the parent merges the pid-keyed
        # /timeseries + /hotgroups series at rung end the way it already
        # merges chrome traces.
        if spec.get("telemetry", True):
            properties.set("raft.tpu.telemetry.enabled", "true")
            if spec.get("telemetry_interval"):
                properties.set("raft.tpu.telemetry.interval",
                               spec["telemetry_interval"])
        me = peers[spec["peer_index"]]
        sm_factory = _mp_sm_factory(spec.get("sm", "counter"))
        if batched:
            from ratis_tpu.engine.engine import QuorumEngine
            top = max(QuorumEngine._bucket(num_groups), 64)
            buckets, b = [], 64
            while b <= max(top, 4096):
                buckets.append(b)
                b *= 4
        server = RaftServer(me.id, me.address,
                            state_machine_registry=lambda gid: sm_factory(),
                            properties=properties,
                            transport_factory=factory,
                            group=groups[0])
        if batched:
            server.engine.prewarm(
                group_counts=[x for x in buckets if x <= top],
                event_counts=buckets)
        await server.start()
        # Phase handshake: report STARTED (imports + prewarm + transport
        # up) and only add groups when the parent says every peer is
        # there.  Without the barrier, the slowest child's jax import
        # lands inside its siblings' election timeouts and fresh
        # followers self-elect against the not-yet-sent appointments.
        # The suffix is this child's metrics scrape port (0 = endpoint
        # off) and the JAX backend its engine runs on, riding the existing
        # phased bring-up pipe.
        import jax
        mport = (server.metrics_http.bound_port
                 if server.metrics_http is not None else 0)
        print(f"MPSTARTED {mport} {jax.default_backend()}", flush=True)

        loop = asyncio.get_running_loop()
        while True:
            line = await loop.run_in_executor(None, sys.stdin.readline)
            cmd = line.strip()
            if not line or cmd == "EXIT":
                # measurement child: no graceful unwind of thousands of
                # divisions — the OS reclaims the process (bench.py's
                # children make the same trade)
                os._exit(0)
            elif cmd == "ADDGROUPS":
                wave = 512
                for i in range(1, len(groups), wave):
                    await asyncio.gather(*(server.group_add(g)
                                           for g in groups[i:i + wave]))
                print("MPADDED", flush=True)
            elif cmd == "APPOINT":
                t0 = time.monotonic()
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
                print(f"MPREADY {time.monotonic() - t0:.2f}", flush=True)
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
            elif cmd == "REPORT":
                report: dict = {
                    "pid": os.getpid(),
                    "engine": {k: server.engine.metrics.get(k, 0)
                               for k in ("ticks", "batched_dispatches",
                                         "commit_advances")},
                    "engine_occupancy": round(
                        len(server.engine.state.active)
                        / server.engine.state.capacity, 4),
                    "watchdog_events": (
                        server.watchdog.event_count()
                        if server.watchdog is not None else 0),
                    "append_rewinds":
                        server.replication.metrics.get("rewinds", 0),
                    # one server per process: the process-wide hop
                    # counters line up exactly with this engine's commits
                    "reply_hops_per_commit":
                        server.reply_hops_per_commit(),
                }
                if spec.get("trace"):
                    from ratis_tpu.trace import get_tracer
                    from ratis_tpu.trace.export import \
                        host_path_decomposition
                    report["host_path_decomposition"] = \
                        host_path_decomposition(get_tracer().snapshot())
                print("MPREPORT " + json.dumps(report), flush=True)

    asyncio.run(main())


def _mp_client_main() -> None:
    """One load-generator shard as its own process (``--mp-client``)."""
    import json
    import os

    spec = json.loads(sys.stdin.readline())

    async def main() -> None:
        import ratis_tpu.transport.tcp  # noqa: F401
        from ratis_tpu.transport.base import TransportFactory
        transport = spec.get("transport", "tcp")
        if transport == "grpc":
            import ratis_tpu.transport.grpc  # noqa: F401
        factory = TransportFactory.get(
            "GRPC" if transport == "grpc" else "TCP")
        # same wire/trace conf as the servers (flush keys, sampling)
        properties = bench_properties(
            spec.get("batched", True), len(spec["groups"]),
            num_servers=len(spec["peers"]), transport=transport,
            trace=spec.get("trace", False),
            trace_sample=spec.get("trace_sample", 32))
        # a client child builds no RaftServer, so the process tracer must
        # be enabled explicitly or begin_trace() stays 0 and the whole
        # cluster's per-request spans vanish
        from ratis_tpu.trace import configure_from_properties
        configure_from_properties(properties)
        peers = [(RaftPeerId.value_of(pid), addr)
                 for pid, addr in spec["peers"]]
        by_id = dict(peers)
        gids = [RaftGroupId.value_of(bytes.fromhex(h))
                for h in spec["groups"]]
        client = factory.new_client_transport(properties)
        writes = spec["writes"]
        sm = spec.get("sm", "counter")
        if sm == "arithmetic":
            seq = itertools.count()
            mf = lambda: f"v{next(seq) % 7}={next(seq) % 97}+1".encode()
        elif sm == "filestore":
            import msgpack
            seq = itertools.count()
            mf = lambda: msgpack.packb(
                {"op": "write", "path": f"mp{os.getpid()}-{next(seq)}",
                 "data": b"x" * 128}, use_bin_type=True)
        else:
            mf = lambda: b"INCREMENT"
        call_ids = itertools.count(1)
        leader_hint: dict = {}
        sem = asyncio.Semaphore(max(1, spec.get("concurrency", 32)))
        latencies: list[float] = []
        failures: list[str] = []
        budget = 60.0 if len(gids) < 8192 else 240.0
        from ratis_tpu.trace.tracer import STAGE_CLIENT, TRACER

        async def one_write(client_id, gid, msg: bytes) -> None:
            pid, addr = leader_hint.get(gid, peers[0])
            deadline = time.monotonic() + budget
            i = 0
            while True:
                trace_id = TRACER.begin_trace()
                req = RaftClientRequest(client_id, pid, gid,
                                        next(call_ids),
                                        Message.value_of(msg),
                                        type=write_request_type(),
                                        timeout_ms=10_000.0,
                                        trace_id=trace_id)
                t0 = TRACER.now() if trace_id else 0
                try:
                    reply = await client.send_request(addr, req)
                except (RaftException, asyncio.TimeoutError, OSError):
                    reply = None
                finally:
                    if trace_id:
                        TRACER.record(trace_id, STAGE_CLIENT, t0,
                                      TRACER.now())
                if reply is not None and reply.success:
                    leader_hint[gid] = (pid, addr)
                    return
                if time.monotonic() > deadline:
                    raise TimeoutError(f"write to {gid} kept failing")
                exc = reply.exception if reply is not None else None
                if isinstance(exc, NotLeaderException) \
                        and exc.suggested_leader is not None \
                        and exc.suggested_leader.id in by_id:
                    pid = exc.suggested_leader.id
                    addr = by_id[pid]
                elif isinstance(exc, LeaderNotReadyException):
                    await asyncio.sleep(0.01)
                else:
                    i += 1
                    pid, addr = peers[i % len(peers)]
                    await asyncio.sleep(0.01)

        async def group_load(gid) -> None:
            client_id = ClientId.random_id()
            for _ in range(writes):
                async with sem:
                    t0 = time.monotonic()
                    try:
                        await one_write(client_id, gid, mf())
                    except TimeoutError as e:
                        failures.append(str(gid))
                        print(f"mp-client: WRITE FAILED {gid}: {e}",
                              file=sys.stderr, flush=True)
                        continue
                    latencies.append(time.monotonic() - t0)

        wall_start = time.time()
        t0 = time.monotonic()
        await asyncio.gather(*(group_load(g) for g in gids))
        elapsed = time.monotonic() - t0
        out = {
            "commits": len(latencies),
            "failures": len(failures),
            "elapsed_s": round(elapsed, 3),
            "wall_start": wall_start,
            "wall_end": time.time(),
            "lat_ms": [round(x * 1e3, 1) for x in latencies],
        }
        if spec.get("trace"):
            from ratis_tpu.trace import get_tracer
            from ratis_tpu.trace.export import host_path_decomposition
            out["client_decomp"] = host_path_decomposition(
                get_tracer().snapshot())
        print("MPRESULT " + json.dumps(out), flush=True)
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
                              batched: bool = True,
                              loop_shards: int = 1,
                              client_procs: int = 4,
                              concurrency: int = 128,
                              sm: str = "counter",
                              trace: bool = False,
                              trace_sample: int = 32,
                              trace_out: Optional[str] = None,
                              bringup_timeout_s: float = 900.0,
                              load_timeout_s: float = 1200.0,
                              telemetry_interval: Optional[str] = None
                              ) -> dict:
    """The cluster as N server processes + M client processes over real
    sockets; returns the same result-dict shape as :func:`run_bench` plus
    an ``mp`` block and a ``cluster_metrics`` block (every child's
    introspection endpoint scraped at rung end and merged into one
    snapshot — metrics/aggregate.py).  With ``trace`` on and
    ``trace_out`` set, each server child dumps its Perfetto export and
    the parent concatenates them into one merged chrome-trace keyed by
    pid at ``trace_out``."""
    import json
    import os

    if transport not in ("tcp", "grpc"):
        raise ValueError("multiproc bench needs a real-socket transport")
    from ratis_tpu.protocol.ids import RaftGroupId as _Gid
    peer_list = [[f"s{i}", f"127.0.0.1:{_ephemeral_port()}"]
                 for i in range(num_servers)]
    gids_hex = [_Gid.random_id().to_bytes().hex() for _ in range(num_groups)]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _repo_root() + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")

    async def spawn(args: list[str], spec: dict):
        proc = await asyncio.create_subprocess_exec(
            sys.executable, "-m", "ratis_tpu.tools.bench_cluster", *args,
            stdin=asyncio.subprocess.PIPE, stdout=asyncio.subprocess.PIPE,
            stderr=None, env=env, cwd=_repo_root(),
            # an MPRESULT line carries every latency sample (hundreds of
            # KB at 10k groups): the default 64KB StreamReader limit
            # truncates it
            limit=64 << 20)
        proc.stdin.write((json.dumps(spec) + "\n").encode())
        await proc.stdin.drain()
        return proc

    servers: list = []
    clients: list = []
    try:
        for i in range(num_servers):
            servers.append(await spawn(["--mp-server"], {
                "peer_index": i, "peers": peer_list, "groups": gids_hex,
                "batched": batched, "transport": transport, "sm": sm,
                "loop_shards": loop_shards, "trace": trace,
                "trace_sample": trace_sample,
                "telemetry_interval": telemetry_interval}))
        scrape_ports: list[int] = []
        platforms: set[str] = set()
        for i, proc in enumerate(servers):
            started = await _mp_wait_line(proc, "MPSTARTED",
                                          bringup_timeout_s, f"server{i}")
            _, port, platform = started.split()
            scrape_ports.append(int(port))
            platforms.add(platform)
        for proc in servers:
            proc.stdin.write(b"ADDGROUPS\n")
            await proc.stdin.drain()
        for i, proc in enumerate(servers):
            await _mp_wait_line(proc, "MPADDED", bringup_timeout_s,
                                f"server{i}")
        t0 = time.monotonic()
        servers[0].stdin.write(b"APPOINT\n")
        await servers[0].stdin.drain()
        ready = await _mp_wait_line(servers[0], "MPREADY",
                                    bringup_timeout_s, "server0")
        convergence_s = time.monotonic() - t0
        for i, proc in enumerate(servers):
            proc.stdin.write(b"SEAL\n")
            await proc.stdin.drain()
            await _mp_wait_line(proc, "MPSEALED", 120.0, f"server{i}")
        if trace:
            for i, proc in enumerate(servers):
                proc.stdin.write(b"RESET_TRACE\n")
                await proc.stdin.drain()
                await _mp_wait_line(proc, "MPTRACED", 60.0, f"server{i}")

        parts = [gids_hex[i::client_procs] for i in range(client_procs)]
        parts = [pt for pt in parts if pt]
        for i, part in enumerate(parts):
            clients.append(await spawn(["--mp-client"], {
                "peers": peer_list, "groups": part,
                "writes": writes_per_group, "batched": batched,
                "concurrency": max(1, concurrency // len(parts)),
                "transport": transport, "sm": sm, "trace": trace,
                "trace_sample": trace_sample}))
        outs = []
        for i, proc in enumerate(clients):
            line = await _mp_wait_line(proc, "MPRESULT", load_timeout_s,
                                       f"client{i}")
            outs.append(json.loads(line[len("MPRESULT "):]))

        # Rung-end cluster scrape: merge every child's registries/health/
        # events into ONE snapshot while the servers are still alive.
        cluster_metrics = None
        cluster_timeseries = None
        addresses = [f"127.0.0.1:{port}" for port in scrape_ports if port]
        if addresses:
            from ratis_tpu.metrics.aggregate import (
                scrape_cluster, scrape_cluster_timeseries)
            try:
                cluster_metrics = await scrape_cluster(addresses)
            except Exception as e:
                print(f"bench: cluster scrape failed: {e}",
                      file=sys.stderr, flush=True)
            # pid-keyed telemetry series + merged hot-group sketch; kept
            # compact (per-pid latest sample, not the whole ring) so the
            # rung artifact stays parseable from the tail window
            try:
                cluster_timeseries = await scrape_cluster_timeseries(
                    addresses)
            except Exception as e:
                print(f"bench: timeseries scrape failed: {e}",
                      file=sys.stderr, flush=True)

        # Merged Perfetto artifact: each server child dumps its chrome
        # trace, the parent concatenates them keyed by pid.
        merged_trace_pids = 0
        if trace and trace_out:
            import tempfile
            tdir = tempfile.mkdtemp(prefix="ratis-mp-trace-")
            paths = []
            for i, proc in enumerate(servers):
                path = os.path.join(tdir, f"trace_s{i}.json")
                proc.stdin.write(f"TRACEDUMP {path}\n".encode())
                await proc.stdin.drain()
                try:
                    await _mp_wait_line(proc, "MPTRACEDUMPED", 120.0,
                                        f"server{i}")
                    paths.append(path)
                except (TimeoutError, RuntimeError) as e:
                    print(f"bench: server{i} trace dump unavailable: {e}",
                          file=sys.stderr, flush=True)
            from ratis_tpu.trace.export import merge_chrome_trace_files
            merged = merge_chrome_trace_files(paths, trace_out)
            merged_trace_pids = len({e.get("pid")
                                     for e in merged["traceEvents"]})

        total = num_groups * writes_per_group
        commits = sum(o["commits"] for o in outs)
        failures = sum(o["failures"] for o in outs)
        lat = sorted(x for o in outs for x in o["lat_ms"])
        if not lat or failures > max(8, total // 100):
            raise TimeoutError(
                f"{failures}/{total} multiproc writes failed")
        # wall-clock over the union of the client windows (time.time() is
        # process-shared; each child's import/startup cost stays outside)
        elapsed = (max(o["wall_end"] for o in outs)
                   - min(o["wall_start"] for o in outs))
        n = len(lat)
        result = {
            "commits": commits,
            "write_failures": failures,
            "elapsed_s": round(elapsed, 3),
            "commits_per_sec": round(commits / elapsed, 1),
            "p50_ms": round(lat[n // 2], 2),
            "p99_ms": round(lat[min(n - 1, (n * 99) // 100)], 2),
            "election_convergence_s": round(convergence_s, 2),
            "child_convergence_s": float(ready.split()[1]),
            "prewarm_s": 0.0,
            "groups": num_groups,
            "mode": "batched" if batched else "scalar",
            "transport": transport,
            "peers": num_servers,
            # the backend the server children's engines ran on (cpu: the
            # children pin it) — never a device number
            "platform": ",".join(sorted(platforms)),
            "mp": {"server_procs": num_servers,
                   "client_procs": len(parts),
                   "loop_shards": loop_shards},
        }
        if cluster_metrics is not None:
            result["cluster_metrics"] = cluster_metrics
            result["watchdog_events"] = cluster_metrics.get(
                "watchdog_events", 0)
        if cluster_timeseries is not None:
            result["cluster_timeseries"] = cluster_timeseries
        if trace and trace_out:
            result["trace_out"] = os.path.abspath(trace_out)
            result["trace_pids"] = merged_trace_pids
        servers[0].stdin.write(b"REPORT\n")
        await servers[0].stdin.drain()
        try:
            rep = await _mp_wait_line(servers[0], "MPREPORT", 120.0,
                                      "server0")
            report = json.loads(rep[len("MPREPORT "):])
            result["append_rewinds"] = report.get("append_rewinds", 0)
            result["engine_occupancy"] = report.get("engine_occupancy")
            result["reply_hops_per_commit"] = report.get(
                "reply_hops_per_commit")
            if trace and "host_path_decomposition" in report:
                result["host_path_decomposition"] = \
                    report["host_path_decomposition"]
            if trace and outs and "client_decomp" in outs[0]:
                result["client_decomp"] = outs[0]["client_decomp"]
        except (TimeoutError, RuntimeError) as e:
            print(f"bench: server0 report unavailable: {e}",
                  file=sys.stderr, flush=True)
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


@contextlib.asynccontextmanager
async def _started_cluster(num_groups: int, batched: bool,
                           transport: str = "sim", sm: str = "counter",
                           datastream: bool = False, num_servers: int = 3,
                           hibernate: bool = False, mesh_devices: int = 0,
                           trace: bool = False, trace_sample: int = 16,
                           loop_shards: int = 1,
                           extra_props: Optional[dict] = None,
                           sm_storage_root: Optional[str] = None):
    """Shared rung scaffold: build + start the cluster with the GC tuning
    every rung needs (defer gen-2 cascades during bring-up, then freeze the
    post-bring-up heap out of the collector — a single gen-2 pass over the
    10k-group live heap measured 52s; the pause monitor caught it)."""
    import gc
    # Bring-up allocates a few million long-lived objects; automatic gen-2
    # passes over that growing heap measured 0.5-1.25s pauses at 4096
    # 5-peer groups (they fire election timeouts -> storms) and tens of
    # seconds at 10k+.  Nothing allocated during bring-up is garbage, so
    # the harness runs with GC OFF while building, then takes the server
    # runtime's one deliberate seal (raft.tpu.gc.discipline supplies the
    # thresholds; RaftServer.seal_heap is the production knob — a server
    # without this harness gets the same seal from its idle janitor).
    gc.disable()
    cluster = None
    try:
        cluster = BenchCluster(num_groups, num_servers=num_servers,
                               batched=batched, transport=transport,
                               sm=sm, datastream=datastream,
                               hibernate=hibernate,
                               mesh_devices=mesh_devices,
                               trace=trace, trace_sample=trace_sample,
                               loop_shards=loop_shards,
                               extra_props=extra_props,
                               sm_storage_root=sm_storage_root)
        await cluster.start()
        cluster.servers[0].seal_heap()
        gc.enable()
        yield cluster
    finally:
        gc.enable()
        if cluster is not None:
            await cluster.close()


def _blocking_best_of_3(fn) -> float:
    """Best-of-3 loop-blocking seconds for one sampling pass: thread CPU
    time, not wall — the device ledger pass runs on XLA's intra-op pool
    with the GIL released, so its wall time is not time stolen from the
    serving event loop, while the pure-python walk holds the GIL for its
    entire wall time.  Thread CPU is the cost a loop-resident sampler
    actually charges the cluster (and what the round-11 ≤2% overhead
    bound is made of)."""
    best = None
    for _ in range(3):
        t0 = time.thread_time()
        fn()
        dt = time.thread_time() - t0
        best = dt if best is None else min(best, dt)
    return best or 0.0


def _pass_cost_pair_ms(cluster, tel) -> tuple:
    """The round-14 before/after, measured back-to-back on the same live
    cluster state: (forced ledger-fed sampler pass, retired PR 8
    per-division python walk), both as best-of-3 loop-blocking ms, worst
    server of each.  The walk gets a fresh anchor dict per call (its
    steady-state get+set cost is the same python loop)."""
    from ratis_tpu.metrics.timeseries import legacy_division_walk
    pass_worst = walk_worst = 0.0
    for s2, t in zip(
            [s2 for s2 in cluster.servers if s2.telemetry is not None],
            tel):
        pass_worst = max(pass_worst, _blocking_best_of_3(t.sample))
        walk_worst = max(walk_worst, _blocking_best_of_3(
            lambda: legacy_division_walk(s2, {})))
    return round(pass_worst * 1e3, 3), round(walk_worst * 1e3, 3)


async def run_bench(num_groups: int, writes_per_group: int,
                    batched: bool = True, concurrency: int = 256,
                    warmup_writes: int = 1, transport: str = "sim",
                    sm: str = "counter", num_servers: int = 3,
                    hibernate: bool = False, active_groups=None,
                    settle_s: float = 0.0, mesh_devices: int = 0,
                    teardown: bool = True, trace: bool = False,
                    trace_sample: int = 16,
                    trace_out: "str | None" = None,
                    loop_shards: int = 1,
                    client_shards: int = 1,
                    extra_props: Optional[dict] = None) -> dict:
    """One ladder rung: build the ``num_servers``-server cluster, elect,
    warm up, measure, tear down.  ``teardown=False`` skips the graceful
    close: a measurement child that exits right after reporting has no
    business spending minutes unwinding 50k divisions (measured: the
    5-peer 10240 rung's close ran LONGER than its measurement; the OS
    reclaims an exiting process instantly).  ``trace`` enables host-path
    tracing (ratis_tpu.trace) over the measured window and attaches the
    ``host_path_decomposition`` block; ``trace_out`` additionally writes
    the Chrome trace-event JSON (Perfetto-loadable) to that path."""
    cm = _started_cluster(num_groups, batched, transport=transport,
                          sm=sm, num_servers=num_servers,
                          hibernate=hibernate, mesh_devices=mesh_devices,
                          trace=trace, trace_sample=trace_sample,
                          loop_shards=loop_shards, extra_props=extra_props)
    cluster = await cm.__aenter__()
    try:
        if hibernate and settle_s:
            # let idle groups actually fall asleep before measuring
            await asyncio.sleep(settle_s)
        mf = None
        if sm == "arithmetic":
            # BASELINE config 2's workload shape: var = expression writes
            import itertools as _it
            seq = _it.count()
            mf = lambda: f"v{next(seq) % 7}={next(seq) % 97}+1".encode()
        if warmup_writes:
            await cluster.run_load(warmup_writes, concurrency,
                                   message_factory=mf,
                                   active_groups=active_groups)
        if trace:
            # decompose the MEASURED window only, not warmup/bring-up
            from ratis_tpu.trace import get_tracer
            get_tracer().reset()
        # hops-per-commit over the MEASURED window only (the fan-out
        # collapse's standing artifact; metrics/hops.py)
        from ratis_tpu.metrics import hops as hops_mod
        engines = [s.engine for s in cluster.servers]
        hops_mod.reset()
        commits_before = sum(e.metrics["commit_advances"] for e in engines)
        result = await cluster.run_load(writes_per_group, concurrency,
                                        message_factory=mf,
                                        active_groups=active_groups,
                                        client_shards=client_shards)
        commit_delta = sum(e.metrics["commit_advances"]
                           for e in engines) - commits_before
        result["scheduling_hops"] = hops_mod.snapshot()
        result["reply_hops_per_commit"] = round(
            hops_mod.reply_plane_hops() / max(1, commit_delta), 3)
        if trace:
            from ratis_tpu.trace import get_tracer
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
                import os
                write_chrome_trace(trace_out, records)
                result["trace_out"] = os.path.abspath(trace_out)
        result["batched_dispatches"] = sum(
            e.metrics["batched_dispatches"] for e in engines)
        result["engine_ticks"] = sum(e.metrics["ticks"] for e in engines)
        # wire fast-path observability: INCONSISTENCY rewinds (should be ~0
        # with the keyed stream dispatch), encode-once reuse, gRPC framing
        # batches — the evidence the round-6 hot-path work actually engaged
        result["append_rewinds"] = sum(
            s2.replication.metrics.get("rewinds", 0)
            for s2 in cluster.servers)
        # round-9 append-window state: peak frames-in-flight over the rung
        # as a fraction of the envelope-slot capacity (the "did the
        # pipeline actually fill" number), plus the windowed-rewind /
        # lane-recovery counters
        result["window_occupancy"] = round(max(
            (s2.replication.metrics.get("win_hwm", 0)
             / max(1, s2.replication.lane_slots))
            for s2 in cluster.servers), 4)
        result["window_rewinds"] = sum(
            s2.replication.metrics.get("windowed_rewinds", 0)
            for s2 in cluster.servers)
        result["lane_resets"] = sum(
            s2.replication.metrics.get("lane_resets", 0)
            for s2 in cluster.servers)
        from ratis_tpu.server.replication import ReplicationScheduler
        result["codec"] = ReplicationScheduler.codec_stats()
        if transport == "grpc":
            result["grpc_dispatch"] = {
                k: sum(s2.transport.dispatch_metrics.get(k, 0)
                       for s2 in cluster.servers)
                for k in ("stream_chunks", "keyed_chunks", "ordered_waits",
                          "batched_messages", "reply_batches")}
        for reason in ("dispatch_upload", "dispatch_commit",
                       "dispatch_dirty", "dispatch_votes",
                       "dispatch_sweep", "dispatch_backlog"):
            v = sum(e.metrics.get(reason, 0) for e in engines)
            if v:
                result[reason] = v
        # flagship observability signals: group-lane occupancy (live rows
        # vs padded [G, P] capacity — the "are we actually batching"
        # number) and the stall watchdog's event count over the rung
        result["engine_occupancy"] = round(
            sum(len(e.state.active) for e in engines)
            / max(1, sum(e.state.capacity for e in engines)), 4)
        result["watchdog_events"] = sum(
            s2.watchdog.event_count() for s2 in cluster.servers
            if s2.watchdog is not None)
        # continuous-telemetry rung summary (raft.tpu.telemetry.enabled
        # via extra_props): sampler coverage + cost and the hot-group
        # skew headline (top group's share of sketched commit load — the
        # signal ROADMAP item 4's admission control will read)
        tel = [s2.telemetry for s2 in cluster.servers
               if s2.telemetry is not None]
        if tel:
            from ratis_tpu.metrics.aggregate import merge_hotgroups
            hot = merge_hotgroups([t.hotgroups_info() for t in tel], n=4)
            top = hot["groups"][0] if hot["groups"] else None
            # the run's cost percentiles BEFORE the forced round-14
            # passes below append their own samples to the reservoir
            sample_cost_p99_ms = round(max(
                t._sample_cost.percentile_s(0.99) for t in tel) * 1e3, 3)
            sampler_pass_ms, walk_pass_ms = _pass_cost_pair_ms(
                cluster, tel)
            result["telemetry"] = {
                "samples": sum(t._samples_taken.count for t in tel),
                "sample_cost_p99_ms": sample_cost_p99_ms,
                # guaranteed share of the hottest group: ~0 under
                # uniform load, the true share under genuine skew
                "hot_share": top["share_min"] if top else 0.0,
                "hot_group": top["group"] if top else None,
                # round-14 headline: loop-blocking ms of the ledger-fed
                # sampler pass vs the retired per-division python walk,
                # back-to-back on the same live state, plus the device
                # ledger fetch (wall p50 over the run)
                "sampler_pass_ms": sampler_pass_ms,
                "walk_pass_ms": walk_pass_ms,
                "ledger_fetch_ms": round(max(
                    (s2.engine.ledger.fetch_timer.percentile_s(0.5)
                     for s2 in cluster.servers
                     if s2.telemetry is not None), default=0.0) * 1e3, 3),
            }
        result["groups"] = num_groups
        result["mode"] = "batched" if batched else "scalar"
        result["transport"] = transport
        result["peers"] = num_servers
        if loop_shards > 1:
            result["loop_shards"] = loop_shards
        if active_groups is not None:
            result["active_groups"] = active_groups
        if hibernate:
            result["hibernate"] = True
            result["hibernated_groups"] = sum(
                1 for s2 in cluster.servers
                for d in s2.divisions.values() if d._hibernating)
        return result
    finally:
        if teardown:
            await cm.__aexit__(None, None, None)


async def run_upkeep_bench(num_groups: int = 10_240, num_servers: int = 3,
                           settle_s: float = 25.0,
                           teardown: bool = False) -> dict:
    """Round-15 upkeep-plane rung: the idle-heavy multi-tenant shape —
    ``num_groups`` hosted, NO client load, hibernation on, array mode
    (raft.tpu.upkeep.enabled) — measured for TICK cost: the vectorized
    plane sweep vs the retired per-division walk, back-to-back on the
    SAME live divisions (thread-CPU best-of-3, worst server of each;
    the _pass_cost_pair_ms pattern from round 14).  The legacy side runs
    the pre-round-15 ``HeartbeatScheduler._run`` body verbatim, so its
    cost includes the per-division ``hibernate_sweep`` calls an asleep
    fleet still paid every sweep."""
    cm = _started_cluster(num_groups, True, hibernate=True,
                          num_servers=num_servers,
                          extra_props={"raft.tpu.upkeep.enabled": "true"})
    cluster = await cm.__aenter__()
    try:
        await asyncio.sleep(settle_s)  # let the idle fleet fall asleep

        def legacy_tick(srv) -> None:
            now = time.monotonic()
            for div in list(srv.divisions.values()):
                if not div.is_leader() or div.leader_ctx is None:
                    continue
                hib = div.hibernate_sweep(now)
                if hib == "asleep":
                    continue
                for appender in list(div.leader_ctx.appenders.values()):
                    appender.heartbeat_item(now,
                                            hibernate=(hib == "request"))

        def array_tick(srv) -> None:
            now = time.monotonic()
            for pl in srv.upkeep:
                pl.sweep(now)

        array_worst = legacy_worst = 0.0
        asleep = registered = due = 0
        for srv in cluster.servers:
            array_worst = max(array_worst, _blocking_best_of_3(
                lambda: array_tick(srv)))
            legacy_worst = max(legacy_worst, _blocking_best_of_3(
                lambda: legacy_tick(srv)))
            asleep += sum(1 for d in srv.divisions.values()
                          if d._hibernating)
            registered += sum(pl.registered for pl in srv.upkeep)
            due += sum(pl.last_due for pl in srv.upkeep)
        return {
            "groups": num_groups, "peers": num_servers,
            "hibernated_groups": asleep,
            "registered_slots": registered, "due_groups": due,
            "tick_array_ms": round(array_worst * 1e3, 3),
            "tick_legacy_ms": round(legacy_worst * 1e3, 3),
            "tick_ratio": round(legacy_worst / max(1e-9, array_worst), 1),
        }
    finally:
        if teardown:
            await cm.__aexit__(None, None, None)


async def run_churn_bench(num_groups: int, writes_per_group: int,
                          transfers: int, batched: bool = True,
                          concurrency: int = 128) -> dict:
    """BASELINE config 4 analog: reconfig/leadership churn under load.

    Drives the normal write load while a churn task performs ``transfers``
    leadership transfers (the reference's TransferLeadership admin path)
    on randomly chosen groups; measures how throughput and tail latency
    hold up while leaderships move underneath the clients."""
    import random

    from ratis_tpu.protocol.admin import TransferLeadershipArguments
    from ratis_tpu.protocol.requests import RequestType, admin_request_type

    async with _started_cluster(num_groups, batched) as cluster:
        client = cluster.factory.new_client_transport()
        rng = random.Random(17)
        churn_stats = {"ok": 0, "failed": 0}

        async def churn():
            client_id = ClientId.random_id()
            by_id = {s.peer_id: s for s in cluster.servers}
            for _ in range(transfers):
                g = rng.choice(cluster.groups)
                leader_srv = cluster._leader_hint.get(g.group_id,
                                                      cluster.servers[0])
                target = rng.choice(
                    [p.id for p in g.peers if p.id != leader_srv.peer_id])
                args = TransferLeadershipArguments(str(target), 3000.0)
                try:
                    # an earlier transfer may have moved this group's
                    # leadership: follow the NotLeader suggestion like any
                    # real admin client (the reference's client retry
                    # policy does exactly this) — bounded to the peer count
                    reply = None
                    for _attempt in range(2 * len(g.peers)):
                        req = RaftClientRequest(
                            client_id, leader_srv.peer_id, g.group_id,
                            next(cluster._call_ids),
                            Message(args.to_payload()),
                            type=admin_request_type(
                                RequestType.TRANSFER_LEADERSHIP),
                            timeout_ms=5000.0)
                        reply = await client.send_request(
                            leader_srv.address, req)
                        exc = reply.exception
                        if reply.success:
                            break
                        if isinstance(exc, LeaderNotReadyException):
                            # transfer raced a just-won election: the new
                            # leader serves admin ops once its startup
                            # entry commits — moments away
                            await asyncio.sleep(0.1)
                            continue
                        if not isinstance(exc, NotLeaderException) \
                                or exc.suggested_leader is None:
                            break
                        leader_srv = by_id.get(exc.suggested_leader.id,
                                               leader_srv)
                        # transferring "away from the leader" must track
                        # the real leader, or we'd ask it to transfer to
                        # itself
                        if target == leader_srv.peer_id:
                            target = rng.choice(
                                [p.id for p in g.peers
                                 if p.id != leader_srv.peer_id])
                            args = TransferLeadershipArguments(
                                str(target), 3000.0)
                    if reply is not None and reply.success:
                        churn_stats["ok"] += 1
                        cluster._leader_hint[g.group_id] = by_id.get(
                            target, cluster.servers[0])
                    else:
                        churn_stats["failed"] += 1
                        exc = reply.exception if reply is not None else None
                        churn_stats.setdefault("failures", []).append(
                            type(exc).__name__ if exc else "no-exception")
                        print(f"bench: transfer {g.group_id} -> {target} "
                              f"REJECTED: {exc}", file=sys.stderr, flush=True)
                except Exception as e:
                    churn_stats["failed"] += 1
                    churn_stats.setdefault("failures", []).append(
                        type(e).__name__)
                    print(f"bench: transfer {g.group_id} -> {target} "
                          f"FAILED: {type(e).__name__}: {e}",
                          file=sys.stderr, flush=True)
                await asyncio.sleep(0.02)

        churn_task = asyncio.create_task(churn())
        result = await cluster.run_load(writes_per_group, concurrency)
        await churn_task
        result["groups"] = num_groups
        result["mode"] = "batched" if batched else "scalar"
        result["transfers_ok"] = churn_stats["ok"]
        result["transfers_failed"] = churn_stats["failed"]
        result["transfer_failures"] = churn_stats.get("failures", [])
        return result


async def run_mixed_bench(num_groups: int, writes_per_group: int,
                          streams: int, stream_bytes: int,
                          batched: bool = True,
                          concurrency: int = 128,
                          num_servers: int = 3,
                          transport: str = "sim",
                          loop_shards: int = 1,
                          client_shards: int = 1,
                          stream_window: int = 16,
                          extra_props: Optional[dict] = None,
                          fsync_delay_ms: float = 0.0) -> dict:
    """BASELINE config 5 analog: filestore + DataStream mixed load.

    Every group runs a FileStore state machine; the bulk load is ordinary
    log-path file writes, while ``streams`` concurrent DataStream file
    streams (stream_bytes each) ride the out-of-band stream plane into a
    subset of groups (ratis-examples filestore LoadGen's mixed mode).
    With ``num_servers``/``transport`` at config 3's 5-peer real-TCP shape
    this is the ``peer5_10240_filestore`` rung: the flagship workload
    (FileStore SM + concurrent DataStream writes) at the flagship scale.

    ``fsync_delay_ms`` > 0 arms a MODELED disk at the LOG_SYNC injection
    point: every log-worker drain sweep awaits delay x distinct-files
    before its real I/O, charging per FSYNC like a device whose sync
    costs that long.  On boxes whose page cache makes real fsyncs free
    (sub-ms) this is the leg that shows the per-group vs shared-plane
    difference in wall-clock, not just in fsync counts; the numbers are
    reported as modeled, never as disk measurements."""
    import msgpack

    from ratis_tpu.client import RaftClient
    from ratis_tpu.util import injection

    async with _started_cluster(num_groups, batched, sm="filestore",
                                datastream=True, transport=transport,
                                num_servers=num_servers,
                                loop_shards=loop_shards,
                                extra_props=extra_props) as cluster:
        stream_stats = {"ok": 0, "failed": 0, "bytes": 0, "elapsed_s": 0.0}
        payload = b"\x5a" * stream_bytes

        async def one_stream(i: int):
            g = cluster.groups[i % len(cluster.groups)]
            client = (RaftClient.builder()
                      .set_raft_group(g)
                      .set_transport(cluster.factory.new_client_transport(
                          cluster.properties))
                      .set_properties(cluster.properties)
                      .build())
            try:
                cmd = msgpack.packb({"op": "stream",
                                     "path": f"stream-{i}.bin"},
                                    use_bin_type=True)
                out = await client.data_stream().stream(
                    cmd, window=stream_window)
                for off in range(0, stream_bytes, 64 << 10):
                    await out.write_async(payload[off:off + (64 << 10)])
                reply = await out.close_async()
                if reply.success:
                    stream_stats["ok"] += 1
                    stream_stats["bytes"] += stream_bytes
                else:
                    # CLASSIFIED, never silent: a failing stream under load
                    # is a correctness signal, not a throughput footnote
                    stream_stats["failed"] += 1
                    exc = type(reply.exception).__name__ \
                        if reply.exception else "no-exception"
                    stream_stats.setdefault("failures", []).append(exc)
                    print(f"bench: stream {i} REJECTED: {exc}: "
                          f"{reply.exception}", file=sys.stderr, flush=True)
            except Exception as e:
                stream_stats["failed"] += 1
                stream_stats.setdefault("failures", []).append(
                    type(e).__name__)
                print(f"bench: stream {i} FAILED: {type(e).__name__}: {e}",
                      file=sys.stderr, flush=True)
            finally:
                await client.close()

        async def stream_load():
            # stream bandwidth is timed over the STREAM work only, not the
            # (longer) concurrent write load
            t0 = time.monotonic()
            sem = asyncio.Semaphore(8)

            async def bounded(i):
                async with sem:
                    await one_stream(i)

            await asyncio.gather(*(bounded(i) for i in range(streams)))
            stream_stats["elapsed_s"] = time.monotonic() - t0

        seq = itertools.count()
        msg_factory = lambda: msgpack.packb(
            {"op": "write", "path": f"w{next(seq)}", "data": b"x" * 128},
            use_bin_type=True)

        def _fsync_total() -> int:
            # durable rungs only (memory mode registers no log workers):
            # cumulative fsyncs across every server's workers — per open
            # segment file with per-group logs, per shard on the shared
            # log plane (raft.tpu.log.shared)
            from ratis_tpu.server.log.segmented import LogWorker
            return sum(w.sync_count for w in LogWorker._instances.values())

        if fsync_delay_ms > 0:
            delay_s = fsync_delay_ms / 1000.0

            async def _disk_model(_local_id, _remote_id, *args):
                files_n = args[0] if args else 1
                await asyncio.sleep(delay_s * files_n)

            injection.put(injection.LOG_SYNC, _disk_model)
        fsyncs_before = _fsync_total()
        try:
            stream_task = asyncio.create_task(stream_load())
            result = await cluster.run_load(writes_per_group, concurrency,
                                            message_factory=msg_factory,
                                            client_shards=client_shards)
            await stream_task
        finally:
            if fsync_delay_ms > 0:
                injection.remove(injection.LOG_SYNC)
        fsyncs = _fsync_total() - fsyncs_before
        if fsyncs:
            result["fsyncs"] = fsyncs
            # per REPLICA: each commit lands one append on every peer, so
            # the per-group store reads ~1.0 here (one fsync per append)
            # and the shared plane ~1/sweep-batch — the "~1 -> ~1/groups"
            # framing, not tripled by the replication factor
            result["fsyncs_per_commit"] = round(
                fsyncs / max(1, result["commits"] * num_servers), 4)
        result["groups"] = num_groups
        result["mode"] = "batched" if batched else "scalar"
        result["transport"] = transport
        result["peers"] = num_servers
        if loop_shards > 1:
            result["loop_shards"] = loop_shards
        result["streams_ok"] = stream_stats["ok"]
        result["streams_failed"] = stream_stats["failed"]
        result["stream_failures"] = stream_stats.get("failures", [])
        result["stream_mb_per_s"] = round(
            stream_stats["bytes"]
            / max(stream_stats["elapsed_s"], 1e-9) / (1 << 20), 2)
        return result


async def run_read_write_bench(num_groups: int = 1024,
                               writes_per_group: int = 4,
                               reads_per_write: int = 3,
                               batched: bool = True,
                               concurrency: int = 128,
                               transport: str = "tcp",
                               num_servers: int = 3,
                               loop_shards: int = 1) -> dict:
    """Mixed read/write rung (VERDICT Missing #4): every write is chased by
    three reads exercising the three read paths the server implements —

    - a LINEARIZABLE read at the LEADER (raft.server.read.option=
      LINEARIZABLE + leader lease: readIndex served from the lease when
      valid, a confirmation round otherwise — LeaderLease.java:36 /
      ReadIndexHeartbeats.java:40),
    - a LINEARIZABLE read at a FOLLOWER (the follower asks the leader for
      a readIndex and waits for local apply — readIndexAsync),
    - a STALE read at a FOLLOWER (local state, no protocol).

    Reports writes/s and reads/s (aggregate + per-path counts)."""
    from ratis_tpu.protocol.requests import (read_request_type,
                                             stale_read_request_type)

    extra = {
        RaftServerConfigKeys.Read.OPTION_KEY: "LINEARIZABLE",
        RaftServerConfigKeys.Read.LEADER_LEASE_ENABLED_KEY: "true",
    }
    async with _started_cluster(num_groups, batched, transport=transport,
                                num_servers=num_servers,
                                loop_shards=loop_shards,
                                extra_props=extra) as cluster:
        client = cluster.factory.new_client_transport(cluster.properties)
        sem = asyncio.Semaphore(concurrency)
        write_lat: list[float] = []
        read_lat: list[float] = []
        counts = {"lease_leader": 0, "follower_lin": 0, "stale": 0,
                  "read_failures": 0}
        failures: list[str] = []

        async def one_read(client_id, g: RaftGroup, kind: str) -> None:
            leader = cluster._leader_hint.get(g.group_id,
                                              cluster.servers[0])
            if kind == "lease_leader":
                server = leader
                rtype = read_request_type()
            else:
                others = [s for s in cluster.servers if s is not leader]
                server = others[0] if others else leader
                rtype = (read_request_type() if kind == "follower_lin"
                         else stale_read_request_type(0))
            req = RaftClientRequest(client_id, server.peer_id, g.group_id,
                                    next(cluster._call_ids),
                                    Message.value_of(b"GET"),
                                    type=rtype, timeout_ms=15_000.0)
            t0 = time.monotonic()
            try:
                reply = await client.send_request(server.address, req)
            except (RaftException, asyncio.TimeoutError):
                reply = None
            if reply is not None and reply.success:
                read_lat.append(time.monotonic() - t0)
                counts[kind] += 1
            else:
                counts["read_failures"] += 1

        async def group_load(g: RaftGroup) -> None:
            client_id = ClientId.random_id()
            for _ in range(writes_per_group):
                async with sem:
                    t0 = time.monotonic()
                    try:
                        await cluster._write(client, client_id, g.group_id)
                    except TimeoutError:
                        failures.append(str(g.group_id))
                        continue
                    write_lat.append(time.monotonic() - t0)
                for kind in ("lease_leader", "follower_lin",
                             "stale")[:reads_per_write]:
                    async with sem:
                        await one_read(client_id, g, kind)

        t_start = time.monotonic()
        await asyncio.gather(*(group_load(g) for g in cluster.groups))
        elapsed = time.monotonic() - t_start
        total_w = num_groups * writes_per_group
        if not write_lat or len(failures) > max(8, total_w // 100):
            raise TimeoutError(f"{len(failures)}/{total_w} writes failed")
        reads_ok = len(read_lat)
        if counts["read_failures"] > max(8, (reads_ok or 1) // 20):
            raise TimeoutError(
                f"{counts['read_failures']} reads failed "
                f"(vs {reads_ok} ok) — the read paths are broken")
        write_lat.sort()
        read_lat.sort()
        nw, nr = len(write_lat), len(read_lat)
        return {
            "commits": total_w - len(failures),
            "write_failures": len(failures),
            "elapsed_s": round(elapsed, 3),
            "commits_per_sec": round((total_w - len(failures)) / elapsed, 1),
            "reads_per_sec": round(reads_ok / elapsed, 1),
            "reads_ok": reads_ok,
            "read_failures": counts["read_failures"],
            "reads_lease_leader": counts["lease_leader"],
            "reads_follower_linearizable": counts["follower_lin"],
            "reads_stale": counts["stale"],
            "p50_ms": round(write_lat[nw // 2] * 1e3, 2),
            "p99_ms": round(write_lat[min(nw - 1, (nw * 99) // 100)] * 1e3,
                            2),
            "read_p50_ms": round(read_lat[nr // 2] * 1e3, 2) if nr else None,
            "read_p99_ms": (round(
                read_lat[min(nr - 1, (nr * 99) // 100)] * 1e3, 2)
                if nr else None),
            "election_convergence_s": round(
                cluster.election_convergence_s, 2),
            "groups": num_groups,
            "mode": "batched" if batched else "scalar",
            "transport": transport,
            "peers": num_servers,
        }


async def run_snapshot_catchup_bench(num_groups: int = 1024,
                                     writes_per_group: int = 4,
                                     batched: bool = True,
                                     concurrency: int = 128,
                                     transport: str = "tcp",
                                     num_servers: int = 3,
                                     loop_shards: int = 1) -> dict:
    """InstallSnapshot-under-load rung (VERDICT Missing #5): seed every
    group with writes, snapshot+purge the leaders' logs, WIPE one follower
    server's replicas (group_remove + fresh group_add — the in-memory
    analog of losing a disk), and measure the chunked-install catch-up
    time while the cluster keeps serving writes.  Asserts the write path
    does not collapse during installs (cps_during >= cps_before / 4 — a
    collapse detector, not a noise gate)."""
    import tempfile
    tmp = tempfile.mkdtemp(prefix="ratis-snap-bench-")
    async with _started_cluster(num_groups, batched, transport=transport,
                                num_servers=num_servers,
                                loop_shards=loop_shards,
                                sm_storage_root=tmp) as cluster:
        victim = cluster.servers[-1]
        # seed: several committed entries per group so the purge leaves a
        # real gap between a fresh log (next=0) and the leader's start
        before = await cluster.run_load(writes_per_group, concurrency)

        # snapshot + purge on every leader (the reference's
        # SnapshotManagement path does exactly this per group)
        snap_indexes: dict = {}
        async def snap(g: RaftGroup):
            leader = cluster._leader_hint.get(g.group_id,
                                              cluster.servers[0])
            d = leader.divisions[g.group_id]
            idx = await d.take_snapshot_async()
            snap_indexes[g.group_id] = idx
        for i in range(0, len(cluster.groups), 256):
            await asyncio.gather(*(snap(g)
                                   for g in cluster.groups[i:i + 256]))
        if not any(v >= 0 for v in snap_indexes.values()):
            raise RuntimeError("no leader produced a snapshot")

        # wipe the victim's replicas: remove + fresh re-add, in waves
        t_wipe = time.monotonic()
        for i in range(0, len(cluster.groups), 256):
            batch = cluster.groups[i:i + 256]
            await asyncio.gather(*(victim.group_remove(g.group_id)
                                   for g in batch))
            await asyncio.gather(*(victim.group_add(g) for g in batch))

        # concurrent write load while the installs catch the victim up
        load_task = asyncio.create_task(
            cluster.run_load(writes_per_group, concurrency))
        deadline = time.monotonic() + 600.0
        pending = {g.group_id for g in cluster.groups
                   if snap_indexes.get(g.group_id, -1) >= 0}
        while pending and time.monotonic() < deadline:
            caught = {gid for gid in pending
                      if (d := victim.divisions.get(gid)) is not None
                      and d._applied_index >= snap_indexes[gid]}
            pending -= caught
            if pending:
                await asyncio.sleep(0.1)
        catchup_s = time.monotonic() - t_wipe
        during = await load_task
        installed = sum(
            1 for gid, idx in snap_indexes.items() if idx >= 0
            and (d := victim.divisions.get(gid)) is not None
            and d.state_machine.get_latest_snapshot() is not None)
        if pending:
            raise TimeoutError(
                f"{len(pending)} groups never caught up after the wipe")
        if during["commits_per_sec"] < before["commits_per_sec"] / 4:
            raise RuntimeError(
                "write path collapsed during snapshot installs: "
                f"{during['commits_per_sec']} vs {before['commits_per_sec']}"
                " before")
        return {
            "commits_per_sec": during["commits_per_sec"],
            "cps_before": before["commits_per_sec"],
            "p99_ms": during["p99_ms"],
            "write_failures": (before["write_failures"]
                               + during["write_failures"]),
            "catchup_s": round(catchup_s, 2),
            "installs": installed,
            "groups": num_groups,
            "transport": transport,
            "peers": num_servers,
            "election_convergence_s": round(
                cluster.election_convergence_s, 2),
        }


async def run_stream_throughput_bench(streams: int, stream_mb: int,
                                      packet_kb: int = 1024,
                                      window: int = 32) -> dict:
    """Dedicated DataStream THROUGHPUT rung: few concurrent streams moving
    tens of MB each over real TCP with big packets — the bulk-bytes job the
    out-of-band plane exists for (reference NettyClientStreamRpc /
    DataStreamManagement; the mixed rung measures coexistence with raft
    load, this one measures the pipe)."""
    import msgpack

    from ratis_tpu.client import RaftClient

    async with _started_cluster(max(streams, 4), True, sm="filestore",
                                datastream=True) as cluster:
        stream_bytes = stream_mb << 20
        packet = packet_kb << 10
        payload = b"\x5a" * packet
        stats = {"ok": 0, "failed": 0, "bytes": 0, "failures": []}

        async def one(i: int):
            g = cluster.groups[i % len(cluster.groups)]
            client = (RaftClient.builder()
                      .set_raft_group(g)
                      .set_transport(cluster.factory.new_client_transport(
                          cluster.properties))
                      .set_properties(cluster.properties)
                      .build())
            try:
                cmd = msgpack.packb({"op": "stream", "path": f"bulk-{i}.bin"},
                                    use_bin_type=True)
                out = await client.data_stream().stream(cmd, window=window)
                for _ in range(stream_bytes // packet):
                    await out.write_async(payload)
                reply = await out.close_async()
                if reply.success:
                    stats["ok"] += 1
                    stats["bytes"] += stream_bytes
                else:
                    stats["failed"] += 1
                    stats["failures"].append(
                        type(reply.exception).__name__
                        if reply.exception else "no-exception")
            except Exception as e:
                stats["failed"] += 1
                stats["failures"].append(type(e).__name__)
                print(f"bench: bulk stream {i} FAILED: "
                      f"{type(e).__name__}: {e}", file=sys.stderr, flush=True)
            finally:
                await client.close()

        t0 = time.monotonic()
        await asyncio.gather(*(one(i) for i in range(streams)))
        elapsed = time.monotonic() - t0
        return {
            "streams": streams,
            "stream_mb": stream_mb,
            "packet_kb": packet_kb,
            "streams_ok": stats["ok"],
            "streams_failed": stats["failed"],
            "stream_failures": stats["failures"],
            "stream_mb_per_s": round(
                stats["bytes"] / max(elapsed, 1e-9) / (1 << 20), 2),
            "elapsed_s": round(elapsed, 2),
        }


async def run_zipf_fleet_bench(num_groups: int = 1024,
                               clients: int = 10240,
                               requests_per_client: int = 1,
                               zipf_s: float = 1.1,
                               concurrency: int = 512,
                               batched: bool = True,
                               transport: str = "tcp",
                               num_servers: int = 3,
                               loop_shards: int = 1,
                               seed: int = 11,
                               element_limit: int = 192,
                               unsat_clients: int = 256) -> dict:
    """Zipf client-fleet rung (serving plane, round 13): drive ``clients``
    logical client connections whose home groups follow a zipf(s) law over
    ``num_groups`` groups — the skewed-popularity regime admission control
    exists for.  Admission is ON with a pending budget deliberately below
    the fleet's offered concurrency, so the rung measures the serving
    plane under genuine overload:

    - writes/s and linearizable reads/s actually served,
    - shed fraction (typed ResourceUnavailableException replies at
      intake; clients honor the retry-after hint and try again),
    - p99 write latency under overload vs an unsaturated baseline phase
      run first at low concurrency (the "does backpressure keep the
      served tail bounded" number),
    - peak pending-budget occupancy (bounded-pending evidence), and
    - the hot-group sketch's view of the skew (round-11 telemetry) vs
      the analytic zipf top-group share.
    """
    import bisect
    import random

    from ratis_tpu.protocol.requests import read_request_type

    keys = RaftServerConfigKeys.Serving
    extra = {
        RaftServerConfigKeys.Read.OPTION_KEY: "LINEARIZABLE",
        RaftServerConfigKeys.Read.LEADER_LEASE_ENABLED_KEY: "true",
        RaftServerConfigKeys.Telemetry.ENABLED_KEY: "true",
        RaftServerConfigKeys.Telemetry.INTERVAL_KEY: "250ms",
        keys.ADMISSION_ENABLED_KEY: "true",
        keys.PENDING_ELEMENT_LIMIT_KEY: str(element_limit),
        keys.RETRY_AFTER_KEY: "40ms",
    }
    rng = random.Random(seed)
    # zipf CDF over group ranks: rank r (0-based) carries weight (r+1)^-s;
    # group 0 is the fleet's hot group by construction
    weights = [(r + 1) ** -zipf_s for r in range(num_groups)]
    total_w = sum(weights)
    cdf, acc = [], 0.0
    for w in weights:
        acc += w
        cdf.append(acc / total_w)
    expected_top_share = weights[0] / total_w

    async with _started_cluster(num_groups, batched, transport=transport,
                                num_servers=num_servers,
                                loop_shards=loop_shards,
                                extra_props=extra) as cluster:
        client = cluster.factory.new_client_transport(cluster.properties)

        def shed_now() -> int:
            return sum(s.serving.admission.shed_total
                       for s in cluster.servers)

        def admitted_now() -> int:
            return sum(s.serving.admission.admitted_total
                       for s in cluster.servers)

        def pending_now() -> int:
            return max(sum(s.serving.admission.pending_count)
                       for s in cluster.servers)

        async def one_op(client_id, gid, is_read, lat, stats) -> None:
            server = cluster._leader_hint.get(gid, cluster.servers[0])
            deadline = time.monotonic() + 60.0
            t0 = time.monotonic()
            while True:
                req = RaftClientRequest(
                    client_id, server.peer_id, gid,
                    next(cluster._call_ids),
                    Message.value_of(b"GET" if is_read else b"INCREMENT"),
                    type=(read_request_type() if is_read
                          else write_request_type()),
                    timeout_ms=10_000.0)
                try:
                    reply = await client.send_request(server.address, req)
                except (RaftException, asyncio.TimeoutError):
                    reply = None
                if reply is not None and reply.success:
                    lat.append(time.monotonic() - t0)
                    cluster._leader_hint[gid] = server
                    return
                if time.monotonic() > deadline:
                    stats["failures"] += 1
                    return
                exc = reply.exception if reply is not None else None
                if isinstance(exc, ResourceUnavailableException):
                    # the typed overload reply: honor the retry-after hint
                    stats["shed_seen"] += 1
                    await asyncio.sleep(max(exc.retry_after_ms, 1) / 1e3)
                elif isinstance(exc, NotLeaderException) \
                        and exc.suggested_leader is not None:
                    by_id = {s.peer_id: s for s in cluster.servers}
                    server = by_id.get(exc.suggested_leader.id, server)
                else:
                    idx = cluster.servers.index(server)
                    server = cluster.servers[(idx + 1) % len(cluster.servers)]
                    await asyncio.sleep(0.01)

        async def drive(n_clients: int, conc: int) -> dict:
            sem = asyncio.Semaphore(conc)
            stats = {"shed_seen": 0, "failures": 0, "pending_peak": 0}
            write_lat: list[float] = []
            read_lat: list[float] = []
            homes = [bisect.bisect_left(cdf, rng.random())
                     for _ in range(n_clients)]

            async def fleet_client(i: int) -> None:
                client_id = ClientId.random_id()
                gid = cluster.groups[min(homes[i], num_groups - 1)].group_id
                for _ in range(requests_per_client):
                    async with sem:
                        await one_op(client_id, gid, False, write_lat, stats)
                    async with sem:
                        await one_op(client_id, gid, True, read_lat, stats)

            async def sample_pending() -> None:
                while True:
                    stats["pending_peak"] = max(stats["pending_peak"],
                                                pending_now())
                    await asyncio.sleep(0.025)

            sampler = asyncio.ensure_future(sample_pending())
            t0 = time.monotonic()
            try:
                await asyncio.gather(*(fleet_client(i)
                                       for i in range(n_clients)))
            finally:
                sampler.cancel()
            elapsed = time.monotonic() - t0
            write_lat.sort()
            read_lat.sort()
            nw, nr = len(write_lat), len(read_lat)
            return {
                "elapsed": elapsed, "writes_ok": nw, "reads_ok": nr,
                "p99_s": write_lat[min(nw - 1, (nw * 99) // 100)] if nw
                else None,
                "read_p99_s": read_lat[min(nr - 1, (nr * 99) // 100)] if nr
                else None,
                **stats,
            }

        # phase 1 — unsaturated baseline: a small fleet at low concurrency
        # (well under the pending budget), the denominator for the
        # overload-p99 ratio
        unsat = await drive(unsat_clients, max(8, element_limit // 8))
        # phase 2 — the fleet: offered concurrency deliberately above the
        # pending budget, so intake sheds and clients back off
        shed0, adm0 = shed_now(), admitted_now()
        sweeps0 = sum(s.serving.read_batch.sweeps for s in cluster.servers
                      if s.serving.read_batch is not None)
        fleet = await drive(clients, concurrency)
        shed = shed_now() - shed0
        admitted = admitted_now() - adm0
        sweeps = sum(s.serving.read_batch.sweeps for s in cluster.servers
                     if s.serving.read_batch is not None) - sweeps0

        total_ops = clients * requests_per_client * 2
        if fleet["failures"] > max(16, total_ops // 50):
            raise TimeoutError(
                f"{fleet['failures']}/{total_ops} fleet ops failed outright "
                f"— shedding must surface typed replies, not timeouts")

        # the hot-group sketch's view of the skew vs the analytic share
        from ratis_tpu.metrics.aggregate import merge_hotgroups
        tel = [s.telemetry for s in cluster.servers
               if s.telemetry is not None]
        hot = merge_hotgroups([t.hotgroups_info() for t in tel], n=4) \
            if tel else {"groups": []}
        top = hot["groups"][0] if hot["groups"] else None
        p99_unsat = unsat["p99_s"]
        p99_fleet = fleet["p99_s"]
        return {
            "clients": clients,
            "groups": num_groups,
            "zipf_s": zipf_s,
            "writes_ok": fleet["writes_ok"],
            "reads_ok": fleet["reads_ok"],
            "failures": fleet["failures"],
            "elapsed_s": round(fleet["elapsed"], 3),
            "writes_per_sec": round(fleet["writes_ok"] / fleet["elapsed"], 1),
            "reads_per_sec": round(fleet["reads_ok"] / fleet["elapsed"], 1),
            # shed fraction of everything that reached intake (server
            # truth) + the client-observed typed replies (retry loop saw
            # them, honored retry-after, and got through)
            "shed": shed,
            "admitted": admitted,
            "shed_frac": round(shed / max(1, shed + admitted), 4),
            "shed_seen_by_clients": fleet["shed_seen"],
            "p99_ms": round(p99_fleet * 1e3, 2) if p99_fleet else None,
            "read_p99_ms": (round(fleet["read_p99_s"] * 1e3, 2)
                            if fleet["read_p99_s"] else None),
            "p99_unsat_ms": round(p99_unsat * 1e3, 2) if p99_unsat else None,
            "overload_p99_ratio": (round(p99_fleet / p99_unsat, 2)
                                   if p99_fleet and p99_unsat else None),
            "pending_peak": fleet["pending_peak"],
            "pending_limit": element_limit,
            # batched readIndex amortization: confirmation sweeps per
            # linearizable read served (lease fast path + batching keep
            # this far under 1; acceptance bound is < 0.1 at 1024 groups)
            "confirm_sweeps_per_read": round(
                sweeps / max(1, fleet["reads_ok"]), 4),
            "hot_share": top["share_min"] if top else 0.0,
            "hot_group": top["group"] if top else None,
            "hot_group_expected": str(cluster.groups[0].group_id),
            "expected_top_share": round(expected_top_share, 4),
            "election_convergence_s": round(
                cluster.election_convergence_s, 2),
            "mode": "batched" if batched else "scalar",
            "transport": transport,
            "peers": num_servers,
        }


async def run_placement_bench(num_groups: int = 48,
                              clients: int = 384,
                              requests_per_client: int = 6,
                              zipf_s: float = 1.2,
                              pace_s: float = 0.25,
                              transport: str = "tcp",
                              num_servers: int = 3,
                              seed: int = 23,
                              element_limit: int = 48,
                              hot_pins: int = 8,
                              grey_delay_ms: int = 120,
                              settle_s: float = 4.0) -> dict:
    """Closed-loop placement rung (round 16): the zipf fleet with an
    INDUCED hotspot and an INDUCED grey follower, measured back-to-back
    with the placement controller OFF then ON.

    Setup: pin the ``hot_pins`` hottest zipf groups' leaderships onto
    server 0 (the hotspot every skewed deployment eventually grows) and
    delay server N-1's append handling by ``grey_delay_ms`` per envelope
    (the grey follower: up, acking, slow).  Leases are disabled so every
    linearizable read rides a batched readIndex confirmation sweep — the
    path steering actually gates.

    Phase OFF drives the fleet and measures the hot-group write p99, the
    pinned server's shed count, and the grey peer's share of
    confirmation group-requests.  Then a PlacementController is armed on
    every server (fast interval, low hot-share floor, zero hysteresis —
    the storm tuning), given ``settle_s`` of load to act, and phase ON
    re-measures the same numbers.  The controller earns its keep iff
    hot p99 and shed drop and the grey confirmation share collapses
    while the peer stays up."""
    import bisect
    import random

    from ratis_tpu.placement import PlacementController
    from ratis_tpu.protocol.admin import TransferLeadershipArguments
    from ratis_tpu.protocol.requests import (RequestType, admin_request_type,
                                             read_request_type)
    from ratis_tpu.util import injection

    keys = RaftServerConfigKeys.Serving
    extra = {
        RaftServerConfigKeys.Read.OPTION_KEY: "LINEARIZABLE",
        # leases OFF: confirmation sweeps must actually fire, or there is
        # nothing for the steering hook to steer
        RaftServerConfigKeys.Read.LEADER_LEASE_ENABLED_KEY: "false",
        RaftServerConfigKeys.Telemetry.ENABLED_KEY: "true",
        RaftServerConfigKeys.Telemetry.INTERVAL_KEY: "250ms",
        keys.ADMISSION_ENABLED_KEY: "true",
        keys.PENDING_ELEMENT_LIMIT_KEY: str(element_limit),
        keys.RETRY_AFTER_KEY: "40ms",
    }
    rng = random.Random(seed)
    weights = [(r + 1) ** -zipf_s for r in range(num_groups)]
    total_w = sum(weights)
    cdf, acc = [], 0.0
    for w in weights:
        acc += w
        cdf.append(acc / total_w)

    async with _started_cluster(num_groups, True, transport=transport,
                                num_servers=num_servers,
                                extra_props=extra) as cluster:
        client = cluster.factory.new_client_transport(cluster.properties)
        hot_srv = cluster.servers[0]
        grey_srv = cluster.servers[-1]
        grey_name = str(grey_srv.peer_id)
        by_id = {s.peer_id: s for s in cluster.servers}
        admin_id = ClientId.random_id()

        async def pin(group, target_srv) -> bool:
            """Transfer ``group``'s leadership to ``target_srv`` (the
            churn rung's NotLeader-following retry idiom)."""
            leader_srv = cluster._leader_hint.get(group.group_id,
                                                  cluster.servers[0])
            if leader_srv is target_srv:
                return True
            args = TransferLeadershipArguments(str(target_srv.peer_id),
                                               3000.0)
            reply = None
            for _attempt in range(2 * len(group.peers)):
                req = RaftClientRequest(
                    admin_id, leader_srv.peer_id, group.group_id,
                    next(cluster._call_ids), Message(args.to_payload()),
                    type=admin_request_type(
                        RequestType.TRANSFER_LEADERSHIP),
                    timeout_ms=5000.0)
                try:
                    reply = await client.send_request(leader_srv.address,
                                                      req)
                except (RaftException, asyncio.TimeoutError):
                    reply = None
                if reply is None:
                    break
                if reply.success:
                    cluster._leader_hint[group.group_id] = target_srv
                    return True
                exc = reply.exception
                if isinstance(exc, LeaderNotReadyException):
                    await asyncio.sleep(0.1)
                    continue
                if isinstance(exc, NotLeaderException) \
                        and exc.suggested_leader is not None:
                    nxt = by_id.get(exc.suggested_leader.id)
                    if nxt is target_srv:   # already there
                        cluster._leader_hint[group.group_id] = target_srv
                        return True
                    leader_srv = nxt or leader_srv
                    continue
                break
            return False

        # the induced hotspot: every hot group's leadership on server 0
        pinned = 0
        for g in cluster.groups[:hot_pins]:
            pinned += bool(await pin(g, hot_srv))

        # the induced grey follower: delay its append HANDLING (inbound)
        # — it stays up and acking, just slow, exactly the regime the lag
        # ledger's health score exists to catch
        delay_s = grey_delay_ms / 1e3

        async def on_append(local_id, _remote_id, *_args):
            if str(local_id).split("@")[0] == grey_name:
                await asyncio.sleep(delay_s)

        injection.put(injection.APPEND_ENTRIES, on_append)

        def confirm_totals() -> tuple:
            """(grey group-requests, all group-requests) across servers."""
            grey_n = tot = 0
            for s in cluster.servers:
                rb = s.serving.read_batch
                if rb is None:
                    continue
                for name, n in rb.confirm_sent.items():
                    tot += n
                    if name == grey_name:
                        grey_n += n
            return grey_n, tot

        def steered_now() -> int:
            return sum(s.read_steering.steered for s in cluster.servers)

        def hot_adm_now() -> tuple:
            """(shed, admitted) on the pinned hot server.  The rung's
            shed metric is the FRACTION of intake shed: the ON phase
            serves ops faster, so its offered per-second rate (and raw
            intake) is higher — raw shed counts aren't comparable."""
            a = hot_srv.serving.admission
            return a.shed_total, a.admitted_total

        async def one_op(client_id, gid, is_read, lat, stats) -> None:
            server = cluster._leader_hint.get(gid, cluster.servers[0])
            deadline = time.monotonic() + 60.0
            t0 = time.monotonic()
            while True:
                req = RaftClientRequest(
                    client_id, server.peer_id, gid,
                    next(cluster._call_ids),
                    Message.value_of(b"GET" if is_read else b"INCREMENT"),
                    type=(read_request_type() if is_read
                          else write_request_type()),
                    timeout_ms=10_000.0)
                try:
                    reply = await client.send_request(server.address, req)
                except (RaftException, asyncio.TimeoutError):
                    reply = None
                if reply is not None and reply.success:
                    lat.append(time.monotonic() - t0)
                    cluster._leader_hint[gid] = server
                    return
                if time.monotonic() > deadline:
                    stats["failures"] += 1
                    return
                exc = reply.exception if reply is not None else None
                if isinstance(exc, ResourceUnavailableException):
                    stats["shed_seen"] += 1
                    await asyncio.sleep(max(exc.retry_after_ms, 1) / 1e3)
                elif isinstance(exc, NotLeaderException) \
                        and exc.suggested_leader is not None:
                    server = by_id.get(exc.suggested_leader.id, server)
                else:
                    idx = cluster.servers.index(server)
                    server = cluster.servers[(idx + 1)
                                             % len(cluster.servers)]
                    await asyncio.sleep(0.01)

        async def drive(n_clients: int, pace_s: float) -> dict:
            """One measured fleet pass, OPEN LOOP: every client fires a
            write+read pair every ``pace_s`` on a fixed schedule,
            regardless of how slowly earlier pairs complete.  A closed
            loop would offer MORE load to whichever configuration serves
            faster, making the OFF/ON shed comparison meaningless; with
            a fixed offered schedule, shed and p99 both measure the
            placement, not the feedback.  Hot-group write latencies are
            tracked separately (the hotspot p99 the rung is about)."""
            stats = {"shed_seen": 0, "failures": 0}
            hot_lat: list[float] = []
            write_lat: list[float] = []
            read_lat: list[float] = []
            homes = [bisect.bisect_left(cdf, rng.random())
                     for _ in range(n_clients)]

            async def pair(client_id, gid, wlat) -> None:
                await one_op(client_id, gid, False, wlat, stats)
                await one_op(client_id, gid, True, read_lat, stats)

            pairs: list = []
            t0 = time.monotonic()

            async def fleet_client(i: int) -> None:
                client_id = ClientId.random_id()
                rank = min(homes[i], num_groups - 1)
                gid = cluster.groups[rank].group_id
                wlat = hot_lat if rank < hot_pins else write_lat
                for k in range(requests_per_client):
                    # synchronized waves, deliberately NOT staggered: the
                    # instantaneous burst a wave lands on the hot server
                    # is what overflows its pending budget, so the shed
                    # comparison tracks burst-vs-budget (placement), not
                    # this box's service rate
                    at = t0 + pace_s * k
                    delay = at - time.monotonic()
                    if delay > 0:
                        await asyncio.sleep(delay)
                    pairs.append(asyncio.ensure_future(
                        pair(client_id, gid, wlat)))

            await asyncio.gather(*(fleet_client(i)
                                   for i in range(n_clients)))
            await asyncio.gather(*pairs)
            elapsed = time.monotonic() - t0
            hot_lat.sort()
            nh = len(hot_lat)
            return {
                "elapsed": elapsed,
                "writes_ok": nh + len(write_lat),
                "reads_ok": len(read_lat),
                "hot_writes": nh,
                "hot_p99_s": (hot_lat[min(nh - 1, (nh * 99) // 100)]
                              if nh else None),
                **stats,
            }

        try:
            # ------------------------------------------- phase OFF
            grey0, tot0 = confirm_totals()
            shed0, adm0 = hot_adm_now()
            off = await drive(clients, pace_s)
            grey1, tot1 = confirm_totals()
            shed1, adm1 = hot_adm_now()
            off_shed, off_adm = shed1 - shed0, adm1 - adm0
            off_grey_frac = ((grey1 - grey0) / max(1, tot1 - tot0))

            # ------------------------------- arm the control loop
            ctrls = []
            for s in cluster.servers:
                # the armed tuning: score the induced laggard low enough
                # to steer — at threshold 1 any link with an entry in
                # flight counts, and only the delayed peer sustains that —
                # and let single-digit-percent groups cross the hot floor
                # (the storm scenario runs the same knobs)
                s.engine.ledger.lag_threshold = 1
                s.engine.ledger.up_window_ms = 8000
                # hysteresis 1 (not the storm's 0): the bench measures
                # CONVERGENCE — the plan must go quiet once balanced, not
                # keep shuffling leaderships through the measured phase
                # cooldown outlasts the measured window: a group moves at
                # most ONCE (during settle) — the ON phase then measures
                # the converged placement, with a mid-phase handover's
                # election pause never polluting the p99/shed numbers
                ctrl = PlacementController(
                    s, interval_s=0.4, cooldown_s=60.0, max_per_round=2,
                    hot_share=0.02, grey_score=0.5, hysteresis=1.0,
                    steer_ttl_s=6.0, transfer_timeout_s=3.0)
                ctrl.start()
                s.placement = ctrl
                ctrls.append(ctrl)
            # settle under SUSTAINED full-fleet load: the controller only
            # sees what the sketch/ledger/admission see — the ledger's
            # active-link scoring needs commits in flight at its sample
            # times, and the shed-rate transfer gate needs the hotspot
            # actually overflowing its budget while rounds fire
            deadline = time.monotonic() + settle_s
            hard_stop = deadline + 2 * settle_s
            while time.monotonic() < deadline:
                await drive(clients, pace_s)
                if time.monotonic() >= deadline \
                        and time.monotonic() < hard_stop \
                        and any(c.last_plan is not None
                                and c.last_plan.transfers()
                                for c in ctrls):
                    # still actuating: give it one more pass (bounded) so
                    # the ON phase measures the converged placement, not
                    # the tail of the rebalance itself
                    deadline = min(hard_stop,
                                   time.monotonic() + settle_s / 2)

            # freeze the placement for the measured phase: the loop stays
            # live (steering is re-planned every round, so the grey peer
            # stays deflected) but the transfer budget drops to zero — a
            # handover's election pause landing INSIDE the measured
            # window would swamp the p99 with a one-off artifact
            for c in ctrls:
                c.policy.max_transfers_per_round = 0

            # -------------------------------------------- phase ON
            grey2, tot2 = confirm_totals()
            shed2, adm2 = hot_adm_now()
            steer0 = steered_now()
            on = await drive(clients, pace_s)
            grey3, tot3 = confirm_totals()
            shed3, adm3 = hot_adm_now()
            on_shed, on_adm = shed3 - shed2, adm3 - adm2
            on_grey_sends = grey3 - grey2
            on_grey_frac = on_grey_sends / max(1, tot3 - tot2)
            steered = steered_now() - steer0
            transfers = sum(c.actuator.transfers_ok for c in ctrls)
            plans = sum(c.rounds for c in ctrls)
        finally:
            for c in list(locals().get("ctrls") or ()):
                await c.close()
            for s in cluster.servers:
                s.placement = None
            injection.remove(injection.APPEND_ENTRIES)

        hot_leads_after = sum(
            1 for g in cluster.groups[:hot_pins]
            if (d := hot_srv.divisions.get(g.group_id)) is not None
            and d.is_leader())
        p99_off = off["hot_p99_s"]
        p99_on = on["hot_p99_s"]
        return {
            "groups": num_groups, "clients": clients, "zipf_s": zipf_s,
            "transport": transport, "peers": num_servers,
            "hot_pins_requested": hot_pins, "hot_pins": pinned,
            "hot_leads_after": hot_leads_after,
            "grey_peer": grey_name, "grey_delay_ms": grey_delay_ms,
            "writes_ok_off": off["writes_ok"], "writes_ok_on": on["writes_ok"],
            "reads_ok_off": off["reads_ok"], "reads_ok_on": on["reads_ok"],
            "failures": off["failures"] + on["failures"],
            "hotspot_p99_before_ms": (round(p99_off * 1e3, 2)
                                      if p99_off else None),
            "hotspot_p99_after_ms": (round(p99_on * 1e3, 2)
                                     if p99_on else None),
            "hotspot_p99_ratio": (round(p99_on / p99_off, 3)
                                  if p99_on and p99_off else None),
            "hot_shed_off": off_shed, "hot_shed_on": on_shed,
            "hot_shed_frac_off": round(
                off_shed / max(1, off_shed + off_adm), 4),
            "hot_shed_frac_on": round(
                on_shed / max(1, on_shed + on_adm), 4),
            "grey_confirm_frac_off": round(off_grey_frac, 4),
            "grey_confirm_frac_on": round(on_grey_frac, 4),
            # of the confirmation group-requests the sweeps WOULD have
            # aimed at the grey peer during ON, the fraction steering
            # actually deflected
            "grey_steer_frac": round(
                steered / max(1, steered + on_grey_sends), 4),
            "steered_reads": steered,
            "transfers": transfers,
            "plans_computed": plans,
            "election_convergence_s": round(
                cluster.election_convergence_s, 2),
        }


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
