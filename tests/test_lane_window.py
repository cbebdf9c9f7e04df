"""The lane's frame window follows how the follower's transport hands a
lane's frames to the server (PR 34).

Where frames are worked on as they arrive (TCP, the simulated transport) a
lane holds ``envelope.inflight`` x ``window-depth`` envelope slots, so a
group's window can fill with frames in work.  Where the transport takes a
lane's frames in turn (gRPC's keyed-FIFO stream dispatch) the lane holds
``envelope.inflight`` slots and a full window batches by its acks: the frame
cut when a reply frees a slot carries everything that gathered meanwhile.
Counts and states only, never a timing.
"""

import asyncio

import pytest

from ratis_tpu.protocol.raftrpc import AppendEnvelope
from ratis_tpu.trace import get_tracer

GROUPS = 32
WINDOW_PROPS = {
    "raft.tpu.replication.window-depth": "4",
    "raft.server.log.appender.envelope.inflight": "4",
    "raft.tpu.replication.sweep": "1",
    "raft.server.log.appender.coalescing.enabled": "true",
    # heartbeats travel beside the lanes, so a lane's frames are appends
    "raft.tpu.heartbeat.coalescing.enabled": "true",
}


async def _until(cond, what: str, timeout_s: float = 20.0) -> None:
    deadline = asyncio.get_running_loop().time() + timeout_s
    while not cond():
        assert asyncio.get_running_loop().time() < deadline, what
        await asyncio.sleep(0.002)


REPLICATE_COUNTERS = ("replicate.frames", "replicate.items",
                      "replicate.sweeps", "replicate.window_full")


@pytest.fixture(autouse=True)
def _tracer_sandbox():
    yield
    get_tracer().configure(enabled=False)


def _session_counters(*names) -> dict:
    """The open trace session's deltas of the counters ``names``."""
    counters = get_tracer().session()["counters"]
    return {name: counters.get(name, 0) for name in names}


class _HeldFrames:
    """Parks every sequenced append frame of a simulated network in front of
    its follower until the test lets it through."""

    def __init__(self, network):
        self.parked: list[tuple] = []   # (src, dst, envelope, gate)
        self.open = False
        self._orig = network.deliver_server_rpc
        network.deliver_server_rpc = self._deliver

    async def _deliver(self, src, dst, msg):
        if (not self.open and isinstance(msg, AppendEnvelope)
                and msg.seq >= 0):
            gate = asyncio.get_running_loop().create_future()
            self.parked.append((src, dst, msg, gate))
            await gate
        return await self._orig(src, dst, msg)

    def toward(self, dst) -> list[tuple]:
        return [p for p in self.parked if p[1] == dst]

    def let_through(self, parked: tuple) -> None:
        self.parked.remove(parked)
        parked[3].set_result(None)

    def let_all_through(self) -> None:
        self.open = True
        for p in list(self.parked):
            self.let_through(p)


async def _held_lane_run(in_turn: bool, monkeypatch) -> dict:
    """32 groups led by one server, one write each, every append frame held
    at the followers; then one reply of one lane is let go.  Returns what
    was seen on the way."""
    from ratis_tpu.client import RaftClient
    from ratis_tpu.server.replication import PeerSender
    from ratis_tpu.server.watchdog import KIND_STUCK_LANE, StallWatchdog
    from ratis_tpu.tools.bench_cluster import BenchCluster
    from ratis_tpu.transport.simulated import SimulatedServerTransport

    monkeypatch.setattr(SimulatedServerTransport, "lane_frames_in_turn",
                        in_turn)
    # an observer of its own for ``replicate.window_full``: a drain pass that
    # met marks, and whether it left some behind
    seen = {"sweeps": 0, "left_marks": 0}
    collect = PeerSender.sweep_collect

    def watched_collect(self):
        had = bool(self._dirty) and self._running
        collect(self)
        seen["sweeps"] += had
        seen["left_marks"] += had and bool(self._dirty)

    monkeypatch.setattr(PeerSender, "sweep_collect", watched_collect)
    cluster = BenchCluster(GROUPS, num_servers=3, batched=False,
                           transport="sim", extra_props=WINDOW_PROPS)
    await cluster.start()
    clients, writes = [], []
    try:
        leader = cluster.servers[0]
        lanes = list(leader.replication._senders.values())
        assert len(lanes) == 2 and all(s.sequenced for s in lanes)
        await _until(lambda: not any(s.frames_in_flight or s._dirty
                                     for s in lanes), "bring-up never idle")
        out = {"cap": [s.inflight_cap for s in lanes]}
        held = _HeldFrames(cluster.network)
        get_tracer().configure(enabled=True, sample_every=1, ring_size=4096)
        seen.update(sweeps=0, left_marks=0)
        leader.replication.metrics["win_hwm"] = 0

        def staged(s):
            return s.frames_in_flight + len(s._dirty)

        for i, g in enumerate(cluster.groups):
            client = (RaftClient.builder().set_raft_group(g)
                      .set_transport(cluster.factory.new_client_transport(
                          cluster.properties))
                      .set_properties(cluster.properties).build())
            clients.append(client)
            writes.append(asyncio.ensure_future(
                client.io().send(b"INCREMENT")))
            # one write a sweep: the next is sent once this one has reached
            # both lanes, in a frame or as a mark
            await _until(lambda: all(staged(s) > i for s in lanes),
                         f"write {i} never reached the lanes")
        out["unanswered"] = [s.frames_in_flight for s in lanes]
        out["marked"] = [len(s._dirty) for s in lanes]

        lane = lanes[0]
        gathered = {a.division.group_id for a in lane._dirty}
        parked = held.toward(lane.to)
        assert len(parked) == lane.frames_in_flight
        frames_before = {id(p[2]) for p in parked}
        held.let_through(parked[0])
        await _until(lambda: any(id(p[2]) not in frames_before
                                 for p in held.toward(lane.to)),
                     "no frame followed the released reply")
        new = [p[2] for p in held.toward(lane.to)
               if id(p[2]) not in frames_before]
        out["gathered"] = gathered
        out["next_frames"] = [
            {r.header.group_id for r in env.items} for env in new]
        out["marked_after"] = len(lane._dirty)
        out["unanswered_after"] = lane.frames_in_flight

        out["counters"] = _session_counters(*REPLICATE_COUNTERS)
        out["seen"] = dict(seen)

        # the watchdog's "lane full" means every slot of THIS lane taken
        watchdog = StallWatchdog(leader, interval_s=60.0)
        try:
            for _ in range(3):      # the baseline, then two flat rounds
                watchdog.sample()
            out["stuck"] = [e["detail"] for e in watchdog.events()
                            if e["kind"] == KIND_STUCK_LANE]
        finally:
            await watchdog.close()

        held.let_all_through()
        replies = await asyncio.wait_for(asyncio.gather(*writes), 30.0)
        out["acked"] = sum(r.success for r in replies)
        out["win_hwm"] = leader.replication.metrics["win_hwm"]
        return out
    finally:
        for w in writes:
            w.cancel()
        for c in clients:
            await c.close()
        await cluster.close()


@pytest.fixture(scope="module")
def held_runs():
    """One run of the scenario a dispatch mode, shared by its tests."""
    runs = {}

    def get(in_turn: bool) -> dict:
        if in_turn not in runs:
            mp = pytest.MonkeyPatch()
            try:
                runs[in_turn] = asyncio.run(_held_lane_run(in_turn, mp))
            finally:
                mp.undo()
        return runs[in_turn]

    return get


@pytest.mark.parametrize("in_turn, slots", [(True, 4), (False, 16)],
                         ids=["in-turn", "as-they-arrive"])
def test_a_lane_keeps_its_slots_of_frames_unanswered(held_runs, in_turn,
                                                     slots):
    """(a) In turn: 4 of 32 writes ride a frame, 28 gather as marks, and
    never a fifth frame goes out.  As they arrive: the window is 4 x 4."""
    run = held_runs(in_turn)
    assert run["cap"] == [slots, slots]
    assert run["unanswered"] == [slots, slots]
    assert run["marked"] == [GROUPS - slots, GROUPS - slots]
    assert run["win_hwm"] == slots
    assert run["acked"] == GROUPS


@pytest.mark.parametrize("in_turn", [True, False],
                         ids=["in-turn", "as-they-arrive"])
def test_the_frame_behind_a_released_reply_carries_all_that_gathered(
        held_runs, in_turn):
    """(a) One reply frees one slot, and the one frame cut into it holds
    every group that was waiting: the frame is elastic, not the window."""
    run = held_runs(in_turn)
    assert len(run["gathered"]) == run["marked"][0]
    assert run["next_frames"] == [run["gathered"]]
    assert run["marked_after"] == 0
    assert run["unanswered_after"] == run["cap"][0]


@pytest.mark.parametrize("in_turn", [True, False],
                         ids=["in-turn", "as-they-arrive"])
def test_window_full_counts_exactly_the_sweeps_that_left_marks(held_runs,
                                                               in_turn):
    """(c) ``replicate.sweeps`` is every drain pass that met marks,
    ``replicate.window_full`` those of them that left some behind, and
    ``replicate.frames`` / ``items`` what the passes cut."""
    run = held_runs(in_turn)
    c, seen = run["counters"], run["seen"]
    assert c["replicate.sweeps"] == seen["sweeps"] > 0
    assert c["replicate.window_full"] == seen["left_marks"]
    slots = run["cap"][0]
    # on both lanes every write behind the first ``slots`` found the window
    # full, once each (a sweep a write)
    assert c["replicate.window_full"] >= 2 * (GROUPS - slots)
    # read after the one reply: ``slots`` frames of one group on either
    # lane, and the one frame of all that gathered on the lane let go
    assert c["replicate.frames"] == 2 * slots + 1
    assert c["replicate.items"] == 2 * slots + (GROUPS - slots)


@pytest.mark.parametrize("in_turn", [True, False],
                         ids=["in-turn", "as-they-arrive"])
def test_the_watchdog_reports_a_lane_full_at_the_lanes_own_slots(held_runs,
                                                                 in_turn):
    """(e) Both lanes stay full with the commit waterline flat: one
    stuck-lane event each, which names the lane's own window."""
    run = held_runs(in_turn)
    slots = run["cap"][0]
    assert len(run["stuck"]) == 2
    assert all(f"full ({slots}/{slots} frames)" in d for d in run["stuck"])


@pytest.mark.parametrize("rpc_type, in_turn", [
    ("GRPC", True), ("TCP", False), ("NETTY", False), ("SIMULATED", False)])
def test_a_transport_says_how_it_hands_a_lanes_frames_on(rpc_type, in_turn):
    """gRPC's stream dispatch takes a lane's frames in turn; the others work
    on them as they arrive, which is the base class's word."""
    from ratis_tpu.transport.base import ServerTransport, TransportFactory
    from ratis_tpu.transport.simulated import SimulatedTransportFactory

    assert ServerTransport.lane_frames_in_turn is False
    factory = (SimulatedTransportFactory() if rpc_type == "SIMULATED"
               else TransportFactory.get(rpc_type))

    async def no_handler(_msg):
        raise AssertionError("never started")

    transport = factory.new_server_transport(
        "s0", "127.0.0.1:0", no_handler, no_handler)
    assert transport.lane_frames_in_turn is in_turn


# ------------------------------------------------------------- over gRPC

def _grpc_cluster(groups: int, envelope_inflight: int):
    """A 3-peer cluster over ``transport/grpc.py`` with every group led by
    the first server and the sequenced window at depth 4."""
    from ratis_tpu.chaos.cluster import ChaosCluster
    from ratis_tpu.tools.bench_cluster import bench_properties

    p = bench_properties(batched=False, num_groups=groups)
    for k, v in WINDOW_PROPS.items():
        p.set(k, v)
    p.set("raft.server.log.appender.envelope.inflight",
          str(envelope_inflight))
    return ChaosCluster(3, groups, properties=p, transport="grpc",
                        sm="counter")


def _hold_sequenced_frames(cluster, hold) -> None:
    """Every server awaits ``hold()`` before it handles a sequenced frame:
    the follower's flush, stretched."""
    for server in cluster.servers.values():
        transport = server.transport
        handler = transport.server_handler

        async def held(msg, handler=handler):
            if isinstance(msg, AppendEnvelope) and msg.seq >= 0:
                await hold()
            return await handler(msg)

        transport.server_handler = held


async def _grpc_counts_per_commit(envelope_inflight: int) -> dict:
    """16 groups, 4 writes of each in flight, 3 rounds, every frame's reply
    held 5 ms: the session's counters a commit."""
    groups, rounds, depth = 16, 3, 4
    cluster = _grpc_cluster(groups, envelope_inflight)
    await cluster.start()
    try:
        _hold_sequenced_frames(cluster, lambda: asyncio.sleep(0.005))

        async def one_group(g):
            client = cluster.new_client(g)
            try:
                for _ in range(rounds):
                    replies = await asyncio.gather(
                        *(client.io().send(b"INCREMENT")
                          for _ in range(depth)))
                    assert all(r.success for r in replies)
            finally:
                await client.close()

        get_tracer().configure(enabled=True, sample_every=1, ring_size=4096)
        await asyncio.gather(*(one_group(g) for g in cluster.groups))
        commits = groups * rounds * depth
        counts = _session_counters("grpc.messages_out", *REPLICATE_COUNTERS)
        leader = cluster.servers[cluster.peers[0].id]
        return {"slots": [s.inflight_cap for s in
                          leader.replication._senders.values()],
                **{k: n / commits for k, n in counts.items()}}
    finally:
        await cluster.close()


def test_over_grpc_fewer_slots_mean_fewer_frames_and_messages_a_commit():
    """(b) The same cluster and load at ``envelope.inflight`` 4 and 16: the
    second is the old window by the key.  A smaller window cuts fewer,
    fuller frames, and every frame less is four gRPC messages less."""
    now = asyncio.run(_grpc_counts_per_commit(4))
    old = asyncio.run(_grpc_counts_per_commit(16))
    assert now["slots"] == [4, 4] and old["slots"] == [16, 16]
    assert now["replicate.frames"] < 0.75 * old["replicate.frames"]
    assert now["grpc.messages_out"] < old["grpc.messages_out"]
    assert (now["replicate.items"] / now["replicate.frames"]
            > old["replicate.items"] / old["replicate.frames"])


def test_over_grpc_one_group_still_fills_its_depth():
    """(d) Four writes of one group, each sent once the last has left in a
    frame, ride four consecutive frames of the lane while the first is
    still unanswered: the group's window is the depth, as it was.  A fifth
    finds lane and group full and waits for an ack."""

    async def main():
        cluster = _grpc_cluster(1, 4)
        await cluster.start()
        gate = asyncio.Event()
        writes = []
        try:
            leader = cluster.servers[cluster.peers[0].id]
            sent = []   # (destination, seq, groups) of each sequenced frame
            send = leader.transport.send_server_rpc

            async def recording_send(to, msg):
                if isinstance(msg, AppendEnvelope) and msg.seq >= 0:
                    sent.append((to, msg.seq, len(msg.items)))
                return await send(to, msg)

            leader.transport.send_server_rpc = recording_send
            _hold_sequenced_frames(cluster, gate.wait)
            (division,) = leader.divisions.values()
            appenders = list(division.leader_ctx.appenders.values())
            lanes = list(leader.replication._senders.values())
            assert [s.inflight_cap for s in lanes] == [4, 4]
            await _until(lambda: not any(a._frames for a in appenders),
                         "bring-up never idle")
            client = cluster.new_client(cluster.groups[0])
            try:
                for n in range(1, 6):
                    writes.append(asyncio.ensure_future(
                        client.io().send(b"INCREMENT")))
                    await _until(
                        lambda: all(a._frames == min(n, 4)
                                    for a in appenders)
                        and (n < 5 or all(s._dirty for s in lanes)),
                        f"write {n} never reached the lanes")
                assert [s.frames_in_flight for s in lanes] == [4, 4]
                for to in {s.to for s in lanes}:
                    seqs = [seq for dest, seq, _ in sent if dest == to]
                    assert len(seqs) == 4
                    assert seqs == list(range(seqs[0], seqs[0] + 4))
                gate.set()
                replies = await asyncio.wait_for(asyncio.gather(*writes),
                                                 30.0)
                assert all(r.success for r in replies)
                assert leader.replication.metrics["win_hwm"] == 4
            finally:
                gate.set()
                await client.close()
        finally:
            for w in writes:
                w.cancel()
            await cluster.close()

    asyncio.run(main())
