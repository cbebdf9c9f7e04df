"""Controls and planted faults.  A control breaks one guarantee the
configuration states and has to come out as not correct; a fault breaks the
timed path underneath the harness.  Neither is part of a benchmark run: they
are switched on by ``--control`` / ``--fault`` (tests, and the chip runs that
set the limits).  One fault, ``leader-moved``, is no fault of the program: a
run with it has to come out correct."""

from __future__ import annotations

import asyncio
import time

from ratis_tpu.models.counter import CounterStateMachine

LOSE_EVERY = 2


class LossyFollowerCounter(CounterStateMachine):
    """The fault "replicas that do not hold what was acknowledged": a
    replica loses every second INCREMENT unless it is the group's appointed
    leader, so the leader still answers right, but no majority of state
    machines holds the count.  (Every second, because a group may see only
    a few writes in a whole run, and the comparison lets a follower be one
    settle write behind.)"""

    def __init__(self, lossy: bool) -> None:
        super().__init__()
        self._lossy = lossy
        self._seen = 0

    async def apply_transaction(self, trx):
        self._seen += 1
        if self._lossy and self._seen % LOSE_EVERY == 0:
            self.counter -= 1
        return await super().apply_transaction(trx)


class AlteredAnswerCounter(CounterStateMachine):
    """The fault "an answer altered where it is produced": every seventh
    INCREMENT answers one more than the counter holds."""

    def __init__(self) -> None:
        super().__init__()
        self._seen = 0

    async def apply_transaction(self, trx):
        from ratis_tpu.protocol.message import Message
        reply = await super().apply_transaction(trx)
        self._seen += 1
        if self._seen % 7 == 0:
            return Message.value_of(str(self.counter + 1))
        return reply


class StaleReadCounter(CounterStateMachine):
    """The fault "a read that is not linearizable": ``query`` answers the
    count as it was before the group's last INCREMENT, so a GET of a group
    at rest reads one less than what was acknowledged before it was sent.
    Writes answer right, and every replica holds the count."""

    async def query(self, request):
        from ratis_tpu.protocol.message import Message
        reply = await super().query(request)      # (refuses what is no GET)
        return Message.value_of(str(max(0, int(bytes(reply.content)) - 1)))


def sm_factory_for(name: str, peers: int):
    """``(server index, group index) -> state machine`` of a control or fault
    that swaps the state machine; None for the others."""
    if name == "lossy-followers":
        return lambda server, group: LossyFollowerCounter(
            lossy=server != group % peers)
    if name == "altered-answer":
        return lambda server, group: AlteredAnswerCounter()
    if name == "stale-read":
        return lambda server, group: StaleReadCounter()
    return None


def freeze_device_step(engines) -> None:
    """The fault "a step that returns its state unchanged": every engine's
    fast step hands back the device state it was given, with an all-zero
    result, so the device never sees an ack."""
    import jax.numpy as jnp
    from ratis_tpu.ops import quorum as q

    def stale_step(state, _events, _clock):
        g = state.commit_index.shape[0]
        return q.ResidentFastStep(state, jnp.zeros((4, g), jnp.int32))

    for e in engines:
        e._fast_kernel = lambda: stale_step


async def move_leader(cluster, group: int, seed: int,
                      timeout_s: float = 30.0) -> None:
    """The fault "a leadership moved before the window": the group's
    leadership goes from its appointee to the next peer through the
    program's own transfer (a client's TRANSFER_LEADERSHIP), and this waits
    until the new leader is ready.  Nothing is wrong with the program here:
    the check has to hold the group on the row of the server that leads it
    when the window opens, and come out correct."""
    from benchmarks.harness.cluster import seeded_ids
    from ratis_tpu.client import RaftClient
    from ratis_tpu.protocol.ids import ClientId
    g = cluster.groups[group]
    source = cluster.leader_server(group)
    target = (source + 1) % cluster.peers_n
    client = (RaftClient.builder().set_raft_group(g)
              .set_client_id(ClientId.value_of(
                  seeded_ids(seed, 1, "fault")[0]))
              .set_leader_id(g.peers[source].id)
              .set_transport(cluster.factory.new_client_transport(
                  cluster.properties))
              .set_properties(cluster.properties).build())
    try:
        reply = await client.admin().transfer_leadership(
            g.peers[target].id, timeout_ms=timeout_s * 1e3)
    finally:
        await client.close()
    if not reply.success:
        raise RuntimeError(f"leader-moved: transfer refused: "
                           f"{reply.exception!r}")
    division = cluster.servers[target].divisions[g.group_id]
    deadline = time.monotonic() + timeout_s
    while not cluster.leads(division):
        if time.monotonic() > deadline:
            raise RuntimeError(f"leader-moved: s{target} not ready "
                               f"after {timeout_s}s")
        await asyncio.sleep(0.01)
