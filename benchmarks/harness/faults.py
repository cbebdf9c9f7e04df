"""Controls and planted faults.  A control breaks one guarantee the
configuration states and has to come out as not correct; a fault breaks the
timed path underneath the harness.  Neither is part of a benchmark run: they
are switched on by ``--control`` / ``--fault`` (tests, and the chip runs that
set the limits)."""

from __future__ import annotations

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
