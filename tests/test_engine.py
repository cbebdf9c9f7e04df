"""QuorumEngine tick-path tests: the device-resident batched path must be
observationally identical to the scalar fallback (same callbacks, same state
mirror) under scripted and randomized scenarios, including dirty-row
refreshes, capacity regrowth, and deadline disarm/re-arm cycles.

Reference behaviors under test: LeaderStateImpl.updateCommit:907,
FollowerState election timeout, LeaderStateImpl.checkLeadership:1096 —
executed here through ops.quorum.engine_step_resident with donated device
buffers (VERDICT r1 item 4: O(events + changed) host<->device per tick).
"""

import asyncio
import random

import numpy as np
import pytest

from ratis_tpu.engine.engine import QuorumEngine
from ratis_tpu.engine.state import (NO_DEADLINE, ROLE_FOLLOWER, ROLE_LEADER,
                                    ROLE_LISTENER)


class FakeClock:
    def __init__(self):
        self.t = 0

    def now_ms(self):
        return self.t

    def advance_epoch(self, delta_ms):
        self.t -= delta_ms


class Recorder:
    def __init__(self):
        self.events = []

    async def on_election_timeout(self):
        self.events.append("timeout")

    async def on_commit_advance(self, c):
        self.events.append(("commit", c))

    async def on_leadership_stale(self):
        self.events.append("stale")


def _mk_engine(use_device: bool, max_groups=8, max_peers=4) -> QuorumEngine:
    e = QuorumEngine(max_groups=max_groups, max_peers=max_peers,
                     scalar_fallback_threshold=10**9,
                     leadership_timeout_ms=300,
                     use_device=use_device)
    e.clock = FakeClock()
    return e


def _setup_leader(e: QuorumEngine, rec, n_peers=3, flush=5):
    slot = e.attach(rec)
    s = e.state
    cur = np.zeros(e.state.max_peers, bool)
    cur[:n_peers] = True
    s.set_conf(slot, 0, cur, np.zeros(e.state.max_peers, bool),
               np.zeros(e.state.max_peers, np.int32), 0)
    s.role[slot] = ROLE_LEADER
    s.flush_index[slot] = flush
    s.commit_index[slot] = -1
    s.first_leader_index[slot] = 0
    s.last_ack_ms[slot, :n_peers] = e.clock.now_ms()
    s.election_deadline_ms[slot] = NO_DEADLINE
    s.mark_dirty(slot)
    return slot


@pytest.mark.parametrize("use_device", [False, True])
def test_commit_advance_via_acks(use_device):
    async def _run():
        e = _mk_engine(use_device)
        rec = Recorder()
        slot = _setup_leader(e, rec, n_peers=3, flush=5)
        # majority = 2 of 3: leader flush=5 plus one follower at 4 -> commit 4
        e.on_ack(slot, 1, 4)
        await e.tick()
        assert ("commit", 4) in rec.events
        assert e.state.commit_index[slot] == 4
        # second follower at 5 -> commit 5 (leader already flushed 5)
        e.on_ack(slot, 2, 5)
        await e.tick()
        assert ("commit", 5) in rec.events
        assert e.state.commit_index[slot] == 5

    asyncio.run(_run())


@pytest.mark.parametrize("use_device", [False, True])
def test_flush_advance_alone_advances_commit(use_device):
    """A leader whose followers already matched must commit when its OWN
    flush catches up — the decoupled-fsync path (flush callback marks the
    slot dirty; no ack event involved)."""

    async def _run():
        e = _mk_engine(use_device)
        rec = Recorder()
        slot = _setup_leader(e, rec, n_peers=3, flush=0)
        e.on_ack(slot, 1, 7)
        e.on_ack(slot, 2, 7)
        await e.tick()
        assert e.state.commit_index[slot] == 7  # majority w/o the leader
        # now a slot untouched by acks: flush alone moves commit via dirty
        e.state.flush_index[slot] = 9
        e.state.mark_dirty(slot)
        e.on_ack(slot, 1, 9)
        await e.tick()
        assert e.state.commit_index[slot] == 9

    asyncio.run(_run())


@pytest.mark.parametrize("use_device", [False, True])
def test_election_timeout_fires_once_and_rearms(use_device):
    async def _run():
        e = _mk_engine(use_device)
        rec = Recorder()
        slot = e.attach(rec)
        s = e.state
        s.role[slot] = ROLE_FOLLOWER
        s.election_deadline_ms[slot] = 100
        s.mark_dirty(slot)
        e.clock.t = 50
        await e.tick()
        assert rec.events == []
        e.clock.t = 150
        await e.tick()
        assert rec.events == ["timeout"]
        # deadline disarmed on both host and device: no refire
        e.clock.t = 250
        await e.tick()
        assert rec.events == ["timeout"]
        assert s.election_deadline_ms[slot] == NO_DEADLINE
        # re-arm (dirty) -> fires again
        s.election_deadline_ms[slot] = 300
        s.mark_dirty(slot)
        e.clock.t = 301
        await e.tick()
        assert rec.events == ["timeout", "timeout"]

    asyncio.run(_run())


@pytest.mark.parametrize("use_device", [False, True])
def test_stale_leadership_detected(use_device):
    async def _run():
        e = _mk_engine(use_device)
        rec = Recorder()
        slot = _setup_leader(e, rec, n_peers=3)
        e.clock.t = 1000
        # scalar path throttles staleness sweeps; tick twice around the gate
        await e.tick()
        e.clock.t = 1400
        await e.tick()
        assert "stale" in rec.events

    asyncio.run(_run())


@pytest.mark.parametrize("use_device", [False, True])
def test_heartbeat_acks_keep_leadership(use_device):
    async def _run():
        e = _mk_engine(use_device)
        rec = Recorder()
        slot = _setup_leader(e, rec, n_peers=3)
        for t in (100, 200, 300, 400):
            e.clock.t = t
            e.on_ack(slot, 1, -1)  # heartbeat acks: time only
            e.on_ack(slot, 2, -1)
            await e.tick()
        assert "stale" not in rec.events

    asyncio.run(_run())


def test_device_capacity_regrow_preserves_state():
    async def _run():
        e = _mk_engine(True, max_groups=2, max_peers=4)
        recs = [Recorder() for _ in range(5)]
        slots = []
        for r in recs[:2]:
            slots.append(_setup_leader(e, r, n_peers=3, flush=5))
        e.on_ack(slots[0], 1, 5)
        await e.tick()  # device state created at capacity 2
        assert e.state.commit_index[slots[0]] == 5
        # allocating past capacity regrows arrays -> device re-upload
        for r in recs[2:]:
            slots.append(_setup_leader(e, r, n_peers=3, flush=3))
        assert e.state.capacity >= 5
        e.on_ack(slots[4], 1, 3)
        e.on_ack(slots[0], 2, 5)
        await e.tick()
        assert e.state.commit_index[slots[4]] == 3
        assert e.state.commit_index[slots[0]] == 5

    asyncio.run(_run())


def test_randomized_scalar_vs_device_equivalence():
    """Drive two engines with an identical random script; callbacks and the
    host state mirrors must agree tick for tick."""

    async def _run():
        rng = random.Random(1234)
        G, P = 12, 4
        eng_s = _mk_engine(False, max_groups=16, max_peers=P)
        eng_d = _mk_engine(True, max_groups=16, max_peers=P)
        recs_s, recs_d, slots = [], [], []
        for g in range(G):
            rs, rd = Recorder(), Recorder()
            recs_s.append(rs)
            recs_d.append(rd)
            role = rng.choice([ROLE_LEADER, ROLE_FOLLOWER, ROLE_LISTENER])
            n_peers = rng.randint(1, P)
            flush = rng.randint(-1, 10)
            deadline = rng.randint(1, 500)
            for e, r in ((eng_s, rs), (eng_d, rd)):
                slot = e.attach(r)
                s = e.state
                cur = np.zeros(P, bool)
                cur[:n_peers] = True
                s.set_conf(slot, 0, cur, np.zeros(P, bool),
                           np.zeros(P, np.int32), 0)
                s.role[slot] = role
                s.flush_index[slot] = flush
                s.first_leader_index[slot] = 0
                if role == ROLE_FOLLOWER:
                    s.election_deadline_ms[slot] = deadline
                s.mark_dirty(slot)
            slots.append(slot)  # same slot ids on both engines

        for step in range(30):
            t = step * 37
            eng_s.clock.t = t
            eng_d.clock.t = t
            for _ in range(rng.randint(0, 6)):
                g = rng.choice(slots)
                p = rng.randint(0, P - 1)
                m = rng.randint(-1, 12)
                eng_s.on_ack(g, p, m)
                eng_d.on_ack(g, p, m)
            if rng.random() < 0.3:
                g = rng.choice(slots)
                f = rng.randint(0, 12)
                for e in (eng_s, eng_d):
                    e.state.flush_index[g] = f
                    e.state.mark_dirty(g)
            if rng.random() < 0.2:
                g = rng.choice(slots)
                d = t + rng.randint(1, 200)
                for e in (eng_s, eng_d):
                    if e.state.role[g] == ROLE_FOLLOWER:
                        e.state.election_deadline_ms[g] = d
                        e.state.mark_dirty(g)
            await eng_s.tick()
            await eng_d.tick()
            np.testing.assert_array_equal(eng_s.state.commit_index,
                                          eng_d.state.commit_index)
            np.testing.assert_array_equal(eng_s.state.match_index,
                                          eng_d.state.match_index)
            np.testing.assert_array_equal(eng_s.state.election_deadline_ms,
                                          eng_d.state.election_deadline_ms)

        for rs, rd in zip(recs_s, recs_d):
            # staleness sweeps are throttled differently (scalar: timeout/4
            # cadence; device: every tick) so compare commit/timeout exactly
            # and staleness as a set property
            assert [x for x in rs.events if x != "stale"] \
                == [x for x in rd.events if x != "stale"]

    asyncio.run(_run())


def test_scalar_batched_mode_crossing_invalidates_device_state():
    """Crossing below the fallback threshold and back must not leave a stale
    device copy: scalar-tick mutations (acks, commit advances, deadline
    disarms) happen host-only, so the next batched tick re-uploads."""

    async def _run():
        e = QuorumEngine(max_groups=8, max_peers=4,
                         scalar_fallback_threshold=3,
                         leadership_timeout_ms=300, use_device=False)
        e.clock = FakeClock()
        recs = [Recorder() for _ in range(3)]
        slots = [_setup_leader(e, r, n_peers=3, flush=5) for r in recs]
        e.on_ack(slots[0], 1, 5)
        await e.tick()  # batched (3 >= 3)
        assert e.state.commit_index[slots[0]] == 5
        assert e._dev is not None

        e.detach(slots[2])  # drops to 2 -> scalar
        e.clock.t = 100
        e.on_ack(slots[1], 1, 3)
        await e.tick()
        assert e._dev is None  # stale device copy dropped
        assert e.state.commit_index[slots[1]] == 3

        # back above the threshold: batched tick must see the scalar-era
        # state (no commit regression, no spurious staleness step-down)
        slots[2] = _setup_leader(e, recs[2], n_peers=3, flush=5)
        e.clock.t = 150
        e.on_ack(slots[0], 1, -1)
        e.on_ack(slots[0], 2, -1)
        e.on_ack(slots[1], 1, -1)
        e.on_ack(slots[1], 2, -1)
        e.on_ack(slots[2], 1, -1)
        e.on_ack(slots[2], 2, -1)
        await e.tick()
        assert e.state.commit_index[slots[0]] == 5
        assert e.state.commit_index[slots[1]] == 3
        assert "stale" not in recs[0].events
        assert "stale" not in recs[1].events

    asyncio.run(_run())


# ---------------------------------------------------------------- vote rounds


def _setup_candidate(e: QuorumEngine, rec, n_peers=3, priorities=None,
                     self_priority=0):
    from ratis_tpu.engine.state import ROLE_CANDIDATE
    slot = e.attach(rec)
    s = e.state
    cur = np.zeros(s.max_peers, bool)
    cur[:n_peers] = True
    prio = np.zeros(s.max_peers, np.int32)
    if priorities is not None:
        prio[:len(priorities)] = priorities
    s.set_conf(slot, 0, cur, np.zeros(s.max_peers, bool), prio,
               self_priority)
    s.role[slot] = ROLE_CANDIDATE
    s.mark_dirty(slot)
    return slot


def test_vote_round_passes_on_majority():
    """Engine-tallied round (LeaderElection.waitForResults analog): self
    grant + one peer grant = 2/3 majority -> PASSED at the next tick."""
    async def run():
        e = _mk_engine(use_device=True)
        rec = Recorder()
        slot = _setup_candidate(e, rec)
        fut = e.begin_vote_round(slot, deadline_ms=10_000)
        e.on_vote_reply(slot, 1, granted=True)
        await e.tick()
        assert fut.done() and fut.result() == "PASSED"

    asyncio.run(run())


def test_vote_round_rejected_by_majority():
    async def run():
        e = _mk_engine(use_device=True)
        rec = Recorder()
        slot = _setup_candidate(e, rec)
        fut = e.begin_vote_round(slot, deadline_ms=10_000)
        e.on_vote_reply(slot, 1, granted=False)
        e.on_vote_reply(slot, 2, granted=False)
        await e.tick()
        assert fut.done() and fut.result() == "REJECTED"

    asyncio.run(run())


def test_vote_round_priority_veto_and_higher_priority_gate():
    """A rejecting higher-priority peer vetoes instantly; an unresponsive
    higher-priority peer blocks the strict pass until the round deadline
    (LeaderElection.java:515-519,554-572)."""
    async def run():
        e = _mk_engine(use_device=True)
        rec = Recorder()
        # peer 1 has priority 5 > self 0; peer 2 same priority
        slot = _setup_candidate(e, rec, priorities=[0, 5, 0])
        fut = e.begin_vote_round(slot, deadline_ms=10_000)
        e.on_vote_reply(slot, 2, granted=True)  # majority, but HP silent
        await e.tick()
        assert not fut.done()  # strict pass gated on the HP peer
        e.clock.t = 10_001  # deadline fires -> passed_on_timeout
        await e.tick()
        assert fut.done() and fut.result() == "PASSED"

        # a rejecting higher-priority peer is an unconditional veto
        rec2 = Recorder()
        slot2 = _setup_candidate(e, rec2, priorities=[0, 5, 0])
        fut2 = e.begin_vote_round(slot2, deadline_ms=20_000)
        e.on_vote_reply(slot2, 2, granted=True)
        e.on_vote_reply(slot2, 1, granted=False)
        await e.tick()
        assert fut2.done() and fut2.result() == "REJECTED"

    asyncio.run(run())


def test_vote_round_timeout_without_majority():
    async def run():
        e = _mk_engine(use_device=True)
        rec = Recorder()
        slot = _setup_candidate(e, rec)
        fut = e.begin_vote_round(slot, deadline_ms=500)
        await e.tick()
        assert not fut.done()
        e.clock.t = 501
        await e.tick()
        assert fut.done() and fut.result() == "TIMEOUT"

    asyncio.run(run())


def test_an_open_vote_round_costs_no_dispatch_until_it_can_be_decided():
    """PR 32: a vote round that is open and has nothing to decide (no reply
    since the last tally, its deadline ahead) lets the ticks pass over it:
    no quorum-step dispatch and no tally, and neither opening a round nor a
    reply wakes the tick loop.  (Open rounds used to open the sweep gate on
    every tick and every reply woke the loop: an election storm fed itself
    on the engines' dispatches.)"""
    async def run():
        e = _mk_engine(use_device=True)
        rec = Recorder()
        slot = _setup_candidate(e, rec)
        await e.tick()  # uploads the device state, clears the dirty row
        fut = e.begin_vote_round(slot, deadline_ms=10_000)
        assert not e._wake.is_set()
        before = (e.metrics["batched_dispatches"], e.metrics["vote_tallies"])
        for _ in range(5):
            await e.tick()
        assert (e.metrics["batched_dispatches"],
                e.metrics["vote_tallies"]) == before
        assert e.metrics["idle_skips"] >= 5 and not fut.done()
        # a reply is tallied at the next tick, once, without the quorum step
        e.on_vote_reply(slot, 1, granted=False)
        assert not e._wake.is_set()
        await e.tick()
        await e.tick()
        assert e.metrics["vote_tallies"] == before[1] + 1
        assert e.metrics["batched_dispatches"] == before[0]
        assert not fut.done()
        # and the deadline alone is enough to decide it
        e.clock.t = 10_001
        await e.tick()
        assert fut.result() == "TIMEOUT"
        assert e.metrics["vote_tallies"] == before[1] + 2
        assert e.state.vote_deadline_ms.min() == NO_DEADLINE

    asyncio.run(run())


def test_a_candidacy_rides_the_next_dispatch_and_causes_none():
    """PR 32: a row marked lazy (a candidacy begun or given up) does not
    open the sweep gate; the next dispatch, whatever causes it, refreshes
    the row before the kernel decides anything, so the device agrees with
    the mirror again and the follower's re-armed deadline fires."""
    from ratis_tpu.engine.state import ROLE_CANDIDATE

    async def run():
        e = _mk_engine(use_device=True)
        rec = Recorder()
        slot = e.attach(rec)
        s = e.state
        s.role[slot] = ROLE_FOLLOWER
        s.election_deadline_ms[slot] = NO_DEADLINE  # fired: a candidate now
        s.mark_dirty(slot)
        e.clock.t = 10
        await e.tick()  # upload
        s.role[slot] = ROLE_CANDIDATE
        s.mark_lazy(slot)
        before = e.metrics["batched_dispatches"]
        for _ in range(3):
            await e.tick()
        assert e.metrics["batched_dispatches"] == before
        assert s.lazy == {slot}
        # candidacy given up: follower again, deadline re-armed
        s.role[slot] = ROLE_FOLLOWER
        s.mark_lazy(slot)
        e.on_deadline(slot, 100)
        await e.tick()
        assert e.metrics["batched_dispatches"] == before
        e.clock.t = 101
        await e.tick()  # the deadline opens the gate; the row goes first
        assert e.metrics["batched_dispatches"] == before + 1
        assert not s.lazy and "timeout" in rec.events
        assert int(np.asarray(e._dev.role)[slot]) == ROLE_FOLLOWER

    asyncio.run(run())


def test_vote_round_first_reply_wins_and_end_round():
    """A flip-flopped duplicate reply must not double-count
    (waitForResults responses.putIfAbsent); end_vote_round cancels."""
    async def run():
        e = _mk_engine(use_device=True)
        rec = Recorder()
        slot = _setup_candidate(e, rec)
        fut = e.begin_vote_round(slot, deadline_ms=10_000)
        e.on_vote_reply(slot, 1, granted=False)
        e.on_vote_reply(slot, 1, granted=True)  # dup: dropped
        await e.tick()
        assert not fut.done()  # 1 grant (self) + 1 reject: undecided
        e.end_vote_round(slot)
        assert fut.cancelled()

    asyncio.run(run())


def test_vote_round_matches_scalar_oracle_randomized():
    """Differential: the engine's batched tally must agree with the
    ops.reference scalar tally for random grant/reject/priority mixes."""
    from ratis_tpu.ops import reference as ref

    async def run():
        rng = random.Random(7)
        for trial in range(40):
            e = _mk_engine(use_device=True, max_groups=8, max_peers=4)
            rec = Recorder()
            n = rng.choice([3, 4])
            priorities = [rng.choice([0, 0, 0, 3]) for _ in range(n)]
            self_priority = priorities[0]
            slot = _setup_candidate(e, rec, n_peers=n,
                                    priorities=priorities,
                                    self_priority=self_priority)
            fut = e.begin_vote_round(slot, deadline_ms=1000)
            grants = [False] * e.state.max_peers
            rejects = [False] * e.state.max_peers
            grants[0] = True
            for peer in range(1, n):
                verdict = rng.choice(["grant", "reject", "silent"])
                if verdict == "grant":
                    e.on_vote_reply(slot, peer, True)
                    grants[peer] = True
                elif verdict == "reject":
                    e.on_vote_reply(slot, peer, False)
                    rejects[peer] = True
            e.clock.t = 1001  # force the deadline path for determinism
            await e.tick()
            conf_cur = [i < n for i in range(e.state.max_peers)]
            conf_old = [False] * e.state.max_peers
            prio = list(priorities) + [0] * (e.state.max_peers - n)
            _, passed_on_timeout, rejected = ref.tally_votes(
                grants, rejects, conf_cur, conf_old, prio, self_priority)
            assert fut.done(), trial
            expect = ("REJECTED" if rejected
                      else "PASSED" if passed_on_timeout else "TIMEOUT")
            assert fut.result() == expect, (trial, fut.result(), expect)

    asyncio.run(run())


def test_sweep_gate_does_not_delay_election_timeout():
    """The sweep-gated dispatch (events accumulate between sweeps) must
    still fire a follower's election timeout at its deadline: the gate is
    bounded by the earliest armed deadline (_compute_next_sweep), not by
    event arrival."""
    async def run():
        e = _mk_engine(use_device=True)
        rec = Recorder()
        slot = e.attach(rec)
        s = e.state
        cur = np.zeros(s.max_peers, bool)
        cur[:3] = True
        s.set_conf(slot, 0, cur, np.zeros(s.max_peers, bool),
                   np.zeros(s.max_peers, np.int32), 0)
        s.role[slot] = ROLE_FOLLOWER
        s.mark_dirty(slot)
        e.on_deadline(slot, 500)
        await e.tick()  # dispatch: upload + arm
        # quiet ticks before the deadline: gated (no dispatch, no timeout)
        before = e.metrics["batched_dispatches"]
        for t in (100, 200, 300):
            e.clock.t = t
            await e.tick()
        assert e.metrics["batched_dispatches"] == before
        assert "timeout" not in rec.events
        # deadline passes: the next tick MUST dispatch and fire
        e.clock.t = 501
        await e.tick()
        assert "timeout" in rec.events

    asyncio.run(run())


@pytest.mark.parametrize("wrapped", [False, True])
def test_a_backlog_in_chunks_fires_no_deadline_a_later_chunk_rearms(wrapped):
    """A backlog over the largest compiled bucket goes to the device in
    chunks; deadlines are judged once every chunk is on the device.  A
    follower re-armed in time never fires because its re-arm sat in a
    later chunk, and one that did expire still fires, once.  ``wrapped``:
    each chunk goes through ``_tick_batched_pass(acks, now)``, the call a
    traced benchmark run wraps (benchmarks/run.py:annotate_dispatches)."""
    async def run():
        e = _mk_engine(use_device=True, max_groups=256)
        e._event_bucket_cap = 64
        if wrapped:
            inner = e._tick_batched_pass

            def two_arguments(acks, now):
                return inner(acks, now)
            e._tick_batched_pass = two_arguments
        recs = [Recorder() for _ in range(150)]
        slots = [e.attach(r) for r in recs]
        s = e.state
        cur = np.zeros(s.max_peers, bool)
        cur[:3] = True
        for slot in slots:
            s.set_conf(slot, 0, cur, np.zeros(s.max_peers, bool),
                       np.zeros(s.max_peers, np.int32), 0)
            s.role[slot] = ROLE_FOLLOWER
            s.mark_dirty(slot)
            e.on_deadline(slot, 500)
        await e.tick()  # upload, every deadline armed on the device
        e.clock.t = 400
        for slot in slots[:-1]:     # 149 re-arms: three chunks of 64
            e.on_deadline(slot, 900)
        e.clock.t = 600
        await e.tick()
        fired = [i for i, r in enumerate(recs) if r.events]
        assert fired == [len(slots) - 1]
        assert recs[-1].events == ["timeout"]
        assert all(s.election_deadline_ms[slot] == 900
                   for slot in slots[:-1])
        e.clock.t = 901
        await e.tick()
        assert all(r.events == ["timeout"] for r in recs)

    asyncio.run(run())


def test_sweep_gate_ships_backlog_before_staleness_check():
    """Accumulated (gated) acks must reach the device BEFORE the staleness
    sweep evaluates — a leader steadily receiving acks during the gated
    window must not be declared stale at the next sweep."""
    async def run():
        e = _mk_engine(use_device=True)
        rec = Recorder()
        slot = _setup_leader(e, rec, n_peers=3, flush=5)
        await e.tick()  # establish device state
        # acks arrive during the gated window, device unaware until sweep
        for t in range(50, 451, 50):
            e.clock.t = t
            e.on_ack(slot, 1, 5)
            e.on_ack(slot, 2, 5)
            await e.tick()
        # leadership_timeout is 300ms; now=450 with fresh acks at 450:
        # the sweep that finally dispatches must see them and NOT step down
        e.clock.t = 460
        await e.tick()
        assert "stale" not in rec.events
        # silence past the timeout -> stale fires at a later sweep
        e.clock.t = 460 + 301
        await e.tick()
        e.clock.t = 460 + 602
        await e.tick()
        assert "stale" in rec.events

    asyncio.run(run())


def test_vote_round_expires_early_when_all_replied():
    """expire_vote_round (all peers replied or failed) resolves the round
    at the NEXT tick via the timeout-path tally instead of waiting out
    the full round deadline — the outstanding==0 early exit of the
    reference's waitForResults."""
    async def run():
        e = _mk_engine(use_device=True)
        rec = Recorder()
        # higher-priority peer 1 never replies (its RPC failed); peer 2
        # grants -> majority, but the strict pass is gated on peer 1
        slot = _setup_candidate(e, rec, priorities=[0, 5, 0])
        fut = e.begin_vote_round(slot, deadline_ms=60_000)
        e.on_vote_reply(slot, 2, granted=True)
        await e.tick()
        assert not fut.done()  # gated on the silent higher-priority peer
        e.expire_vote_round(slot)  # all RPCs concluded
        e.clock.t += 1
        await e.tick()
        assert fut.done() and fut.result() == "PASSED"

    asyncio.run(run())
