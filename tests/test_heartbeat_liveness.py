"""Heartbeat due-ness keys on CONFIRMED follower contact (round-5
deposition-storm fix): a queued/backed-off data send must not suppress
the compact heartbeat while the follower hears silence, and hibernation
wake's force-due marker must emit on the next sweep.  Exercised against
a REAL leader's appender objects (full wiring, no mocks)."""

import asyncio
import time

import pytest

from minicluster import MiniCluster, batched_properties, run_with_new_cluster


async def _leader_appender(cluster: MiniCluster):
    leader = await cluster.wait_for_leader()
    for _ in range(200):
        if leader.leader_ctx and leader.leader_ctx.appenders:
            return leader, next(iter(leader.leader_ctx.appenders.values()))
        await asyncio.sleep(0.02)
    raise TimeoutError("no appenders")


def test_heartbeat_emits_despite_backoff_and_queued_sends():
    async def body(cluster: MiniCluster):
        leader, a = await _leader_appender(cluster)
        assert (await cluster.send_write()).success
        now = time.monotonic()
        hb = a.heartbeat_interval_s
        # follower silent past the interval, data path recently QUEUED a
        # send and is in error backoff — the exact shape that deposed
        # thousands of healthy leaders before the fix
        a.follower.last_rpc_response_s = now - 10 * hb
        a._last_send_s = now - 0.5 * hb   # recent queue-time stamp
        a._backoff_until = now + 10 * hb  # send-error backoff engaged
        item = a.heartbeat_item(now, 0.9 * hb)
        assert item is not None, \
            "backoff/queued-send suppressed the heartbeat (deposition bug)"

    run_with_new_cluster(3, body, properties=batched_properties())


@pytest.mark.parametrize("sent_ago, suppressed", [
    # a reply just in, to something sent half an interval ago: the follower
    # heard from us that lately, no heartbeat
    (0.5, True),
    # a reply just in, but to something sent two intervals ago (it was
    # late: a stalled loop): the follower has heard nothing for two
    # intervals, which is its shortest election timeout; the reply's age
    # alone used to read as fresh here (PR 32)
    (2.0, False)])
def test_heartbeat_suppressed_while_follower_demonstrably_fresh(
        sent_ago, suppressed):
    async def body(cluster: MiniCluster):
        leader, a = await _leader_appender(cluster)
        now = time.monotonic()
        hb = a.heartbeat_interval_s
        a.follower.last_rpc_response_s = now - 0.1 * hb  # fresh reply
        a._last_send_s = now - sent_ago * hb
        assert (a.next_due(now) > now) == suppressed  # (the plane's view)
        assert (a.heartbeat_item(now, 0.9 * hb) is None) == suppressed

    run_with_new_cluster(3, body, properties=batched_properties())


@pytest.mark.parametrize("period, length, fresh_for", [
    # an idle loop: the bound it always had
    (1.0, 0.0, 0.9),
    # a sweep that came 0.3 of an interval late and took 0.4: a follower
    # skipped now would hear next after its shortest election timeout
    (1.3, 0.4, 0.2),
    # later still: nothing is fresh enough to skip
    (1.6, 0.5, 0.0)])
def test_a_late_or_long_sweep_skips_only_what_stays_fresh_till_the_next(
        period, length, fresh_for):
    """The sweep skips a follower contacted lately only if the next sweep,
    as late and as long as the last, still reaches it inside its shortest
    election timeout (two intervals) with a tenth of one to spare."""
    from ratis_tpu.server.server import HeartbeatScheduler

    async def body(cluster: MiniCluster):
        leader, a = await _leader_appender(cluster)
        hb = a.heartbeat_interval_s
        sched = HeartbeatScheduler(leader.server, hb)
        reckoned = sched._fresh_for(period * hb, length * hb)
        assert reckoned == pytest.approx(fresh_for * hb)
        now = time.monotonic()
        a.follower.last_rpc_response_s = now - 0.1 * hb  # fresh reply
        a._last_send_s = now - 0.5 * hb
        assert (a.heartbeat_item(now, reckoned) is None) == \
            (fresh_for > 0.5)

    run_with_new_cluster(3, body, properties=batched_properties())


def test_the_sweep_keeps_its_rate_whatever_its_length():
    """A sweep is due an interval after the last one was due, not after it
    ended."""
    from ratis_tpu.server import server as server_module

    async def body(cluster: MiniCluster):
        leader = await cluster.wait_for_leader()
        hb = 0.05
        sched = server_module.HeartbeatScheduler(leader.server, hb)
        starts = []

        async def long_sweep(now):
            starts.append(now)
            await asyncio.sleep(0.6 * hb)

        sched._sweep = long_sweep
        sched.start()
        await asyncio.sleep(10.5 * hb)
        await sched.close()
        gaps = [b - a for a, b in zip(starts, starts[1:])]
        assert len(starts) >= 8
        assert sum(gaps) / len(gaps) == pytest.approx(hb, rel=0.2)
        assert sched.fresh_for_s == pytest.approx(
            min(0.9 * hb, 1.9 * hb - gaps[-2] - 0.6 * hb), abs=0.3 * hb)

    run_with_new_cluster(3, body, properties=batched_properties())


def test_heartbeat_rate_cap_two_attempts_per_interval():
    async def body(cluster: MiniCluster):
        leader, a = await _leader_appender(cluster)
        now = time.monotonic()
        hb = a.heartbeat_interval_s
        # unresponsive follower, but we JUST emitted: capped
        a.follower.last_rpc_response_s = now - 10 * hb
        a._last_send_s = now - 0.2 * hb
        assert a.heartbeat_item(now, 0.9 * hb) is None
        # past the half-interval cap: due again (second attempt)
        a._last_send_s = now - 0.5 * hb
        assert a.heartbeat_item(now, 0.9 * hb) is not None

    run_with_new_cluster(3, body, properties=batched_properties())


def test_wake_force_due_marker_emits_immediately():
    async def body(cluster: MiniCluster):
        leader, a = await _leader_appender(cluster)
        now = time.monotonic()
        # hibernation wake sets _last_send_s = 0.0 ("next sweep
        # heartbeats immediately") and refreshes the reply clock for
        # slowness bookkeeping — the marker must override freshness
        a.follower.last_rpc_response_s = now
        a._last_send_s = 0.0
        assert a.heartbeat_item(now, 0.9 * a.heartbeat_interval_s) \
            is not None

    run_with_new_cluster(3, body, properties=batched_properties())


def test_stream_dial_gate_paces_per_address():
    from ratis_tpu.transport.grpc import _StreamDialGate
    g = _StreamDialGate()
    assert g.may_dial("a:1")
    assert not g.may_dial("a:1")  # within the pacing window
    assert g.may_dial("b:2")      # other addresses unaffected
    g._last["a:1"] = time.monotonic() - _StreamDialGate.WINDOW_S - 0.01
    assert g.may_dial("a:1")      # window elapsed


def test_a_timeout_the_follower_was_re_armed_after_starts_no_election():
    """The engine marks a deadline it fired NO_DEADLINE until the division
    re-arms it.  A timeout whose callback runs after a heartbeat re-armed
    the row (the tick awaits its callbacks one by one) is stale: the
    healthy group holds no election.  A timeout still unanswered is not."""
    from ratis_tpu.engine.state import NO_DEADLINE

    async def body(cluster: MiniCluster):
        leader = await cluster.wait_for_leader()
        follower = next(d for d in cluster.divisions() if not d.is_leader())
        engine = follower.server.engine
        assert follower.engine_slot >= 0
        timeouts = follower.election_metrics.timeout_count
        before = timeouts.count
        follower.reset_election_deadline()
        await follower.on_election_timeout()
        assert follower.is_follower() and timeouts.count == before
        engine.state.election_deadline_ms[follower.engine_slot] = NO_DEADLINE
        await follower.on_election_timeout()
        assert timeouts.count == before + 1
        assert not follower.is_follower()      # its candidacy has begun

    run_with_new_cluster(3, body, properties=batched_properties())
