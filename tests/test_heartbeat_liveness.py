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
        item = a.heartbeat_item(now)
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
        assert (a.heartbeat_item(now) is None) == suppressed

    run_with_new_cluster(3, body, properties=batched_properties())


def test_heartbeat_rate_cap_two_attempts_per_interval():
    async def body(cluster: MiniCluster):
        leader, a = await _leader_appender(cluster)
        now = time.monotonic()
        hb = a.heartbeat_interval_s
        # unresponsive follower, but we JUST emitted: capped
        a.follower.last_rpc_response_s = now - 10 * hb
        a._last_send_s = now - 0.2 * hb
        assert a.heartbeat_item(now) is None
        # past the half-interval cap: due again (second attempt)
        a._last_send_s = now - 0.5 * hb
        assert a.heartbeat_item(now) is not None

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
        assert a.heartbeat_item(now) is not None

    run_with_new_cluster(3, body, properties=batched_properties())


def test_stream_dial_gate_paces_per_address():
    from ratis_tpu.transport.grpc import _StreamDialGate
    g = _StreamDialGate()
    assert g.may_dial("a:1")
    assert not g.may_dial("a:1")  # within the pacing window
    assert g.may_dial("b:2")      # other addresses unaffected
    g._last["a:1"] = time.monotonic() - _StreamDialGate.WINDOW_S - 0.01
    assert g.may_dial("a:1")      # window elapsed
