"""Scheduling-hop accounting for the replication/reply plane.

The round-6/7 traced decomposition located the commit wall in event-loop
scheduling hops (`server.route`/`server.reply`/`server.respond`, ~100µs
each under load), not in serialization (docs/perf.md).  The round-8
batching work collapses those hops; this module makes the collapse a
standing measured artifact instead of a one-off trace read: every site
the batching targets counts the scheduling operations it issues, and
``hops-per-commit`` (reply-plane hops / engine commit advances) rides the
bench line (``secondary.obs``) and the per-server registry.

Process-wide by design, like :data:`ratis_tpu.trace.tracer.TRACER` and
the codec's ``FANOUT_STATS``: co-hosted servers in one process share the
counters, and the bench's cluster-wide hops line up with its
cluster-wide commit count.  Sites:

- ``sender_wake``  — a PeerSender flush-loop wakeup (legacy) or one armed
  cross-group sweep pass (sweep mode) in the replication scheduler.
- ``engine_wake``  — an engine tick wake actually scheduled
  (``call_soon_threadsafe`` issued / event set); the intake-lock dedupe
  collapses ack bursts to one.
- ``reply_future`` — one per-request pending-reply future resolution
  waking the parked write-handler task (the legacy commit->reply wakeup
  the waterline fan-out removes).
- ``reply_window`` — one per-request ordered-window future resolution
  carrying a real reply (second wakeup of the legacy chain; absent when
  the client skips the sliding window).
- ``reply_send``   — one per-request reply handed to the transport by
  its own handler task (third wakeup of the legacy chain on socket
  transports; over gRPC the task also suspends for the flush).
- ``reply_flush``  — one per-connection callback armed by the
  transport's deferred-reply path (sweep mode's replacement for ALL of
  the above: one scheduled callback per connection per burst — over TCP
  the connection's one write of the loop pass).
- ``reply_batch``  — one waterline fan-out pass resolving a whole batch
  of committed requests.  NOT a hop (the pass is a synchronous call the
  apply loop was running anyway); counted for batch-size observability
  (deliveries / passes = the average fan-out batch).

The reply-plane metric counts the SCHEDULED operations between a commit
advancing and its reply reaching the wire; the final client-waiter
wakeup (transport reply hand-back) exists identically in both modes and
is excluded as common cost, as is the connection coalescer's flush task
(identical per-batch cost both modes).
"""

from __future__ import annotations

HOP_SITES = ("sender_wake", "engine_wake", "reply_future", "reply_window",
             "reply_send", "reply_batch", "reply_flush")

# reply-plane subset: the SCHEDULED hops between a commit advancing and
# its reply reaching the transport — the surface the fan-out collapse
# targets (reply_batch is a synchronous pass, not a hop; see above)
REPLY_SITES = ("reply_future", "reply_window", "reply_send", "reply_flush")

_counts: dict[str, int] = {s: 0 for s in HOP_SITES}


def hop(site: str) -> None:
    """Count one scheduling operation at ``site`` (hot path: one dict
    increment; sites are fixed, an unknown site is a programming error)."""
    _counts[site] += 1


def snapshot() -> dict[str, int]:
    return dict(_counts)


def reply_plane_hops() -> int:
    return sum(_counts[s] for s in REPLY_SITES)


def total_hops() -> int:
    return sum(_counts.values())


def reset() -> None:
    for s in HOP_SITES:
        _counts[s] = 0
