"""Leader-side machinery: pending requests, watch bookkeeping, log appenders.

Capability parity with the reference LeaderStateImpl + LogAppender
(ratis-server/.../impl/LeaderStateImpl.java:101, PendingRequests.java:51,
leader/LogAppenderBase.java:50, LogAppenderDefault.java:43): per-follower
replication drivers with batched AppendEntries and nextIndex backoff, a
pending-request registry completed on apply, and step-down draining.

Differences from the reference by design: there is no per-group
EventProcessor thread — commit advancement happens in the server-wide
QuorumEngine (ratis_tpu.engine) and calls back into the division.
"""

from __future__ import annotations

import asyncio
import logging
import time
from typing import Optional

from ratis_tpu.metrics.hops import hop
from ratis_tpu.protocol.exceptions import (NotLeaderException,
                                           ResourceUnavailableException)
from ratis_tpu.protocol.ids import RaftPeerId
from ratis_tpu.protocol.message import Message
from ratis_tpu.protocol.raftrpc import (AppendEntriesReply,
                                        AppendEntriesRequest, AppendResult,
                                        RaftRpcHeader)
from ratis_tpu.protocol.requests import RaftClientReply, RaftClientRequest
from ratis_tpu.protocol.termindex import TermIndex
from ratis_tpu.server.replication import OutItem

LOG = logging.getLogger(__name__)


class PendingRequest:
    def __init__(self, index: int, request: RaftClientRequest):
        self.index = index
        self.request = request
        self.future: asyncio.Future = asyncio.get_running_loop().create_future()
        # Deferred-reply mode (commit fan-out collapse): a synchronous
        # completion callback replaces the per-request future wakeup chain
        # — the waterline fan-out invokes it inline and the reply lands in
        # the transport's per-connection batcher with no task resume.
        self._sink_cb = None

    def deliver_to(self, cb) -> None:
        """Register the deferred completion callback.  If the reply was
        already set (e.g. a step-down drain raced the append await), the
        callback fires immediately — exactly-once either way."""
        self._sink_cb = cb
        if self.future.done() and not self.future.cancelled():
            cb(self.future.result())

    def _resolve(self, reply: RaftClientReply) -> None:
        if self.future.done():
            return
        self.future.set_result(reply)
        cb = self._sink_cb
        if cb is not None:
            cb(reply)
        else:
            # legacy commit->reply path: this resolution wakes the parked
            # write-handler task — the per-request hop the waterline
            # fan-out removes (metric site, see metrics/hops.py)
            hop("reply_future")

    def set_reply(self, reply: RaftClientReply) -> None:
        self._resolve(reply)

    def fail(self, exception: Exception) -> None:
        self._resolve(RaftClientReply.failure_reply(self.request, exception))


class PendingRequests:
    """index -> in-flight client write, with byte/element permits
    (reference PendingRequests.java:51,100-110)."""

    def __init__(self, element_limit: int = 4096, byte_limit: int = 64 << 20,
                 mirror=None):
        self._map: dict[int, PendingRequest] = {}
        self._element_limit = element_limit
        self._byte_limit = byte_limit
        self._bytes = 0
        # depth mirror into the engine's pending_count[G] (lag ledger /
        # telemetry sampler read it array-wise instead of walking leaders)
        self._mirror = mirror

    def add(self, index: int, request: RaftClientRequest) -> PendingRequest:
        size = request.message.size()
        if (len(self._map) >= self._element_limit
                or (self._bytes + size) > self._byte_limit):
            raise ResourceUnavailableException(
                f"pending requests full: {len(self._map)} elements, "
                f"{self._bytes} bytes")
        p = PendingRequest(index, request)
        self._map[index] = p
        self._bytes += size
        if self._mirror is not None:
            self._mirror(len(self._map))
        return p

    def pop(self, index: int) -> Optional[PendingRequest]:
        p = self._map.pop(index, None)
        if p is not None:
            self._bytes -= p.request.message.size()
            if self._mirror is not None:
                self._mirror(len(self._map))
        return p

    def requests(self) -> list[RaftClientRequest]:
        return [p.request for p in self._map.values()]

    def drain_not_leader(self, exception: NotLeaderException) -> int:
        """Step-down: fail everything (PendingRequests.notifyNotLeader)."""
        n = len(self._map)
        for p in self._map.values():
            p.fail(exception)
        self._map.clear()
        self._bytes = 0
        if self._mirror is not None:
            self._mirror(0)
        return n

    def __len__(self) -> int:
        return len(self._map)


class FollowerInfo:
    """Leader's view of one follower (reference server-api leader/FollowerInfo)."""

    def __init__(self, peer_id: RaftPeerId, next_index: int):
        self.peer_id = peer_id
        self.next_index = next_index
        self.match_index = -1
        self.commit_index = -1  # piggybacked on append replies
        self.snapshot_in_progress = False
        self.attend_vote = True  # False for listeners
        self.last_rpc_response_s = time.monotonic()

    def update_match(self, match: int) -> bool:
        self.last_rpc_response_s = time.monotonic()
        if match > self.match_index:
            self.match_index = match
            return True
        return False

class LogAppender:
    """One leader->follower replication state machine with a pipelined send
    window, driven by the server-level PeerSender fabric.

    Mirrors the reference GrpcLogAppender (GrpcLogAppender.java:343-381):
    up to ``window_limit`` AppendEntries requests are in flight at once —
    ``follower.next_index`` is the optimistic *send* cursor, advanced when a
    batch is handed to the transport, while ``follower.match_index`` advances
    only on acks.  Unlike the reference there is NO daemon per (group,
    follower): the appender is passive state; the per-destination PeerSender
    (ratis_tpu.server.replication) calls :meth:`collect` to drain its window
    fills into shared multi-group envelopes and dispatches replies back via
    :meth:`on_send_reply`/:meth:`on_send_error`.  Per-group FIFO holds (see
    replication module docstring); reordered delivery at worst produces a
    spurious INCONSISTENCY -> window reset + resend, and match only ever
    advances from per-request-capped SUCCESS confirmations.  A dedicated
    heartbeat timer (reference's separate heartbeat channel,
    GrpcLogAppender.java:172) fires outside the window and is never queued
    behind a full pipeline.  On INCONSISTENCY or an RPC error the window
    resets: the epoch is bumped so in-flight completions from before the
    reset are ignored, and the send cursor rewinds
    (GrpcLogAppender.onError/resetClient:475-530).
    """

    def __init__(self, division, follower: FollowerInfo,
                 heartbeat_interval_s: float, buffer_byte_limit: int,
                 window_limit: int = 16):
        self.division = division
        self.follower = follower
        self.heartbeat_interval_s = heartbeat_interval_s
        self.buffer_byte_limit = buffer_byte_limit
        self.window_limit = max(1, window_limit)
        self.sender = division.server.replication.acquire(
            follower.peer_id, self)
        self._running = False
        self._epoch = 0        # bumped on window reset; stale replies ignored
        self._inflight = 0     # pipelined (non-heartbeat) requests outstanding
        # In-flight FRAMES carrying this group's items.  The bound is the
        # sender's per-group window (raft.tpu.replication.window-depth):
        # 1 = the classic one-envelope-at-a-time FIFO latch; >1 (sequenced
        # lanes only) lets collect() cut the next batch from the
        # speculative next-index while earlier frames are still on the
        # wire, hiding the append round trip (GrpcLogAppender.java:343's
        # sliding window, batched across groups).
        self._frames = 0
        self._frame_limit = max(1, getattr(self.sender, "group_window", 1))
        self._probe_due = False
        self._last_send_s = 0.0
        self._backoff_until = 0.0
        self._last_error_log_s = 0.0
        self._prefaulting = False
        self._ci_countdown = 0  # commit-infos piggyback thinning
        # follower accepted a hibernate request (division.hibernate_sweep);
        # cleared on wake / any send / window reset
        self.hibernate_acked = False
        self._pending_sends: set[asyncio.Task] = set()

    def start(self) -> None:
        self._running = True
        # Initial empty append: announces leadership and probes the follower
        # log position right away (the reference appender sends immediately
        # on start; followers learn leader identity from this probe).
        self._probe_due = True
        self.sender.mark(self)

    async def stop(self) -> None:
        self._running = False
        self.sender.unmark(self)
        # stop() can be reached from INSIDE one of this appender's own
        # pending tasks (e.g. _send_heartbeat's reply carries a higher term
        # -> change_to_follower -> ctx.stop -> this): never cancel-and-await
        # the task we are currently running in — the pending
        # self-cancellation would detonate at the next await and abort the
        # rest of the step-down cleanup.
        cur = asyncio.current_task()
        tasks = [t for t in self._pending_sends if t is not cur]
        self._pending_sends.clear()
        for t in tasks:
            t.cancel()
        for t in tasks:
            try:
                await t
            except (asyncio.CancelledError, Exception):
                pass
        # Retire the shared per-destination sender when this was its last
        # appender (otherwise departed peers leak standing flush tasks).
        await self.division.server.replication.release(
            self.follower.peer_id, self)

    def notify(self) -> None:
        if self._running:
            self.sender.mark(self)

    def _build_request(self, next_idx: int, heartbeat: bool = False
                       ) -> Optional[AppendEntriesRequest]:
        div = self.division
        log = div.state.log
        if next_idx < log.start_index:
            return None  # needs snapshot (handled by caller)
        prev: Optional[TermIndex] = None
        if next_idx > 0:
            prev = log.term_at_or_before(next_idx - 1)
            if prev is None and next_idx - 1 >= log.start_index:
                return None
            if prev is None and not div.snapshot_covers(next_idx - 1):
                prev = None  # empty log start
            elif prev is None:
                prev = div.snapshot_term_index(next_idx - 1)
                if prev is None:
                    return None
        if heartbeat:
            entries = ()
        else:
            entries = tuple(log.get_entries(next_idx, log.next_index,
                                            self.buffer_byte_limit))
        # Cluster-wide commit picture piggyback (CommitInfoCache): on every
        # probe/heartbeat, but only every 8th data batch — the infos are
        # advisory (commit levels for *_COMMITTED watches and group-info),
        # and rebuilding + re-parsing them per batch taxed the hot path.
        self._ci_countdown -= 1
        if heartbeat or self._ci_countdown <= 0:
            self._ci_countdown = 8
            infos = div.get_commit_infos_wire()
        else:
            infos = ()
        return AppendEntriesRequest(
            header=RaftRpcHeader(div.member_id.peer_id, self.follower.peer_id,
                                 div.group_id),
            leader_term=div.state.current_term,
            previous=prev,
            entries=entries,
            leader_commit=log.get_last_committed_index(),
            commit_infos=infos,
        )

    # -------------------------------------------------------------- window

    def _reset_window(self, *, rewind_to: Optional[int] = None,
                      backoff_s: float = 0.0) -> None:
        """Discard the pipeline: ignore everything in flight, rewind the send
        cursor (reference resetClient: follower.decreaseNextIndex + clear the
        request map)."""
        self._epoch += 1
        self._inflight = 0
        self.hibernate_acked = False  # the follower's timer may be re-armed
        f = self.follower
        # NB: the rewind target is deliberately NOT floored at log.start_index
        # — next_index < start_index is exactly what routes collect() into
        # the snapshot-install path for a follower behind the purged log.
        if rewind_to is not None:
            target = max(rewind_to, 0)
            if target <= f.match_index:
                # The follower's INCONSISTENCY hint is authoritative: it has
                # lost entries past its recorded match (possible only with a
                # volatile log, e.g. memory-log restart) — regress the match
                # so commit quorum math stays honest.
                f.match_index = target - 1
                self.division.on_follower_match_regressed(f)
            f.next_index = target
        else:
            f.next_index = max(f.match_index + 1, 0)
        if backoff_s > 0:
            self._backoff_until = time.monotonic() + backoff_s
        if self._running:
            self.sender.mark(self)

    @staticmethod
    def _approx_bytes(request) -> int:
        """Cheap request-size estimate for the envelope byte budget (the
        exact serialized size was already paid once inside get_entries; do
        not serialize again here)."""
        total = 128
        for e in request.entries:
            if e.smlog is not None:
                total += (len(e.smlog.log_data)
                          + len(e.smlog.sm_data or b"") + 48)
            else:
                total += 64
        return total

    def collect(self, out: list, budget: int) -> int:
        """Drain this follower's due sends into ``out`` (PeerSender flush):
        the start probe, then window fills until the window is full, the
        byte budget is spent, or the log is drained.  Returns the
        (approximate) bytes added.  The busy latch guarantees a group's
        items are never split across two racing envelopes."""
        div = self.division
        f = self.follower
        if not self._running or not div.is_leader() \
                or self._frames >= self._frame_limit:
            return 0
        now = time.monotonic()
        if now < self._backoff_until:
            return 0
        added = 0
        # Count the frame BEFORE anything can be appended to out: if a
        # later fill iteration raises, already-collected items still ship
        # in this flush's envelope — without the latch a re-mark could
        # split this group's items across two racing envelopes.  At frame
        # limit 1 that is the full FIFO guarantee; above it, racing frames
        # are ordered by the sequenced-lane intake instead.  Un-count on
        # the no-item path at the end.
        self._frames += 1
        try:
            if self._probe_due:
                probe = self._build_request(f.next_index, heartbeat=True)
                if probe is not None:
                    self._probe_due = False
                    self._last_send_s = now
                    added += 128
                    out.append(OutItem(self, probe, self._epoch, False))
            log = div.state.log
            while (self._inflight < self.window_limit
                   and not f.snapshot_in_progress and added <= budget):
                next_idx = f.next_index
                if next_idx >= log.next_index:
                    break  # fully caught up (at send level)
                if not log.is_resident(next_idx):
                    # evicted segment: fault it in off-loop, then resume — a
                    # synchronous multi-MB read+decode here would stall every
                    # division's heartbeats and election timers
                    if not self._prefaulting:
                        self._prefaulting = True
                        self._spawn(self._prefault(next_idx))
                    break
                request = self._build_request(next_idx)
                if request is None:
                    # behind the purged log -> snapshot path, serialized by
                    # the snapshot_in_progress flag in try_install_snapshot
                    self._spawn(self._install_snapshot())
                    break
                if not request.entries:
                    break
                f.next_index = request.entries[-1].index + 1
                self._inflight += 1
                self._last_send_s = now
                added += self._approx_bytes(request)
                out.append(OutItem(self, request, self._epoch, True))
        finally:
            if not added:
                self._frames -= 1
            else:
                # any send re-arms the follower's election timer: a stale
                # hibernate ack must not let the leader fall asleep without
                # a fresh handshake
                self.hibernate_acked = False
        return added

    def has_backlog(self) -> bool:
        """Entries remain past the send cursor AND the frame window has
        room: the sweep's drain pass uses this to keep cutting frames for
        this group in the SAME pass (pipelining), instead of waiting out
        the in-flight frame's round trip for the envelope_done re-mark."""
        return (self._running and self._frames < self._frame_limit
                and not self.follower.snapshot_in_progress
                and self.division.is_leader()
                and self.division.state.log.next_index
                > self.follower.next_index)

    def envelope_done(self, remark: bool = True) -> None:
        """An envelope carrying this appender's items completed (all its
        replies/errors dispatched): release its frame-window slot and
        re-mark so the next flush refills the window."""
        self._frames = max(0, self._frames - 1)
        if remark and self._running and self.division.is_leader():
            self.sender.mark(self)

    def on_send_error(self, item, e: Exception) -> None:
        """An envelope / unary send carrying ``item`` failed."""
        if item.epoch != self._epoch or not self._running:
            return
        # Connection trouble: drop the pipeline, retry after a pause paced
        # by the heartbeat timer (GrpcLogAppender.onError).  Log
        # (rate-limited) — a silent persistent error here looks like a
        # wedged follower with no trace of why.
        now = time.monotonic()
        if now - self._last_error_log_s > 2.0:
            self._last_error_log_s = now
            LOG.warning("%s -> %s append failed (epoch %d): %s",
                        self.division.member_id, self.follower.peer_id,
                        self._epoch, e)
        self._reset_window(backoff_s=self.heartbeat_interval_s)

    async def on_send_reply(self, item, reply: AppendEntriesReply,
                            ack_sink: Optional[list] = None) -> None:
        """``ack_sink`` (sweep mode): collect this reply's engine ack as a
        packed row instead of a scalar on_ack call — the PeerSender feeds
        the whole envelope's rows to QuorumEngine.on_ack_batch at once."""
        if item.epoch != self._epoch or not self._running:
            return  # window was reset while this was in flight
        if item.pipelined:
            self._inflight -= 1
        await self._on_reply(item.request, reply, item.epoch, ack_sink)

    def _spawn(self, coro) -> None:
        t = asyncio.create_task(coro)
        self._pending_sends.add(t)
        t.add_done_callback(self._pending_sends.discard)

    async def _install_snapshot(self) -> None:
        div = self.division
        handled = await div.try_install_snapshot(self.follower)
        if handled:
            self.notify()

    async def _prefault(self, index: int) -> None:
        try:
            await asyncio.to_thread(self.division.state.log.prefault, index)
        finally:
            self._prefaulting = False
        self.notify()

    async def _send_heartbeat(self, request: AppendEntriesRequest,
                              epoch: int) -> None:
        """The unary dedicated heartbeat channel (reference cost shape,
        used when bulk-heartbeat coalescing is disabled): outside the
        PeerSender window, never queued behind a full data pipeline."""
        div = self.division
        try:
            reply = await div.server.send_server_rpc(
                self.follower.peer_id, request)
        except asyncio.CancelledError:
            raise
        except Exception as e:
            self.on_send_error(OutItem(self, request, epoch, False), e)
            return
        if epoch != self._epoch or not self._running:
            return  # window was reset while this was in flight
        await self._on_reply(request, reply, epoch)
        self.notify()

    def heartbeat_item(self, now: float, fresh_for: float,
                       hibernate: bool = False) -> Optional[tuple]:
        """Contribute this follower's compact item to the sweep's
        BulkHeartbeat toward its destination server, or None when not due
        (recent traffic doubles as a heartbeat, exactly like the unary
        path).  Also doubles as the periodic fill-retry waker.  With
        ``hibernate`` the item carries the hibernate flag, asking the follower
        to disarm its election timer (idle-group quiescence).
        ``fresh_for``: how recent a contact lets the sweep skip the follower
        (the sweep's own reckoning, ``HeartbeatScheduler.fresh_for_s``)."""
        div = self.division
        if not self._running or not div.is_leader():
            return None
        f = self.follower
        # Fill-retry mark only when a fill could actually produce work:
        # pending data, a due probe, or an expired backoff.  Marking every
        # appender every sweep made the PeerSender flush loop re-collect
        # thousands of idle appenders per interval (profiling at 1024
        # groups: 6 collect calls per actual send).
        if self._backoff_until and now >= self._backoff_until:
            # one-shot: clear on expiry, or every later sweep re-marks an
            # idle appender forever once it has had a single send error
            self._backoff_until = 0.0
            self.sender.mark(self)
        elif self._probe_due or div.state.log.next_index > f.next_index:
            self.sender.mark(self)
        if not div.hibernating:
            # while asleep the ONLY traffic is the backstop slow tick, so
            # ack clocks are legitimately backstop/4 old — judging that as
            # follower slowness would spam notifications for silence the
            # leader itself requested
            div.check_follower_slowness(f)
        # Due-ness keys on CONFIRMED contact (the follower's replies), not
        # on queueing: a data batch stamps _last_send_s when it enters an
        # envelope, and under congestion that envelope can sit queued (or
        # time out) while the follower hears silence past its election
        # timeout — measured at 5-peer x 10240 bring-up, thousands of
        # healthy leaders were deposed by followers whose p50 silence was
        # 17.8s.  Policy for a follower that stops replying: up to TWO
        # heartbeat attempts per interval (the 0.45*hb send cap), so an
        # unresponsive peer costs at most 2x the idle item volume.
        # _last_send_s == 0.0 is the explicit force-due marker (hibernation
        # wake sets it: "next sweep heartbeats immediately").
        hb = self.heartbeat_interval_s
        if self._last_send_s:
            if self._contact_fresh(now, fresh_for):
                return None  # follower demonstrably fresh (recent reply)
            if now - self._last_send_s < hb * 0.45:
                return None  # give the in-flight contact a chance to land
        if f.snapshot_in_progress:
            return None
        # NB: _backoff_until deliberately does NOT suppress the compact
        # heartbeat — the data window pauses on send errors, but this is
        # exactly the contact that must keep flowing while it does (the
        # reference's separate heartbeat channel has the same property,
        # GrpcLogAppender heartbeat channel).
        log = div.state.log
        commit = log.get_last_committed_index()
        self._last_send_s = now
        cti = log.get_term_index(commit) if commit >= 0 else None
        base = (div.group_id.to_bytes(), div.state.current_term, commit,
                cti.term if cti is not None else -1)
        # hibernate request rides as a 5th flag field so the item still
        # carries real commit info (a lagging follower must be able to
        # catch its commit up from these very items to pass the sync gate)
        return base + (1,) if hibernate else base

    def next_due(self, now: float) -> float:
        """Earliest time ``heartbeat_item`` could next produce an item,
        derived from the same confirmed-contact gate (upkeep plane's
        CH_HEARTBEAT arm).  Conservative-EARLY by construction: the gate
        re-checks at dispatch, so an early deadline costs one declined
        call, never a changed decision — and a LATE one is impossible
        because every input that moves the true due-time earlier
        (wake/leadership/conf-change) sets the force-due marker or re-arms
        the slot.  ``_last_send_s == 0.0`` is that marker: due now."""
        if not self._last_send_s:
            return now
        hb = self.heartbeat_interval_s
        return max(min(self.follower.last_rpc_response_s, self._last_send_s)
                   + hb * 0.9,
                   self._last_send_s + hb * 0.45)

    def _contact_fresh(self, now: float, fresh_for: float) -> bool:
        """The follower has heard from this leader within ``fresh_for``
        (the sweep's reckoning, ``HeartbeatScheduler.fresh_for_s``), for all
        the leader can tell: a reply came in
        that lately AND it answered something sent that lately.  The reply
        alone is no proof: it says when the follower's answer got here, not
        when the follower last heard.  A heartbeat whose reply came 0.3 s
        late (a stalled loop) read as fresh at the next sweep, one interval
        after it was sent, the sweep skipped the follower, and it heard
        nothing for two intervals, which is the shortest election timeout:
        every stall of the loop over 0.1 x the interval cost healthy
        leaders elections (PERF.md §7)."""
        return (now - self.follower.last_rpc_response_s < fresh_for
                and now - self._last_send_s < fresh_for)

    def on_bulk_reply(self, code: int, term: int, next_index: int,
                      follower_commit: int, flush_index: int,
                      ack_sink: Optional[list] = None) -> Optional[int]:
        """Dispatch one aligned BulkHeartbeatReply item.  Happy path keeps
        the follower fresh (staleness + watch frontiers); any anomaly
        escalates to a full AppendEntries probe on the data path, which
        carries the prev check the compact item omits.  Returns the
        follower's term where it is higher than ours: the caller then steps
        the division down (the one part that waits)."""
        from ratis_tpu.protocol.raftrpc import (BULK_HB_HIBERNATED,
                                                BULK_HB_OK,
                                                BULK_HB_UNKNOWN_GROUP)
        div = self.division
        if not self._running or not div.is_leader():
            return None
        if code == BULK_HB_UNKNOWN_GROUP:
            return None  # peer doesn't host this group (e.g. mid group-add)
        if term > div.state.current_term:
            return term
        if code == BULK_HB_HIBERNATED:
            # follower disarmed its election timer: this channel may sleep
            self.hibernate_acked = True
            f = self.follower
            f.last_rpc_response_s = time.monotonic()
            div.on_follower_heartbeat_ack(f, ack_sink)
            return None
        self.hibernate_acked = False  # any other reply: timer is armed
        if code != BULK_HB_OK:
            # stale NOT_LEADER at <= our term, or BUSY (the item was skipped
            # because our own in-flight append holds the division's lock —
            # that append doubles as the heartbeat): ignore, retry next sweep
            return None
        f = self.follower
        f.last_rpc_response_s = time.monotonic()
        if follower_commit > f.commit_index:
            f.commit_index = follower_commit
            div.update_commit_info(f.peer_id, follower_commit)
        div.on_follower_heartbeat_ack(f, ack_sink)
        log = div.state.log
        if (next_index < f.next_index and self._inflight == 0
                and self._frames == 0):
            # Follower's log ends before our send cursor with nothing in
            # flight: it lost entries (restart) or our cursor is stale.
            # Send a full probe so the INCONSISTENCY path decides with
            # prev-check fidelity (including the match-regress protocol).
            self._probe_due = True
            self.sender.mark(self)
        elif log.next_index > f.next_index:
            self.sender.mark(self)  # data pending: wake the fill path
        return None

    async def _on_reply(self, request: AppendEntriesRequest,
                        reply: AppendEntriesReply, epoch: int,
                        ack_sink: Optional[list] = None) -> None:
        div = self.division
        if reply.term > div.state.current_term:
            await div.change_to_follower(reply.term, leader_id=None,
                                         reason="higher term in append reply")
            return
        if reply.result == AppendResult.SUCCESS:
            self.follower.commit_index = max(self.follower.commit_index,
                                             reply.follower_commit)
            div.update_commit_info(self.follower.peer_id,
                                   reply.follower_commit)
            # Cap the confirmed match at what THIS request actually verified
            # against our log (prev check + entries sent).  The follower's
            # raw flush_index may cover a stale tail from a previous term
            # that a heartbeat never examined; counting it toward quorum
            # could commit entries that are not truly replicated.
            last_covered = (request.entries[-1].index if request.entries
                            else (request.previous.index if request.previous
                                  else -1))
            confirmed = min(reply.match_index, last_covered)
            if self.follower.update_match(confirmed):
                div.on_follower_ack(self.follower, ack_sink)
            else:
                div.on_follower_heartbeat_ack(self.follower, ack_sink)
        elif reply.result == AppendResult.INCONSISTENCY:
            if epoch == self._epoch:
                # observable reorder/rewind churn (ADVICE r5): the keyed
                # gRPC stream dispatch should keep this at ~0 under load
                m = div.server.replication.metrics
                m["rewinds"] = m.get("rewinds", 0) + 1
                if self._frames > 1 or self._inflight > 0:
                    # windowed rewind: >0 unacked pipelined frames beyond
                    # this one are being dropped (epoch bump) and the lane
                    # re-cuts from the rewound next-index — not a full
                    # per-destination reset
                    m["windowed_rewinds"] = \
                        m.get("windowed_rewinds", 0) + 1
                hint = min(reply.next_index,
                           max(request.previous.index if request.previous
                               else 0, 0))
                f = self.follower
                if hint <= f.match_index and (
                        request.previous is None
                        or request.previous.index != f.match_index):
                    # Heartbeats travel unary/coalesced while entry appends
                    # ride the ordered stream, so a stale heartbeat's
                    # INCONSISTENCY can land after a newer SUCCESS raised
                    # match in the same epoch.  This request never examined
                    # our recorded match position, so its rejection is not
                    # authoritative for a regress: reset the window and
                    # re-probe at the match instead.  A genuine volatile-log
                    # restart fails the probe (previous.index == match) too
                    # and regresses then, via the authoritative branch.
                    self._reset_window()
                else:
                    self._reset_window(rewind_to=hint)
        elif reply.result == AppendResult.NOT_LEADER:
            # stale term on our side already handled above; otherwise ignore
            pass

    # ----------------------------------------------------------- heartbeats

    def on_heartbeat_sweep(self, now: float, fresh_for: float) -> None:
        """One iteration of the unary dedicated heartbeat channel, driven by
        the SERVER-level sweep (server.HeartbeatScheduler) when bulk
        coalescing is disabled.  Semantics match the reference's dedicated
        heartbeat stream: an empty AppendEntries goes out whenever nothing
        else has been sent for an interval, regardless of window occupancy
        (GrpcLogAppender.java:172).  ``fresh_for``: as ``heartbeat_item``'s."""
        div = self.division
        if not self._running or not div.is_leader():
            return
        self.sender.mark(self)  # periodic fill retry (backoff expiry etc.)
        try:
            div.check_follower_slowness(self.follower)
            # same confirmed-contact due-ness as heartbeat_item: a QUEUED
            # (or erroring, backed-off) data batch must not suppress the
            # dedicated heartbeat while the follower hears silence — the
            # deposal mechanism was identical on this path
            f = self.follower
            interval = self.heartbeat_interval_s
            if self._last_send_s:
                if self._contact_fresh(now, fresh_for):
                    return  # follower demonstrably fresh (recent reply)
                if now - self._last_send_s < interval * 0.45:
                    return
            hb = self._build_request(self.follower.next_index,
                                     heartbeat=True)
            if hb is None:
                return  # snapshot path owns this follower right now
            self._last_send_s = now
            self._spawn(self._send_heartbeat(hb, self._epoch))
        except Exception:
            # the sweep must never die on one follower's error — the mark
            # above already ran, so fills keep retrying regardless
            LOG.exception("%s heartbeat sweep iteration failed",
                          self.division.member_id)


class LeaderContext:
    """Everything that exists only while this division leads
    (reference LeaderStateImpl minus the event thread)."""

    def __init__(self, division, properties=None):
        from ratis_tpu.conf.keys import RaftServerConfigKeys
        self.division = division
        p = division.server.properties
        self.pending = PendingRequests(
            RaftServerConfigKeys.Write.element_limit(p),
            RaftServerConfigKeys.Write.byte_limit(p),
            mirror=division._engine_set_pending)
        self.followers: dict[RaftPeerId, FollowerInfo] = {}
        self.appenders: dict[RaftPeerId, LogAppender] = {}
        self.startup_index: int = -1  # the conf entry appended on election
        self.leader_ready = asyncio.get_running_loop().create_future()
        # shared with the server-level HeartbeatScheduler sweep — the two
        # cadences must agree or heartbeat gaps silently grow
        self._heartbeat_interval_s = division.server.heartbeat_interval_s
        self._buffer_byte_limit = \
            RaftServerConfigKeys.Log.Appender.buffer_byte_limit(p)
        self._window_limit = \
            RaftServerConfigKeys.Log.Appender.pipeline_window(p)
        from ratis_tpu.metrics import LogAppenderMetrics
        self.appender_metrics = LogAppenderMetrics(division.member_id)

    def start_appenders(self) -> None:
        div = self.division
        next_index = div.state.log.next_index
        for peer in div.state.configuration.all_peers():
            if peer.id == div.member_id.peer_id:
                continue
            self.add_follower(peer.id, next_index)

    def add_follower(self, peer_id: RaftPeerId, next_index: int) -> None:
        if peer_id in self.followers:
            return
        info = FollowerInfo(peer_id, next_index)
        self.followers[peer_id] = info
        appender = LogAppender(self.division, info, self._heartbeat_interval_s,
                               self._buffer_byte_limit, self._window_limit)
        self.appenders[peer_id] = appender
        self.appender_metrics.add_follower_gauges(
            peer_id, lambda i=info: i.next_index,
            lambda i=info: i.match_index,
            lambda i=info: time.monotonic() - i.last_rpc_response_s)
        appender.start()
        # a freshly-added appender is due immediately; in array mode the
        # division's CH_HEARTBEAT slot must hear about it or the plane
        # would wait out the previously-armed deadline
        self.division.upkeep_touch_heartbeat()

    async def remove_follower(self, peer_id: RaftPeerId) -> None:
        self.followers.pop(peer_id, None)
        self.appender_metrics.remove_follower_gauges(peer_id)
        a = self.appenders.pop(peer_id, None)
        if a is not None:
            await a.stop()

    def notify_appenders(self) -> None:
        for a in self.appenders.values():
            a.notify()

    async def stop(self, exception: Optional[NotLeaderException] = None) -> None:
        for a in list(self.appenders.values()):
            await a.stop()
        self.appenders.clear()
        self.appender_metrics.unregister()
        if exception is not None:
            # StateMachine.notifyNotLeader (StateMachine.java:241): the SM
            # sees the client requests that will never commit here, before
            # their futures fail with NotLeaderException.
            pending_reqs = self.pending.requests()
            if pending_reqs:
                try:
                    await self.division.state_machine.notify_not_leader(
                        pending_reqs)
                except Exception:
                    LOG.exception("%s notify_not_leader raised",
                                  self.division.member_id)
            self.pending.drain_not_leader(exception)
        if not self.leader_ready.done():
            self.leader_ready.cancel()
