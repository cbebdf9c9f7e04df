"""Stall watchdog: always-on derived health signals per server.

No reference analog — the reference leaves "is the cluster making
progress?" to external alerting over its metrics; the multi-raft host
here can answer it locally, cheaply, from state it already maintains.  A
single per-server sampling task (``raft.tpu.watchdog.*``) reads the lag
ledger's one pass over the engine's arrays every interval (it walks no
division) and journals structured events for the failure shapes the perf
rounds have actually hit:

- **commit-stall**: a leader's commitIndex is flat across consecutive
  samples while client requests are pending — the shape of a lost quorum
  (isolated leader, dead followers) or a wedged replication path.
- **election-churn**: server-wide election activity (timeouts fired +
  elections started) above a rate threshold — the storm signature that
  deposed thousands of leaders in rounds 4-5.
- **follower-lag**: a follower's match index more than a threshold of
  entries behind its leader's commit — a snapshot-install candidate or a
  silently failing appender.
- **stuck-lane**: a replication sender's append window stays FULL
  (every envelope slot in flight) across consecutive samples while the
  engine's commit waterline is flat — the shape of a wedged append
  round trip (frozen peer, lost replies, a lane gap that never
  recovers) under the round-9 pipelined window.

Events land in a bounded ring journal (never unbounded memory, oldest
drop first) served at ``GET /events`` by the metrics endpoint and
pretty-printed by ``python -m ratis_tpu.shell health``.  Detection
counters live in a real registry ("server" component, name "watchdog")
so the scrape carries them too.  The watchdog only READS division state
— it never awaits into division code and adds nothing to the request
path.
"""

from __future__ import annotations

import asyncio
import collections
import logging
import time
from typing import Optional

from ratis_tpu.metrics.registry import (MetricRegistries, MetricRegistryInfo,
                                        labeled)

LOG = logging.getLogger(__name__)

KIND_COMMIT_STALL = "commit-stall"
KIND_ELECTION_CHURN = "election-churn"
KIND_FOLLOWER_LAG = "follower-lag"
KIND_STUCK_LANE = "stuck-lane"
# Chaos campaign journaling (ratis_tpu.chaos): every DELIBERATELY injected
# fault lands in the same journal the organic detections use — paired
# with a fault-recovered event once its recovery SLO was observed — so a
# scrape of /events during a campaign shows faults and their recoveries
# interleaved with whatever the fault actually broke.  An injected-fault
# event without its recovery pair is an UNRECOVERED fault (the shell
# health subcommand exits 1 on it).
KIND_INJECTED_FAULT = "injected-fault"
KIND_FAULT_RECOVERED = "fault-recovered"
# Sustained overload (serving plane): admission control shedding above
# the configured rate for a whole interval — bounded pending is working
# as designed, but the operator should know the fleet is over capacity.
KIND_OVERLOAD = "overload"
# Grey follower (lag-ledger detector): one peer slow-but-alive across a
# threshold fraction of the groups it follows — every link up (acking
# within the up-window) yet lagging on most advancing groups at once.
# Neither commit-stall (quorum still commits) nor election-churn (the
# peer never times out) catches this shape; it is the signature partial
# failure of a fleet-wide slow disk/NIC.  Episodes pair grey-follower
# with grey-recovered through the same fault-correlation id the chaos
# campaign uses for injected faults.
KIND_GREY_FOLLOWER = "grey-follower"
KIND_GREY_RECOVERED = "grey-recovered"
# Placement controller actuations (ratis_tpu.placement): every leadership
# transfer or read-steering decision the policy loop executes journals a
# rebalance event, paired with a rebalance-done close carrying the
# outcome (success/failed/aborted) through the same fault-correlation id
# the chaos/grey pairs use.  A rebalance without its done pair is an
# actuation that never converged — the chaos rebalance_storm SLO and the
# shell health subcommand both check the pairing.
KIND_REBALANCE = "rebalance"
KIND_REBALANCE_DONE = "rebalance-done"
KINDS = (KIND_COMMIT_STALL, KIND_ELECTION_CHURN, KIND_FOLLOWER_LAG,
         KIND_STUCK_LANE, KIND_INJECTED_FAULT, KIND_FAULT_RECOVERED,
         KIND_OVERLOAD, KIND_GREY_FOLLOWER, KIND_GREY_RECOVERED,
         KIND_REBALANCE, KIND_REBALANCE_DONE)

# consecutive flat samples (with pending requests) before a commit-stall
# event is journaled: one flat interval is ordinary queueing, two is not
_STALL_ROUNDS = 2


class StallWatchdog:
    def __init__(self, server, interval_s: Optional[float] = None,
                 journal_size: Optional[int] = None,
                 lag_threshold: Optional[int] = None,
                 churn_threshold: Optional[int] = None):
        from ratis_tpu.conf.keys import RaftServerConfigKeys
        keys = RaftServerConfigKeys.Watchdog
        p = server.properties
        self.server = server
        self.interval_s = (interval_s if interval_s is not None
                           else keys.interval(p).seconds)
        self.lag_threshold = (lag_threshold if lag_threshold is not None
                              else keys.follower_lag_threshold(p))
        self.churn_threshold = (churn_threshold
                                if churn_threshold is not None
                                else keys.churn_threshold(p))
        size = (journal_size if journal_size is not None
                else keys.journal_size(p))
        self.journal: collections.deque = collections.deque(
            maxlen=max(1, size))
        # monotonic event sequence id: lets /events?since=<seq> serve
        # incrementally (the flight recorder and shell poll deltas
        # instead of re-reading and re-deduping the whole ring) and
        # survives ring wraparound — a consumer that slept through a
        # full ring sees the gap in seq, not silent loss
        self._next_seq = 0
        # emit hook (flight recorder): called with each journaled record
        # AFTER it lands; exceptions are swallowed — observability of the
        # observability plane must not break detection
        self.on_event = None
        self._task: Optional[asyncio.Task] = None
        self._running = False
        # the last sample's per-slot commit, generation and leader mask,
        # and each slot's consecutive flat-with-pending rounds
        self._stall: dict = {}
        # groups currently inside a reported stall / lag episode: one event
        # per episode, not one per sample
        self._stalled: set = set()
        self._lagging: set = set()
        self._last_elections = None  # server-wide election activity count
        # stuck-lane detection: (destination, sender id) -> consecutive
        # window-full-while-commits-flat samples; one event per episode
        self._lane_full: dict = {}
        self._lane_stuck: set = set()
        self._last_commits = None  # engine commit_advances at last sample
        # sustained-overload detection: shed total at last sample + an
        # in-episode latch (one event per overload episode, not per
        # saturated interval)
        self.shed_rate_threshold = \
            RaftServerConfigKeys.Serving.overload_shed_rate(p)
        self._last_shed = None
        self._overloaded = False
        # grey-follower detection over the lag ledger (raft.tpu.lag.grey.*;
        # mutable attributes so tests/chaos retune live, like lag_threshold)
        lag_keys = RaftServerConfigKeys.Lag
        self.grey_fraction = lag_keys.grey_fraction(p)
        self.grey_min_groups = lag_keys.grey_min_groups(p)
        self.grey_rounds = lag_keys.grey_rounds(p)
        self._grey_seen: dict = {}   # peer name -> consecutive grey rounds
        self._grey: set = set()      # peers inside a reported grey episode
        self._grey_fault: dict = {}  # peer name -> episode correlation id
        self._grey_seq = 0
        info = MetricRegistryInfo(prefix=str(server.peer_id),
                                  application="ratis", component="server",
                                  name="watchdog")
        self.registry = MetricRegistries.global_registries().create(info)
        self.event_counters = {
            kind: self.registry.counter(labeled("events", kind=kind))
            for kind in KINDS}
        self.registry.gauge("journalSize", lambda: len(self.journal))

    # ------------------------------------------------------------ lifecycle

    def start(self) -> None:
        self._running = True
        self._task = asyncio.create_task(
            self._run(), name=f"watchdog-{self.server.peer_id}")

    async def close(self) -> None:
        self._running = False
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None
        MetricRegistries.global_registries().remove(self.registry.info)

    # -------------------------------------------------------------- journal

    def emit(self, kind: str, group: Optional[str], detail: str,
             fault: Optional[str] = None) -> None:
        """``fault``: injected-fault correlation id — the same id on a
        KIND_INJECTED_FAULT event and its KIND_FAULT_RECOVERED pair is
        how consumers (shell health, chaos_replay) match them up."""
        record = {
            "seq": self._next_seq,
            "t": round(time.time(), 3),
            "kind": kind,
            "group": group,
            "detail": detail,
        }
        self._next_seq += 1
        if fault is not None:
            record["fault"] = fault
        self.journal.append(record)
        c = self.event_counters.get(kind)
        if c is not None:
            c.inc()
        LOG.warning("%s watchdog: %s%s: %s", self.server.peer_id, kind,
                    f" [{group}]" if group else "", detail)
        cb = self.on_event
        if cb is not None:
            try:
                cb(record)
            except Exception:
                LOG.exception("%s watchdog: on_event hook failed",
                              self.server.peer_id)

    def events(self, since: Optional[int] = None) -> list[dict]:
        """Journal contents, oldest first (the /events payload);
        ``since`` returns only records with ``seq > since``."""
        if since is None:
            return list(self.journal)
        return [e for e in self.journal if e["seq"] > since]

    @property
    def last_seq(self) -> int:
        """Newest journaled seq (-1 when nothing journaled yet)."""
        return self._next_seq - 1

    def event_count(self) -> int:
        return sum(c.count for c in self.event_counters.values())

    # ------------------------------------------------------------- sampling

    async def _run(self) -> None:
        while self._running:
            await asyncio.sleep(self.interval_s)
            try:
                self.sample()
            except asyncio.CancelledError:
                raise
            except Exception:
                # the watchdog must never take the server down with it
                LOG.exception("%s watchdog sample failed",
                              self.server.peer_id)

    def sample(self) -> None:
        """One detection pass (synchronous reads only).  Public so tests
        and harnesses can force a pass.  Nothing here walks the division
        fleet: commit stalls, follower lag and grey followers read one
        lag-ledger pass (one fused pass + one fetch), election churn the
        server's own count."""
        led = self._ledger_sample()
        if led is not None:
            self._check_commit_stall(led)
            self._check_follower_lag(led)
            self._check_grey(led)
        # election churn: rate of new election activity per interval
        elections = self.server.election_activity.count
        if self._last_elections is not None:
            delta = elections - self._last_elections
            if delta >= self.churn_threshold:
                self.emit(KIND_ELECTION_CHURN, None,
                          f"{delta} election timeouts/starts in "
                          f"{self.interval_s:.1f}s "
                          f"(threshold {self.churn_threshold})")
        self._last_elections = elections
        self._check_stuck_lanes()
        self._check_overload()

    def _check_commit_stall(self, s) -> None:
        """Leaders whose commit index stayed flat with requests pending for
        ``_STALL_ROUNDS`` samples in a row, from the ledger's per-slot
        commit, pending depth and allocation generation: python touches
        only the stalled slots.  A slot counts a round only if it led, in
        the same allocation, at the last sample too."""
        import numpy as np
        watched = s.leader_mask & (s.pending > 0)
        last = self._stall
        if last and len(last["commit"]) == s.capacity:
            flat = (watched & last["leader"] & (s.gen == last["gen"])
                    & (s.commit == last["commit"]))
            rounds = np.where(flat, last["rounds"] + 1, 0)
        else:
            rounds = np.zeros(s.capacity, np.int64)
        self._stall = {"commit": s.commit, "gen": s.gen,
                       "leader": s.leader_mask, "rounds": rounds}
        engine = self.server.engine
        current: set = set()
        for slot in np.nonzero(rounds >= _STALL_ROUNDS)[0]:
            listener = engine._listeners.get(int(slot))
            if listener is None:
                continue  # detached mid-pass
            gid = str(listener.group_id)
            current.add(gid)
            if gid in self._stalled:
                continue
            self._stalled.add(gid)
            self.emit(KIND_COMMIT_STALL, gid,
                      f"commitIndex flat at {int(s.commit[slot])} for "
                      f"{int(rounds[slot]) * self.interval_s:.1f}s with "
                      f"{int(s.pending[slot])} pending request(s)")
        self._stalled &= current

    def _ledger_sample(self):
        """One lag-ledger pass (engine/ledger.py); None if the engine is
        mid-teardown — detection must degrade, never throw."""
        try:
            return self.server.engine.ledger.sample()
        except Exception:
            LOG.exception("%s watchdog: ledger sample failed",
                          self.server.peer_id)
            return None

    def _check_follower_lag(self, s) -> None:
        """Follower lag from the ledger's per-group worst-link vector:
        python touches only the slots past threshold.  Same kind, same
        detail shape, same one-event-per-episode latch as the old
        division walk, so shell health and flight pairing are unchanged."""
        import numpy as np
        engine = self.server.engine
        current: set = set()
        for slot in np.nonzero(s.worst_lag > self.lag_threshold)[0]:
            listener = engine._listeners.get(int(slot))
            if listener is None:
                continue  # detached mid-pass
            gid = str(listener.group_id)
            current.add(gid)
            if gid in self._lagging:
                continue
            self._lagging.add(gid)
            peer_idx = int(s.worst_peer[slot])
            peer = (s.peer_names[peer_idx]
                    if 0 <= peer_idx < len(s.peer_names) else "?")
            self.emit(KIND_FOLLOWER_LAG, gid,
                      f"follower {peer} is {int(s.worst_lag[slot])} "
                      f"entries behind commit {int(s.commit[slot])} "
                      f"(threshold {self.lag_threshold})")
        self._lagging &= current

    def _check_grey(self, s) -> None:
        """Grey-follower episodes from the ledger's per-peer link counts:
        a peer whose links are ALL up (acking inside the up-window) while
        >= grey_fraction of its active links (up links of groups whose
        commit advanced this pass, at least grey_min_groups of them) sit
        past the lag threshold, sustained grey_rounds consecutive
        samples.  One grey-follower event per episode, paired with a
        grey-recovered event through a fault correlation id on close."""
        grey_now: set = set()
        for i, name in enumerate(s.peer_names):
            links = int(s.peer_links[i])
            if links == 0:
                continue  # self, or a peer this server leads no groups to
            down = links - int(s.peer_up[i])
            active = int(s.peer_active[i])
            laggy = int(s.peer_laggy_active[i])
            if (down == 0 and active >= self.grey_min_groups
                    and laggy / max(1, active) >= self.grey_fraction):
                grey_now.add(name)
                rounds = self._grey_seen.get(name, 0) + 1
                self._grey_seen[name] = rounds
                if rounds >= self.grey_rounds and name not in self._grey:
                    self._grey.add(name)
                    fault = f"grey-{name}-{self._grey_seq}"
                    self._grey_seq += 1
                    self._grey_fault[name] = fault
                    self.emit(
                        KIND_GREY_FOLLOWER, None,
                        f"peer {name} grey: {laggy}/{active} active "
                        f"links >= {self.server.engine.ledger.lag_threshold} "
                        f"entries behind while all {links} links are up "
                        f"(fraction {laggy / max(1, active):.2f} >= "
                        f"{self.grey_fraction:g}, max lag "
                        f"{int(s.peer_max_lag[i])})", fault=fault)
        for name in list(self._grey_seen):
            if name not in grey_now:
                self._grey_seen.pop(name, None)
        for name in list(self._grey):
            if name not in grey_now:
                self._grey.discard(name)
                self.emit(KIND_GREY_RECOVERED, None,
                          f"peer {name} recovered: grey episode over",
                          fault=self._grey_fault.pop(name, None))

    def _check_overload(self) -> None:
        """Sustained overload: the admission controller's shed rate over
        the last interval above raft.tpu.serving.overload.shed-rate.  One
        event per episode; the episode closes once a whole interval
        passes under threshold."""
        serving = getattr(self.server, "serving", None)
        if serving is None:
            return
        shed = serving.admission.shed_total
        last = self._last_shed
        self._last_shed = shed
        if last is None:
            return
        rate = (shed - last) / max(self.interval_s, 1e-9)
        if rate > self.shed_rate_threshold:
            if not self._overloaded:
                self._overloaded = True
                self.emit(KIND_OVERLOAD, None,
                          f"admission control shedding {rate:.0f} "
                          f"requests/s (threshold "
                          f"{self.shed_rate_threshold:.0f}/s); pending "
                          f"budgets holding, clients told to back off")
        else:
            self._overloaded = False

    def _check_stuck_lanes(self) -> None:
        """Stuck-lane detection (round-9 append windows): a sender whose
        envelope window stays FULL across consecutive samples while the
        engine's commit waterline is flat is a wedged round trip — under
        pipelining a healthy full window drains within one RTT, so full +
        no commit progress twice in a row is an anomaly, not load."""
        commits = int(self.server.engine.metrics.get("commit_advances", 0))
        flat = (self._last_commits is not None
                and commits == self._last_commits)
        self._last_commits = commits
        live = set()
        for (dest, _loop_key), sender in \
                list(self.server.replication._senders.items()):
            key = (dest, id(sender))
            live.add(key)
            full = sender.frames_in_flight >= sender.inflight_cap
            if full and flat:
                rounds = self._lane_full.get(key, 0) + 1
            else:
                rounds = 0
                self._lane_stuck.discard(key)
            self._lane_full[key] = rounds
            if rounds >= _STALL_ROUNDS and key not in self._lane_stuck:
                self._lane_stuck.add(key)
                self.emit(KIND_STUCK_LANE, None,
                          f"window toward {dest} full "
                          f"({sender.frames_in_flight}/"
                          f"{sender.inflight_cap} frames) for "
                          f"{rounds * self.interval_s:.1f}s with the "
                          f"commit waterline flat at {commits}")
        for key in list(self._lane_full):
            if key not in live:
                self._lane_full.pop(key, None)
        self._lane_stuck &= live
