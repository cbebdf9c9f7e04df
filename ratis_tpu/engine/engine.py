"""QuorumEngine: one tick loop advances every group's consensus math.

This is the replacement for the reference's thread-per-division daemons
(FollowerState timeout thread FollowerState.java:64, LeaderStateImpl
EventProcessor LeaderStateImpl.java:108-190): a single asyncio task per
server drains packed ack events and, in one pass over the group batch,

- advances leader commit indexes (ops.quorum.update_commit),
- fires follower election timeouts (ops.quorum.election_timeout),
- detects stale leadership (ops.quorum.check_leadership),

then invokes per-division callbacks for the few groups whose state changed.
Below ``scalar_fallback_threshold`` active groups the same math runs through
:mod:`ratis_tpu.ops.reference` (no device dispatch); above it, the jitted
kernels take over (the 10k-group path).
"""

from __future__ import annotations

import asyncio
import logging
import threading
import time
import zlib
from typing import Callable, Optional, Protocol

import numpy as np

from ratis_tpu.engine.state import (GroupBatchState, NO_DEADLINE,
                                    ROLE_CANDIDATE, ROLE_FOLLOWER,
                                    ROLE_LEADER, ROLE_LISTENER, ROLE_UNUSED)
from ratis_tpu.metrics.hops import hop
from ratis_tpu.ops import reference as ref
from ratis_tpu.trace.tracer import (STAGE_ACK, STAGE_COLLECT, STAGE_ENGINE,
                                    STAGE_FETCH, STAGE_LAUNCH, STAGE_PACK,
                                    TRACER, Tiles)

# keep in sync with ops.quorum.PACK_SENTINEL (not imported here: engine
# import must not eagerly pull in jax)
_PACK_SENTINEL = -(2 ** 31)
# a dispatch that judges nothing (a backlog's chunks before the last): a
# ``now`` before every deadline, a leadership timeout no gap exceeds
_JUDGE_NOTHING_NOW = -(2 ** 31)
_NO_TIMEOUT_MS = 2 ** 31 - 1

LOG = logging.getLogger(__name__)

_SHARED_STEP = None
_SHARED_FAST_STEP = None
_SHARED_TALLY = None
# (device ids, axis names) -> (refresh, fast) sharded jitted steps
_SHARDED_STEPS: dict = {}


def _shared_tally():
    """Process-wide jitted vote tally: ALL candidate rounds on a server
    tallied in one ops.quorum.tally_votes dispatch per tick."""
    global _SHARED_TALLY
    if _SHARED_TALLY is None:
        from ratis_tpu.ops import quorum as q
        from ratis_tpu.util.jaxenv import jit
        _SHARED_TALLY = jit(q.tally_votes)
    return _SHARED_TALLY


def _shared_step():
    """Process-wide jitted resident step (see QuorumEngine._kernels)."""
    global _SHARED_STEP
    if _SHARED_STEP is None:
        from ratis_tpu.ops import quorum as q
        from ratis_tpu.util.jaxenv import jit
        # Donating the DeviceState keeps the [G, P] batch resident on
        # device: each tick consumes the old buffers and returns new ones
        # without a host round-trip.
        _SHARED_STEP = jit(q.engine_step_resident, donate_argnums=(0,))
    return _SHARED_STEP


def _shared_fast_step():
    """Zero-dirty steady-state variant: packed events in, packed outs back."""
    global _SHARED_FAST_STEP
    if _SHARED_FAST_STEP is None:
        from ratis_tpu.ops import quorum as q
        from ratis_tpu.util.jaxenv import jit
        _SHARED_FAST_STEP = jit(q.engine_step_resident_fast,
                                donate_argnums=(0,))
    return _SHARED_FAST_STEP


# Why the sweep gate let a batched dispatch through — the dispatch count at
# scale is THE batched-mode cost driver, so its composition is a first-class
# labeled counter set instead of a guess.
DISPATCH_REASONS = ("upload", "commit", "dirty", "sweep", "backlog")


class EngineMetrics:
    """The engine's observability surface: a real ``RatisMetricRegistry``
    ("engine" component) instead of the plain dict of earlier rounds.

    Carries what the dict could not express: a per-sweep dispatch-latency
    timer (host -> XLA -> host wall per batched dispatch), batch
    lane-occupancy gauges (live rows vs padded capacity per packed tensor
    — the "are we actually batching" TPU signal), an ack-batch size
    histogram, and the per-reason dispatch counters as labeled counters.
    The old dict keys stay readable through :class:`_EngineMetricsView`
    (``engine.metrics``) for bench/test compatibility."""

    def __init__(self, engine: "QuorumEngine", prefix: str) -> None:
        from ratis_tpu.metrics.registry import (MetricRegistries,
                                                MetricRegistryInfo, labeled)
        info = MetricRegistryInfo(prefix=prefix, application="ratis",
                                  component="engine", name="quorum_engine")
        self.registry = MetricRegistries.global_registries().create(info)
        r = self.registry
        # the historical dict keys, now real counters (names preserved so
        # the scrape and the dict view agree)
        self.ticks = r.counter("ticks")
        self.acks = r.counter("acks")
        self.commit_advances = r.counter("commit_advances")
        self.batched_dispatches = r.counter("batched_dispatches")
        self.refresh_rows = r.counter("refresh_rows")
        self.fast_ticks = r.counter("fast_ticks")
        self.refresh_ticks = r.counter("refresh_ticks")
        self.idle_skips = r.counter("idle_skips")
        # tally dispatches of the open vote rounds (_vote_pass): one a tick
        # that found a reply queued or a round's deadline passed
        self.vote_tallies = r.counter("vote_tallies")
        self.reasons = {reason: r.counter(labeled("dispatches",
                                                  reason=reason))
                        for reason in DISPATCH_REASONS}
        # host->XLA->host wall clock of one batched dispatch (upload +
        # kernel + output download), and the packed ack batch it carried
        self.dispatch_timer = r.timer("dispatchLatency")
        self.ack_batch = r.histogram("ackBatchSize")
        # Lane occupancy: live rows vs padded lane capacity for the two
        # packed tensors the kernel consumes — the [G, P] group batch and
        # the [7, E] event pack of the LAST dispatch.  Occupancy near 0
        # means the server pays full-width dispatches for a few live lanes.
        r.gauge("laneGroupsLive", lambda: len(engine.state.active))
        r.gauge("laneGroupsCapacity", lambda: engine.state.capacity)
        r.gauge("laneOccupancyGroups",
                lambda: len(engine.state.active) / engine.state.capacity)
        r.gauge("laneEventsLastDispatch", lambda: engine._last_event_rows)
        r.gauge("laneEventCapacityLastDispatch",
                lambda: engine._last_event_cap)
        r.gauge("laneOccupancyEvents",
                lambda: (engine._last_event_rows / engine._last_event_cap
                         if engine._last_event_cap else 0.0))

    def unregister(self) -> None:
        from ratis_tpu.metrics.registry import MetricRegistries
        MetricRegistries.global_registries().remove(self.registry.info)


class _EngineMetricsView:
    """Dict-shaped read view over :class:`EngineMetrics` — the
    ``engine.metrics`` the bench and tests already consume.  Supports
    ``m["ticks"]``, ``m.get``, iteration, and ``m[k] = v`` (tests reset
    counters through it); the per-reason dispatch counters appear under
    their historical ``dispatch_<reason>`` keys only once non-zero, like
    the dict they replace."""

    _PLAIN = ("ticks", "acks", "commit_advances", "batched_dispatches",
              "refresh_rows", "fast_ticks", "refresh_ticks", "idle_skips",
              "vote_tallies")

    def __init__(self, em: EngineMetrics) -> None:
        self._em = em

    def _counter(self, key: str):
        if key in self._PLAIN:
            return getattr(self._em, key)
        if key.startswith("dispatch_"):
            return self._em.reasons.get(key[len("dispatch_"):])
        return None

    def __getitem__(self, key: str) -> int:
        c = self._counter(key)
        if c is None:
            raise KeyError(key)
        return c.count

    def __setitem__(self, key: str, value: int) -> None:
        c = self._counter(key)
        if c is None:
            raise KeyError(key)
        c._value = int(value)

    def get(self, key: str, default=None):
        c = self._counter(key)
        return default if c is None else c.count

    def __contains__(self, key: str) -> bool:
        return self._counter(key) is not None

    def keys(self) -> list[str]:
        return [*self._PLAIN,
                *(f"dispatch_{r}" for r, c in self._em.reasons.items()
                  if c.count)]

    def __iter__(self):
        return iter(self.keys())

    def items(self):
        return [(k, self[k]) for k in self.keys()]

    def __len__(self) -> int:
        return len(self.keys())

    def __repr__(self) -> str:
        return repr(dict(self.items()))


class EngineListener(Protocol):
    """What a division implements to be driven by the engine."""

    async def on_election_timeout(self) -> None: ...

    async def on_commit_advance(self, new_commit: int) -> None: ...

    async def on_leadership_stale(self) -> None: ...


def _no_part(stage: int) -> None:
    """:meth:`QuorumEngine._tick_batched_dispatch` outside a trace session."""


class Clock:
    """Millisecond clock relative to a movable epoch (int32-friendly).

    The epoch advances when the engine rebases (see
    QuorumEngine._maybe_rebase_epoch), keeping now_ms well inside int32 for
    arbitrarily long uptimes."""

    def __init__(self):
        self._t0 = time.monotonic()

    def now_ms(self) -> int:
        return int((time.monotonic() - self._t0) * 1000)

    def advance_epoch(self, delta_ms: int) -> None:
        self._t0 += delta_ms / 1000.0


class QuorumEngine:
    def __init__(self, max_groups: int = 1024, max_peers: int = 8,
                 tick_interval_s: float = 0.002,
                 scalar_fallback_threshold: int = 16,
                 leadership_timeout_ms: int = 300,
                 use_device: bool = False,
                 mesh=None, name: str = ""):
        # Optional jax.sharding.Mesh: the PRODUCTION resident tick
        # (engine_step_resident / _fast_sliced, donated DeviceState) runs
        # sharded over the group axis — each device owns one contiguous
        # SLICE of G/n rows, packed events are routed per slice ([7, S, E],
        # slice axis sharded) so a device only scans events for rows it
        # holds, and the row-local quorum math keeps the step
        # collective-free (ratis_tpu.parallel.mesh).
        self.mesh = mesh
        n_slices = 1
        if mesh is not None:
            n_slices = int(mesh.devices.size)
            # auto-pad: mesh size no longer needs to divide max-groups —
            # padded rows stay ROLE_UNUSED and cost nothing
            from ratis_tpu.parallel.mesh import pad_to_mesh
            max_groups = pad_to_mesh(max_groups, n_slices)
        self.state = GroupBatchState(max_groups, max_peers,
                                     n_slices=n_slices)
        self.clock = Clock()
        self.tick_interval_s = tick_interval_s
        self.scalar_fallback_threshold = scalar_fallback_threshold
        self.leadership_timeout_ms = leadership_timeout_ms
        self.use_device = use_device
        self._listeners: dict[int, EngineListener] = {}
        self._ack_ring: list[tuple[int, int, int, int]] = []  # (slot, peer, match, t)
        self._vote_ring: list[tuple[int, int, bool]] = []  # (slot, peer, granted)
        self._vote_rounds: dict[int, asyncio.Future] = {}
        # slot -> [flush | SENTINEL, deadline | SENTINEL]: high-rate scalar
        # mutations packed into the fast tick instead of dirty-row refreshes
        self._slot_updates: dict[int, list] = {}
        self._task: Optional[asyncio.Task] = None
        self._wake = asyncio.Event()
        self._running = False
        # Device-resident copy of the batch state (ops.quorum.DeviceState);
        # None until the first batched tick, invalidated on rebase/regrow.
        self._dev = None
        # Next time the scalar path sweeps leaders for staleness; the batched
        # kernel checks every tick for free, the scalar path throttles the
        # O(leaders) python sweep to timeout/4.
        self._next_staleness_ms = 0
        # Batched-path dispatch gate: when a tick has NO events to ship and
        # the next follower deadline / staleness sweep is not due yet, the
        # device dispatch is skipped entirely (the dominant idle cost at
        # high group counts is the fixed per-dispatch overhead, not the
        # kernel).  0 forces the first dispatch.
        self._next_sweep_ms = 0
        # A listener without the sync commit hook has an undelivered commit
        # riding the tick path; the sweep gate must not skip while set.
        self._tick_commit_pending = False
        # largest compiled event bucket (lowered by prewarm): dispatch
        # chunks never exceed it, so no fresh jit shape mid-run
        self._event_bucket_cap = self._MAX_EVENT_BUCKET
        # last-dispatch packed-event lane fill (read by the occupancy
        # gauges; see EngineMetrics)
        self._last_event_rows = 0
        self._last_event_cap = 0
        # monotonic time of the last completed tick (engine freshness for
        # the /health endpoint); None until the loop runs once
        self.last_tick_monotonic: Optional[float] = None
        # The exception that killed the tick loop (None while it lives).
        # Commits advance inline at ack intake without the loop, so a dead
        # loop does not stop the server acknowledging writes — /health and
        # any harness read this instead of inferring it from staleness.
        self.failure: Optional[BaseException] = None
        # Real metric registry ("engine" component); engine.metrics keeps
        # the historical dict read surface over it.
        self._m = EngineMetrics(
            self, name or f"engine-{id(self):x}")
        self.metrics = _EngineMetricsView(self._m)
        # Lag & health ledger over the same host mirrors (one fused pass +
        # one fetch per telemetry tick; engine/ledger.py).  Lazy import:
        # the engine module must stay importable without jax.
        from ratis_tpu.engine.ledger import LagLedger
        self.ledger = LagLedger(self, name or f"engine-{id(self):x}")
        # Cross-shard intake safety (raft.tpu.server.loop-shards): divisions
        # pinned to worker event loops call the intake methods from their
        # own threads while the tick task reads/swaps the same rings and
        # mirror on the engine's home loop.  An RLock (re-entrant: an
        # inline-commit callback may re-enter intake synchronously)
        # serializes the mutation windows; the home loop lets off-loop
        # intake wake the tick via call_soon_threadsafe.  With one loop
        # (the default) every acquisition is uncontended.
        self._lock = threading.RLock()
        # off-loop wake already scheduled and not yet fired (guarded by
        # the intake lock): dedupes call_soon_threadsafe notify storms
        self._wake_pending = False
        self._home_loop: Optional[asyncio.AbstractEventLoop] = None
        # slot -> loop the listener's division runs on (for cross-shard
        # callback dispatch); absent/same-loop listeners take the direct
        # await path, identical to the unsharded runtime.
        self._listener_loops: dict[int, asyncio.AbstractEventLoop] = {}

    # -- registration --------------------------------------------------------

    def slice_of(self, key: bytes) -> int:
        """Owning mesh slice for a group id: the same crc32 pin as
        LoopShardPool.shard_of, taken modulo the slice count — so whenever
        the mesh size divides loop-shards, one slice maps to a whole
        shard-set and intake for a slice's groups arrives from a stable
        subset of loops."""
        return zlib.crc32(key) % self.state.n_slices

    def attach(self, listener: EngineListener,
               slice_idx: int = -1) -> int:
        """Register a listener; ``slice_idx`` pins the group's slot inside
        one mesh slice's row range (divisions pass slice_of(group id);
        -1 = lowest slice with room, the non-mesh default)."""
        with self._lock:
            slot = self.state.allocate(slice_idx)
            self._listeners[slot] = listener
        try:
            self._listener_loops[slot] = asyncio.get_running_loop()
        except RuntimeError:
            pass  # attached outside a loop (tests): direct-await path
        return slot

    def detach(self, slot: int) -> None:
        self.end_vote_round(slot)
        with self._lock:
            self._listeners.pop(slot, None)
            self._listener_loops.pop(slot, None)
            self.state.release(slot)

    # -- event intake (transport/appender threads call these) ---------------

    def on_ack(self, slot: int, peer_slot: int, match_index: int) -> None:
        """Record a follower ack: update the host mirror eagerly, try the
        O(P) commit advance INLINE, and queue the packed event for the
        device (which applies the same scatter-max at the next tick, so
        host and device stay in agreement).

        The inline commit is the latency-critical redesign: commits used to
        advance only inside the engine tick task, and under load that task
        is one of thousands competing for the event loop — profiling at
        1024 groups measured it scheduled ~50x/s, putting 100ms+ of pure
        queueing delay into EVERY commit (and the client pipelines that
        wait on them).  The per-ack math is a [P]-element majority-min
        (P <= 8); the device keeps the work that actually batches — the
        O(G) timeout/staleness/lease sweeps."""
        with self._lock:
            self._on_ack_locked(slot, peer_slot, match_index,
                                self.clock.now_ms())

    def on_ack_batch(self, rows) -> None:
        """Packed ack intake: ``rows`` is a sequence of
        ``(slot, peer_slot, match_index)`` rows (list of tuples or an
        ``[N, 3]`` int array).  Applies exactly the per-row operations of
        :meth:`on_ack` — mirror scatter-max, ring append, inline commit —
        in row order, under ONE intake-lock acquisition, so a follower
        reply frame carrying N co-hosted groups' acks costs one lock
        round-trip and (via the wake dedupe in :meth:`_wake_set`) at most
        one tick wake instead of N.  Commit advancement is bit-identical
        to feeding the same rows through scalar ``on_ack`` one by one
        (asserted in tests/test_loop_shards.py)."""
        if rows is None or len(rows) == 0:
            return
        if isinstance(rows, np.ndarray):
            rows = rows.tolist()
        # ack.intake work span: the mirror updates, the ring appends and
        # the inline commits (with the divisions' commit callbacks) of one
        # reply frame's rows
        span = TRACER.begin(STAGE_ACK) if TRACER.enabled else None
        try:
            with self._lock:
                now = self.clock.now_ms()
                for slot, peer_slot, match_index in rows:
                    self._on_ack_locked(int(slot), int(peer_slot),
                                        int(match_index), now)
        finally:
            if span is not None:
                TRACER.end(span, tag=len(rows))

    def _on_ack_locked(self, slot: int, peer_slot: int, match_index: int,
                       now: int) -> None:
        s = self.state
        if s.match_index[slot, peer_slot] < match_index:
            s.match_index[slot, peer_slot] = match_index
        if s.last_ack_ms[slot, peer_slot] < now:
            s.last_ack_ms[slot, peer_slot] = now
        self._ack_ring.append((slot, peer_slot, match_index, now))
        self._try_commit_inline(slot, match_index)

    def _try_commit_inline(self, slot: int, hint: int) -> None:
        """Advance ``slot``'s commit from the host mirror if possible and
        deliver the (synchronous) listener callback immediately.  Listeners
        without the sync hook keep the tick-driven path: their mirror is
        left untouched so the device/tick dispatch still fires for them."""
        s = self.state
        if s.role[slot] != ROLE_LEADER:
            return
        if hint <= int(s.commit_index[slot]):
            return  # the triggering value cannot raise the majority-min
        listener = self._listeners.get(slot)
        cb = getattr(listener, "on_commit_advance_now", None)
        if cb is None:
            # tick path owns this listener's commits: force the next tick
            # through the dispatch (the sweep gate must not skip it)
            self._tick_commit_pending = True
            self._wake_set()
            return
        new_commit, did = ref.update_commit(
            s.match_index[slot].tolist(), int(s.self_slot[slot]),
            int(s.flush_index[slot]), s.conf_cur[slot].tolist(),
            s.conf_old[slot].tolist(), int(s.commit_index[slot]),
            int(s.first_leader_index[slot]), True)
        if did:
            s.commit_index[slot] = new_commit
            self._m.commit_advances.inc()
            cb(new_commit)

    def on_flush(self, slot: int, flush_index: int) -> None:
        """A log's flush frontier advanced: update the mirror and queue a
        packed slot update for the fast tick path (these fire on every
        append — routing them through mark_dirty would force the dirty-row
        refresh on every tick)."""
        with self._lock:
            self._on_flush_locked(slot, flush_index)

    def on_flush_batch(self, rows) -> None:
        """Packed flush intake (envelope sweep intake): ``rows`` is a
        sequence of ``(slot, flush_index)`` rows — one multi-group append
        frame's flush advances.  Applies exactly the per-row operations of
        :meth:`on_flush`, in row order, under ONE intake-lock acquisition,
        so a frame carrying N co-hosted groups' appends costs one lock
        round-trip (and, via the wake dedupe, at most one tick wake)
        instead of N."""
        if not rows:
            return
        span = TRACER.begin(STAGE_ACK) if TRACER.enabled else None
        try:
            with self._lock:
                for slot, flush_index in rows:
                    self._on_flush_locked(int(slot), int(flush_index))
        finally:
            if span is not None:
                TRACER.end(span, tag=len(rows))

    def _on_flush_locked(self, slot: int, flush_index: int) -> None:
        s = self.state
        if flush_index < int(s.flush_index[slot]):
            # regression (follower truncate): rare — take the refresh
            # path, the device-side scatter-max would ignore a lower
            # value
            s.flush_index[slot] = flush_index
            s.mark_dirty(slot)
            self._wake_set()
            return
        s.flush_index[slot] = flush_index
        u = self._slot_updates.get(slot)
        if u is None:
            self._slot_updates[slot] = [flush_index, _PACK_SENTINEL]
        elif u[0] == _PACK_SENTINEL or flush_index > u[0]:
            u[0] = flush_index
        # A leader's own flush counts toward quorum: try the commit
        # inline (single-peer groups commit on flush alone).
        self._try_commit_inline(slot, flush_index)

    def on_deadline(self, slot: int, deadline_ms: int) -> None:
        """(Re-)arm a follower election deadline; same packed-update route.
        No wake: a postponed deadline needs no immediate tick."""
        with self._lock:
            s = self.state
            s.election_deadline_ms[slot] = deadline_ms
            if deadline_ms < self._next_sweep_ms:
                self._next_sweep_ms = deadline_ms  # earlier than planned
            u = self._slot_updates.get(slot)
            if u is None:
                self._slot_updates[slot] = [_PACK_SENTINEL, deadline_ms]
            else:
                u[1] = deadline_ms

    # -- cross-loop plumbing (loop sharding) ---------------------------------

    def _wake_set(self) -> None:
        """Wake the tick loop from any thread: direct on the home loop,
        call_soon_threadsafe from a shard loop (asyncio.Event.set is not
        thread-safe).  Off-loop wakes are DEDUPED under the intake lock:
        a burst of cross-shard acks/flushes schedules ONE home-loop
        callback, not one per caller — profiling showed notify storms
        queueing thousands of redundant call_soon_threadsafe callbacks
        behind the very tick they all wanted to wake."""
        home = self._home_loop
        if home is not None:
            try:
                running = asyncio.get_running_loop()
            except RuntimeError:
                running = None
            if running is not home:
                with self._lock:
                    if self._wake_pending:
                        return  # a scheduled wake already covers this burst
                    self._wake_pending = True
                try:
                    hop("engine_wake")
                    home.call_soon_threadsafe(self._wake_fire)
                except RuntimeError:
                    # home loop closing: nothing left to wake
                    with self._lock:
                        self._wake_pending = False
                return
        if not self._wake.is_set():
            hop("engine_wake")
        self._wake.set()

    def _wake_fire(self) -> None:
        """Home-loop half of the deduped off-loop wake: clear the pending
        latch FIRST (a wake requested after this point must schedule a
        fresh callback — the event below may be consumed immediately),
        then set the event."""
        with self._lock:
            self._wake_pending = False
        self._wake.set()

    @staticmethod
    def _resolve_future(fut: asyncio.Future, result: str) -> None:
        """set_result on the future's OWN loop (vote futures are created on
        the division's shard loop; the tick resolves them from the home
        loop)."""
        floop = fut.get_loop()
        try:
            running = asyncio.get_running_loop()
        except RuntimeError:
            running = None
        if floop is running:
            if not fut.done():
                fut.set_result(result)
            return

        def _set() -> None:
            if not fut.done():
                fut.set_result(result)

        try:
            floop.call_soon_threadsafe(_set)
        except RuntimeError:
            pass  # owner loop closed: the round's division is gone

    @staticmethod
    def _cancel_future(fut: asyncio.Future) -> None:
        floop = fut.get_loop()
        try:
            running = asyncio.get_running_loop()
        except RuntimeError:
            running = None
        if floop is running:
            if not fut.done():
                fut.cancel()
            return

        def _cancel() -> None:
            if not fut.done():
                fut.cancel()

        try:
            floop.call_soon_threadsafe(_cancel)
        except RuntimeError:
            pass

    # -- batched vote rounds (SURVEY §3.3 HOT LOOP #2) -----------------------

    @property
    def tally_batched(self) -> bool:
        """Whether candidate vote rounds run through the engine's batched
        tally (the per-division scalar loop stays below the threshold —
        same policy as the commit/timeout math)."""
        return (self.use_device
                or len(self.state.active) >= self.scalar_fallback_threshold)

    def begin_vote_round(self, slot: int, deadline_ms: int) -> asyncio.Future:
        """Open a vote round for ``slot``: reset the grant/reject masks
        (self-grant pre-set), arm the round deadline, and return a future
        the tick resolves with "PASSED" / "REJECTED" / "TIMEOUT".  The
        conf masks and priorities were already synced via set_conf."""
        with self._lock:
            s = self.state
            s.vote_grants[slot] = False
            s.vote_rejects[slot] = False
            s.vote_grants[slot, s.self_slot[slot]] = True
            s.vote_deadline_ms[slot] = deadline_ms
            old = self._vote_rounds.pop(slot, None)
            if old is not None:
                self._cancel_future(old)
            fut = asyncio.get_running_loop().create_future()
            self._vote_rounds[slot] = fut
        return fut

    def on_vote_reply(self, slot: int, peer_slot: int, granted: bool) -> None:
        """Queue a reply for the next tick's tally.  No wake: the tick loop
        runs every tick interval anyway, and one tally a tick for every
        reply that came in since the last is the batch the kernel wants.
        Waking for each reply made an election storm feed itself: thousands
        of open rounds kept every engine dispatching at the front of every
        loop pass, the votes' own RPCs starved behind them, and the rounds
        timed out and were asked again."""
        with self._lock:
            if slot not in self._vote_rounds:
                return
            self._vote_ring.append((slot, peer_slot, granted))

    def end_vote_round(self, slot: int) -> None:
        """Abandon a round (candidate stopped / stepped down / special
        reply handled inline): cancel its future and disarm the deadline."""
        with self._lock:
            self.state.vote_deadline_ms[slot] = NO_DEADLINE
            fut = self._vote_rounds.pop(slot, None)
        if fut is not None:
            self._cancel_future(fut)

    def expire_vote_round(self, slot: int) -> None:
        """Every peer has replied or failed: pull the round deadline to now
        so the next tick resolves it through the timeout-path tally — the
        outstanding==0 early exit of the reference's waitForResults (a
        majority gated only on a SILENT higher-priority peer must not wait
        out the full randomized deadline once that peer's RPC has failed)."""
        with self._lock:
            if slot not in self._vote_rounds:
                return
            s = self.state
            now = np.int32(self.clock.now_ms())
            if s.vote_deadline_ms[slot] > now:
                s.vote_deadline_ms[slot] = now

    def _vote_pass(self, now: int) -> list[tuple[asyncio.Future, str]]:
        """Apply queued vote replies and tally EVERY open round in one
        jitted dispatch; returns (future, result) pairs to resolve."""
        s = self.state
        events, self._vote_ring = self._vote_ring, []
        for slot, peer, granted in events:
            if slot not in self._vote_rounds:
                continue
            if s.vote_grants[slot, peer] or s.vote_rejects[slot, peer]:
                continue  # first reply wins (waitForResults putIfAbsent)
            if granted:
                s.vote_grants[slot, peer] = True
            else:
                s.vote_rejects[slot, peer] = True
        if not self._vote_rounds:
            return []
        import jax.numpy as jnp
        self._m.vote_tallies.inc()
        res = _shared_tally()(
            jnp.asarray(s.vote_grants), jnp.asarray(s.vote_rejects),
            jnp.asarray(s.conf_cur), jnp.asarray(s.conf_old),
            jnp.asarray(s.priority), jnp.asarray(s.self_priority))
        passed = np.asarray(res.passed)
        passed_on_timeout = np.asarray(res.passed_on_timeout)
        rejected = np.asarray(res.rejected)
        out: list[tuple[asyncio.Future, str]] = []
        for slot, fut in list(self._vote_rounds.items()):
            if fut.done():
                self._vote_rounds.pop(slot)
                s.vote_deadline_ms[slot] = NO_DEADLINE
                continue
            if rejected[slot]:
                result = "REJECTED"
            elif passed[slot]:
                result = "PASSED"
            elif now >= s.vote_deadline_ms[slot]:
                result = ("PASSED" if passed_on_timeout[slot] else "TIMEOUT")
            else:
                continue  # round still open
            self._vote_rounds.pop(slot)
            s.vote_deadline_ms[slot] = NO_DEADLINE
            out.append((fut, result))
        return out

    def regress_match(self, slot: int, peer_slot: int, match_index: int) -> None:
        """A follower provably lost acked entries (volatile-log restart):
        lower the mirror AND clamp any acks for this (group, peer) still
        queued in the ring — otherwise the next tick's scatter-max replays a
        pre-restart ack and silently restores the lost match."""
        with self._lock:
            self._ack_ring = [
                (g, p,
                 min(m, match_index) if (g, p) == (slot, peer_slot) else m, t)
                for g, p, m, t in self._ack_ring]
            self.state.match_index[slot, peer_slot] = match_index
            self.state.mark_dirty(slot)

    def notify(self) -> None:
        """Wake the tick loop early (e.g. flush index advanced)."""
        self._wake_set()

    # -- lifecycle -----------------------------------------------------------

    async def start(self) -> None:
        self._running = True
        self._home_loop = asyncio.get_running_loop()
        self._task = asyncio.create_task(self._run(), name="quorum-engine")

    async def close(self) -> None:
        self._running = False
        if self._task is not None:
            self._wake.set()
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None
        # drop the engine registry from the global scrape surface; the
        # counters stay readable through engine.metrics (tests inspect a
        # closed cluster's engines)
        self._m.unregister()
        self.ledger.unregister()

    @property
    def tick_alive(self) -> bool:
        """The tick loop task exists and has not ended."""
        return self._task is not None and not self._task.done()

    async def _run(self) -> None:
        try:
            await self._run_ticks()
        except Exception as e:
            # A tick that raises (a program the compiler refuses, a
            # donation or sharding error, device memory) ends election
            # timeouts, staleness sweeps and vote tallies for every hosted
            # group while inline commits carry on: say so at once, and
            # leave the cause where the server's health surface reads it.
            self.failure = e
            LOG.exception("quorum engine tick loop died; elections and "
                          "staleness sweeps for %d hosted groups have "
                          "stopped", len(self.state.active))

    async def _run_ticks(self) -> None:
        loop = asyncio.get_running_loop()
        while self._running:
            if self._wake.is_set():
                # busy: events already queued — tick now, skip the timer
                # allocation wait_for would make (hot at high group counts).
                # NOTE: pacing dispatches at a tick_interval floor was tried
                # here and measured ~2.5x WORSE end-to-end at 1024 groups:
                # commit latency compounds through the sequential per-group
                # write pipelines, so ticking at the front of the loop
                # backlog beats amortizing dispatch overhead.
                await asyncio.sleep(0)
            else:
                try:
                    await asyncio.wait_for(self._wake.wait(),
                                           self.tick_interval_s)
                except asyncio.TimeoutError:
                    pass
            self._wake.clear()
            # the one place that watches for a profiler session starting or
            # stopping in this process (ratis_tpu.trace: a trace session
            # opens and closes with it); this loop wakes at least every
            # tick interval
            TRACER.poll()
            t0 = loop.time()
            await self.tick()
            self.last_tick_monotonic = loop.time()
            cost = loop.time() - t0
            if cost > self.tick_interval_s:
                # Self-pacing: a dispatch that cost more than the tick
                # interval (big [G, P] batch on a slow backend) must not
                # monopolize the event loop — sleeping roughly one tick-cost
                # bounds the engine's duty cycle at ~50% and lets more acks
                # accumulate per dispatch.  Capped: one pathological tick
                # (wedged backend, measured wall-clock inflated by other
                # coroutines) must not impose an unbounded second stall on
                # every group's quorum/election processing.
                await asyncio.sleep(min(cost, 8 * self.tick_interval_s))

    # -- the tick ------------------------------------------------------------

    # Rebase when now_ms passes this (half of int32 max, lots of margin).
    _REBASE_THRESHOLD_MS = 1 << 30
    _REBASE_KEEP_MS = 3_600_000  # keep the last hour of history meaningful

    def _maybe_rebase_epoch(self, now: int) -> int:
        """Shift the clock epoch forward and subtract the delta from every
        stored time so int32 never wraps (see ops.quorum time convention)."""
        if now < self._REBASE_THRESHOLD_MS:
            return now
        s = self.state
        delta = now - self._REBASE_KEEP_MS
        self.clock.advance_epoch(delta)
        s.last_ack_ms -= np.int32(delta)
        np.maximum(s.last_ack_ms, 0, out=s.last_ack_ms)
        mask = s.election_deadline_ms != NO_DEADLINE
        s.election_deadline_ms[mask] -= np.int32(delta)
        vmask = s.vote_deadline_ms != NO_DEADLINE
        s.vote_deadline_ms[vmask] -= np.int32(delta)
        self._ack_ring = [(g, p, m, max(0, t - delta))
                          for g, p, m, t in self._ack_ring]
        for u in self._slot_updates.values():
            if u[1] != _PACK_SENTINEL and u[1] != NO_DEADLINE:
                u[1] = max(0, u[1] - delta)
        self._next_staleness_ms = 0
        self._next_sweep_ms = 0  # pre-rebase timestamp would gate forever
        self._dev = None  # wholesale time shift: re-upload the device state
        return now - delta

    # Ack/flush backlog bound for the sweep-gated batched path: beyond this
    # many queued events, ship them even with no sweep due (keeps the ring
    # far below the chunking cap and the device's staleness inputs fresh).
    _EVENT_BACKLOG_MAX = 8192

    async def tick(self) -> None:
        # The math pass runs under the intake lock: shard-loop intake
        # (on_ack/on_flush/...) and the tick swap/read the same rings and
        # host mirror.  The lock is released BEFORE listener callbacks —
        # holding a threading lock across awaits would stall every shard's
        # intake for the duration of division code.
        with self._lock:
            changed, votes = self._tick_locked()
        for fut, result in votes:
            self._resolve_future(fut, result)

        # dispatch callbacks outside the math pass; a listener pinned to a
        # different shard loop gets its callback ON that loop
        running = asyncio.get_running_loop()
        for slot, kind, value in changed:
            listener = self._listeners.get(slot)
            if listener is None:
                continue
            if kind == "commit":
                self._m.commit_advances.inc()
                coro = listener.on_commit_advance(value)
            elif kind == "timeout":
                coro = listener.on_election_timeout()
            else:  # "stale"
                if getattr(listener, "hibernating", False):
                    continue  # requested silence; cheap skip, no coroutine
                coro = listener.on_leadership_stale()
            lloop = self._listener_loops.get(slot)
            if lloop is None or lloop is running:
                await coro
            else:
                try:
                    await asyncio.wrap_future(
                        asyncio.run_coroutine_threadsafe(coro, lloop))
                except RuntimeError:
                    coro.close()  # shard loop gone (server closing)

    def _tick_locked(self) -> tuple[list, list]:
        """One tick's math pass (caller holds the intake lock).  Returns
        (changed listener events, resolved vote futures)."""
        s = self.state
        now = self._maybe_rebase_epoch(self.clock.now_ms())
        self._m.ticks.inc()

        active = s.active
        if not active:
            self._ack_ring.clear()
            s.dirty.clear()
            s.lazy.clear()
            self._slot_updates.clear()
            self._dev = None
            return [], []

        use_batched = (self.use_device
                       or len(active) >= self.scalar_fallback_threshold)
        if use_batched and self._dev is not None \
                and not self._tick_commit_pending \
                and not s.dirty and now < self._next_sweep_ms \
                and (len(self._ack_ring) + len(self._slot_updates)
                     < self._EVENT_BACKLOG_MAX):
            # Nothing the device could DECIDE right now: commits already
            # advanced inline at intake, and no deadline/staleness sweep is
            # due.  Let events accumulate — the next dispatch carries a
            # bigger packed batch (the shape the kernel wants) and the
            # engine's dispatch rate drops from per-tick to per-sweep.
            # Open vote rounds do not open this gate: the quorum step
            # decides nothing about them (the tally below does).
            self._m.idle_skips.inc()
            return [], self._votes_if_due(now)
        if use_batched:
            # why did the gate let this dispatch through? (the labeled
            # dispatches{reason=...} counters; see EngineMetrics)
            reasons = self._m.reasons
            if self._dev is None:
                reasons["upload"].inc()
            elif self._tick_commit_pending:
                reasons["commit"].inc()
            elif s.dirty:
                reasons["dirty"].inc()
            elif now >= self._next_sweep_ms:
                reasons["sweep"].inc()
            else:
                reasons["backlog"].inc()

        acks = self._ack_ring
        self._ack_ring = []
        self._m.acks.inc(len(acks))

        # The host mirror was updated eagerly at ack intake (on_ack), where
        # the commit advance now happens inline; the events still travel to
        # the device below so the resident state applies the same
        # scatter-max and stays in agreement without ever downloading the
        # [G, P] arrays.
        touched: set[int] = set(s.dirty)
        touched.update(a[0] for a in acks)

        if use_batched:
            self._tick_commit_pending = False
            changed = self._tick_batched(acks, now)
            self._next_sweep_ms = self._compute_next_sweep(now)
        else:
            # flush advances queued as packed updates still need their
            # slots' commit math in the scalar pass (mirror already has the
            # values)
            touched.update(self._slot_updates)
            self._slot_updates.clear()
            # host-only mutations make any retained device copy stale; drop
            # it so a later crossing back over the threshold re-uploads
            s.dirty.clear()
            s.lazy.clear()
            self._dev = None
            self._tick_commit_pending = False
            changed = self._tick_scalar(touched, now)

        return changed, self._votes_if_due(now)

    def _votes_if_due(self, now: int) -> list[tuple[asyncio.Future, str]]:
        """The tally, only where it can decide something: a reply came in
        since the last one, or an open round's deadline has passed (a
        closed round's reads NO_DEADLINE).  An open round with neither is
        left alone, however many ticks pass over it."""
        if self._vote_ring or (
                self._vote_rounds
                and now >= int(self.state.vote_deadline_ms.min())):
            return self._vote_pass(now)
        return []

    def _compute_next_sweep(self, now: int) -> int:
        """Earliest time the device must be consulted again with no new
        events: the soonest armed follower deadline, bounded by the
        staleness-sweep cadence (timeout/4, matching the scalar path) —
        but only when this server leads anything (a follower-only or idle
        server has no leaderships to check for staleness)."""
        s = self.state
        dl = np.where(s.role == ROLE_FOLLOWER, s.election_deadline_ms,
                      NO_DEADLINE)
        nxt = int(dl.min()) if dl.size else NO_DEADLINE
        if bool((s.role == ROLE_LEADER).any()):
            nxt = min(nxt, now + max(1, self.leadership_timeout_ms // 4))
        return nxt

    # -- scalar path ---------------------------------------------------------

    def _tick_scalar(self, touched: set[int], now: int
                     ) -> list[tuple[int, str, int]]:
        """Python fallback for small group counts: commit math only for
        slots with new acks / flush advances (``touched``); the O(leaders)
        staleness sweep runs at most every leadership_timeout/4."""
        s = self.state
        changed: list[tuple[int, str, int]] = []
        check_stale = now >= self._next_staleness_ms
        if check_stale:
            self._next_staleness_ms = now + max(
                1, self.leadership_timeout_ms // 4)
        # engine.dispatch work span; the scalar pass has no device parts
        span = (TRACER.begin(STAGE_ENGINE)
                if touched and TRACER.enabled else None)

        for slot in list(s.active):
            role = s.role[slot]
            if role == ROLE_LEADER:
                if slot in touched:
                    new_commit, did = ref.update_commit(
                        s.match_index[slot].tolist(), int(s.self_slot[slot]),
                        int(s.flush_index[slot]), s.conf_cur[slot].tolist(),
                        s.conf_old[slot].tolist(), int(s.commit_index[slot]),
                        int(s.first_leader_index[slot]), True)
                    if did:
                        s.commit_index[slot] = new_commit
                        changed.append((slot, "commit", new_commit))
                if check_stale and ref.check_leadership(
                        s.last_ack_ms[slot].tolist(), int(s.self_slot[slot]),
                        s.conf_cur[slot].tolist(), s.conf_old[slot].tolist(),
                        now, self.leadership_timeout_ms, True):
                    changed.append((slot, "stale", 0))
            elif role == ROLE_FOLLOWER and now >= s.election_deadline_ms[slot]:
                s.election_deadline_ms[slot] = NO_DEADLINE  # re-armed by div
                changed.append((slot, "timeout", 0))
        if span is not None:
            TRACER.end(span, tag=len(touched))
        return changed

    # -- batched path --------------------------------------------------------

    def _kernels(self):
        # One process-wide jitted step: the kernel is pure and every engine
        # in the process (one per co-hosted server) shares shapes, so a
        # shared wrapper compiles each shape bucket once instead of once
        # per server.  With a mesh, the per-engine sharded variants are
        # used instead (same kernels, group axis partitioned).
        if self.mesh is not None:
            return self._mesh_steps()[0]
        return _shared_step()

    def _fast_kernel(self):
        if self.mesh is not None:
            return self._mesh_steps()[1]
        return _shared_fast_step()

    def _mesh_steps(self):
        # Process-wide like _shared_step: co-hosted servers build EQUAL
        # meshes over the same devices, so keying by (devices, axes) lets
        # one compile serve every engine (prewarming servers[0] covers the
        # trio) instead of each engine landing its own synchronous compile
        # mid-run.
        key = (tuple(d.id for d in self.mesh.devices.flat),
               self.mesh.axis_names)
        steps = _SHARDED_STEPS.get(key)
        if steps is None:
            from ratis_tpu.parallel.mesh import (
                sharded_resident_fast_step_sliced, sharded_resident_step)
            # fast path: the SLICED variant — events pre-routed per device
            # ([7, S, E]) instead of replicated; refresh path keeps
            # replicated inputs (dirty rows are rare and whole-row)
            steps = (sharded_resident_step(self.mesh),
                     sharded_resident_fast_step_sliced(self.mesh))
            _SHARDED_STEPS[key] = steps
        return steps

    def prewarm(self, group_counts=(64, 256, 1024),
                event_counts=(64, 256, 1024)) -> None:
        """Compile the batched kernel for the standard pad buckets up front.

        XLA compiles per shape signature; without prewarming, the first tick
        that hits a new (dirty-rows, events) bucket stalls the event loop for
        the compile — long enough on slow backends to fire election timeouts
        and churn leadership mid-benchmark.  Runs the real tick path against
        the current (zero/idle) state; listeners never fire because outputs
        are filtered by the active set."""
        s = self.state
        now = self.clock.now_ms()
        saved_dirty, saved_lazy = set(s.dirty), set(s.lazy)
        s.lazy = set()
        # backlog chunking must stay inside what this call compiles — a
        # bigger batch mid-run would be a fresh shape = a synchronous
        # multi-second compile on the event loop
        self._event_bucket_cap = max(self._bucket(ec) for ec in event_counts)
        # Resident state first: a tick that finds no device copy uploads one
        # and absorbs the dirty rows, which turned the grid's first entry
        # into a fast tick and left that refresh shape — the smallest, the
        # one a server hits first — to compile on the event loop mid-run.
        self._dev = self._upload_device_state()
        for dc in group_counts:
            if dc > s.capacity:
                continue
            for ec in event_counts:
                s.dirty = set(range(dc))
                acks = [(0, 0, -1, now)] * ec
                self._tick_batched(acks, now)
        # fast path (zero dirty rows): one compile per event bucket
        for ec in event_counts:
            s.dirty = set()
            self._tick_batched([(0, 0, -1, now)] * ec, now)
        # vote tally: one compile for the [G, P] shape (fires during
        # bring-up election storms otherwise)
        import jax.numpy as jnp
        _shared_tally()(
            jnp.asarray(s.vote_grants), jnp.asarray(s.vote_rejects),
            jnp.asarray(s.conf_cur), jnp.asarray(s.conf_old),
            jnp.asarray(s.priority), jnp.asarray(s.self_priority))
        s.dirty, s.lazy = saved_dirty, saved_lazy
        self._dev = None  # drop the prewarm device copy; re-upload on use

    def _upload_device_state(self):
        import jax.numpy as jnp
        from ratis_tpu.ops import quorum as q
        s = self.state
        dev = q.DeviceState(
            jnp.asarray(s.match_index), jnp.asarray(s.last_ack_ms),
            jnp.asarray(s.self_mask), jnp.asarray(s.conf_cur),
            jnp.asarray(s.conf_old), jnp.asarray(s.role),
            jnp.asarray(s.flush_index), jnp.asarray(s.commit_index),
            jnp.asarray(s.first_leader_index),
            jnp.asarray(s.election_deadline_ms))
        if self.mesh is not None:
            from ratis_tpu.parallel.mesh import shard_device_state
            dev = shard_device_state(self.mesh, dev)
        return dev

    @staticmethod
    def _pow2(n: int) -> int:
        return 1 << (max(1, n) - 1).bit_length()

    @staticmethod
    def _bucket(n: int) -> int:
        """Pad size for event/dirty batches: 64 * 4^k.  Coarser than plain
        pow2 so the jit compiles O(few) shape buckets instead of one per
        power of two — padding costs bytes, recompiles cost tens of
        milliseconds (CPU) to seconds (TPU)."""
        b = 64
        while b < n:
            b *= 4
        return b

    def _pack_tick(self, acks, updates: dict) -> np.ndarray:
        """Pack acks + slot updates into the [7, E] fast-tick array (column
        layout documented at ops.quorum.engine_step_resident_fast)."""
        n = len(acks) + len(updates)
        ecap = self._bucket(n)
        self._last_event_rows, self._last_event_cap = n, ecap
        evp = np.full((7, ecap), _PACK_SENTINEL, np.int32)
        evp[0] = 0
        evp[1] = 0
        evp[4] = 0
        if acks:
            a = np.asarray(acks, np.int32)  # [E, 4]
            k = len(acks)
            evp[:4, :k] = a.T
            evp[4, :k] = 1
        if updates:
            k = len(acks)
            for i, (slot, (flush, deadline)) in enumerate(updates.items()):
                evp[0, k + i] = slot
                evp[5, k + i] = flush
                evp[6, k + i] = deadline
        return evp

    def _pack_tick_sliced(self, acks, updates: dict) -> np.ndarray:
        """Slice-routed fast-tick packing: [7, S, E] with SLICE-LOCAL row
        indices (ops.quorum.engine_step_resident_fast_sliced).  Each mesh
        device receives only its slice's [7, 1, E] plane; E is the bucket
        of the FULLEST slice, so a balanced intake ships ~1/S of the flat
        pack's columns per device."""
        s = self.state
        n_slices, rows = s.n_slices, s.slice_rows
        na = len(acks)
        a = np.asarray(acks, np.int32).reshape(na, 4)  # slot,peer,match,t
        asl = a[:, 0] // rows
        ack_counts = np.bincount(asl, minlength=n_slices)
        counts = ack_counts.copy()
        for slot in updates:
            counts[slot // rows] += 1
        n = int(counts.max()) if n_slices else 0
        ecap = self._bucket(n)
        self._last_event_rows, self._last_event_cap = n, ecap
        evp = np.full((7, n_slices, ecap), _PACK_SENTINEL, np.int32)
        evp[0] = 0
        evp[1] = 0
        evp[4] = 0
        if na:
            order = np.argsort(asl, kind="stable")
            srt, ssl = a[order], asl[order]
            starts = np.concatenate(
                ([0], np.cumsum(ack_counts)[:-1])).astype(np.int64)
            col = np.arange(na) - starts[ssl]
            evp[0, ssl, col] = srt[:, 0] % rows
            evp[1, ssl, col] = srt[:, 1]
            evp[2, ssl, col] = srt[:, 2]
            evp[3, ssl, col] = srt[:, 3]
            evp[4, ssl, col] = 1
        cur = ack_counts.copy()
        for slot, (flush, deadline) in updates.items():
            sl = slot // rows
            c = int(cur[sl])
            cur[sl] += 1
            evp[0, sl, c] = slot % rows
            evp[5, sl, c] = flush
            evp[6, sl, c] = deadline
        return evp

    # Hard ceiling on one dispatch's event bucket (64 * 4^4).  A backlog
    # tick must NEVER exceed the largest COMPILED bucket: the next bucket
    # would be a brand-new jit shape, and that compile (measured minutes
    # on the CPU backend at E=65536, 12.9s at E=8192->16384) lands
    # synchronously on the event loop mid-run.  Oversized batches are
    # processed as bounded-shape chunks instead; prewarm() lowers the
    # effective cap to the largest bucket it actually compiled.
    _MAX_EVENT_BUCKET = 16384

    def _tick_batched(self, acks, now: int) -> list[tuple[int, str, int]]:
        cap = min(self._MAX_EVENT_BUCKET, self._event_bucket_cap)
        if len(acks) + len(self._slot_updates) <= cap:
            return self._tick_batched_pass(acks, now)
        # Backlog over the largest compiled bucket (at 10k groups a server
        # a heartbeat round's re-arms and acks alone): run bounded chunks
        # through the same kernels.  Only the last chunk judges deadlines
        # and staleness, once every chunk's events are on the device: an
        # earlier one would fire the stale deadline of a follower whose
        # re-arm waits in a later chunk (and judge a leader by acks not yet
        # applied).  Duplicate commit events self-suppress in
        # _collect_changed (device value vs mirror), so the chunk merge is
        # a plain concatenation.
        changed: list[tuple[int, str, int]] = []
        updates_all, self._slot_updates = self._slot_updates, {}
        idx = 0
        last = False
        while not last:
            chunk = acks[idx:idx + cap]
            idx += cap
            room = cap - len(chunk)
            upd: dict[int, list] = {}
            while room > 0 and updates_all:
                k, v = updates_all.popitem()
                upd[k] = v
                room -= 1
            self._slot_updates = upd
            last = idx >= len(acks) and not updates_all
            # (the judging switch rides ``now``: a traced run wraps
            # _tick_batched_pass as (acks, now))
            changed.extend(self._tick_batched_pass(
                chunk, now if last else _JUDGE_NOTHING_NOW))
        return changed

    def _tick_batched_pass(self, acks, now: int
                           ) -> list[tuple[int, str, int]]:
        """``now`` ``_JUDGE_NOTHING_NOW``: apply the events and the commit
        math, fire no election timeout and no staleness (a ``now`` before
        every deadline, and a leadership timeout no gap exceeds)."""
        # dispatch-latency timer: host -> XLA -> host wall for this sweep
        # (pack + upload + kernel + output download), recorded even on an
        # exception path so a wedged backend shows up in the p99
        with self._m.dispatch_timer.time():
            self._m.ack_batch.update(len(acks))
            if not TRACER.enabled:
                return self._tick_batched_dispatch(acks, now)
            # engine.dispatch host-path span (process-level; every dispatch
            # of a session: one costs milliseconds and the sweep gate keeps
            # them rare), tag = packed event count.  Its four parts tile
            # it: engine.pack, engine.launch (uploads + the step call
            # returning), engine.fetch (the outputs on the host: kernel +
            # device->host), engine.collect.
            tiles = Tiles(TRACER, STAGE_ENGINE)
            try:
                return self._tick_batched_dispatch(acks, now, tiles.part)
            finally:
                tiles.close(tag=len(acks))

    def _leadership_timeout_for(self, now: int) -> int:
        return (_NO_TIMEOUT_MS if now == _JUDGE_NOTHING_NOW
                else self.leadership_timeout_ms)

    def _tick_batched_dispatch(self, acks, now: int, part=_no_part
                               ) -> list[tuple[int, str, int]]:
        """``part(stage)``: the dispatch enters that part of its span."""
        import jax.numpy as jnp

        s = self.state
        self._m.batched_dispatches.inc()

        if self._dev is None or self._dev.match_index.shape != s.match_index.shape:
            # first batched tick / capacity regrow / epoch rebase: one full
            # upload, after which only dirty rows and events travel.
            part(STAGE_LAUNCH)
            self._dev = self._upload_device_state()
            s.dirty.clear()
            s.lazy.clear()
            self._slot_updates.clear()  # the full upload carried them

        part(STAGE_PACK)
        if not s.dirty and not s.lazy:
            # Fast path (the steady state under load): two packed uploads,
            # one packed download — profiling showed the unpacked step's 18
            # small transfers costing more than the quorum math itself.
            # Flush advances and deadline re-arms travel as packed updates
            # alongside the acks, so routine traffic never needs a refresh.
            self._m.fast_ticks.inc()
            step = self._fast_kernel()
            updates, self._slot_updates = self._slot_updates, {}
            # mesh: slice-routed [7, S, E] planes for the sliced kernel;
            # single device: the flat [7, E] pack
            ev = (self._pack_tick_sliced(acks, updates)
                  if self.mesh is not None
                  else self._pack_tick(acks, updates))
            part(STAGE_LAUNCH)
            res = step(self._dev, jnp.asarray(ev),
                       jnp.asarray(np.array(
                           [now, self._leadership_timeout_for(now)],
                           np.int32)))
            self._dev = res.state
            part(STAGE_FETCH)
            out = np.asarray(res.out)
            part(STAGE_COLLECT)
            return self._collect_changed(out[0], out[1] != 0, out[2] != 0,
                                         out[3] != 0)

        # dirty-row refresh: O(changed slots) host->device.  Slots with
        # queued packed updates fold in here — the mirror already holds
        # their values, so the row refresh carries them.
        self._m.refresh_ticks.inc()
        dirty = sorted(s.dirty | s.lazy | set(self._slot_updates))
        self._slot_updates.clear()
        s.dirty.clear()
        s.lazy.clear()
        self._m.refresh_rows.inc(len(dirty))
        dcap = self._bucket(len(dirty))
        # padded entries point one past the end -> dropped by the scatter
        rf_idx = np.full(dcap, s.capacity, np.int32)
        rf_idx[:len(dirty)] = dirty
        gi = np.minimum(rf_idx, s.capacity - 1)  # in-range gather indices

        # packed ack events: O(events) host->device
        ecap = self._bucket(len(acks))
        self._last_event_rows, self._last_event_cap = len(acks), ecap
        evg = np.zeros(ecap, np.int32)
        evp = np.zeros(ecap, np.int32)
        evm = np.zeros(ecap, np.int32)
        evt = np.zeros(ecap, np.int32)
        evv = np.zeros(ecap, bool)
        for i, (slot, peer, match, t) in enumerate(acks):
            evg[i], evp[i], evm[i], evt[i], evv[i] = slot, peer, match, t, True

        step = self._kernels()
        part(STAGE_LAUNCH)
        res = step(
            self._dev,
            jnp.asarray(rf_idx), jnp.asarray(s.match_index[gi]),
            jnp.asarray(s.last_ack_ms[gi]), jnp.asarray(s.self_mask[gi]),
            jnp.asarray(s.conf_cur[gi]), jnp.asarray(s.conf_old[gi]),
            jnp.asarray(s.role[gi]), jnp.asarray(s.flush_index[gi]),
            jnp.asarray(s.commit_index[gi]),
            jnp.asarray(s.first_leader_index[gi]),
            jnp.asarray(s.election_deadline_ms[gi]),
            jnp.asarray(evg), jnp.asarray(evp), jnp.asarray(evm),
            jnp.asarray(evt), jnp.asarray(evv),
            jnp.int32(now), jnp.int32(self._leadership_timeout_for(now)))
        self._dev = res.state
        part(STAGE_FETCH)

        # downloads: only the [G] outputs (masks + commit values), never the
        # [G, P] state
        outs = (np.asarray(res.new_commit), np.asarray(res.commit_changed),
                np.asarray(res.timeouts), np.asarray(res.stale))
        part(STAGE_COLLECT)
        return self._collect_changed(*outs)

    def _collect_changed(self, new_commit_np, commit_changed_np, timeouts_np,
                         stale_np) -> list[tuple[int, str, int]]:
        s = self.state
        changed: list[tuple[int, str, int]] = []
        for slot in np.nonzero(commit_changed_np)[0]:
            i = int(slot)
            if i in s.active:
                v = int(new_commit_np[i])
                # The inline ack path usually advanced the mirror (and fired
                # the listener) before this tick; the device event is then a
                # duplicate and must not re-fire.  Fire only when the device
                # is genuinely ahead (e.g. a dirty-row refresh carried state
                # the inline path never saw).
                if v > int(s.commit_index[i]):
                    s.commit_index[i] = v
                    changed.append((i, "commit", v))
        for slot in np.nonzero(timeouts_np)[0]:
            i = int(slot)
            # the kernel disarmed the deadline on device; mirror that here
            # (direct write, NOT mark_dirty: host and device already agree)
            if i in s.active:
                s.election_deadline_ms[i] = NO_DEADLINE
                changed.append((i, "timeout", 0))
        for slot in np.nonzero(stale_np)[0]:
            i = int(slot)
            if i in s.active:
                changed.append((i, "stale", 0))
        return changed
