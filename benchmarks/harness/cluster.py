"""Assembly of the system under test: ``peers`` RaftServers in this process
over real localhost TCP, hosting ``groups`` sibling groups with seeded ids and
appointed, balanced leaders; prewarm of the cell's own bucket grid; the
counters the per-layer readers take deltas of; and the look at the device
state after a drained dispatch.

Copied from what PR 21 proved on the chip (``chip_smoke.py``: CompileLog,
memory_block, drained_agreement; ``ratis_tpu/tools/bench_cluster.py``: the TCP
branch of BenchCluster, prewarm, start, the appointed-leader waves), with
group ids from the seed and leaders spread over the servers.  The originals
stay where they are (PERF.md, Open questions)."""

from __future__ import annotations

import asyncio
import importlib
import os
import random
import resource
import shutil
import socket
import time
import uuid
from typing import Callable, Optional


def raise_nofile() -> tuple[int, int]:
    """Soft RLIMIT_NOFILE up to the hard limit: a durable cell holds one
    open segment file per division plus the sockets."""
    soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
    if soft != hard:
        resource.setrlimit(resource.RLIMIT_NOFILE, (hard, hard))
    return resource.getrlimit(resource.RLIMIT_NOFILE)


def seeded_ids(seed: int, n: int, salt: str) -> list[bytes]:
    """``n`` distinct 16-byte ids from the seed (version-4 UUID layout)."""
    rng = random.Random(f"{salt}:{seed}")
    return [uuid.UUID(int=rng.getrandbits(128), version=4).bytes
            for _ in range(n)]


def load_object(spec: str):
    """``package.module:Name`` -> the object."""
    module, _, name = spec.partition(":")
    return getattr(importlib.import_module(module), name)


def run_storage_dir(checkout: str, config: dict) -> Optional[str]:
    """A run's own storage directory, ``<checkout>/<storage dir>/run-<pid>``:
    runs of one checkout side by side (the tests' rehearsals) never share
    one.  None where the configuration names no storage directory."""
    where = config.get("storage", {}).get("dir")
    return os.path.join(checkout, where, f"run-{os.getpid()}") \
        if where else None


def remove_dead_runs(parent: str) -> None:
    """Storage that runs whose process is gone left under ``parent`` (a run
    ended at its deadline removes nothing)."""
    if not os.path.isdir(parent):
        return
    for name in os.listdir(parent):
        if not name.startswith("run-"):
            continue
        try:
            os.kill(int(name[4:]), 0)
        except ProcessLookupError:
            shutil.rmtree(os.path.join(parent, name), ignore_errors=True)
        except (ValueError, PermissionError):
            pass


def _ephemeral_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class CompileLog:
    """Counts what JAX compiles or loads: every program a jit needs goes
    through one ``backend_compile_duration`` event, served either by the
    compiler (a persistent-cache miss, written back) or by the cache."""

    def __init__(self) -> None:
        import jax
        self.names: list[str] = []
        self.hits = self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, _secs: float, **kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.names.append(str(kw.get("fun_name")))

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self) -> tuple[int, int, int]:
        return len(self.names), self.hits, self.misses


def memory_peak_bytes() -> Optional[int]:
    """Peak bytes in use on the fullest device, as the backend reports it
    (None where it keeps no such record, e.g. the CPU)."""
    import jax
    peaks = [ms["peak_bytes_in_use"] for d in jax.devices()
             if (ms := d.memory_stats()) and "peak_bytes_in_use" in ms]
    return max(peaks) if peaks else None


async def drained_device_state(engine, what: str) -> dict:
    """The engine's device arrays and host mirror, taken once the engine has
    drained its ack ring and slot updates through a dispatch.  The dispatch
    is pulled past the sweep gate by making a sweep due, not by a dirty row:
    a dirty row would turn it into a refresh, which uploads from the mirror
    every row that has an update queued and so hides what the fast steps
    left on the device.  If the tick yielded to listener callbacks, new
    intake may have landed behind it — then the rings are not empty and the
    pass is repeated.  Between the tick returning and the copies there is no
    await, so nothing can move in between."""
    import numpy as np
    s = engine.state
    for attempt in range(1, 201):
        engine._next_sweep_ms = 0
        await engine.tick()
        if not (engine._ack_ring or engine._slot_updates or s.dirty
                or engine._dev is None):
            break
        await asyncio.sleep(0.01)
    else:
        raise RuntimeError(f"{what}: engine never drained its intake")
    dev = engine._dev
    fields = ("match_index", "self_mask", "conf_cur", "role", "flush_index",
              "commit_index")
    return {"passes": attempt,
            "active": np.fromiter(s.active, np.int64),
            "device": {f: np.asarray(getattr(dev, f)) for f in fields},
            "mirror": {f: np.array(getattr(s, f)) for f in fields}}


class Cluster:
    """The deployment a configuration file describes, in this process."""

    def __init__(self, config: dict, seed: int, checkout: str,
                 property_overrides: Optional[dict] = None,
                 sm_factory: Optional[Callable] = None) -> None:
        from ratis_tpu.conf import RaftProperties, RaftServerConfigKeys
        from ratis_tpu.protocol.group import RaftGroup
        from ratis_tpu.protocol.ids import RaftGroupId, RaftPeerId
        from ratis_tpu.protocol.peer import RaftPeer
        from ratis_tpu.server.server import RaftServer
        from ratis_tpu.transport.base import TransportFactory
        import ratis_tpu.transport.tcp  # noqa: F401  (registers TCP)

        self.config = config
        self.peers_n = int(config["peers"])
        self.groups_n = int(config["groups"])
        self.properties = RaftProperties()
        props = dict(config["properties"])
        props.update(property_overrides or {})
        for k, v in props.items():
            self.properties.set(k, str(v))
        self.durable = not RaftServerConfigKeys.Log.use_memory(self.properties)
        self.storage_dir: Optional[str] = None
        if self.durable:
            self.storage_dir = run_storage_dir(checkout, config)
            RaftServerConfigKeys.set_storage_dir(self.properties,
                                                 self.storage_dir)
        self.factory = TransportFactory.get(config["transport"])
        # a peer has one address, unless the configuration asks for a
        # stream server beside it ("datastream": true): then each has a
        # ``datastream_address`` on a port of its own, which is what makes
        # ``RaftServer`` start its stream server and a client find a primary
        streams = bool(config.get("datastream"))
        base = [RaftPeer(RaftPeerId.value_of(f"s{i}"),
                         address=f"127.0.0.1:{_ephemeral_port()}",
                         datastream_address=f"127.0.0.1:{_ephemeral_port()}"
                         if streams else None)
                for i in range(self.peers_n)]
        self.addresses = [(p.id.id, p.address) for p in base]
        self.datastream_addresses = {p.id.id: p.datastream_address
                                     for p in base if p.datastream_address}
        self.group_id_bytes = seeded_ids(seed, self.groups_n, "group")
        # group i's appointee is the voting peer of highest priority: server
        # i mod peers (Division.bootstrap_appointee)
        by_leader = [[p.with_priority(1 if j == lead else 0)
                      for j, p in enumerate(base)]
                     for lead in range(self.peers_n)]
        self.groups = [RaftGroup.value_of(RaftGroupId.value_of(b),
                                          by_leader[i % self.peers_n])
                       for i, b in enumerate(self.group_id_bytes)]
        index_of = {g.group_id: i for i, g in enumerate(self.groups)}
        if sm_factory is None:
            sm_class = load_object(config["state_machine"])

            def sm_factory(_server: int, _group: int):
                return sm_class()

        def registry_for(server: int):
            return lambda gid: sm_factory(server, index_of[gid])

        self.servers = [
            RaftServer(p.id, p.address,
                       state_machine_registry=registry_for(i),
                       properties=self.properties,
                       transport_factory=self.factory, group=self.groups[0])
            for i, p in enumerate(base)]
        self.engines = [s.engine for s in self.servers]
        self.prewarm_s = 0.0
        self.bring_up_s = 0.0

    # ------------------------------------------------------------ bring-up

    def leader_server(self, group: int) -> int:
        return group % self.peers_n

    def prewarm(self) -> None:
        """Compile (or load from the cache) the cell's own bucket grid.  The
        jitted steps are process-wide, so one engine warms every server."""
        t0 = time.monotonic()
        grid = self.config["prewarm"]
        self.engines[0].prewarm(group_counts=grid["group_counts"],
                                event_counts=grid["event_counts"])
        self.engines[0].ledger.sample()
        self.prewarm_s = time.monotonic() - t0

    async def start(self) -> None:
        """Every group up with a ready, appointed leader: no election wave.
        Wave k's leader-ready wait overlaps wave k+1's group-add."""
        t0 = time.monotonic()
        await asyncio.gather(*(s.start() for s in self.servers))
        first = [0]
        await self._appoint(first)
        await self._wait_ready(first, timeout=120.0)
        wave = int(self.config["bring_up_wave"])
        pending: list[int] = []
        for lo in range(1, self.groups_n, wave):
            batch = list(range(lo, min(lo + wave, self.groups_n)))
            await asyncio.gather(*(s.group_add(self.groups[i])
                                   for i in batch for s in self.servers))
            await self._appoint(batch)
            if pending:
                await self._wait_ready(pending)
            pending = batch
        if pending:
            await self._wait_ready(pending)
        self.bring_up_s = time.monotonic() - t0

    async def _appoint(self, batch: list[int]) -> None:
        await asyncio.gather(*(
            self.servers[self.leader_server(i)].bootstrap_division(
                self.groups[i].group_id) for i in batch))

    @staticmethod
    def leads(d) -> bool:
        """``d`` is its group's leader, its startup entry committed."""
        return (d is not None and d.is_leader() and d.leader_ctx is not None
                and d.leader_ctx.leader_ready.done())

    def _ready(self, i: int) -> bool:
        return self.leads(self.servers[self.leader_server(i)].divisions.get(
            self.groups[i].group_id))

    async def _wait_ready(self, batch: list[int],
                          timeout: float = 120.0) -> None:
        deadline = time.monotonic() + timeout
        pending = set(batch)
        while pending and time.monotonic() < deadline:
            pending = {i for i in pending if not self._ready(i)}
            if pending:
                await asyncio.sleep(0.05)
        if pending:
            raise TimeoutError(
                f"{len(pending)}/{len(batch)} groups of this wave have no "
                f"ready appointed leader after {timeout}s")

    async def groups_without_ready_leader(self, grace_s: float) -> list[int]:
        """Groups whose appointed server is not their ready leader, after
        waiting ``grace_s`` at the most: where an election during bring-up
        took a leadership away, the peers' priorities bring it back."""
        deadline = time.monotonic() + grace_s
        while True:
            missing = [i for i in range(self.groups_n) if not self._ready(i)]
            if not missing or time.monotonic() > deadline:
                return missing
            await asyncio.sleep(0.25)

    def seal_heap(self) -> None:
        self.servers[0].seal_heap()

    # ------------------------------------------------------------ readings

    def counters(self) -> dict:
        """Everything the per-layer readers take deltas of, read at one
        instant (no await)."""
        from ratis_tpu.server.log.segmented import LogWorker
        out = {"t": time.monotonic(), "engines": []}
        for e in self.engines:
            timer = e._m.dispatch_timer
            row = {k: e.metrics.get(k, 0)
                   for k in ("batched_dispatches", "fast_ticks",
                             "refresh_ticks", "idle_skips", "ticks",
                             "refresh_rows")}
            row["dispatch_count"] = timer.count
            row["dispatch_total_s"] = timer.mean_s * timer.count
            out["engines"].append(row)
        out["fsyncs"] = sum(w.sync_count
                            for w in LogWorker._instances.values())
        out["elections"] = sum(
            d.election_metrics.election_count.count
            for s in self.servers for d in s.divisions.values())
        out["reads"] = self._read_counters()
        # CPU seconds of the calling thread, which is the servers' loop's
        out["loop_cpu_s"] = time.thread_time()
        return out

    def _read_counters(self) -> Optional[dict]:
        """The read path's own counts, where the program keeps them: every
        division's ``readRequestLatency`` timer (reads served, and their
        seconds from the request's arrival at its division to its reply)
        and every server's batched readIndex scheduler (confirmation sweeps
        fired, group confirmations sent to followers).  None in a program
        that keeps none of them."""
        try:
            timers = [d.metrics.read_timer for s in self.servers
                      for d in s.divisions.values()]
            batches = [s.serving.read_batch for s in self.servers]
            return {
                "requests": sum(t.count for t in timers),
                "total_s": sum(t.mean_s * t.count for t in timers),
                "sweeps": sum(b.sweeps for b in batches if b is not None),
                "confirms_sent": sum(sum(b.confirm_sent.values())
                                     for b in batches if b is not None)}
        except AttributeError:
            return None

    def standing(self, group: int, server: int) -> dict:
        """The group's division on ``server``: its engine slot, term, role,
        whether it leads (``leads``) and its log's last index (-1: empty)."""
        d = self.servers[server].divisions[self.groups[group].group_id]
        ti = d.state.log.get_last_entry_term_index()
        return {"server": server, "slot": d.engine_slot,
                "term": d.state.current_term, "role": d.role.name,
                "leads": self.leads(d),
                "last_index": -1 if ti is None else ti.index}

    def leaders_now(self) -> list[dict]:
        """Each group's ready leader, on whichever server it is (of two
        that lead, the one of the higher term), as ``standing`` gives it;
        the appointee's standing where no server leads the group."""
        out = []
        for i in range(self.groups_n):
            rows = [self.standing(i, s) for s in range(self.peers_n)]
            leading = [r for r in rows if r["leads"]]
            out.append(max(leading, key=lambda r: r["term"]) if leading
                       else rows[self.leader_server(i)])
        return out

    async def leaders_after(self, grace_s: float) -> list[dict]:
        """``leaders_now`` once every group has a ready leader, or after
        ``grace_s`` at the most: an election in flight has its winner."""
        deadline = time.monotonic() + grace_s
        while True:
            leaders = self.leaders_now()
            if all(r["leads"] for r in leaders) \
                    or time.monotonic() > deadline:
                return leaders
            await asyncio.sleep(0.25)

    def replica_values(self, group: int) -> list:
        """What each replica's state machine holds for the group: the
        attribute the configuration names (``replica_state``), for the plain
        reference to judge."""
        gid = self.groups[group].group_id
        attribute = self.config["replica_state"]["attribute"]
        return [getattr(s.divisions[gid].state_machine, attribute)
                for s in self.servers]

    def replica_log_dir(self, server: int, group: int) -> str:
        """``<storage>/<peer>/<group uuid>/current`` — where a durable
        replica's segment files live (RaftStorageDirectory's layout)."""
        return os.path.join(self.storage_dir or "",
                            self.addresses[server][0],
                            str(uuid.UUID(bytes=self.group_id_bytes[group])),
                            "current")


class LagProbe:
    """A coroutine on the servers' own loop that sleeps ``period_s`` and
    records how much later than that it woke: what every handler on the loop
    waits behind."""

    def __init__(self, period_s: float = 0.010) -> None:
        self.period_s = period_s
        self.samples: list[tuple[float, float]] = []  # (woke at, overshoot s)
        self._task: Optional[asyncio.Task] = None

    def start(self) -> None:
        self._task = asyncio.ensure_future(self._run())

    async def _run(self) -> None:
        while True:
            t0 = time.monotonic()
            await asyncio.sleep(self.period_s)
            t1 = time.monotonic()
            self.samples.append((t1, t1 - t0 - self.period_s))

    async def stop(self) -> None:
        if self._task is not None:
            self._task.cancel()
            try:
                await self._task
            except asyncio.CancelledError:
                pass
            self._task = None

    def overshoots_ms(self, t_from: float, t_to: float) -> list[float]:
        return [o * 1e3 for t, o in self.samples if t_from <= t <= t_to]
