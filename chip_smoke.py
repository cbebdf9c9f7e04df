#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the served path still starts on
the chip.

One process (a chip belongs to one process) brings up BASELINE config 3's
cluster shape — 5 ``RaftServer``s x 10,240 groups = 51,200 divisions over real
localhost TCP, every server's quorum engine resident on the TPU as the full
``[16384, 8]`` batch — and drives it through the public surface only
(``RaftServer``, ``RaftClient``, the TCP transport factory; ``BenchCluster``
merely assembles the servers):

1. device first: ``jax.default_backend()`` must be ``tpu``, else exit non-zero
   naming the platform found, before anything is built;
2. prewarm (the engine's compile grid + the ledger pass), timed, with the
   persistent compile cache reported cold or warm;
3. bring-up: a first wave elects by ordinary randomized timeout (the device's
   ``election_timeout`` output and the batched ``tally_votes`` decide), the
   rest by the appointed-leader bootstrap;
4. requests: one acknowledged write to every group, a LINEARIZABLE read on
   every tenth group, >= 3 of 5 replicas' state machines agreeing, admin
   leadership transfers followed by a write and a read-back through the new
   leader.  Any unacknowledged or mismatching operation fails the run;
5. the device's own evidence, per server engine: tick loop alive, /health ok,
   batched/fast/refresh dispatch counters > 0, the resident ``DeviceState`` on
   the expected platform, the host mirror (advanced by ops/reference.py) equal
   to the device state (advanced by ops/quorum.py) on every active row, a
   ledger pass, zero compilations since prewarm, device memory;
6. with >= 4 devices, the same on a mesh-sharded engine (5 x 1,024 groups,
   ``mesh-devices=4``, the DeviceState on 4 distinct devices).

Exit 0 only if every check held.  Then everything that was seen goes out as
one ``RESULT {...}`` line (and to ``chiprun_out/chip_smoke.json``), and the
last line of stdout is the verdict alone, one JSON object with exactly these
keys: ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
the device as JAX reports it.  A failed check raises: neither line.

``--rehearse-cpu`` rehearses the control flow on the CPU backend at a tiny
size (5 x 64 groups; 4 virtual devices so the mesh phase runs too).  It is
explicit, printed in the result, and never a device record.
"""

from __future__ import annotations

import argparse
import asyncio
import faulthandler
import gc
import json
import os
import sys
import time
import traceback

PEERS = 5                   # the north star / BASELINE config 3: 5-peer groups
GROUPS = 10_240             # x 5 peers = 51,200 divisions in this process
ENGINE_MAX_GROUPS = 16_384  # QuorumEngine._bucket(10240): the resident batch
ENGINE_MAX_PEERS = 8
ELECTED = 64                # first wave: randomized-timeout elections
TRANSFERRED = 64            # admin leadership transfers
READ_EVERY = 10             # LINEARIZABLE read on every tenth group
MIN_REPLICAS = 3            # of 5 must hold the acknowledged value
CLIENT_CONCURRENCY = 64      # requests in flight: a bring-up, not a load
MESH_DEVICES = 4
MESH_GROUPS = 1_024
REHEARSAL_GROUPS = 64
DEADLINE_S = 1150           # the contract allows 1200 s, compilation included

REDUCED = [
    "state machine: CounterStateMachine, not config 3's FileStore + "
    "DataStream",
    "log: MEMORY (raft.server.log.use.memory=true), nothing fsynced",
    "traffic: one write per group, reads on every tenth, 64 transfers — a "
    "bring-up, not a load",
]


def check(ok, what: str) -> None:
    """A failed check fails the run (``assert`` would vanish under -O)."""
    if not ok:
        raise AssertionError(what)


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


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


def memory_block() -> list:
    """Per device: what ``memory_stats()`` gives (None on backends that
    keep no such record, e.g. the CPU)."""
    import jax
    out = []
    for d in jax.devices():
        ms = d.memory_stats()
        out.append(None if ms is None else {
            k: ms[k] for k in ("bytes_in_use", "peak_bytes_in_use",
                               "bytes_limit") if k in ms})
    return out


async def drained_agreement(engine, what: str) -> dict:
    """Host mirror == device state on every active slot, taken once the
    engine has drained its ack ring and slot updates through a dispatch.

    The mirror advances inline by the scalar ops/reference.py, the device by
    ops/quorum.py from the same events: equal rows are this system's "same
    answers as the plain reference".  One dirty row forces the dispatch
    past the sweep gate; if the tick yielded to listener callbacks, new
    intake may have landed behind it — then the rings are not empty and the
    pass is repeated.  Between the tick returning and the comparison there
    is no await, so nothing can move in between."""
    import numpy as np
    s = engine.state
    for attempt in range(1, 201):
        s.mark_dirty(next(iter(s.active)))
        await engine.tick()
        if not (engine._ack_ring or engine._slot_updates or s.dirty
                or engine._dev is None):
            break
        await asyncio.sleep(0.01)
    else:
        raise AssertionError(f"{what}: engine never drained its intake")
    active = np.fromiter(s.active, np.int64)
    dev = engine._dev
    out = {"active_slots": int(active.size), "passes": attempt}
    for field in ("commit_index", "flush_index", "match_index", "role"):
        d = np.asarray(getattr(dev, field))
        h = getattr(s, field)
        differ = np.any((d[active] != h[active]).reshape(active.size, -1),
                        axis=1)
        out[f"{field}_rows_differing"] = int(differ.sum())
        if differ.any():
            out[f"{field}_sample"] = [
                {"slot": int(r), "host": h[r].tolist(),
                 "device": d[r].tolist()} for r in active[differ][:4]]
    return out


async def serve_phase(name: str, *, groups: int, capacity: int,
                      mesh_devices: int, platform: str,
                      compiles: CompileLog, close: bool) -> dict:
    """Bring one cluster up, serve the request script, take the evidence,
    and (``close``) shut it down.  Every check raises; the returned dict is
    what was seen."""
    from ratis_tpu.client import RaftClient
    from ratis_tpu.conf import RaftServerConfigKeys
    from ratis_tpu.engine.state import ROLE_LEADER
    from ratis_tpu.retry.policies import RetryPolicies
    from ratis_tpu.tools.bench_cluster import BenchCluster, bench_properties

    elected = min(ELECTED, groups // 4)
    transferred = min(TRANSFERRED, groups // 4)
    out: dict = {"peers": PEERS, "groups": groups,
                 "divisions": PEERS * groups, "transport": "tcp",
                 "mesh_devices": mesh_devices,
                 "elected_groups": elected, "transferred_groups": transferred}
    t_phase = time.monotonic()

    # Election timeouts at twice the harness's density-scaled values: on one
    # saturated Python loop a follower that misses its leader for a whole
    # timeout starts an election nobody asked for, and at this density such
    # elections feed on themselves (the shape is metastable, see PERF.md).
    # This run proves the path, it does not time elections.
    rpc = RaftServerConfigKeys.Rpc
    scaled = bench_properties(True, groups, num_servers=PEERS)
    extra_props = {
        rpc.TIMEOUT_MIN_KEY: f"{int(2 * rpc.timeout_min(scaled).to_ms())}ms",
        rpc.TIMEOUT_MAX_KEY: f"{int(2 * rpc.timeout_max(scaled).to_ms())}ms",
        RaftServerConfigKeys.Read.OPTION_KEY:
            RaftServerConfigKeys.Read.Option.LINEARIZABLE,
        RaftServerConfigKeys.Engine.MAX_GROUPS_KEY: capacity,
        RaftServerConfigKeys.Engine.MAX_PEERS_KEY: ENGINE_MAX_PEERS}
    if mesh_devices:
        # shard the resident engine state over the group axis of an
        # n-device mesh (parallel/mesh.py): each device owns one contiguous
        # slice of the group batch; capacity is auto-padded to the mesh
        extra_props[RaftServerConfigKeys.Engine.MESH_DEVICES_KEY] = \
            mesh_devices
    cluster = BenchCluster(groups, num_servers=PEERS, batched=True,
                           transport="tcp", extra_props=extra_props)
    out["properties"] = dict(sorted(cluster.properties.items()))
    engines = [s.engine for s in cluster.servers]
    out["engine_state_shape"] = [engines[0].state.capacity,
                                 engines[0].state.max_peers]

    # ---- prewarm: every shape the run can dispatch, and the ledger pass
    before = compiles.mark()
    t0 = time.monotonic()
    cluster.prewarm()
    engines[0].ledger.sample()
    after = compiles.mark()
    programs, hits, misses = (a - b for a, b in zip(after, before))
    out["prewarm"] = {
        "seconds": round(time.monotonic() - t0, 2),
        "programs": programs, "cache_hits": hits, "cache_misses": misses,
        "cache": ("warm" if hits and not misses else
                  "cold" if misses and not hits else
                  "off" if not (hits or misses) else "partial")}
    say(f"{name}: prewarm {out['prewarm']}")
    # prewarm ran real dispatches on engine 0: evidence counts from here
    base = [dict(e.metrics.items()) for e in engines]

    # ---- bring-up: first wave by election, the rest appointed
    gc.disable()  # nothing built here is garbage (see run_bench)
    try:
        await cluster.start(elect_first=elected)
        cluster.servers[0].seal_heap()
    finally:
        gc.enable()
    out["bring_up_s"] = round(cluster.election_convergence_s, 2)
    say(f"{name}: {groups} groups up in {out['bring_up_s']} s")

    def leader_of(i: int):
        gid = cluster.groups[i].group_id
        leaders = [s for s in cluster.servers
                   if s.divisions[gid].is_leader()]
        check(len(leaders) == 1,
              f"{name}: group {i} has {len(leaders)} leaders")
        return leaders[0]

    # A client's retry budget has to outlast one re-election of its group
    # (the default 50 x 100 ms gives up first at this density's timeouts).
    election_s = rpc.timeout_max(cluster.properties).seconds
    retry = RetryPolicies.retry_up_to_maximum_count_with_fixed_sleep(
        50 + int(2 * election_s / 0.2), "200ms")
    transport = cluster.factory.new_client_transport(cluster.properties)
    clients = [RaftClient.builder().set_raft_group(g)
               .set_transport(transport).set_retry_policy(retry)
               .set_properties(cluster.properties).build()
               for g in cluster.groups]
    acked = [0] * groups
    sem = asyncio.Semaphore(CLIENT_CONCURRENCY)

    async def write(i: int) -> None:
        async with sem:
            reply = await clients[i].io().send(b"INCREMENT")
        check(reply.success, f"{name}: write to group {i} failed: "
                             f"{reply.exception!r}")
        acked[i] += 1
        check(int(reply.message.content) == acked[i],
              f"{name}: group {i} write returned {reply.message.content!r}"
              f", {acked[i]} acknowledged")

    async def read(i: int) -> None:
        async with sem:
            reply = await clients[i].io().send_read_only(b"GET")
        check(reply.success, f"{name}: read of group {i} failed: "
                             f"{reply.exception!r}")
        check(int(reply.message.content) == acked[i],
              f"{name}: group {i} read {reply.message.content!r}, "
              f"{acked[i]} increments acknowledged")

    # ---- one acknowledged write to every group
    t0 = time.monotonic()
    await asyncio.gather(*(write(i) for i in range(groups)))
    out["writes"] = {"acknowledged": groups,
                     "seconds": round(time.monotonic() - t0, 2)}
    say(f"{name}: writes {out['writes']}")

    # ---- LINEARIZABLE read on every tenth group, exact
    sampled = list(range(0, groups, READ_EVERY))
    t0 = time.monotonic()
    await asyncio.gather(*(read(i) for i in sampled))
    out["reads"] = {"linearizable_exact": len(sampled),
                    "seconds": round(time.monotonic() - t0, 2)}
    say(f"{name}: reads {out['reads']}")

    # ---- on those groups, >= 3 of the 5 replicas hold the value
    # (followers apply once the next append or heartbeat carries the commit)
    t0 = time.monotonic()
    deadline = t0 + 60 + 4 * cluster.servers[0].heartbeat_interval_s
    pending = set(sampled)
    while pending and time.monotonic() < deadline:
        for i in list(pending):
            gid = cluster.groups[i].group_id
            holding = sum(s.divisions[gid].state_machine.counter == acked[i]
                          for s in cluster.servers)
            if holding >= MIN_REPLICAS:
                pending.discard(i)
        if pending:
            await asyncio.sleep(0.1)
    check(not pending, f"{name}: {len(pending)} groups never had "
                       f"{MIN_REPLICAS} replicas holding the acknowledged "
                       f"value, e.g. group {min(pending, default=None)}")
    out["replica_agreement"] = {"groups": len(sampled),
                                "min_replicas": MIN_REPLICAS,
                                "seconds": round(time.monotonic() - t0, 2)}

    # ---- admin leadership transfer, then a write and a read-back through
    # the new leader
    tsem = asyncio.Semaphore(16)

    async def transfer(k: int, i: int) -> None:
        gid = cluster.groups[i].group_id
        old = cluster.servers.index(leader_of(i))
        target = cluster.servers[(old + 1 + k % (PEERS - 1)) % PEERS]
        async with tsem:
            reply = await clients[i].admin().transfer_leadership(
                target.peer_id, timeout_ms=60_000.0)
        check(reply.success, f"{name}: transfer of group {i} to "
                             f"{target.peer_id} failed: {reply.exception!r}")
        check(leader_of(i) is target,
              f"{name}: group {i} not led by {target.peer_id} after transfer")
        await write(i)
        await read(i)

    t0 = time.monotonic()
    moved = list(range(elected, elected + transferred))
    await asyncio.gather(*(transfer(k, i) for k, i in enumerate(moved)))
    out["transfers"] = {"ok": len(moved), "write_and_readback_ok": len(moved),
                        "seconds": round(time.monotonic() - t0, 2)}
    say(f"{name}: transfers {out['transfers']}")

    # ---- the leaders that can only have come through the batched vote
    # tally: the timeout-elected wave and the transfer targets each won an
    # election (appointed leaders never ran one), with tally_batched true
    voted = [*range(elected), *moved]
    for i in voted:
        lead = leader_of(i)
        d = lead.divisions[cluster.groups[i].group_id]
        check(lead.engine.tally_batched,
              f"{name}: {lead.peer_id} tallies votes off the device")
        check(d.election_metrics.election_count.count >= 1,
              f"{name}: group {i}'s leader {lead.peer_id} never ran an "
              f"election")
    out["voted_leaders"] = len(voted)
    # every other election is one nobody asked for (a follower that missed
    # its leader for a whole timeout on a saturated loop): printed, since a
    # storm of them is the host runtime's known failure at this density
    out["elections_run"] = sum(
        d.election_metrics.election_count.count
        for s in cluster.servers for d in s.divisions.values())
    out["leaders_per_server"] = [
        sum(d.is_leader() for d in s.divisions.values())
        for s in cluster.servers]
    check(sum(out["leaders_per_server"]) == groups,
          f"{name}: {sum(out['leaders_per_server'])} leaders for {groups} "
          f"groups")

    # ---- the device's own evidence, per server engine
    out["engines"] = []
    for srv, eng, b in zip(cluster.servers, engines, base):
        who = f"{name}: engine {srv.peer_id}"
        ev: dict = {"server": str(srv.peer_id)}
        counters = {k: v - b.get(k, 0) for k, v in eng.metrics.items()}
        ev["counters_since_prewarm"] = counters
        for k in ("batched_dispatches", "fast_ticks", "refresh_ticks"):
            check(counters[k] > 0, f"{who}: {k} = {counters[k]}")
        check(eng.tally_batched, f"{who}: tally_batched is false")
        check(eng.tick_alive and eng.failure is None,
              f"{who}: tick loop dead: {eng.failure!r}")
        check(eng._dev is not None, f"{who}: no resident DeviceState")
        placed = {f: sorted(str(d) for d in a.devices())
                  for f, a in zip(eng._dev._fields, eng._dev)}
        for f, a in zip(eng._dev._fields, eng._dev):
            check(all(d.platform == platform for d in a.devices()),
                  f"{who}: DeviceState.{f} on {placed[f]}, not {platform}")
        ev["device_state_on"] = sorted({d for ds in placed.values()
                                        for d in ds})
        ev["device_state_logical_bytes"] = sum(a.nbytes for a in eng._dev)
        if mesh_devices:
            shards = {sh.device for sh in
                      eng._dev.match_index.addressable_shards}
            check(len(shards) == mesh_devices,
                  f"{who}: DeviceState on {len(shards)} devices, not "
                  f"{mesh_devices}")
            ev["mesh_shard_devices"] = len(shards)
        ev["agreement"] = await drained_agreement(eng, who)
        for f in ("commit_index", "flush_index", "match_index", "role"):
            check(ev["agreement"][f"{f}_rows_differing"] == 0,
                  f"{who}: host mirror != device state: {ev['agreement']}")
        sample = eng.ledger.sample()
        check(sample.leading == int((eng.state.role == ROLE_LEADER).sum()),
              f"{who}: ledger counts {sample.leading} leaders")
        ev["ledger"] = {"leading": sample.leading,
                        "gap_total": sample.gap_total,
                        "fetch_ms": sample.fetch_ms}
        health = srv.health_info()
        check(health["status"] == "ok", f"{who}: /health {health}")
        ev["health"] = health["status"]
        out["engines"].append(ev)
    check(sum(e["ledger"]["leading"] for e in out["engines"]) == groups,
          f"{name}: ledgers count "
          f"{sum(e['ledger']['leading'] for e in out['engines'])} leaders "
          f"for {groups} groups")
    out["compiles_after_prewarm"] = compiles.mark()[0] - after[0]
    check(out["compiles_after_prewarm"] == 0,
          f"{name}: programs compiled after prewarm: "
          f"{compiles.names[after[0]:]}")
    out["device_memory"] = memory_block()
    say(f"{name}: evidence ok on {len(engines)} engines; memory "
        f"{out['device_memory'][0]}")

    if close:
        t0 = time.monotonic()
        await transport.close()
        await cluster.close()
        out["close_s"] = round(time.monotonic() - t0, 2)
        for eng in engines:
            check(eng.failure is None,
                  f"{name}: engine failed: {eng.failure!r}")
    out["seconds"] = round(time.monotonic() - t_phase, 2)
    return out


async def smoke(args, device: dict, compiles: CompileLog) -> dict:
    platform = device["platform"]
    rehearsal = platform == "cpu"
    groups = args.groups or (REHEARSAL_GROUPS if rehearsal else GROUPS)
    reduced = list(REDUCED)
    if groups != GROUPS:
        reduced.append(f"hosted groups cut from {GROUPS} to {groups} "
                       f"(peers, transport and engine capacity kept)")
    # returned only if every check below held
    result: dict = {"ok": True, "device": device,
                    "rehearsal": "cpu" if rehearsal else None,
                    "reduced": reduced}
    # The mesh phase goes first and is closed properly; the served phase
    # goes last and is NOT unwound — closing 51,200 divisions takes longer
    # than everything before it (over 3 min on the CPU), and the process
    # ends right after the result.
    if device["count"] >= MESH_DEVICES:
        mesh_groups = REHEARSAL_GROUPS if rehearsal else MESH_GROUPS
        # capacity 4x the groups: slots are pinned to the slice their group
        # id hashes to, and an uneven hash overflows a slice sized exactly
        result["mesh"] = await serve_phase(
            "mesh", groups=mesh_groups, capacity=4 * mesh_groups,
            mesh_devices=MESH_DEVICES, platform=platform, compiles=compiles,
            close=True)
    else:
        result["mesh"] = f"not run ({device['count']} device)"
    result["served"] = await serve_phase(
        "served", groups=groups, capacity=ENGINE_MAX_GROUPS, mesh_devices=0,
        platform=platform, compiles=compiles, close=False)
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="rehearse on the CPU backend at a tiny size "
                         "(never a device record)")
    ap.add_argument("--groups", type=int, default=0,
                    help=f"cut hosted groups below {GROUPS} (printed under "
                         f"'reduced')")
    ap.add_argument("--out", default=os.path.join("chiprun_out",
                                                  "chip_smoke.json"))
    args = ap.parse_args(argv)
    # a hang (a wedged device call, a stuck bring-up) must end inside the
    # contract's time limit, with every thread's stack on stderr
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)

    from ratis_tpu.util.jaxenv import (pin_cpu, place_compile_cache,
                                       require_backend)
    if args.rehearse_cpu:
        pin_cpu(virtual_devices=MESH_DEVICES)
    expected = "cpu" if args.rehearse_cpu else "tpu"
    try:
        device = require_backend(expected)
    except RuntimeError as e:
        sys.exit(f"chip_smoke: {e}: no accelerator, nothing built, no "
                 f"result")
    import jax
    import jaxlib
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:
        libtpu = None
    say(f"device {device} jax {jax.__version__} jaxlib "
        f"{jaxlib.__version__} libtpu {libtpu}")

    cache_dir = place_compile_cache()
    compiles = CompileLog()
    loop = asyncio.new_event_loop()
    try:
        result = loop.run_until_complete(smoke(args, device, compiles))
    except BaseException:
        # a failed check: the traceback, a non-zero exit, no result line —
        # and no unwinding of 51,200 divisions first
        traceback.print_exc()
        sys.stderr.flush()
        os._exit(1)
    result["versions"] = {"jax": jax.__version__,
                          "jaxlib": jaxlib.__version__, "libtpu": libtpu,
                          "python": sys.version.split()[0]}
    result["compile_cache_dir"] = cache_dir
    try:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(result, f, indent=1)
    except OSError as e:
        say(f"could not write {args.out}: {e}")
    print("RESULT " + json.dumps(result, separators=(",", ":")), flush=True)
    # the last line is the verdict and nothing else
    print(json.dumps({"ok": True, "device": device}), flush=True)
    # every check has held and the result is out: end here, without
    # unwinding the served cluster
    os._exit(0)


if __name__ == "__main__":
    main()
