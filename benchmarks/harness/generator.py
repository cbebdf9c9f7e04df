"""The load generator: one general generator that reads a traffic file's
parameters.  It runs as a child process of ``benchmarks/run.py`` (spec as one
JSON line on stdin, ``GENREADY`` / ``GENDONE`` lines on stdout), never imports
JAX (the parent holds the chip), and drives the servers through the public
client surface only: one ``RaftClient`` per group over the TCP transport.
What a request *is* comes from a file of its own, ``benchmarks/ops/<op>.py``,
found by the traffic file's ``op``.

Open loop: requests leave on a schedule fixed before the window (whatever the
servers do), and every request is timed from when it was *due*.  Closed loop:
``in_flight`` callers, each sending its next request when the previous one is
acknowledged, timed from send.  In both, the schedule is the same set of gaps
and targets for every seed, in another order."""

from __future__ import annotations

import asyncio
import gc
import importlib.util
import json
import os
import random
import sys
import time
from typing import Optional

CANON_SEED = 20240924  # the one fixed set of gaps every --seed reorders


# ----------------------------------------------------------------- schedules

def balanced_targets(target: dict, groups: int, n: int, rng: random.Random
                     ) -> list[int]:
    """``n`` targets spread over the groups as evenly as whole numbers
    allow, in the seed's order: every seed sends the same multiset of
    targets."""
    if target.get("dist", "uniform") != "uniform":
        raise ValueError(f"unknown target distribution {target['dist']!r}")
    out = [g for g in range(groups) for _ in range(n // groups)]
    out += list(range(n % groups))
    rng.shuffle(out)
    return out


def open_schedule(traffic: dict, groups: int, seconds: float, seed: int
                  ) -> tuple[list[float], list[int]]:
    """Due times (seconds from the window's start) and targets of an open
    loop.  ``rate_per_s * seconds`` requests exactly; exponential gaps drawn
    once from a fixed seed and scaled to fill the window, ordered by
    ``seed``."""
    n = max(1, int(round(float(traffic["rate_per_s"]) * seconds)))
    canon = random.Random(CANON_SEED)
    gaps = [canon.expovariate(1.0) for _ in range(n)]
    scale = seconds / sum(gaps)
    rng = random.Random(f"open:{seed}")
    rng.shuffle(gaps)
    due, t = [], 0.0
    for g in gaps:
        due.append(t)       # the first request is due at 0, the last before
        t += g * scale      # the window closes
    return due, balanced_targets(traffic["target"], groups, n, rng)


def closed_targets(traffic: dict, groups: int, seed: int):
    """Endless targets of a closed loop: pass after pass over a balanced
    multiset of ``groups`` targets, each pass in a new order from the seed."""
    rng = random.Random(f"closed:{seed}")
    while True:
        yield from balanced_targets(traffic["target"], groups, groups, rng)


# ------------------------------------------------------------------- driving

class Recorder:
    """One row per request, in submission order."""

    def __init__(self) -> None:
        self.group: list[int] = []
        self.payload: list[Optional[str]] = []   # what was sent, as ASCII
        self.due: list[float] = []
        self.sent: list[float] = []
        self.acked: list[Optional[float]] = []
        self.answer: list[Optional[str]] = []
        self.error: list[Optional[str]] = []

    def submit(self, group: int, due: float, sent: float) -> int:
        self.group.append(group)
        self.payload.append(None)
        self.due.append(due)
        self.sent.append(sent)
        self.acked.append(None)
        self.answer.append(None)
        self.error.append(None)
        return len(self.group) - 1

    def as_dict(self, t0: float) -> dict:
        return {"group": self.group, "payload": self.payload,
                "due": [round(t - t0, 6) for t in self.due],
                "sent": [round(t - t0, 6) for t in self.sent],
                "acked": [None if t is None else round(t - t0, 6)
                          for t in self.acked],
                "answer": self.answer, "error": self.error}


async def one_request(send, rec: Recorder, row: int) -> None:
    """``send()`` gives what it sends (ASCII) and the awaitable of the
    reply; the reply's content is the answer."""
    rec.payload[row], pending = send()
    try:
        reply = await pending
    except asyncio.CancelledError:
        raise
    except Exception as e:  # the client gave up: recorded, counted as failed
        rec.error[row] = f"{type(e).__name__}: {e}"[:200]
        return
    rec.acked[row] = time.monotonic()
    if reply.success:
        rec.answer[row] = bytes(reply.message.content).decode(
            "ascii", "replace")
    else:
        rec.error[row] = f"reply: {reply.exception!r}"[:200]


async def run_open(senders, due: list[float], targets: list[int],
                   t0: float, drain_s: float) -> Recorder:
    rec = Recorder()
    tasks = []
    for at, g in zip(due, targets):
        delay = t0 + at - time.monotonic()
        if delay > 0:
            await asyncio.sleep(delay)
        row = rec.submit(g, t0 + at, time.monotonic())
        tasks.append(asyncio.ensure_future(one_request(senders[g], rec, row)))
    await _drain(tasks, drain_s)
    return rec


async def run_closed(senders, targets, in_flight: int, t0: float,
                     seconds: float, drain_s: float) -> Recorder:
    rec = Recorder()
    t_end = t0 + seconds
    delay = t0 - time.monotonic()
    if delay > 0:
        await asyncio.sleep(delay)

    async def caller() -> None:
        while True:
            now = time.monotonic()
            if now >= t_end:
                return
            g = next(targets)
            row = rec.submit(g, now, now)
            await one_request(senders[g], rec, row)

    tasks = [asyncio.ensure_future(caller()) for _ in range(in_flight)]
    await asyncio.sleep(max(0.0, t_end - time.monotonic()))
    await _drain(tasks, drain_s)
    return rec


async def _drain(tasks: list, drain_s: float) -> None:
    """Wait for what is still out, ``drain_s`` at the most; what has not
    come by then never came."""
    if not tasks:
        return
    _, pending = await asyncio.wait(tasks, timeout=drain_s)
    for t in pending:
        t.cancel()
    if pending:
        await asyncio.wait(pending, timeout=5.0)


# ------------------------------------------------------------- child process

def load_op(checkout: str, name: str):
    """``benchmarks/ops/<name>.py``: the operation a traffic file names."""
    path = os.path.join(checkout, "benchmarks", "ops", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.ops." + name.replace("-", "_").replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def build_senders(spec: dict):
    """One RaftClient per group on one shared client transport; returns the
    transport and, per group, the sender that the traffic's operation makes
    of that client, and the sender of the warm-up and settle rounds: the
    same one, unless the traffic names a ``round_op`` of its own for them
    (a mix with reads keeps its rounds writes).  An operation gets the
    traffic file's parameters and ``group_index``, the group's place in the
    deployment, which no seed changes."""
    import ratis_tpu.transport.tcp  # noqa: F401  (registers TCP)
    from ratis_tpu.client import RaftClient
    from ratis_tpu.conf import RaftProperties
    from ratis_tpu.protocol.group import RaftGroup
    from ratis_tpu.protocol.ids import ClientId, RaftGroupId, RaftPeerId
    from ratis_tpu.protocol.peer import RaftPeer
    from ratis_tpu.retry.policies import RetryPolicies
    from ratis_tpu.transport.base import TransportFactory

    props = RaftProperties()
    for k, v in spec["properties"].items():
        props.set(k, str(v))
    transport = TransportFactory.get(spec["transport"]) \
        .new_client_transport(props)
    streams = spec.get("datastream") or {}
    peers = [RaftPeer(RaftPeerId.value_of(pid), address=addr,
                      datastream_address=streams.get(pid))
             for pid, addr in spec["peers"]]
    retry = RetryPolicies.retry_up_to_maximum_count_with_fixed_sleep(
        int(spec["client"]["retry_count"]), spec["client"]["retry_sleep"])
    traffic = spec["traffic"]
    op = load_op(spec["checkout"], traffic["op"])
    round_op = (load_op(spec["checkout"], traffic["round_op"])
                if "round_op" in traffic else None)
    senders, round_senders = [], []
    for i, (ghex, chex, lead) in enumerate(zip(
            spec["groups"], spec["client_ids"], spec["leaders"])):
        client = (RaftClient.builder()
                  .set_raft_group(RaftGroup.value_of(
                      RaftGroupId.value_of(bytes.fromhex(ghex)), peers))
                  .set_client_id(ClientId.value_of(bytes.fromhex(chex)))
                  .set_leader_id(peers[lead].id)
                  .set_transport(transport).set_retry_policy(retry)
                  .set_properties(props).build())
        params = dict(traffic, group_index=i)
        senders.append(op.sender(client, params))
        round_senders.append(senders[-1] if round_op is None
                             else round_op.sender(client, params))
    return transport, senders, round_senders


def say(prefix: str, obj: dict) -> None:
    sys.stdout.write(prefix + " " + json.dumps(obj, separators=(",", ":"))
                     + "\n")
    sys.stdout.flush()


async def rounds(senders, per_group: int, in_flight: int) -> Recorder:
    """``per_group`` rounds of one request to every group, ``in_flight`` at
    a time, each round after the one before."""
    rec = Recorder()
    sem = asyncio.Semaphore(in_flight)

    async def one(g: int) -> None:
        async with sem:
            now = time.monotonic()
            await one_request(senders[g], rec, rec.submit(g, now, now))

    for _ in range(per_group):
        await asyncio.gather(*(one(g) for g in range(len(senders))))
    return rec


async def child_main(spec: dict) -> None:
    traffic = spec["traffic"]
    groups = len(spec["groups"])
    seed, seconds = int(spec["seed"]), float(spec["seconds"])
    transport, senders, round_senders = build_senders(spec)
    in_flight = int(traffic.get("warmup_in_flight", 64))

    # warm-up: the same number of writes to every group, through the same
    # clients — connections, windows and retry caches exist before the window
    t_w = time.monotonic()
    warm = await rounds(round_senders,
                        int(traffic.get("warmup_writes_per_group", 1)),
                        in_flight)
    if traffic["loop"] == "open":
        due, targets = open_schedule(traffic, groups, seconds, seed)
    else:
        due, targets = [], closed_targets(traffic, groups, seed)
    gc.collect()
    gc.freeze()
    say("GENREADY", {"warmup": warm.as_dict(t_w),
                     "warmup_s": time.monotonic() - t_w,
                     "scheduled": len(due) or None})

    loop = asyncio.get_running_loop()
    line = await loop.run_in_executor(None, sys.stdin.readline)
    word, _, arg = line.strip().partition(" ")
    if word != "GO":
        raise SystemExit(f"generator: expected GO, got {line!r}")
    t0 = float(arg)  # CLOCK_MONOTONIC, shared with the parent
    drain_s = float(traffic.get("drain_s", 60))
    # this process's CPU seconds over the window: a generator that needs
    # most of one core is what a sweep's knee may be
    cpu_go, cpu_close = time.process_time(), []
    loop.call_later(max(0.0, t0 + seconds - time.monotonic()),
                    lambda: cpu_close.append(time.process_time()))
    if traffic["loop"] == "open":
        rec = await run_open(senders, due, targets, t0, drain_s)
    elif traffic["loop"] == "closed":
        rec = await run_closed(senders, targets, int(traffic["in_flight"]),
                               t0, seconds, drain_s)
    else:
        raise ValueError(f"unknown loop {traffic['loop']!r}")
    say("GENDONE", {"requests": rec.as_dict(t0), "t0": t0,
                    "cpu_s_in_window": (cpu_close or [time.process_time()])[0]
                    - cpu_go,
                    "jax_imported": "jax" in sys.modules})
    # settle, when the parent asks for it (it has looked at the device
    # first): one more write to every group.  A follower learns that an
    # entry is committed from the next append (or heartbeat) behind it, so
    # this round brings a majority of every group up to the window's last
    # acknowledged write at once, where waiting for the heartbeat takes half
    # an election timeout
    line = await loop.run_in_executor(None, sys.stdin.readline)
    if line.strip() == "SETTLE":
        settle = await rounds(
            round_senders, int(traffic.get("settle_writes_per_group", 0)),
            in_flight)
        say("GENSETTLED", {"settle": settle.as_dict(t0)})
    await transport.close()


def main() -> None:
    spec = json.loads(sys.stdin.readline())
    sys.path.insert(0, spec["checkout"])
    asyncio.run(child_main(spec))


if __name__ == "__main__":
    main()
