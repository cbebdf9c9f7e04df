#!/usr/bin/env python3
"""The benchmark's command: one cell, one run.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell, its configuration, its traffic mix and every per-layer metric
as files by the names in ``BENCHMARK.json``; brings the deployment up in this
process (which holds the chip), drives it from a child process that never
imports JAX, measures one window, then compares what the window produced with
the configuration's plain reference.  The last line of stdout is the result:
one JSON object with the keys ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (and ``breakdown`` with ``--trace 1``), then
``compared``.  Everything else seen goes out earlier, on one ``RESULT`` line.

Without a TPU it exits non-zero and prints no result.  ``--rehearse-cpu`` is
the explicit switch for rehearsing the control flow on the CPU backend
(``--groups`` cuts the deployment for it); such a run names the CPU as its
device and reports nothing that comes from a device trace.
"""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()  # set-up counts from here

import argparse
import asyncio
import faulthandler
import gc
import importlib.util
import json
import math
import os
import shutil
import subprocess
import sys
import threading
import traceback

CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(CHECKOUT, "benchmarks")
if CHECKOUT not in sys.path:
    sys.path.insert(0, CHECKOUT)

DEADLINE_S = 345          # a run exits within 360 s, whatever happens
REPLICA_WAIT_S = 60.0     # an answer that comes late is late, not wrong
READY_GRACE_S = 30.0      # for an election's winner (bring-up, window start)
PIPE_LIMIT = 1 << 28      # the generator's result is one long line
# the configuration's keys that a client reads: the generator's gets them all
CLIENT_KEY_PREFIXES = ("raft.tpu.tcp.", "raft.grpc.", "raft.tpu.grpc.",
                       "raft.client.", "raft.netty.")


def say(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------------ manifest

def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(checkout: str = CHECKOUT) -> dict:
    return load_json(os.path.join(checkout, "BENCHMARK.json"))


def resolve_cell(manifest: dict, name: str, checkout: str = CHECKOUT) -> dict:
    """The cell's entry, its configuration (the file BENCHMARK.json names)
    and its traffic mix (``benchmarks/traffic/<traffic>.json``)."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = load_json(os.path.join(checkout, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(checkout, "benchmarks", "traffic",
                                     cell["traffic"] + ".json"))
    # the plain reference is the configuration's, unless the traffic brings
    # operations that need one of their own
    reference = traffic.get("reference", config["reference"])
    return {"cell": cell, "config": config, "traffic": traffic,
            "reference": reference}


def metrics_of(manifest: dict, kind: str, cell: str) -> list[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    return [m for m in manifest[kind]
            if "workloads" not in m or cell in m["workloads"]]


def load_reader(name: str, checkout: str = CHECKOUT):
    """``benchmarks/layer_metrics/<name>.py`` -> its ``read``."""
    path = os.path.join(checkout, "benchmarks", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "benchmarks.layer_metrics." + name.replace("-", "_").replace(".", "_"),
        path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


# ------------------------------------------------------------------- tracing

class TraceWindow(threading.Thread):
    """Starts the profiler at ``t_start`` and stops it at ``t_stop``
    (CLOCK_MONOTONIC), from a thread of its own so that neither call runs on
    the servers' loop.  The stretch between is one ``bench:traced_window``
    span, which gives the reduction the window on the profiler's clock."""

    def __init__(self, trace_dir: str, t_start: float, t_stop: float) -> None:
        super().__init__(name="bench-trace", daemon=True)
        self.trace_dir, self.t_start, self.t_stop = trace_dir, t_start, t_stop
        self.error: BaseException | None = None

    def run(self) -> None:
        import jax
        try:
            time.sleep(max(0.0, self.t_start - time.monotonic()))
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation("bench:traced_window"):
                    time.sleep(max(0.0, self.t_stop - time.monotonic()))
            finally:
                jax.profiler.stop_trace()
        except BaseException as e:  # reported by the run, which then fails
            self.error = e


def annotate_dispatches(engines) -> None:
    """Traced runs only: each engine's batched dispatch becomes a
    ``bench:engine_dispatch`` host span, so an idle gap of the device can be
    told apart: inside a dispatch (pack, upload, fetch) or between them."""
    import jax
    for e in engines:
        inner = e._tick_batched_pass

        def traced(acks, now, _inner=inner):
            with jax.profiler.TraceAnnotation("bench:engine_dispatch"):
                return _inner(acks, now)
        e._tick_batched_pass = traced


# -------------------------------------------------------------------- window

async def read_tagged(stream, tag: str, who: str, timeout: float) -> dict:
    """The child's next ``<tag> {json}`` line; other lines go to stderr."""
    deadline = time.monotonic() + timeout
    while True:
        left = deadline - time.monotonic()
        if left <= 0:
            raise TimeoutError(f"{who}: no {tag} line within {timeout}s")
        line = await asyncio.wait_for(stream.readline(), left)
        if not line:
            raise RuntimeError(f"{who}: ended before its {tag} line")
        text = line.decode("utf-8", "replace")
        if text.startswith(tag + " "):
            return json.loads(text[len(tag) + 1:])
        sys.stderr.write(f"{who}: {text}")


async def drive_window(cluster, traffic: dict, seed: int, seconds: float,
                       trace_dir: str | None, compiles, on_ready=None,
                       on_drained=None) -> dict:
    """One generator child, one window.  Returns what the generator saw and
    the program's counters at the window's start and close.  ``on_ready``
    runs after the warm-up, ``on_drained`` once the window's last answer is
    in and before the settle round."""
    from benchmarks.harness.cluster import (LagProbe, memory_peak_bytes,
                                            seeded_ids)
    config = cluster.config
    spec = {
        "checkout": CHECKOUT, "seed": seed, "seconds": seconds,
        "traffic": traffic, "transport": config["transport"],
        "client": config["client"],
        "properties": {k: v for k, v in cluster.properties.items()
                       if k.startswith(CLIENT_KEY_PREFIXES)},
        "peers": cluster.addresses,
        "datastream": cluster.datastream_addresses,
        "groups": [b.hex() for b in cluster.group_id_bytes],
        "client_ids": [b.hex() for b in seeded_ids(seed, cluster.groups_n,
                                                   "client")],
        "leaders": [cluster.leader_server(i)
                    for i in range(cluster.groups_n)],
    }
    child = await asyncio.create_subprocess_exec(
        sys.executable, os.path.join(HERE, "harness", "generator.py"),
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, limit=PIPE_LIMIT)
    try:
        child.stdin.write((json.dumps(spec) + "\n").encode())
        await child.stdin.drain()
        ready = await read_tagged(child.stdout, "GENREADY", "generator", 240)
        warm = ready["warmup"]
        bad = [e for e in warm["error"] if e] \
            + [1 for a in warm["answer"] if a is None]
        if bad:
            raise RuntimeError(f"warm-up: {len(bad)} writes were not "
                               f"acknowledged, e.g. {bad[0]}")
        extra = await on_ready() if on_ready is not None else None
        c0_compiles = compiles.mark()[0]
        probe = LagProbe()
        probe.start()
        t0 = time.monotonic() + 0.25
        tracer = None
        if trace_dir is not None:
            # the whole window: the device runs so rarely that a short
            # stretch may hold no operation at all
            tracer = TraceWindow(trace_dir, t0, t0 + seconds)
            tracer.start()
        c0 = cluster.counters()
        child.stdin.write(f"GO {t0!r}\n".encode())
        await child.stdin.drain()
        await asyncio.sleep(max(0.0, t0 + seconds - time.monotonic()))
        c1 = cluster.counters()               # at the window's close
        compiled = list(compiles.names[c0_compiles:])
        peak = memory_peak_bytes()
        await probe.stop()
        done = await read_tagged(
            child.stdout, "GENDONE", "generator",
            float(traffic.get("drain_s", 60)) + 30)
        if tracer is not None:
            await asyncio.to_thread(tracer.join)
            if tracer.error is not None:
                raise RuntimeError(f"profiler: {tracer.error!r}")
        drained = await on_drained() if on_drained is not None else None
        child.stdin.write(b"SETTLE\n")
        await child.stdin.drain()
        settled = await read_tagged(
            child.stdout, "GENSETTLED", "generator",
            float(traffic.get("drain_s", 60)) + 60)
        child.stdin.close()
        try:
            await asyncio.wait_for(child.wait(), 10)
        except asyncio.TimeoutError:
            pass
    finally:
        if child.returncode is None:
            child.kill()
            await child.wait()
    return {"warmup": warm, "warmup_s": ready["warmup_s"],
            "requests": done["requests"], "settle": settled["settle"],
            "on_drained": drained,
            "t0": t0, "c0": c0, "c1": c1,
            "generator_imported_jax": done["jax_imported"],
            "generator_cpu_s": done["cpu_s_in_window"],
            "compiled_in_window": compiled, "memory_peak_bytes": peak,
            "lag_ms": probe.overshoots_ms(t0, t0 + seconds),
            "on_ready": extra}


def summarize(requests: dict, seconds: float, drain_s: float) -> dict:
    """The window's end-to-end numbers from the generator's rows.  A request
    that failed or never got its answer sits at the top of the distribution;
    where a percentile falls among those it reads the longest any answer was
    waited for (window + drain), so the line stays a finite number."""
    from benchmarks.harness.stats import window_summary
    lat, missing, in_window = [], 0, 0
    for due, acked, answer in zip(requests["due"], requests["acked"],
                                  requests["answer"]):
        if acked is None or answer is None:
            missing += 1
            continue
        lat.append((acked - due) * 1e3)
        if acked <= seconds:
            in_window += 1
    out = window_summary(lat, missing, in_window, seconds)
    for k, v in out.items():
        if math.isinf(v):
            out[k] = (seconds + drain_s) * 1e3
    out.update(attempted=len(requests["due"]), failed=missing,
               acked_in_window=in_window)
    return out


# ----------------------------------------------------------------------- run

async def bring_up(args, resolved: dict, compiles):
    """The cell's deployment, up: storage fresh, the cell's own programs
    compiled or loaded, every group with a ready appointed leader, the heap
    sealed once (the program's call for an operator who knows that bring-up
    has just ended).  Returns the cluster, the configuration as run, and
    what the prewarm loaded."""
    from benchmarks.harness import faults
    from benchmarks.harness.cluster import (Cluster, raise_nofile,
                                            remove_dead_runs)
    config = resolved["config"]
    if args.groups:
        config = dict(config, groups=args.groups)
    say(f"{resolved['cell']['name']}: {config['peers']} x "
        f"{config['groups']} groups, nofile {raise_nofile()}")
    overrides, sm_factory = {}, None
    for name in filter(None, (args.control, args.fault)):
        control = config.get("controls", {}).get(name, {})
        overrides.update(control.get("properties", {}))
        sm_factory = faults.sm_factory_for(name, config["peers"]) \
            or sm_factory
    cluster = Cluster(config, args.seed, CHECKOUT, overrides, sm_factory)
    if cluster.storage_dir:
        remove_dead_runs(os.path.dirname(cluster.storage_dir))
        shutil.rmtree(cluster.storage_dir, ignore_errors=True)
        os.makedirs(cluster.storage_dir)
    before = compiles.mark()
    cluster.prewarm()
    prewarmed = [a - b for a, b in zip(compiles.mark(), before)]
    say(f"prewarm {cluster.prewarm_s:.2f}s: {prewarmed[0]} programs, "
        f"{prewarmed[1]} cache hits, {prewarmed[2]} misses")
    gc.disable()          # nothing built during bring-up is garbage
    try:
        await cluster.start()
        cluster.seal_heap()
    finally:
        gc.enable()
    missing = await cluster.groups_without_ready_leader(READY_GRACE_S)
    if missing:
        raise RuntimeError(f"{len(missing)} groups have no ready leader "
                           f"before the window, e.g. group {missing[0]}")
    say(f"bring-up {cluster.bring_up_s:.2f}s")
    if args.fault == "frozen-device-step":
        faults.freeze_device_step(cluster.engines)
    return cluster, config, prewarmed


async def run_cell(args, resolved: dict, manifest: dict, device: dict,
                   compiles) -> dict:
    from benchmarks.harness import compare, faults, trace_reduce
    from benchmarks.harness.cluster import drained_device_state
    traffic = dict(resolved["traffic"])
    cell = resolved["cell"]["name"]
    if args.rate:
        traffic["rate_per_s"] = args.rate
    cluster, config, prewarmed = await bring_up(args, resolved, compiles)
    trace_dir = None
    if args.trace:
        trace_dir = os.path.join(CHECKOUT, ".bench_trace", cell)
        shutil.rmtree(trace_dir, ignore_errors=True)
        annotate_dispatches(cluster.engines)

    start: dict = {}

    async def baseline() -> dict:
        """After the warm-up, before the window: which server leads each
        group and where its log ends (whatever of it is committed yet), to
        hold the window's advance on the device against.  Where an election
        is in flight the window waits for its winner, ``READY_GRACE_S`` at
        the most (set-up)."""
        if args.fault == "leader-moved":
            await faults.move_leader(cluster, 0, args.seed)
        t = time.monotonic()
        start["leaders"] = await cluster.leaders_after(READY_GRACE_S)
        start["wait_s"] = time.monotonic() - t
        return start

    async def snapshots() -> dict:
        """At the window's close, before the settle round: acks only ever
        raise a match index, so a round of fresh acks to every group would
        paper over any that the window's own dispatches lost.  Then where
        each group's leader at the start stands now."""
        snaps = [await drained_device_state(e, f"engine {i}")
                 for i, e in enumerate(cluster.engines)]
        return {"snaps": snaps,
                "standing": [cluster.standing(g, r["server"])
                             for g, r in enumerate(start["leaders"])]}

    w = await drive_window(cluster, traffic, args.seed, args.seconds,
                           trace_dir, compiles, on_ready=baseline,
                           on_drained=snapshots)
    setup_s = w["t0"] - T_PROCESS
    e2e = summarize(w["requests"], args.seconds,
                    float(traffic.get("drain_s", 60)))
    say(f"window closed: {e2e['attempted']} attempted, {e2e['failed']} "
        f"failed, {e2e['commits_per_s']:.1f} commits/s")

    # ---- the comparison: everything the window produced, once it has closed
    ref = compare.load_reference(resolved["reference"])
    need = int(config["guarantees"]["replicas_acknowledging"])
    answers = ref.judge_answers(
        cluster.groups_n, [w["warmup"], w["requests"], w["settle"]])
    acked, submitted = (answers["acked_per_group"],
                        answers["submitted_per_group"])
    unsettled = [0] * cluster.groups_n
    for g in w["settle"]["group"]:
        unsettled[g] += 1
    # (a control or a planted fault is not waited for as long)
    t_wait = time.monotonic()
    deadline = t_wait + (5.0 if args.control or args.fault
                         else REPLICA_WAIT_S)
    while True:
        values = [cluster.replica_values(g)
                  for g in range(cluster.groups_n)]
        short = compare.replicas_short(ref, values, acked, submitted, need,
                                       unsettled)
        if not short or time.monotonic() > deadline:
            break
        await asyncio.sleep(0.2)
    replica_wait_s = time.monotonic() - t_wait
    short_seen = [{"group": g, "replicas": values[g], "acked": acked[g],
                   "submitted": submitted[g], "unsettled": unsettled[g]}
                  for g in short[:8]]
    snaps = w["on_drained"]["snaps"]
    in_window = compare.window_entries(answers, w["requests"],
                                       cluster.groups_n)
    dev = compare.check_device(
        ref, snaps, start["leaders"], w["on_drained"]["standing"],
        [cluster.leader_server(g) for g in range(cluster.groups_n)],
        in_window)
    numbers = {
        "never_answered": (answers["never_answered"], 0),
        "answers_wrong": (answers["answers_wrong"], 0),
        "groups_short_of_replicas": (len(short), 0),
        "device_rows_differing": (dev["device_rows_differing"], 0),
        "device_quorum_rows_wrong": (dev["device_quorum_rows_wrong"], 0),
        "device_commit_advance_wrong": (dev["device_commit_advance_wrong"],
                                        0),
    }
    if config["guarantees"]["durable"]:
        needle = traffic["payload_ascii"].encode("ascii")
        # (in a thread: the servers' loop keeps its heartbeats meanwhile)
        lost = await asyncio.to_thread(
            compare.durable_short, ref,
            [[cluster.replica_log_dir(s, g) for s in range(cluster.peers_n)]
             for g in range(cluster.groups_n)], acked, need, needle)
        numbers["groups_short_of_durable"] = (len(lost), 0)
    # whatever further numbers the reference judged by itself, each exact
    numbers.update((name, (value, 0))
                   for name, value in answers.get("compared", {}).items())
    correct, compared = compare.verdict(numbers)

    # ---- metrics
    device = dict(device, memory_peak_bytes=w["memory_peak_bytes"])
    result = {"correct": correct, "attempted": e2e["attempted"],
              "failed": e2e["failed"]}
    seen = {"cell": cell, "seed": args.seed, "seconds": args.seconds,
            "setup_s": setup_s, "prewarm_s": cluster.prewarm_s,
            "prewarm_programs": prewarmed,
            "bring_up_s": cluster.bring_up_s, "warmup_s": w["warmup_s"],
            "end_to_end": {k: v for k, v in e2e.items()
                           if k.startswith("commit")},
            "answers_compared": answers["answers_compared"],
            "reads_compared": answers.get("reads_compared"),
            "replica_wait_s": replica_wait_s, "groups_short": short_seen,
            "elections_at_end": cluster.counters()["elections"],
            "device_rows_compared": dev["device_rows_compared"],
            "answer_samples": answers["samples"],
            "generator_imported_jax": w["generator_imported_jax"],
            "control": args.control, "fault": args.fault,
            "rate_override": args.rate or None,
            "counters": {"c0": w["c0"], "c1": w["c1"]}}
    if not args.trace:
        values = dict(e2e, setup_s=setup_s)
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in metrics_of(manifest, "end_to_end", cell)}
    else:
        reduced = None
        path = trace_reduce.find_xplane(trace_dir)
        if path is not None and device["platform"] != "cpu":
            parsed = trace_reduce.load(path)
            spans = [(s, e) for n, s, e in parsed["host_spans"]
                     if n == "bench:traced_window"]
            reduced = trace_reduce.reduce(parsed, spans[0] if spans else None)
        shutil.rmtree(os.path.join(CHECKOUT, ".bench_trace"),
                      ignore_errors=True)
        ctx = {"c0": w["c0"], "c1": w["c1"], "requests": w["requests"],
               "seconds": args.seconds,
               "acked_in_window": e2e["acked_in_window"], "end_to_end": e2e,
               "lag_ms": w["lag_ms"],
               "compiled_in_window": w["compiled_in_window"],
               "trace": reduced, "device": device, "config": config}
        result["metrics"] = {}
        for m in metrics_of(manifest, "per_layer", cell):
            value = load_reader(m["name"])(ctx)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        if reduced is not None:
            device.update(busy_s=reduced["busy_s"],
                          window_s=reduced["window_s"])
            result["breakdown"] = {
                "device_ops": [[n, t] for n, t in reduced["device_ops"]],
                "idle_gaps": [[n, t] for n, t in reduced["idle_gaps"]]}
            seen["trace"] = {k: reduced[k] for k in
                             ("module_time_s", "module_count",
                              "device_planes")}
    result["device"] = device
    result["compared"] = compared
    # the window's start and the device check come last on the line: its
    # end is what the record of a run that was not correct keeps
    seen.update(window_start_wait_s=start["wait_s"],
                leaders_away_at_window_start=dev[
                    "leaders_away_at_window_start"],
                leaderless_at_window_start=dev["leaderless_at_window_start"],
                device_commit_skipped=dev["device_commit_skipped"],
                device_groups_failing=dev["device_groups_failing"])
    return {"result": result, "seen": seen}


def remove_storage(config: dict) -> None:
    """A durable run's storage directory goes when the run ends, and the
    directory above it once no other run keeps one there."""
    from benchmarks.harness.cluster import run_storage_dir
    path = run_storage_dir(CHECKOUT, config)
    if path:
        shutil.rmtree(path, ignore_errors=True)
        try:
            os.rmdir(os.path.dirname(path))
        except OSError:
            pass


def print_result(result: dict) -> None:
    """The numbers compared, beside their limits, as the last lines of
    stderr; the result as the last line of stdout, ``compared`` last."""
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result, separators=(",", ":")), flush=True)


def parse_args(argv=None, extra=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="rehearse the control flow on the CPU backend "
                         "(never a device record)")
    ap.add_argument("--groups", type=int, default=0,
                    help="cut the deployment's groups (rehearsal only)")
    ap.add_argument("--rate", type=float, default=0,
                    help="override the open loop's rate (rehearsal only)")
    ap.add_argument("--control", default=None,
                    help="run a control of the configuration's 'controls'")
    ap.add_argument("--fault", default=None,
                    help="plant a fault under the harness (tests)")
    if extra is not None:
        extra(ap)
    args = ap.parse_args(argv)
    if (args.groups or args.rate) and not args.rehearse_cpu:
        sys.exit("bench: --groups and --rate only go with --rehearse-cpu")
    return args


def claim_device(args, resolved: dict) -> dict:
    """The device as JAX reports it, or no run: without a TPU (or with
    fewer chips than the cell asks for) nothing is built and nothing is
    printed."""
    from ratis_tpu.util.jaxenv import (pin_cpu, place_compile_cache,
                                       require_backend)
    if args.rehearse_cpu:
        pin_cpu()
    try:
        device = require_backend("cpu" if args.rehearse_cpu else "tpu")
    except RuntimeError as e:
        sys.exit(f"bench: {e}: no accelerator, nothing built, no result")
    if device["count"] < resolved["cell"]["chips"]:
        sys.exit(f"bench: the cell asks for {resolved['cell']['chips']} "
                 f"chips, JAX sees {device['count']}: no result")
    if not args.rehearse_cpu:
        from benchmarks.harness.peaks import peaks_for
        peaks_for(device["kind"])   # an unknown device is an error, now
    place_compile_cache()
    return device


def run_to_the_end(resolved: dict, coro) -> dict:
    """Runs ``coro`` on a loop of its own.  If it raises: the traceback, a
    non-zero exit, no result line — and no unwinding of thousands of
    divisions first."""
    loop = asyncio.new_event_loop()
    try:
        return loop.run_until_complete(coro)
    except BaseException:
        traceback.print_exc()
        sys.stderr.flush()
        remove_storage(resolved["config"])
        os._exit(1)


def main(argv=None) -> None:
    args = parse_args(argv)
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
    manifest = load_manifest()
    resolved = resolve_cell(manifest, args.workload)
    device = claim_device(args, resolved)
    from benchmarks.harness.cluster import CompileLog
    out = run_to_the_end(resolved, run_cell(args, resolved, manifest, device,
                                            CompileLog()))
    print("RESULT " + json.dumps(out["seen"], separators=(",", ":")),
          flush=True)
    print_result(out["result"])
    # the result is out: end here, without unwinding the cluster (closing
    # thousands of divisions takes longer than the run)
    remove_storage(resolved["config"])
    os._exit(0)


if __name__ == "__main__":
    main()
