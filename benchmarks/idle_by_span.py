#!/usr/bin/env python3
"""Not the benchmark's command: the builder's look at one kept profiler trace
(``.xplane.pb``).  Splits the device's idle time by what the program's busiest
host thread (the servers' event loop) was doing meanwhile: blocked in its
selector (``ratis:loop.select``), inside a named ``ratis:`` work span (the
innermost one where they nest), or between spans.  The program writes those
spans itself while a profiler session is open (ratis_tpu/trace), on the
profiler's own clock, so they line up with the device's operations.

    python3 benchmarks/idle_by_span.py <file.xplane.pb>

The benchmark's command deletes its trace once reduced; to keep one, run the
cell through this script, which runs ``benchmarks/run.py`` unchanged, copies
the xplane out before it goes and prints the table:

    python3 benchmarks/idle_by_span.py --keep chiprun_out/open.xplane.pb -- \\
        --workload ratis-3x1k.write-open --seed 7 --seconds 25 --trace 1
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness import trace_reduce

SPAN_PREFIX = "ratis:"
BETWEEN = "(between ratis: spans)"
LOOP_SELECT = "ratis:loop.select"   # only an event loop's thread holds it


def load(path: str) -> dict:
    """``{"device": [(s, e)], "window": (s, e) | None, "threads":
    [[(name, s, e)]], "clock_marks": n}``: the device's operations, the
    benchmark's traced window and every host thread's ``ratis:`` spans, in
    nanoseconds on the profiler's clock."""
    from jax.profiler import ProfileData
    device, threads, window, marks = [], [], None, 0
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(trace_reduce.DEVICE_PLANE_PREFIX):
            by_line = {line.name: [(ev.start_ns, ev.start_ns + ev.duration_ns)
                                   for ev in line.events]
                       for line in plane.lines
                       if line.name in (trace_reduce.OPS_LINE,
                                        trace_reduce.MODULES_LINE)}
            ops = by_line.get(trace_reduce.OPS_LINE) \
                or by_line.get(trace_reduce.MODULES_LINE) or []
            if len(ops) > len(device):
                device = ops          # the busiest device plane
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                spans = []
                for ev in line.events:
                    name = ev.name
                    if name == "ratis:clock":
                        marks += 1
                    elif name.startswith(SPAN_PREFIX):
                        spans.append((name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
                    elif name == trace_reduce.WINDOW_SPAN:
                        window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                if spans:
                    threads.append(spans)
    return {"device": device, "window": window, "threads": threads,
            "clock_marks": marks}


def leaf_segments(spans: list, t0: float, t1: float) -> list:
    """One thread's ``(name, s, e)`` spans -> disjoint ``(s, e, label)``
    segments covering [t0, t1]: the innermost open span at each instant, or
    BETWEEN where none is open."""
    points = []
    for k, (name, s, e) in enumerate(spans):
        s, e = max(s, t0), min(e, t1)
        if e > s:
            points.append((s, 1, k))
            points.append((e, 0, k))
    points.sort()           # at one instant, ends (0) before starts (1)
    out, open_spans, cur = [], [], t0
    for t, is_start, k in points:
        if t > cur:
            label = spans[open_spans[-1]][0] if open_spans else BETWEEN
            out.append((cur, t, label))
            cur = t
        if is_start:
            open_spans.append(k)
        elif k in open_spans:
            open_spans.remove(k)
    if cur < t1:
        out.append((cur, t1, BETWEEN))
    return out


def split_idle(parsed: dict) -> dict:
    """The table: the device's idle seconds by label, on the servers' event
    loop's thread: the one that holds ``ratis:loop.select`` (the busiest of
    them where loops are sharded), and only where no thread holds one the
    thread with most time inside ``ratis:`` spans, which a log worker's
    thread can win where fsyncs are slow."""
    threads = parsed["threads"]
    if not threads:
        return {"error": "no ratis: span in the trace: the program had no "
                         "trace session (ratis_tpu/trace) while it was taken"}
    every = [iv for spans in threads for _, *iv in spans] + parsed["device"]
    t0, t1 = parsed["window"] or (min(s for s, _ in every),
                                  max(e for _, e in every))
    gaps = trace_reduce._gaps(parsed["device"], t0, t1)
    at_work = [trace_reduce.union_ns([(s, e) for name, s, e in spans
                                      if name != LOOP_SELECT])
               for spans in threads]
    loops = [i for i, spans in enumerate(threads)
             if any(name == LOOP_SELECT for name, _, _ in spans)]
    main = max(loops or range(len(threads)), key=at_work.__getitem__)
    by_label: dict[str, float] = {}
    g = 0
    for s, e, label in leaf_segments(threads[main], t0, t1):
        # both lists are sorted and disjoint: walk them together
        while g < len(gaps) and gaps[g][1] <= s:
            g += 1
        k = g
        while k < len(gaps) and gaps[k][0] < e:
            lo, hi = max(s, gaps[k][0]), min(e, gaps[k][1])
            if hi > lo:
                by_label[label] = by_label.get(label, 0.0) + (hi - lo) / 1e9
            k += 1
    idle_s = sum(b - a for a, b in gaps) / 1e9
    named = sum(v for k, v in by_label.items() if k != BETWEEN)
    others: dict[str, float] = {}
    for i, spans in enumerate(threads):
        if i != main:
            for name, s, e in spans:
                others[name] = others.get(name, 0.0) + (e - s) / 1e9
    return {
        "window_s": (t1 - t0) / 1e9,
        "device_idle_s": idle_s,
        "device_ops_seen": len(parsed["device"]),
        "clock_marks": parsed["clock_marks"],
        "host_threads_with_spans": len(threads),
        "idle_by_label_s": dict(sorted(by_label.items(),
                                       key=lambda kv: -kv[1])),
        "under_select_or_named_span_pct": 100.0 * named / idle_s
        if idle_s else 0.0,
        "spans_on_other_threads_s": dict(sorted(others.items(),
                                                key=lambda kv: -kv[1])),
    }


def render(table: dict) -> str:
    if "error" in table:
        return table["error"]
    idle = table["device_idle_s"]
    lines = [f"window {table['window_s']:.3f} s, device idle {idle:.3f} s "
             f"({table['device_ops_seen']} device ops seen, "
             f"{table['clock_marks']} ratis:clock mark(s), "
             f"{table['host_threads_with_spans']} host threads with spans)",
             f"{'the busiest host thread was in':<34}{'idle s':>10}"
             f"{'% of idle':>11}"]
    for label, s in table["idle_by_label_s"].items():
        lines.append(f"{label:<34}{s:>10.3f}{100 * s / idle:>11.2f}")
    lines.append(f"under loop.select or a named span: "
                 f"{table['under_select_or_named_span_pct']:.2f} % of idle")
    if table["spans_on_other_threads_s"]:
        lines.append("meanwhile on other threads (span seconds): " + ", ".join(
            f"{k} {v:.3f}"
            for k, v in table["spans_on_other_threads_s"].items()))
    return "\n".join(lines)


def report(path: str) -> dict:
    table = split_idle(load(path))
    print(render(table), flush=True)
    print("IDLE_BY_SPAN " + json.dumps(table), flush=True)
    return table


def run_and_keep(keep: str, run_args: list) -> None:
    """``benchmarks/run.py`` as it is, with the xplane copied to ``keep``
    (and the table printed) at the moment the run has found it."""
    from benchmarks import run as bench
    find = trace_reduce.find_xplane

    def find_and_keep(trace_dir):
        path = find(trace_dir)
        if path is not None:
            os.makedirs(os.path.dirname(os.path.abspath(keep)), exist_ok=True)
            shutil.copyfile(path, keep)
            report(keep)
        return path

    trace_reduce.find_xplane = find_and_keep
    bench.main(run_args)


def main(argv=None) -> None:
    argv = list(sys.argv[1:] if argv is None else argv)
    run_args = []
    if "--" in argv:              # what follows goes to benchmarks/run.py
        cut = argv.index("--")
        argv, run_args = argv[:cut], argv[cut + 1:]
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("xplane", nargs="?", help="a kept .xplane.pb")
    ap.add_argument("--keep", help="run a cell (arguments of benchmarks/"
                                   "run.py after --) and keep its xplane here")
    args = ap.parse_args(argv)
    if args.keep:
        run_and_keep(args.keep, run_args)
    elif args.xplane:
        report(args.xplane)
    else:
        ap.error("give a kept xplane, or --keep <path> -- <run.py arguments>")


if __name__ == "__main__":
    main()
