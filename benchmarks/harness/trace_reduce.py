"""Reduction from a profiler trace (``.xplane.pb``) to numbers: the seconds in
which an operation ran on the device (union of intervals), the device time of
each XLA module, the operations that took most time, and the longest idle gaps
labelled by what the host was doing.  Read with nothing but
``jax.profiler.ProfileData`` (no device needed)."""

from __future__ import annotations

import bisect
import glob
import os
from typing import Iterable, Optional

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
BENCH_SPAN_PREFIX = "bench:"
WINDOW_SPAN = "bench:traced_window"   # marks the window; labels no gap


def find_xplane(trace_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return files[-1] if files else None


def union_ns(intervals: Iterable[tuple[float, float]]) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def _gaps(intervals: list[tuple[float, float]], t0: float, t1: float
          ) -> list[tuple[float, float]]:
    """The uncovered stretches of [t0, t1]."""
    out, cur = [], t0
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, t1)))
        cur = max(cur, e)
        if cur >= t1:
            break
    if cur < t1:
        out.append((cur, t1))
    return [(a, b) for a, b in out if b > a]


def op_short_name(event_name: str) -> str:
    """``%fusion.7 = s32[8192]{...} fusion(...)`` -> ``%fusion.7``."""
    return event_name.split(" = ", 1)[0].strip()[:80]


def module_base_name(event_name: str) -> str:
    """``jit_engine_step_resident_fast(1234...)`` -> the jitted function's
    name, ``engine_step_resident_fast``."""
    name = event_name.split("(", 1)[0].strip()
    return name[4:] if name.startswith("jit_") else name


def load(path: str) -> dict:
    """Parse one xplane file into plain lists.  Times are nanoseconds on the
    profiler's clock: ``{"devices": {plane: {"ops": [(name, s, e)],
    "modules": [(name, s, e)]}}, "host_spans": [(name, s, e)]}`` where
    host_spans are the benchmark's own ``TraceAnnotation``s (``bench:*``)."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: dict = {}
    host_spans: list = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dst = ops
                elif line.name == MODULES_LINE:
                    dst = modules
                else:
                    continue
                for ev in line.events:
                    dst.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns))
            devices[plane.name] = {"ops": ops, "modules": modules}
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(BENCH_SPAN_PREFIX):
                        host_spans.append((ev.name, ev.start_ns,
                                           ev.start_ns + ev.duration_ns))
    return {"devices": devices, "host_spans": host_spans}


def reduce(parsed: dict, window_ns: Optional[tuple[float, float]] = None,
           top: int = 10) -> Optional[dict]:
    """The device's busy time and breakdown over the traced window.

    ``window_ns`` is the traced window on the profiler's clock; without it
    the window runs from the first to the last event seen (device or
    ``bench:*`` host span).  Busy seconds are averaged over the device
    planes that ran anything.  Returns None when no operation ran on any
    device: there is nothing to read."""
    used = {k: v for k, v in parsed["devices"].items()
            if v["ops"] or v["modules"]}
    if not used:
        return None
    every = [iv for d in used.values() for iv in (d["ops"] or d["modules"])]
    spans = parsed["host_spans"]
    if window_ns is None:
        starts = [s for _, s, _ in every] + [s for _, s, _ in spans]
        ends = [e for _, _, e in every] + [e for _, _, e in spans]
        window_ns = (min(starts), max(ends))
    t0, t1 = window_ns
    busy = []
    for d in used.values():
        ivs = [(max(s, t0), min(e, t1)) for _, s, e in (d["ops"] or
                                                        d["modules"])
               if e > t0 and s < t1]
        busy.append(union_ns(ivs))
    busy_s = sum(busy) / len(busy) / 1e9

    op_time: dict[str, float] = {}
    module_time: dict[str, float] = {}
    module_count: dict[str, int] = {}
    for d in used.values():
        mods = sorted((s, e, module_base_name(name))
                      for name, s, e in d["modules"])
        starts = [m[0] for m in mods]
        for name, s, e in d["ops"]:
            if e > t0 and s < t1:
                # an operation is named with the module that holds it
                i = bisect.bisect_right(starts, s) - 1
                owner = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
                key = f"{owner}/{op_short_name(name)}"
                op_time[key] = op_time.get(key, 0.0) + (e - s) / 1e9
        for s, e, base in mods:
            if e > t0 and s < t1:
                module_time[base] = module_time.get(base, 0.0) + (e - s) / 1e9
                module_count[base] = module_count.get(base, 0) + 1

    # idle time of the busiest device plane, split by what the host was
    # doing: inside each kind of benchmark span, or outside all of them
    first = max(used.values(), key=lambda d: len(d["ops"] or d["modules"]))
    gaps = _gaps([(s, e) for _, s, e in (first["ops"] or first["modules"])],
                 t0, t1)
    idle_ns = sum(b - a for a, b in gaps)
    gap_by_label: dict[str, float] = {}
    for label in {n for n, _, _ in spans if n != WINDOW_SPAN}:
        inside = [(max(a, s), min(b, e)) for a, b in gaps
                  for n, s, e in spans
                  if n == label and min(b, e) > max(a, s)]
        gap_by_label[f"host inside {label}"] = union_ns(inside) / 1e9
    gap_by_label["host outside every benchmark span (serving requests, "
                 "waiting)"] = idle_ns / 1e9 - sum(gap_by_label.values())
    return {
        "busy_s": busy_s,
        "window_s": (t1 - t0) / 1e9,
        "device_planes": sorted(used),
        "op_time_s": op_time,
        "module_time_s": module_time,
        "module_count": module_count,
        "device_ops": sorted(op_time.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(gap_by_label.items(), key=lambda kv: -kv[1])[:top],
    }
