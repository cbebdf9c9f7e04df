#!/usr/bin/env python3
"""Not the benchmark's command: the builder's stepped sweep of one traffic
parameter, in one process on the chip.  Each step is a window of
``--seconds`` with ``--key`` set to the next of ``--values`` (the open
loop's ``rate_per_s``, the closed loop's ``in_flight``).  It finds the
highest load the system sustains and decides nothing by itself: the number
chosen from it goes into the traffic file, the steps into PERF.md.

    python3 benchmarks/sweep.py --workload <cell> --seed <n> --seconds 8 \\
        --key rate_per_s --values 200,400,600,800
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import run as bench
from benchmarks.harness.stats import percentile


async def sweep(args, resolved: dict, compiles) -> list[dict]:
    cluster, _config, _prewarmed = await bench.bring_up(args, resolved,
                                                        compiles)
    steps = []
    for k, value in enumerate(args.values):
        traffic = dict(resolved["traffic"], drain_s=20,
                       warmup_writes_per_group=1 if k == 0 else 0,
                       settle_writes_per_group=0, **{args.key: value})
        w = await bench.drive_window(cluster, traffic, args.seed + k,
                                     args.seconds, None, compiles)
        r = w["requests"]
        e2e = bench.summarize(r, args.seconds, 20.0)

        def in_flight(at: float) -> int:
            return sum(1 for s, a in zip(r["sent"], r["acked"])
                       if s <= at and (a is None or a > at))
        late = [(s - d) * 1e3 for s, d in zip(r["sent"], r["due"])]
        wall = w["c1"]["t"] - w["c0"]["t"]
        steps.append({
            args.key: value, "commits_per_s": e2e["commits_per_s"],
            "p50_ms": e2e["commit_p50_ms"], "p99_ms": e2e["commit_p99_ms"],
            "failed": e2e["failed"],
            "in_flight_at_third": in_flight(args.seconds / 3),
            "in_flight_at_two_thirds": in_flight(2 * args.seconds / 3),
            "in_flight_at_close": in_flight(args.seconds),
            "gen_late_p99_ms": percentile(late, 0.99),
            "elections": w["c1"]["elections"] - w["c0"]["elections"],
            "lag_p99_ms": percentile(w["lag_ms"], 0.99),
            # whose knee it is: the CPU share of the servers' loop thread
            # and of the generator's process over the window
            "loop_cpu_pct": 100 * (w["c1"]["loop_cpu_s"]
                                   - w["c0"]["loop_cpu_s"]) / wall,
            "gen_cpu_pct": 100 * w["generator_cpu_s"] / args.seconds})
        bench.say(f"sweep {steps[-1]}")
    return steps


def main(argv=None) -> None:
    def more(ap):
        ap.add_argument("--key", required=True,
                        help="the traffic parameter to step")
        ap.add_argument("--values", required=True,
                        type=lambda s: [float(x) if "." in x else int(x)
                                        for x in s.split(",")])
    args = bench.parse_args(argv, more)
    resolved = bench.resolve_cell(bench.load_manifest(), args.workload)
    bench.claim_device(args, resolved)
    from benchmarks.harness.cluster import CompileLog
    steps = bench.run_to_the_end(resolved, sweep(args, resolved, CompileLog()))
    print("SWEEP " + json.dumps(steps), flush=True)
    bench.remove_storage(resolved["config"])
    os._exit(0)


if __name__ == "__main__":
    main()
