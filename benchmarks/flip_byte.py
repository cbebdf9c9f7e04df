#!/usr/bin/env python3
"""Not the benchmark's command: the builder's planted fault for a cell whose
replicas keep bytes beside their log.  Runs ``benchmarks/run.py`` as it is
and, at the moment the run starts to compare the durable state, flips one
byte of one written file in two replicas of one group: the comparison has to
come out not ``correct`` under ``groups_short_of_durable`` (the logs still
hold every header; one replica alone still has the bytes, which is not 2 of
3).

    python3 benchmarks/flip_byte.py --workload \\
        ratis-filestore-3x1k.loadgen-closed --seed 7 --seconds 25 --trace 0
"""

from __future__ import annotations

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

GROUP = 0
REPLICAS = 2


def flip_one_byte(log_dir: str) -> str:
    """Flips the first byte of the first file found beside ``log_dir``
    (``<group>/sm/files``, open or closed); returns the file's path."""
    root = os.path.join(os.path.dirname(os.path.normpath(log_dir)),
                        "sm", "files")
    for where, _, names in sorted(os.walk(root)):
        for name in sorted(names):
            path = os.path.join(where, name)
            if os.path.getsize(path):
                with open(path, "r+b") as f:
                    first = f.read(1)
                    f.seek(0)
                    f.write(bytes([first[0] ^ 0xFF]))
                return path
    raise RuntimeError(f"no written file under {root}")


def main(argv=None) -> None:
    from benchmarks import run as bench
    from benchmarks.harness import compare
    durable_short = compare.durable_short

    def plant_then_compare(ref, log_dirs, acked, need, needle):
        for log_dir in log_dirs[GROUP][:REPLICAS]:
            print(f"flip_byte: {flip_one_byte(log_dir)}", file=sys.stderr,
                  flush=True)
        return durable_short(ref, log_dirs, acked, need, needle)

    compare.durable_short = plant_then_compare
    bench.main(argv)


if __name__ == "__main__":
    main()
