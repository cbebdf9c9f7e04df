"""Dev tool: cProfile the bench load phase at N groups (not part of the
framework; run as
`python -m ratis_tpu.tools.profile_load [groups] [batched|scalar] [writes]
 [transport] [peers]`)."""
import asyncio
import cProfile
import io
import json
import pstats
import sys


def main():
    # a host-side cProfile tool: CPU-only, never a device measurement
    from ratis_tpu.util.jaxenv import pin_cpu
    pin_cpu()
    groups = int(sys.argv[1]) if len(sys.argv) > 1 else 1024
    writes = int(sys.argv[3]) if len(sys.argv) > 3 else 8
    batched = (sys.argv[2] != "scalar") if len(sys.argv) > 2 else True
    transport = sys.argv[4] if len(sys.argv) > 4 else "sim"
    peers = int(sys.argv[5]) if len(sys.argv) > 5 else 3
    from ratis_tpu.tools.bench_cluster import BenchCluster

    async def run():
        cluster = BenchCluster(groups, batched=batched, transport=transport,
                               num_servers=peers)
        try:
            await cluster.start()
            await cluster.run_load(1, 128)  # warmup
            prof = cProfile.Profile()
            prof.enable()
            result = await cluster.run_load(writes, 128)
            prof.disable()
            print("RESULT " + json.dumps(result))
            s = io.StringIO()
            ps = pstats.Stats(prof, stream=s).sort_stats("cumulative")
            ps.print_stats(45)
            print(s.getvalue())
            s = io.StringIO()
            ps = pstats.Stats(prof, stream=s).sort_stats("tottime")
            ps.print_stats(35)
            print(s.getvalue())
        finally:
            await cluster.close()

    asyncio.run(run())


if __name__ == "__main__":
    main()
