"""XLA profiler hook (raft.tpu.engine.profile-dir, SURVEY §5 tracing): the
server runs inside a jax.profiler session from start to close, opened by
ratis_tpu.trace — which makes it a trace session of the program too, so the
written xplane holds the engine's ``ratis:engine.*`` work spans on the
profiler's clock beside the device ops."""

import asyncio
import glob

from minicluster import MiniCluster, batched_properties, run_with_new_cluster
from ratis_tpu.conf.keys import RaftServerConfigKeys


def test_profile_dir_produces_xla_trace(tmp_path):
    trace_dir = str(tmp_path / "prof")
    from ratis_tpu.trace import TRACER
    seen = {}

    async def body(cluster: MiniCluster):
        assert (await cluster.send_write()).success
        await asyncio.sleep(0.2)  # a few ticks inside the trace
        seen.update(enabled=TRACER.enabled, annotate=TRACER.annotate)

    p = batched_properties()
    p.set(RaftServerConfigKeys.Engine.PROFILE_DIR_KEY, trace_dir)
    try:
        run_with_new_cluster(3, body, properties=p)
        # the profiler session was the program's trace session ...
        assert seen == {"enabled": True, "annotate": True}
        # ... and closed with it at server close
        assert not TRACER.enabled and TRACER.session()["t_off"] > 0
    finally:
        TRACER.configure(enabled=False)

    # stop_trace (at server close) materializes the xplane dump
    dumps = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    assert dumps, f"no xplane trace written under {trace_dir}"
    from jax.profiler import ProfileData
    names = {ev.name for plane in ProfileData.from_file(dumps[0]).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith("ratis:")}
    assert "ratis:clock" in names
    assert {"ratis:engine.dispatch", "ratis:engine.pack",
            "ratis:engine.launch", "ratis:engine.fetch",
            "ratis:engine.collect"} <= names, names
