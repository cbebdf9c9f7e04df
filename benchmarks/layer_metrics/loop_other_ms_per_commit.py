"""Loop time by layer, other: milliseconds of the busiest server loop charged
to what no program code owns: asyncio's own pass after each selector wait
(its events, its timer heap), its self-pipe wake-ups, and callbacks of no
ratis_tpu module, over the trace session, per acknowledged operation of the
window (the program's counter loop.layer_ns,
ratis_tpu/trace/tracer.py:LoopClock; benchmarks/harness/loop_layers.py)."""


def read(ctx):
    from benchmarks.harness.loop_layers import ms_per_commit
    return ms_per_commit(ctx, "other")
