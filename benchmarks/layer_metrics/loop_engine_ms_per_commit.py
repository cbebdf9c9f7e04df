"""Loop time by layer, engine: milliseconds of the busiest server loop charged
to the engine's host half: engine/* callbacks and the work span
engine.dispatch with its parts, over the trace session, per acknowledged
operation of the window (the program's counter loop.layer_ns,
ratis_tpu/trace/tracer.py:LoopClock; benchmarks/harness/loop_layers.py)."""


def read(ctx):
    from benchmarks.harness.loop_layers import ms_per_commit
    return ms_per_commit(ctx, "engine")
