"""Loop time by layer, stream: milliseconds of the busiest server loop charged
to the stream plane: server/datastream.py, transport/datastream.py and the
stream.* work spans, over the trace session, per acknowledged operation of
the window (the program's counter loop.layer_ns,
ratis_tpu/trace/tracer.py:LoopClock; benchmarks/harness/loop_layers.py)."""


def read(ctx):
    from benchmarks.harness.loop_layers import ms_per_commit
    return ms_per_commit(ctx, "stream")
