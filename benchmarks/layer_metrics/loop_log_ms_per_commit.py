"""Loop time by layer, log: milliseconds of the busiest server loop charged to
the log on the loop: server/log/* callbacks there (LogWorker._completed,
_on_record_flushed), over the trace session, per acknowledged operation of
the window (the program's counter loop.layer_ns,
ratis_tpu/trace/tracer.py:LoopClock; benchmarks/harness/loop_layers.py)."""


def read(ctx):
    from benchmarks.harness.loop_layers import ms_per_commit
    return ms_per_commit(ctx, "log")
