"""Loop time by layer, sm: milliseconds of the busiest server loop charged to
the state machine: server/statemachine.py and models/* callbacks on the
loop, the link, apply and query the division awaits (Tracer.enter_layer,
up to their first suspension), and the work spans sm.data_write and
sm.data_fsync where they run there, over the trace session, per acknowledged operation of the window (the
program's counter loop.layer_ns, ratis_tpu/trace/tracer.py:LoopClock;
benchmarks/harness/loop_layers.py)."""


def read(ctx):
    from benchmarks.harness.loop_layers import ms_per_commit
    return ms_per_commit(ctx, "sm")
