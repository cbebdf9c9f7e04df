"""Loop time by layer, wire: milliseconds of the busiest server loop charged to
the wire: the transports' socket callbacks (transport/*) and the protocol
codec, with the work spans tcp.read, wire.flush, grpc.read, grpc.write and
codec.*, over the trace session, per acknowledged operation of the window
(the program's counter loop.layer_ns, ratis_tpu/trace/tracer.py:LoopClock;
benchmarks/harness/loop_layers.py)."""


def read(ctx):
    from benchmarks.harness.loop_layers import ms_per_commit
    return ms_per_commit(ctx, "wire")
