"""Loop time by layer, edge: milliseconds of the busiest server loop charged to
the server's edge: client routing (server/server.py, server/shards.py), the
reply fan-out and the client writes' handlers (the dispatch in
RaftServer._handle_client_request names them), over the trace session, per
acknowledged operation of the window (the program's counter loop.layer_ns,
ratis_tpu/trace/tracer.py:LoopClock; benchmarks/harness/loop_layers.py)."""


def read(ctx):
    from benchmarks.harness.loop_layers import ms_per_commit
    return ms_per_commit(ctx, "edge")
