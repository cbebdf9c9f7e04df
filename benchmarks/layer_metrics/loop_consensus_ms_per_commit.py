"""Loop time by layer, consensus: milliseconds of the busiest server loop
charged to consensus: division.py's append, commit and apply scheduling,
leader.py, replication.py, election.py and heartbeats, the appends' handlers
(the dispatch in RaftServer._handle_server_rpc names them) and the work
spans replicate.sweep and ack.intake, over the trace session, per
acknowledged operation of the window (the program's counter loop.layer_ns,
ratis_tpu/trace/tracer.py:LoopClock; benchmarks/harness/loop_layers.py)."""


def read(ctx):
    from benchmarks.harness.loop_layers import ms_per_commit
    return ms_per_commit(ctx, "consensus")
