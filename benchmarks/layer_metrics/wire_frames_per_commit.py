"""Wire: rpc frames the servers' process handed to the socket layer during
the trace session per acknowledged operation: the counter ``wire.frames``
(TCP: transport/tcp.py:_FramedProtocol._flush, after a loop pass's one
write; gRPC: transport/grpc.py:_WireCount.wrote, once grpc.aio has taken a
message) over the window's acknowledged operations.  Append frames, their
replies and the clients' replies; the generator's requests are written by
its own process and not counted."""


def read(ctx):
    from ratis_tpu.trace import TRACER
    if not hasattr(TRACER, "session"):
        return None     # the driver reads PR 25's parent with this file too
    sess = TRACER.session()
    if not sess["t_on"] or not ctx["acked_in_window"]:
        return None
    frames = sess["counters"].get("wire.frames", 0)
    return frames / ctx["acked_in_window"] if frames else None
