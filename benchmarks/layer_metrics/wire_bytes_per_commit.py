"""Wire: bytes the servers' process handed to the socket layer during the
trace session per acknowledged operation: the counter ``wire.bytes``
(TCP: transport/tcp.py:_FramedProtocol._flush; gRPC:
transport/grpc.py:_WireCount.wrote) over the window's acknowledged
operations."""


def read(ctx):
    from ratis_tpu.trace import TRACER
    if not hasattr(TRACER, "session"):
        return None     # the driver reads PR 25's parent with this file too
    sess = TRACER.session()
    if not sess["t_on"] or not ctx["acked_in_window"]:
        return None
    nbytes = sess["counters"].get("wire.bytes", 0)
    return nbytes / ctx["acked_in_window"] if nbytes else None
