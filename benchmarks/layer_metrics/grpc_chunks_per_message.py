"""Wire, under gRPC: ``[call_id, payload]`` chunks and replies carried by
one written gRPC message, over the trace session: the counter
``grpc.chunks_out`` over ``grpc.messages_out`` (ratis_tpu/transport/grpc.py;
a unary request or reply counts as one chunk in one message).  1.0 while
nothing batches (``raft.tpu.grpc.flush-micros`` 0, the default: one chunk a
stream message); above it where the stream framing coalesces.  Nothing to
read over another transport, or in a program without the counters."""


def read(ctx):
    from ratis_tpu.trace import TRACER
    if not hasattr(TRACER, "session"):
        return None
    sess = TRACER.session()
    if not sess["t_on"]:
        return None
    messages = sess["counters"].get("grpc.messages_out", 0)
    chunks = sess["counters"].get("grpc.chunks_out", 0)
    return chunks / messages if messages else None
