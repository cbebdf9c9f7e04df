"""Wire, under gRPC: gRPC messages the servers' process wrote and read during
the trace session per acknowledged write: the counters ``grpc.messages_out``
+ ``grpc.messages_in`` (ratis_tpu/transport/grpc.py: every stream message of
the bidi append and client streams at both of their ends, every unary
request and reply) over the window's acknowledged writes.  A message is what
grpc.aio is called for once (one ``write``, one ``yield``, one read), which
is what the transport costs the loop; it falls only when a message carries
more chunks (``grpc_chunks_per_message``).  The generator's requests are
written by its own process and counted here once, as read.  Nothing to read
over another transport, or in a program without the counters."""


def read(ctx):
    from ratis_tpu.trace import TRACER
    if not hasattr(TRACER, "session"):
        return None
    sess = TRACER.session()
    if not sess["t_on"] or not ctx["acked_in_window"]:
        return None
    counters = sess["counters"]
    messages = (counters.get("grpc.messages_out", 0)
                + counters.get("grpc.messages_in", 0))
    return messages / ctx["acked_in_window"] if messages else None
