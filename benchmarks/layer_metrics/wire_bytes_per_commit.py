"""Wire: bytes the servers' process wrote to its sockets during the trace
session per acknowledged write: the counter ``wire.bytes``
(transport/coalesce.py:WriteCoalescer) over the window's acknowledged
writes."""


def read(ctx):
    from ratis_tpu.trace import TRACER
    if not hasattr(TRACER, "session"):
        return None     # the driver reads PR 25's parent with this file too
    sess = TRACER.session()
    if not sess["t_on"] or not ctx["acked_in_window"]:
        return None
    nbytes = sess["counters"].get("wire.bytes", 0)
    return nbytes / ctx["acked_in_window"] if nbytes else None
