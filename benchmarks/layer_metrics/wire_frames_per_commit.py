"""Wire: frames the servers' process wrote to its sockets during the trace
session per acknowledged write: the counter ``wire.frames``
(transport/coalesce.py:WriteCoalescer) over the window's acknowledged
writes.  Append frames, their replies and the clients' replies; the
generator's requests are written by its own process and not counted."""


def read(ctx):
    from ratis_tpu.trace import TRACER
    if not hasattr(TRACER, "session"):
        return None     # the driver reads PR 25's parent with this file too
    sess = TRACER.session()
    if not sess["t_on"] or not ctx["acked_in_window"]:
        return None
    frames = sess["counters"].get("wire.frames", 0)
    return frames / ctx["acked_in_window"] if frames else None
