"""Stream plane: TCP connections the servers opened to a successor or
accepted on their stream ports during the trace session, per acknowledged
stream: the counter ``stream.connects`` (keys ``opened``,
server/datastream.py:_RemoteStream.connect, and ``accepted``,
transport/datastream.py:PeerConnection.connection_made on its ``server``
side) over the window's acknowledged streams.  A stream down a chain of
three is 5 today (the client's, and each of two legs at both its ends); 0
once connections are kept."""


def read(ctx):
    from ratis_tpu.trace import TRACER
    sess = TRACER.session()
    if not sess["t_on"] or not ctx["acked_in_window"]:
        return None
    n = sess["counters"].get("stream.connects")
    return None if n is None else n / ctx["acked_in_window"]
