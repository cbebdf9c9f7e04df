"""Stream plane: bytes the primaries wrote into their own channels during
the trace session, in MB (10^6 B) per second of it: the counter
``stream.bytes`` of the key ``primary`` (server/datastream.py:_write_local:
every DATA packet's bytes once, at the peer the client sent them to) over
the session's length.  Each byte is written twice more, by the successors
(the key ``successor``)."""


def read(ctx):
    from ratis_tpu.trace import TRACER
    sess = TRACER.session()
    if not sess["t_on"] or not sess["t_off"]:
        return None
    nbytes = sess["keyed"].get("stream.bytes", {}).get("primary")
    if not nbytes:
        return None     # (a program without the counter, as this PR's parent)
    return nbytes / 1e6 / ((sess["t_off"] - sess["t_on"]) / 1e9)
