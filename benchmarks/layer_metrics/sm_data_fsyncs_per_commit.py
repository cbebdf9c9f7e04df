"""State machine data: forces issued behind data_write during the trace
session per acknowledged write, all replicas together: the counter
``sm.data_fsyncs`` (models/filestore.py:data_write, data_flush) over the
window's acknowledged writes."""


def read(ctx):
    from ratis_tpu.trace import TRACER
    sess = TRACER.session()
    if not sess["t_on"] or not ctx["acked_in_window"]:
        return None
    n = sess["counters"].get("sm.data_fsyncs")
    return None if n is None else n / ctx["acked_in_window"]
