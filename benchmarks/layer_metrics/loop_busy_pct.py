"""Server edge / host event loop: the share of the trace session in which
the busiest server loop was NOT blocked in its selector, from the program's
own counters ``loop.select_ns`` and ``loop.iterations``
(ratis_tpu/trace/tracer.py:instrument_loop, installed by RaftServer.start
and by each loop shard): 100 x (1 - select time / session length).  What
is not selector time is the loop running callbacks."""


def read(ctx):
    from ratis_tpu.trace import TRACER
    if not hasattr(TRACER, "session"):
        return None     # the driver reads PR 25's parent with this file too
    sess = TRACER.session()
    if not sess["t_on"] or not sess["t_off"]:
        return None
    length = sess["t_off"] - sess["t_on"]
    iterations = sess["keyed"].get("loop.iterations", {})
    waits = [ns for key, ns in sess["keyed"].get("loop.select_ns", {}).items()
             if iterations.get(key, 0) > 0]
    if not waits or length <= 0:
        return None
    return 100.0 * (1.0 - min(waits) / length)
