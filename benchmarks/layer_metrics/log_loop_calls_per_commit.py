"""Log: calls the log plane scheduled onto an event loop during the trace
session per acknowledged write (all replicas together): the counter
``log.loop_calls`` -- each log worker thread's one call back a batch and loop
(server/log/segmented.py:LogWorker._call_back) and each state-machine data
write's completion seen on the loop (server/log/base.py:_on_data_written) --
over the window's acknowledged writes.  It falls when batches grow (one call
carries more records) and would rise with any per-record hop back to the
loop."""


def read(ctx):
    from ratis_tpu.trace import TRACER
    if not hasattr(TRACER, "session"):
        return None
    sess = TRACER.session()
    if not sess["t_on"] or not ctx["acked_in_window"]:
        return None
    calls = sess["counters"].get("log.loop_calls", 0)
    return calls / ctx["acked_in_window"] if calls else None
