"""Consensus: the share of the senders' drain passes that left appenders
marked because every slot of the lane's window was taken, over the trace
session: 100 x the counter ``replicate.window_full`` over
``replicate.sweeps`` (ratis_tpu/server/replication.py:PeerSender.
sweep_collect: a pass that met marks, and one of those that ended with marks
and no free slot).  Near 0 the window is never in the way and a frame leaves
in the pass that marked it; near 100 the lane is pinned and what is marked
waits for a reply, so ``append_rtt_ms`` is in every commit.  Nothing to read
in a program without the counters."""


def read(ctx):
    from ratis_tpu.trace import TRACER
    if not hasattr(TRACER, "session"):
        return None
    sess = TRACER.session()
    if not sess["t_on"]:
        return None
    sweeps = sess["counters"].get("replicate.sweeps", 0)
    full = sess["counters"].get("replicate.window_full", 0)
    return 100.0 * full / sweeps if sweeps else None
