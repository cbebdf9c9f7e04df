"""Consensus: groups' append items a frame the leaders cut, over the trace
session: the counter ``replicate.items`` over ``replicate.frames``
(ratis_tpu/server/replication.py:PeerSender.sweep_collect, beside the
scheduler's ``envelopes`` / ``items``; every lane of every server together).
A lane whose window of unanswered frames is full cuts its next frame when a
reply frees a slot, with everything that gathered meanwhile: fewer slots,
fuller frames, fewer messages a commit.  Nothing to read in a program
without the counters."""


def read(ctx):
    from ratis_tpu.trace import TRACER
    if not hasattr(TRACER, "session"):
        return None
    sess = TRACER.session()
    if not sess["t_on"]:
        return None
    frames = sess["counters"].get("replicate.frames", 0)
    items = sess["counters"].get("replicate.items", 0)
    return items / frames if frames else None
