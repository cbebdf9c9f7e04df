"""Consensus: upper quartile of ``server.apply_queue`` over the traced
requests: from the commit index covering the entry
(division.py:on_commit_advance_now, inline at ack intake or by a tick) to
its apply starting (division.py:_apply_one): the wake of the apply loop and
whatever the loop runs first."""
from benchmarks.harness.stats import percentile


def read(ctx):
    from ratis_tpu.trace import export
    if not hasattr(export, "session_rows"):
        return None     # the driver reads PR 25's parent with this file too
    ms = export.session_durations_ms("server.apply_queue")
    return percentile(ms, 0.75) if ms else None
