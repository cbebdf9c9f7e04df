"""Consensus: median of ``replicate.rtt``: from an append frame cut for a
destination to its reply taken in (replication.py:PeerSender.sweep_collect
-> _send), a follower's log flush inside it."""
from benchmarks.harness.stats import percentile


def read(ctx):
    from ratis_tpu.trace import export
    if not hasattr(export, "session_rows"):
        return None     # the driver reads PR 25's parent with this file too
    ms = export.session_durations_ms("replicate.rtt")
    return percentile(ms, 0.50) if ms else None
