"""State machine data: median of ``server.data_wait``: how long the log
worker held a batch back for the data_write of one of its records before
writing and fsyncing it (segmented.py:LogWorker._after_gates): what the data
before the record added to ``server.flush_wait``; 0 for a record whose data
came first.  It leaves 0 when a log's queue gets shorter than a data write
(fsyncs merged, a faster log device) or the data writes slower."""
from benchmarks.harness.stats import percentile


def read(ctx):
    from ratis_tpu.trace import STAGE_NAMES, export
    if "server.data_wait" not in STAGE_NAMES:
        return None     # the driver reads this PR's parent with this file too
    ms = export.session_durations_ms("server.data_wait")
    return percentile(ms, 0.50) if ms else None
