"""State machine data: median of ``server.data_wait``, one row a gated
record, on the log worker's own thread (segmented.py:LogWorker._write): from
when the thread could have taken the record (it met the record's shut gate
at the head of its queue) to when it saw the record's data_write done: what
the data before the record added to ``server.flush_wait``; 0 for a record
whose data came first.  It leaves 0 when a log's queue gets shorter than a data write
(fsyncs merged, a faster log device) or the data writes slower."""
from benchmarks.harness.stats import percentile


def read(ctx):
    from ratis_tpu.trace import STAGE_NAMES, export
    if "server.data_wait" not in STAGE_NAMES:
        return None     # the driver reads this PR's parent with this file too
    ms = export.session_durations_ms("server.data_wait")
    return percentile(ms, 0.50) if ms else None
