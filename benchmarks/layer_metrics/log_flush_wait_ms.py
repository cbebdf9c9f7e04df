"""Log: median of ``server.flush_wait`` over the traced requests: from the
leader's in-memory append to its own log worker's flush seen on the loop
(division.py:_write_impl -> _on_log_flush, which the worker's one call back
to the loop a batch runs: segmented.py:LogWorker._completed ->
log/base.py:_on_record_flushed): queue wait, write, fsync and the hop back
to the loop."""
from benchmarks.harness.stats import percentile


def read(ctx):
    from ratis_tpu.trace import export
    if not hasattr(export, "session_rows"):
        return None     # the driver reads PR 25's parent with this file too
    ms = export.session_durations_ms("server.flush_wait")
    return percentile(ms, 0.50) if ms else None
