"""Stream plane: median of ``stream.close``: from a stream's CLOSE packet
off the socket at its primary to ``submit_data_stream_request`` called
(server/datastream.py:_on_close_data, _finish): the packets' pipeline
drained, the CLOSE forwarded down the chain and acknowledged, the primary's
own channel forced and closed.  What a file waits between its last byte and
its raft write."""
from benchmarks.harness.stats import percentile


def read(ctx):
    from ratis_tpu.trace import STAGE_NAMES, export
    if "stream.close" not in STAGE_NAMES:
        return None     # the driver reads this PR's parent with this file too
    ms = export.session_durations_ms("stream.close")
    return percentile(ms, 0.50) if ms else None
