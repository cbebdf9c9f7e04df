"""Server edge / host event loop: median over the traced requests of the
session of ``server.apply`` + ``server.reply`` + ``server.respond``: from
the start of the state machine's apply to the reply handed to the
connection's batched write path.  Ring rows of ratis_tpu.trace:
division.py:_apply_one and _write_impl, tcp.py:_DeferredReplyFanout."""
from benchmarks.harness.stats import percentile


def read(ctx):
    from ratis_tpu.trace import export
    if not hasattr(export, "session_rows"):
        return None     # the driver reads PR 25's parent with this file too
    ns = export.session_request_sums_ns(
        ("server.apply", "server.reply", "server.respond"))
    return percentile([v / 1e6 for v in ns], 0.50) if ns else None
