"""Server edge / host event loop: median over the traced requests of the
session of ``server.route`` + ``server.txn_start`` + ``server.append``: from
the request's arrival at the leader (decoded, id minted at the server's
ingress) to its entry appended in memory.  Ring rows of ratis_tpu.trace:
server.py:_handle_client_request, division.py:_write_impl."""
from benchmarks.harness.stats import percentile


def read(ctx):
    from ratis_tpu.trace import export
    if not hasattr(export, "session_rows"):
        return None     # the driver reads PR 25's parent with this file too
    ns = export.session_request_sums_ns(
        ("server.route", "server.txn_start", "server.append"))
    return percentile([v / 1e6 for v in ns], 0.50) if ns else None
