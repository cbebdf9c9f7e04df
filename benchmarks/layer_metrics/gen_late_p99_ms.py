"""Load generator: 99th percentile of (actual send - due time) in an open
loop.  A starved generator must not read as a fast server.  A closed loop
sends when a caller is free, so it has no due time and nothing to read."""
from benchmarks.harness.stats import percentile


def read(ctx):
    r = ctx["requests"]
    late = [(s - d) * 1e3 for s, d in zip(r["sent"], r["due"])]
    if not late or max(late) == 0.0:
        return None
    return percentile(late, 0.99)
