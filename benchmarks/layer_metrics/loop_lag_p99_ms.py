"""Server edge / host event loop: 99th percentile overshoot of a benchmark
coroutine that sleeps 10 ms on the servers' own loop during the window."""
from benchmarks.harness.stats import percentile


def read(ctx):
    lag = ctx["lag_ms"]
    return percentile(lag, 0.99) if lag else None
