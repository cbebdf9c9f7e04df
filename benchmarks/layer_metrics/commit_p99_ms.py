"""Served path as the client sees it: 99th percentile commit latency over all
requests due in the window (open loop: from the due time; closed loop: from
send), a request that never got its answer sitting at the top: the window's
own summary (benchmarks/run.py ``summarize``), from the same rows as the
end-to-end median and upper quartile.  Beside them and not end to end
because one to three ~0.1 s stalls of the host loop decide it from run to
run: above the upper quartile no percentile's spread admits a bound (PERF.md
section 2)."""


def read(ctx):
    return ctx["end_to_end"]["commit_p99_ms"] if ctx["requests"]["due"] \
        else None
