"""One small reader per per-layer metric, found by the metric's name:
``benchmarks/layer_metrics/<name>.py`` with ``read(ctx) -> float | None``.
A reader that finds nothing to read returns None and the metric is left out
of the line.  ``ctx`` (built by benchmarks/run.py) holds: ``c0`` / ``c1`` the
program's counters at the window's start and close, ``requests`` the
generator's rows, ``seconds``, ``acked_in_window``, ``end_to_end`` (the
window's summary), ``lag_ms``, ``compiled_in_window``, ``trace``
(trace_reduce.reduce's result or None), ``device`` and ``config``."""
