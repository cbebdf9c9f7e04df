"""Engine, host half: mean per batched dispatch of ``engine.pack`` +
``engine.launch`` (engine.py:_tick_batched_dispatch): the events packed, the
uploads and the step call returning — the host's work before the device's
answer is waited for.  Every dispatch of the session has a row."""


def read(ctx):
    from ratis_tpu.trace import export
    if not hasattr(export, "session_rows"):
        return None     # the driver reads PR 25's parent with this file too
    dispatches = export.session_rows("engine.dispatch")
    if dispatches is None or not len(dispatches):
        return None
    ns = export.session_rows("engine.pack")[:, 2].sum() \
        + export.session_rows("engine.launch")[:, 2].sum()
    return float(ns) / len(dispatches) / 1e6
