"""Engine, host half: mean per batched dispatch of ``engine.fetch``
(engine.py:_tick_batched_dispatch): the step's outputs brought to the host,
which waits out the kernel and the device-to-host copy."""


def read(ctx):
    from ratis_tpu.trace import export
    if not hasattr(export, "session_rows"):
        return None     # the driver reads PR 25's parent with this file too
    dispatches = export.session_rows("engine.dispatch")
    if dispatches is None or not len(dispatches):
        return None
    fetch = export.session_rows("engine.fetch")
    return float(fetch[:, 2].sum()) / len(dispatches) / 1e6
