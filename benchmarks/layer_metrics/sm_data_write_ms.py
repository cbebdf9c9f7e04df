"""State machine data: how long ONE data_write takes with its force, on the
writing thread: the mean of the ``sm.data_write`` work spans (one entry's
bytes into its file, models/filestore.py:_UnderConstruction.write) plus the
mean of the ``sm.data_fsync`` spans behind them (summed duration over summed
tag = files)."""


def read(ctx):
    from ratis_tpu.trace import STAGE_NAMES, export
    if "sm.data_write" not in STAGE_NAMES:
        return None     # the driver reads this PR's parent with this file too
    writes = export.session_rows("sm.data_write")
    if writes is None or not len(writes):
        return None
    ms = float(writes[:, 2].mean()) / 1e6
    forces = export.session_rows("sm.data_fsync")
    files = int(forces[:, 3].sum()) if forces is not None else 0
    if files:
        ms += float(forces[:, 2].sum()) / files / 1e6
    return ms
