"""Log: how long ONE fsync takes, from the ``log.fsync`` work spans on the
log worker's thread (segmented.py:LogWorker._do_io): the spans' summed
duration over their summed tag (the distinct files of each batch, one fsync
each)."""


def read(ctx):
    from ratis_tpu.trace import export
    if not hasattr(export, "session_rows"):
        return None     # the driver reads PR 25's parent with this file too
    rows = export.session_rows("log.fsync")
    if rows is None or not len(rows):
        return None
    files = int(rows[:, 3].sum())
    return float(rows[:, 2].sum()) / files / 1e6 if files else None
