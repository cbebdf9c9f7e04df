"""Stream plane: what one packet's write into the peer's own file takes as
the loop sees it, from the packet's bytes queued to its stream's writer lane
(``channel.submit_write``) to the write's completion seen on the loop, by the
callback of the lane's pass that wrote it.  The mean of the ``stream.write``
rows, all peers alike (server/datastream.py:_queue_local)."""


def read(ctx):
    from ratis_tpu.trace import STAGE_NAMES, export
    if "stream.write" not in STAGE_NAMES:
        return None     # the driver reads this PR's parent with this file too
    rows = export.session_rows("stream.write")
    if rows is None or not len(rows):
        return None
    return float(rows[:, 2].mean()) / 1e6
