"""Stream plane: what one packet's write into the peer's own file takes as
the loop sees it, from ``channel.write`` called to its return: the hop to a
thread, the write, the hop back.  The mean of the ``stream.write`` rows, all
peers alike (server/datastream.py:_write_local)."""


def read(ctx):
    from ratis_tpu.trace import STAGE_NAMES, export
    if "stream.write" not in STAGE_NAMES:
        return None     # the driver reads this PR's parent with this file too
    rows = export.session_rows("stream.write")
    if rows is None or not len(rows):
        return None
    return float(rows[:, 2].mean()) / 1e6
