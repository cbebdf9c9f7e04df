"""Stream plane: what one DATA packet takes at its primary, from the packet
off the socket to its ack written to the client's connection: the local
channel write, the copy sent down the chain, and the successors' acks back.
The mean of the ``stream.packet`` rows whose tag (the packet's bytes) is
positive: from ``_on_data``, inside the connection's read callback, to
``_PacketAck.part_done``, where the last of the packet's parts queues its
ack (server/datastream.py; a successor's rows carry the tag negated)."""


def read(ctx):
    from ratis_tpu.trace import STAGE_NAMES, export
    if "stream.packet" not in STAGE_NAMES:
        return None     # the driver reads this PR's parent with this file too
    rows = export.session_rows("stream.packet")
    if rows is None:
        return None
    rows = rows[rows[:, 3] > 0]
    return float(rows[:, 2].mean()) / 1e6 if len(rows) else None
