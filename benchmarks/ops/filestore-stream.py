"""The operation ``filestore-stream``: upstream's ``filestore datastream``,
one group at a time.  A group's k-th request of the window is the stream of
its file ``<prefix>s<k>``: a header ``{op: stream, path, size, packet}`` to
the group's appointed leader as primary, with the chain primary -> next peer
-> last peer as routing table; the file's bytes as packets of the traffic's
packet size, all of them outstanding (the client's window is 16); then
CLOSE, whose ack carries the reply of the one raft write the stream ends in.
The bytes of a packet are a function of the group's id, the path and the
packet's offset (``payload_bytes``, which the plain reference has a copy
of).  What was sent goes to the generator as ASCII (``STREAM <path> <size>
<packet>``), and so does the reply (``OK <path> <size>``).

``stream_sender`` is also what the rounds' operation
(``filestore-stream-round.py``) is made of: the same stream, one packet
long, to paths of its own (``<prefix>r<k>``)."""

import random
import uuid

import msgpack


def payload_bytes(group_uuid: str, path: str, offset: int, length: int
                  ) -> bytes:
    return random.Random(f"{group_uuid}:{path}:{offset}").randbytes(length)


class _Ascii:
    """A reply as the generator reads one: ``success``, ``message.content``
    (ASCII), ``exception``."""

    def __init__(self, reply, content: bytes) -> None:
        self.success, self.exception = reply.success, reply.exception
        self.content = content

    @property
    def message(self):
        return self


async def _stream(client, header: bytes, routing, primary, path: str,
                  packets: list) -> _Ascii:
    out = await client.data_stream().stream(header, routing_table=routing,
                                            primary=primary)
    for data in packets:
        await out.write_async(data)
    reply = await out.close_async()
    if not reply.success:
        return _Ascii(reply, b"")
    got = msgpack.unpackb(bytes(reply.message.content), raw=False)
    text = (f"OK {path} {got['size']}" if got.get("ok")
            else f"ERR {got.get('error')}")
    return _Ascii(reply, text.encode("ascii", "replace"))


def stream_sender(client, traffic: dict, letter: str, size: int):
    """The sender of one group's streams of ``size`` bytes to the paths
    ``<prefix><letter><k>``, k counting this sender's requests."""
    from ratis_tpu.protocol.routing import RoutingTable
    packet = int(traffic["stream"]["packet_bytes"])
    prefix = traffic["payload_ascii"] + letter
    group = str(uuid.UUID(bytes=client.group_id.to_bytes()))
    # group i's appointed leader is server i mod peers: the primary, and
    # the head of the chain over the other peers in the servers' order
    peers = list(client.group.peers)
    lead = int(traffic["group_index"]) % len(peers)
    chain = peers[lead:] + peers[:lead]
    routing = RoutingTable.chain([p.id for p in chain])
    sent = 0

    def send():
        nonlocal sent
        k, sent = sent, sent + 1
        path = f"{prefix}{k}"
        header = msgpack.packb({"op": "stream", "path": path, "size": size,
                                "packet": packet}, use_bin_type=True)
        packets = [payload_bytes(group, path, off, min(packet, size - off))
                   for off in range(0, size, packet)]
        return (f"STREAM {path} {size} {packet}",
                _stream(client, header, routing, chain[0], path, packets))
    return send


def sender(client, traffic: dict):
    return stream_sender(client, traffic, "s",
                         int(traffic["stream"]["file_bytes"]))
