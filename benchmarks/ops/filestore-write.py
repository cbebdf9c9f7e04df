"""The operation ``filestore-write``: upstream's ``filestore loadgen``, one
group at a time.  A group's k-th request is write ``k mod n`` of its file
``k div n`` (``n`` = the traffic's file size over its write size):
``{op: write, path: loadgen/f<k div n>, offset: (k mod n) x write size,
close on the n-th, sync}``, the bytes a function of the group's id, the path
and the offset (``payload_bytes``, which the plain reference has a copy of).
The position lives in the sender's closure: warm-up, window and settle walk
one sequence.  What was sent goes to the generator as ASCII (``WRITE <path>
<offset> <length> <close>``), and so does the reply (``OK <path> <offset>
<length>``): the generator decodes answers as ASCII."""

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


async def _as_ascii(pending) -> _Ascii:
    reply = await pending
    if not reply.success:
        return _Ascii(reply, b"")
    got = msgpack.unpackb(bytes(reply.message.content), raw=False)
    text = (f"OK {got['path']} {got['offset']} {got['length']}"
            if got.get("ok") else f"ERR {got.get('error')}")
    return _Ascii(reply, text.encode("ascii", "replace"))


def sender(client, traffic: dict):
    w = traffic["write"]
    size, per_file = int(w["bytes"]), int(w["file_bytes"]) // int(w["bytes"])
    sync, prefix = bool(w["sync"]), traffic["payload_ascii"]
    group = str(uuid.UUID(bytes=client.group_id.to_bytes()))
    api = client.io()
    sent = 0

    def send():
        nonlocal sent
        k, sent = sent, sent + 1
        path = f"{prefix}f{k // per_file}"
        offset = (k % per_file) * size
        close = k % per_file == per_file - 1
        request = msgpack.packb(
            {"op": "write", "path": path, "offset": offset, "close": close,
             "sync": sync, "data": payload_bytes(group, path, offset, size)},
            use_bin_type=True)
        return (f"WRITE {path} {offset} {size} {int(close)}",
                _as_ascii(api.send(request)))
    return send
