"""Plain reference of the FileStore's WRITE semantics for the tests: a dict
of path -> bytes and open / closed, the reply to each write, and so the
bytes of every file after any prefix of a request list.  Imports nothing of
ratis_tpu."""

from __future__ import annotations

import random


class FileStoreModel:
    def __init__(self) -> None:
        self.files: dict[str, bytearray] = {}
        self.closed: set[str] = set()
        self.writes = 0

    def write(self, path: str, offset: int, data: bytes, close: bool):
        """The reply's {path, offset, length}, or None where the write is
        refused (a closed file, an offset that is not the file's length so
        far), which leaves the file as it was."""
        if path in self.closed or offset != len(self.files.get(path, b"")):
            return None
        self.files.setdefault(path, bytearray()).extend(data)
        if close:
            self.closed.add(path)
        self.writes += 1
        return {"path": path, "offset": offset, "length": len(data)}


def seeded_requests(seed: int, files: int, prefix: str = "d/f") -> list[dict]:
    """``files`` files of 1-5 writes of 1-4 KiB of seeded random bytes, in
    offset order, ``close`` on each file's last write, ``sync`` on some."""
    rng = random.Random(seed)
    out = []
    for i in range(files):
        n, offset = rng.randint(1, 5), 0
        for k in range(n):
            data = rng.randbytes(rng.randint(1024, 4096))
            out.append({"op": "write", "path": f"{prefix}{i}",
                        "offset": offset, "close": k == n - 1,
                        "sync": rng.random() < 0.5, "data": data})
            offset += len(data)
    return out
