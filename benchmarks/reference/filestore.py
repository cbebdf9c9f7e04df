"""Plain reference of the FileStore deployment's semantics, independent of
the program: what each WRITE must answer, how many committed writes every
replica's state machine must count afterwards, what a durable replica must
hold on disk (the header of every acknowledged write in its segmented log,
and the write's bytes in its files, at the stated offset), and the quorum
rule a leader's commit index obeys.  Imports nothing of ratis_tpu: the
segment reader, the quorum rule and the generator of the bytes are copies.
"""

from __future__ import annotations

import functools
import os
import random
import struct
import zlib
from typing import Iterable, Sequence

import msgpack

# the segmented log's on-disk format, read here with nothing of the program:
#   file := MAGIC record* ; record := u32_le len | u32_le crc32(payload) | payload
#   payload := msgpack {t, i, k, s: {c, id, d: msgpack header, sx: data size}}
SEGMENT_MAGIC = b"RTPULOG\x01"
_REC_HDR = struct.Struct("<II")
# A record that holds a WRITE's header is a few hundred bytes; one that held
# the write's bytes as well would not be a header beside the data.
HEADER_RECORD_MAX = 4096
# a replica's files lie beside its log directory (<group>/current):
#   <group>/sm/files/<path> closed, <group>/sm/files/.uc/<path> open
FILES = ("sm", "files")
UNDER_CONSTRUCTION = ".uc"


@functools.lru_cache(maxsize=64)   # a group's three replicas come in a row
def payload_bytes(group_uuid: str, path: str, offset: int, length: int
                  ) -> bytes:
    """The bytes a group's write carries: a function of the group's id (made
    from the seed), the path and the offset."""
    return random.Random(f"{group_uuid}:{path}:{offset}").randbytes(length)


class FileStoreReference:
    """Per group, path -> [bytes written so far, closed].  ``apply`` is what
    the state machine answers to a request, both as ASCII: ``WRITE <path>
    <offset> <length> <close>`` answers ``OK <path> <offset> <length>`` if
    the file is open (or new) and ``offset`` is its length so far; anything
    else is refused and leaves the file as it was."""

    def __init__(self, groups: int) -> None:
        self.files: list[dict] = [{} for _ in range(groups)]
        self.writes = [0] * groups

    def apply(self, group: int, payload: str) -> bytes:
        word, path, offset, length, close = payload.split(" ")
        if word != "WRITE":
            raise ValueError(f"the filestore reference has no semantics "
                             f"for the payload {payload!r}")
        offset, length = int(offset), int(length)
        f = self.files[group].setdefault(path, [0, False])
        if f[1] or offset != f[0]:
            return b"REFUSED"
        f[0] += length
        f[1] = close == "1"
        self.writes[group] += 1
        return f"OK {path} {offset} {length}".encode()


def judge_answers(groups: int, parts: Sequence[dict]) -> dict:
    """Every request of the run's ``parts`` in order (warm-up, window,
    settle; each the generator's rows ``group`` / ``payload`` / ``answer``)
    against the reference run over the same requests in each group's order.
    A request that never got an answer is counted apart, and breaks the
    order of those behind it in its group: those are held only to echo what
    they asked for."""
    ref = FileStoreReference(groups)
    submitted = [0] * groups
    for part in parts:
        for g in part["group"]:
            submitted[g] += 1
    broken = [False] * groups
    wrong = never = 0
    acked = [0] * groups
    samples = []
    for part in parts:
        for g, payload, ans in zip(part["group"], part["payload"],
                                   part["answer"]):
            expected = ref.apply(g, payload)
            if ans is None:
                never += 1
                broken[g] = True
                continue
            acked[g] += 1
            if broken[g]:
                _, path, offset, length, _ = payload.split(" ")
                expected = f"OK {path} {offset} {length}".encode()
            if ans.encode() != expected:
                wrong += 1
                if len(samples) < 4:
                    samples.append({"group": g, "answer": ans,
                                    "reference": expected.decode()})
    return {"answers_wrong": wrong, "never_answered": never,
            "acked_per_group": acked, "submitted_per_group": submitted,
            "answers_compared": sum(acked), "samples": samples}


def majority_min(values: Sequence[int], members: Sequence[bool]) -> int:
    """The greatest v that a majority of the members has reached."""
    vs = sorted(v for v, m in zip(values, members) if m)
    if not vs:
        raise ValueError("no members")
    return vs[(len(vs) - 1) // 2]


def leader_commit(match_index: Sequence[int], self_slot: int,
                  flush_index: int, members: Sequence[bool]) -> int:
    """Raft's commit rule for a leader with a stable configuration: the
    majority's match index, the leader's own slot counting what it has
    flushed."""
    eff = [flush_index if i == self_slot else v
           for i, v in enumerate(match_index)]
    return majority_min(eff, members)


def write_headers(path: str, needle: bytes) -> list[dict]:
    """The WRITE headers {path, offset, length, close, sync} of the
    CRC-valid records of one segment file that carry ``needle`` and nothing
    the size of a write's bytes.  Stops at the first torn record."""
    with open(path, "rb") as f:
        data = f.read()
    if not data.startswith(SEGMENT_MAGIC):
        return []
    out, off = [], len(SEGMENT_MAGIC)
    while off + _REC_HDR.size <= len(data):
        ln, crc = _REC_HDR.unpack_from(data, off)
        end = off + _REC_HDR.size + ln
        if end > len(data):
            break
        payload = data[off + _REC_HDR.size:end]
        if zlib.crc32(payload) != crc:
            break
        off = end
        if needle not in payload or ln > HEADER_RECORD_MAX:
            continue
        entry = msgpack.unpackb(payload, raw=False)
        header = msgpack.unpackb(entry["s"]["d"], raw=False)
        if header.get("op") == "write":
            out.append(header)
    return out


def _read_at(paths: Sequence[str], offset: int, length: int) -> bytes:
    for p in paths:
        try:
            with open(p, "rb") as f:
                return os.pread(f.fileno(), length, offset)
        except FileNotFoundError:
            continue
    return b""


def durable_writes(log_dir: str, needle: bytes) -> int:
    """Writes one replica holds durably: the lesser of the write headers in
    its segment files (``log_*`` of its group directory's ``current``) and
    of those whose bytes, read from the replica's files beside the log
    (the closed file in place, else the one under construction), equal the
    reference's; 0 when the directory is not there."""
    try:
        names = [n for n in os.listdir(log_dir) if n.startswith("log_")]
    except FileNotFoundError:
        return 0
    headers = [h for n in names
               for h in write_headers(os.path.join(log_dir, n), needle)]
    group_dir = os.path.dirname(os.path.normpath(log_dir))
    group_uuid = os.path.basename(group_dir)
    root = os.path.join(group_dir, *FILES)
    read_back = 0
    for h in headers:
        expected = payload_bytes(group_uuid, h["path"], h["offset"],
                                 h["length"])
        got = _read_at((os.path.join(root, h["path"]),
                        os.path.join(root, UNDER_CONSTRUCTION, h["path"])),
                       h["offset"], h["length"])
        read_back += got == expected
    return min(len(headers), read_back)


def replicas_holding(values: Iterable[int], at_least: int, at_most: int
                     ) -> int:
    """Replicas whose count of committed writes lies in [at_least,
    at_most]."""
    return sum(1 for v in values if at_least <= v <= at_most)
