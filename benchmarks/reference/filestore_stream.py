"""Plain reference of the FileStore stream deployment's semantics
(``ratis-filestore-stream-3x1k``), independent of the program: what each
STREAM must answer, how many committed streams every replica's state machine
must count afterwards, what a durable replica must hold on disk (the header
of every acknowledged stream in its segmented log, and the stream's whole
file, byte for byte, in a file of the replica's own), and the quorum rule a
leader's commit index obeys.  Imports nothing of ratis_tpu: the segment
reader, the quorum rule and the generator of the bytes are copies, as in
``filestore.py``.

A stream adds one entry to its group's log (its header); that is what the
harness takes an answered request to add where a reference does not say
(``compare.window_entries``), in the window and in the rounds alike.
"""

from __future__ import annotations

import functools
import os
import random
import struct
import zlib
from typing import Iterable, Optional, Sequence

import msgpack

# the segmented log's on-disk format, read here with nothing of the program:
#   file := MAGIC record* ; record := u32_le len | u32_le crc32(payload) | payload
#   payload := msgpack {t, i, k, s: {c, id, d: msgpack header}}
SEGMENT_MAGIC = b"RTPULOG\x01"
_REC_HDR = struct.Struct("<II")
# A record that holds a stream's header is a few hundred bytes: the bytes of
# the file go round the log, and a record that held them would not be a
# header beside the data.
HEADER_RECORD_MAX = 4096
# a replica's files lie beside its log directory (<group>/current):
#   <group>/sm/files/<path> a committed file (linked at apply),
#   <group>/sm/files/.tmp/stream_* the files streams are written into
FILES = ("sm", "files")
STREAMED = ".tmp"


@functools.lru_cache(maxsize=512)   # a group's three replicas come in a row
def payload_bytes(group_uuid: str, path: str, offset: int, length: int
                  ) -> bytes:
    """The bytes a packet of a group's stream carries: a function of the
    group's id (made from the seed), the path and the packet's offset."""
    return random.Random(f"{group_uuid}:{path}:{offset}").randbytes(length)


def file_bytes(group_uuid: str, path: str, size: int, packet: int) -> bytes:
    """A streamed file, whole: its packets in offset order."""
    return b"".join(payload_bytes(group_uuid, path, off,
                                  min(packet, size - off))
                    for off in range(0, size, packet))


class FileStoreStreamReference:
    """Per group, path -> size of the committed files.  ``apply`` is what the
    state machine answers to a stream's header once the stream is closed,
    both as ASCII: ``STREAM <path> <size> <packet>`` answers ``OK <path>
    <size>``; a stream to a path that is committed is refused and leaves the
    file as it was."""

    def __init__(self, groups: int) -> None:
        self.files: list[dict] = [{} for _ in range(groups)]

    def apply(self, group: int, payload: str) -> bytes:
        words = payload.split(" ")
        if len(words) != 4 or words[0] != "STREAM":
            raise ValueError(f"the filestore stream reference has no "
                             f"semantics for the payload {payload!r}")
        _, path, size, _packet = words
        if path in self.files[group]:
            return b"REFUSED"
        self.files[group][path] = int(size)
        return f"OK {path} {size}".encode()


def judge_answers(groups: int, parts: Sequence[dict]) -> dict:
    """Every request of the run's ``parts`` in order (warm-up, window,
    settle; each the generator's rows ``group`` / ``payload`` / ``answer``)
    against the reference run over the same requests in each group's order.
    A request that never got an answer is counted apart; a stream answers
    for itself alone (its path is its own), so those behind it in its group
    are judged like any other."""
    ref = FileStoreStreamReference(groups)
    submitted = [0] * groups
    for part in parts:
        for g in part["group"]:
            submitted[g] += 1
    wrong = never = 0
    acked = [0] * groups
    samples = []
    for part in parts:
        for g, payload, ans in zip(part["group"], part["payload"],
                                   part["answer"]):
            expected = ref.apply(g, payload)
            if ans is None:
                never += 1
                continue
            acked[g] += 1
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


def stream_headers(path: str, needle: bytes) -> list[dict]:
    """The stream headers {path, size, packet} of the CRC-valid records of
    one segment file that carry ``needle`` and nothing the size of a file's
    bytes.  Stops at the first torn record."""
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
        if header.get("op") == "stream":
            out.append(header)
    return out


def _read(path: str) -> Optional[bytes]:
    try:
        with open(path, "rb") as f:
            return f.read()
    except (FileNotFoundError, IsADirectoryError):
        return None


def _streamed_files(root: str) -> dict:
    """size -> the paths of the replica's own streamed files of that size
    (``.tmp/stream_*``: where a stream's bytes lie until its entry is
    applied)."""
    where = os.path.join(root, STREAMED)
    by_size: dict = {}
    try:
        names = sorted(os.listdir(where))
    except FileNotFoundError:
        return by_size
    for name in names:
        p = os.path.join(where, name)
        if name.startswith("stream_") and os.path.isfile(p):
            by_size.setdefault(os.path.getsize(p), []).append(p)
    return by_size


def durable_writes(log_dir: str, needle: bytes) -> int:
    """Streams one replica holds durably: those whose header is in its
    segment files (``log_*`` of its group directory's ``current``) and whose
    whole file, read from the replica's files beside the log, equals the
    reference's byte for byte: the file in place under its path where the
    entry is applied, else (the entry is in the log and not yet applied) one
    of the replica's own streamed files.  0 when the directory is not
    there."""
    try:
        names = [n for n in os.listdir(log_dir) if n.startswith("log_")]
    except FileNotFoundError:
        return 0
    headers = {h["path"]: h for n in names
               for h in stream_headers(os.path.join(log_dir, n), needle)}
    group_dir = os.path.dirname(os.path.normpath(log_dir))
    group_uuid = os.path.basename(group_dir)
    root = os.path.join(group_dir, *FILES)
    streamed = None
    read_back = 0
    for path, h in headers.items():
        expected = file_bytes(group_uuid, path, h["size"], h["packet"])
        got = _read(os.path.join(root, path))
        if got is None:
            if streamed is None:
                streamed = _streamed_files(root)
            read_back += any(_read(p) == expected
                             for p in streamed.get(len(expected), ()))
        else:
            read_back += got == expected
    return read_back


def replicas_holding(values: Iterable[int], at_least: int, at_most: int
                     ) -> int:
    """Replicas whose count of committed streams lies in [at_least,
    at_most]."""
    return sum(1 for v in values if at_least <= v <= at_most)
