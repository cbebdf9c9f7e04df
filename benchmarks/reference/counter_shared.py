"""Plain reference of the counter deployment on one shared log a server: the
counter's judges as they are (``counter``), and what a durable replica holds
where a server keeps every group's records in one segment sequence.
Imports nothing of ratis_tpu.

The harness asks for a replica's writes by the per-group path
``<storage>/<peer>/<group uuid>/current``, which holds nothing here: every
record of the peer lies in ``<storage>/<peer>/_sharedlog/shard-<k>/``, in
sealed segments ``shared_<n>`` and at most one open ``shared_inprogress_<n>``,
read here in the order of ``n``::

    file    := MAGIC record*
    record  := u32_le len | u32_le crc32(payload) | payload
    payload := group_id[16] | index i64 | term i64 | kind u8 | body

A write is held once an ENTRY record of its group carries the request's bytes
and nothing later took it back: a TOMBSTONE at index i, a later ENTRY at an
index at or below it (the log's truncate-then-append), a REMOVE of the group.
Other kinds (the group's term, vote or configuration, a purge) hold no write
and are skipped.
"""

from __future__ import annotations

import os
import re
import struct
import zlib

from benchmarks.reference.counter import (INCREMENT, CounterReference,
                                          judge_answers, leader_commit,
                                          majority_min, replicas_holding)

__all__ = ["INCREMENT", "CounterReference", "judge_answers", "leader_commit",
           "majority_min", "replicas_holding", "durable_writes",
           "peer_writes"]

SEGMENT_MAGIC = b"RTPULOG\x01"
_REC_HDR = struct.Struct("<II")
_HEAD = struct.Struct("<16sqqB")

ENTRY, TOMBSTONE, REMOVE = 0, 1, 5

SHARED_DIR = "_sharedlog"
_SEGMENT = re.compile(r"^shared_(inprogress_)?(\d+)$")

# a peer's writes, parsed once a run: peer root -> needle -> group -> count
_parsed: dict[tuple[str, bytes], dict[bytes, int]] = {}


def segment_payloads(path: str, is_open: bool) -> list[bytes]:
    """The CRC-valid payloads of one segment, in order.  Only the open
    segment may end in a torn record (one that runs past the end of the
    file, or the last one, whose bytes did not all land); any other bad
    record is an error."""
    with open(path, "rb") as f:
        data = f.read()
    if not data.startswith(SEGMENT_MAGIC):
        if is_open and len(data) < len(SEGMENT_MAGIC):
            return []
        raise ValueError(f"{path}: not a log segment")
    out, off = [], len(SEGMENT_MAGIC)
    while off < len(data):
        end = off + _REC_HDR.size
        if end <= len(data):
            ln, crc = _REC_HDR.unpack_from(data, off)
            end += ln
        if end > len(data):
            if is_open:
                break
            raise ValueError(f"{path}: record at {off} runs past the end")
        payload = data[off + _REC_HDR.size:end]
        if zlib.crc32(payload) != crc:
            if is_open and end == len(data):
                break
            raise ValueError(f"{path}: bad checksum at {off}")
        out.append(payload)
        off = end
    return out


def peer_segments(peer_root: str) -> list[tuple[str, bool]]:
    """Every segment of every shard of one peer, each shard's in order."""
    base = os.path.join(peer_root, SHARED_DIR)
    try:
        shards = sorted(os.listdir(base))
    except FileNotFoundError:
        return []
    out = []
    for shard in shards:
        d = os.path.join(base, shard)
        found = []
        for name in os.listdir(d):
            m = _SEGMENT.match(name)
            if m:
                found.append((int(m.group(2)), name, m.group(1) is not None))
        out += [(os.path.join(d, name), is_open)
                for _, name, is_open in sorted(found)]
    return out


def peer_writes(peer_root: str, needle: bytes) -> dict[bytes, int]:
    """Group id -> writes its records hold, for one peer, by one forward
    replay of its segments."""
    held: dict[bytes, dict[int, bool]] = {}
    for path, is_open in peer_segments(peer_root):
        for payload in segment_payloads(path, is_open):
            gid, index, _term, kind = _HEAD.unpack_from(payload, 0)
            if kind not in (ENTRY, TOMBSTONE, REMOVE):
                continue
            entries = held.setdefault(gid, {})
            if kind == REMOVE:
                entries.clear()
                continue
            for i in [i for i in entries if i >= index]:
                del entries[i]
            if kind == ENTRY:
                entries[index] = needle in payload[_HEAD.size:]
    return {gid: sum(e.values()) for gid, e in held.items()}


def durable_writes(log_dir: str, needle: bytes) -> int:
    """Writes the peer's shared log holds for the group whose per-group
    directory ``log_dir`` would be (``<storage>/<peer>/<uuid>/current``); 0
    when the peer has no shared log or the group no record there."""
    group_dir = os.path.dirname(os.path.normpath(log_dir))
    peer_root = os.path.dirname(group_dir)
    gid = bytes.fromhex(os.path.basename(group_dir).replace("-", ""))
    key = (peer_root, needle)
    counts = _parsed.get(key)
    if counts is None:
        counts = _parsed[key] = peer_writes(peer_root, needle)
    return counts.get(gid, 0)
