"""Plain reference of the counter deployment's semantics, independent of the
program: what each acknowledged INCREMENT must answer, what every replica's
state machine must hold afterwards, what a durable log must hold on disk, and
the quorum rule a leader's commit index obeys.  Imports nothing of ratis_tpu.
"""

from __future__ import annotations

import os
import struct
import zlib
from typing import Iterable, Sequence

# the segmented log's on-disk format, read here with nothing of the program:
#   file := MAGIC record* ; record := u32_le len | u32_le crc32(payload) | payload
SEGMENT_MAGIC = b"RTPULOG\x01"
_REC_HDR = struct.Struct("<II")


INCREMENT = "INCREMENT"


class CounterReference:
    """One counter per group.  ``apply`` is what the state machine answers
    to a request's bytes: a group's k-th INCREMENT answers k, as ASCII
    digits.  A payload this reference has no semantics for is an error: the
    traffic that sends it names a reference that has (its ``reference``)."""

    def __init__(self, groups: int) -> None:
        self.counters = [0] * groups

    def apply(self, group: int, payload: str = INCREMENT) -> bytes:
        if payload != INCREMENT:
            raise ValueError(f"the counter reference has no semantics for "
                             f"the payload {payload!r}")
        self.counters[group] += 1
        return str(self.counters[group]).encode()


def judge_answers(groups: int, parts: Sequence[dict]) -> dict:
    """Every request of the run's ``parts`` in order (warm-up, window,
    settle; each the generator's rows ``group`` / ``payload`` / ``answer``)
    against the reference run over the same requests.  A request that never
    got an answer is counted apart (and breaks the order of those behind it
    in its group, which are then only held to be increasing and in range)."""
    ref = CounterReference(groups)
    submitted = [0] * groups
    for part in parts:
        for g in part["group"]:
            submitted[g] += 1
    broken = [False] * groups
    last_seen = [0] * groups
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
            if not broken[g]:
                ok = ans.encode() == expected
            else:
                ok = (ans.isdigit() and last_seen[g] < int(ans)
                      <= submitted[g])
            if ans.isdigit():
                last_seen[g] = int(ans)
            if not ok:
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


def count_payload_records(path: str, needle: bytes) -> int:
    """CRC-valid records of one segment file whose payload carries
    ``needle`` (the request's bytes).  Stops at the first torn record."""
    with open(path, "rb") as f:
        data = f.read()
    if not data.startswith(SEGMENT_MAGIC):
        return 0
    n, off = 0, len(SEGMENT_MAGIC)
    while off + _REC_HDR.size <= len(data):
        ln, crc = _REC_HDR.unpack_from(data, off)
        end = off + _REC_HDR.size + ln
        if end > len(data):
            break
        payload = data[off + _REC_HDR.size:end]
        if zlib.crc32(payload) != crc:
            break
        if needle in payload:
            n += 1
        off = end
    return n


def durable_writes(log_dir: str, needle: bytes) -> int:
    """Writes held by the segment files (``log_*``) of one replica's group
    directory; 0 when the directory or the files are not there."""
    try:
        names = [n for n in os.listdir(log_dir) if n.startswith("log_")]
    except FileNotFoundError:
        return 0
    return sum(count_payload_records(os.path.join(log_dir, n), needle)
               for n in names)


def replicas_holding(values: Iterable[int], at_least: int, at_most: int
                     ) -> int:
    """Replicas whose counter lies in [at_least, at_most]."""
    return sum(1 for v in values if at_least <= v <= at_most)
