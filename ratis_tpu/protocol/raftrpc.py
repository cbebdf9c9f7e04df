"""Server-to-server Raft RPC messages.

Capability parity with the reference wire format (Raft.proto):
RequestVoteRequestProto:161 (with preVote flag), AppendEntriesRequestProto:180
(batched entries + leaderCommit + commitInfos), AppendEntriesReplyProto with
SUCCESS/NOT_LEADER/INCONSISTENCY results, InstallSnapshotRequestProto:208
(chunked SnapshotChunkProto mode and notification mode),
ReadIndexRequestProto:245, StartLeaderElectionRequestProto (leader transfer).
All messages carry (requestorId, replyId, groupId) routing like
RaftRpcRequestProto:140.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Optional

import msgpack

from ratis_tpu.protocol.ids import RaftGroupId, RaftPeerId
from ratis_tpu.protocol.logentry import LogEntry
from ratis_tpu.protocol.termindex import TermIndex
from ratis_tpu.trace.tracer import STAGE_DECODE, STAGE_ENCODE, TRACER


@dataclasses.dataclass(frozen=True)
class RaftRpcHeader:
    """(requestor, reply-to, group) routing triple on every server RPC."""

    requestor_id: RaftPeerId
    reply_id: RaftPeerId
    group_id: RaftGroupId
    call_id: int = 0

    def to_dict(self) -> dict:
        return {"rq": self.requestor_id.id, "rp": self.reply_id.id,
                "g": self.group_id.to_bytes(), "c": self.call_id}

    @staticmethod
    def from_dict(d: dict) -> "RaftRpcHeader":
        return RaftRpcHeader(RaftPeerId.value_of(d["rq"]),
                             RaftPeerId.value_of(d["rp"]),
                             RaftGroupId.value_of(d["g"]), d.get("c", 0))


@dataclasses.dataclass(frozen=True)
class RequestVoteRequest:
    header: RaftRpcHeader
    candidate_term: int
    candidate_last_entry: TermIndex
    pre_vote: bool = False
    # Leadership-transfer election (startLeaderElection target): voters skip
    # the live-leader stickiness check, as the transfer was initiated by the
    # current leader itself (Raft §3.10 TimeoutNow semantics).
    force: bool = False

    def to_dict(self) -> dict:
        return {"h": self.header.to_dict(), "t": self.candidate_term,
                "lt": self.candidate_last_entry.term,
                "li": self.candidate_last_entry.index, "pv": self.pre_vote,
                "f": self.force}

    @staticmethod
    def from_dict(d: dict) -> "RequestVoteRequest":
        return RequestVoteRequest(RaftRpcHeader.from_dict(d["h"]), d["t"],
                                  TermIndex(d["lt"], d["li"]),
                                  d.get("pv", False), d.get("f", False))


@dataclasses.dataclass(frozen=True)
class RequestVoteReply:
    header: RaftRpcHeader
    term: int
    granted: bool
    should_shutdown: bool = False
    # Replier's log-up-to-dateness hint used by the candidate's priority logic.
    last_entry: TermIndex = TermIndex.INITIAL_VALUE

    def to_dict(self) -> dict:
        return {"h": self.header.to_dict(), "t": self.term, "g": self.granted,
                "sd": self.should_shutdown,
                "lt": self.last_entry.term, "li": self.last_entry.index}

    @staticmethod
    def from_dict(d: dict) -> "RequestVoteReply":
        return RequestVoteReply(RaftRpcHeader.from_dict(d["h"]), d["t"], d["g"],
                                d.get("sd", False),
                                TermIndex(d.get("lt", -1), d.get("li", -1)))


class AppendResult(enum.IntEnum):
    """AppendEntriesReplyProto.AppendResult (Raft.proto:189-193)."""

    SUCCESS = 0
    NOT_LEADER = 1
    INCONSISTENCY = 2


@dataclasses.dataclass(frozen=True)
class AppendEntriesRequest:
    header: RaftRpcHeader
    leader_term: int
    previous: Optional[TermIndex]
    entries: tuple[LogEntry, ...]
    leader_commit: int
    initializing: bool = False  # bootstrapping a newly-staged peer
    commit_infos: tuple[tuple[str, int], ...] = ()

    def is_heartbeat(self) -> bool:
        return not self.entries

    def to_dict(self) -> dict:
        return {"h": self.header.to_dict(), "t": self.leader_term,
                "pt": -1 if self.previous is None else self.previous.term,
                "pi": -1 if self.previous is None else self.previous.index,
                "e": [e.to_dict() for e in self.entries],
                "lc": self.leader_commit, "init": self.initializing,
                "ci": [list(x) for x in self.commit_infos]}

    @staticmethod
    def from_dict(d: dict) -> "AppendEntriesRequest":
        prev = None if d["pi"] < 0 and d["pt"] < 0 else TermIndex(d["pt"], d["pi"])
        return AppendEntriesRequest(
            RaftRpcHeader.from_dict(d["h"]), d["t"], prev,
            tuple(LogEntry.from_dict(e) for e in d["e"]), d["lc"],
            d.get("init", False),
            tuple(tuple(x) for x in d.get("ci", ())))


@dataclasses.dataclass(frozen=True)
class AppendEntriesReply:
    header: RaftRpcHeader
    term: int
    result: AppendResult
    next_index: int
    follower_commit: int
    match_index: int
    is_heartbeat: bool = False

    def to_dict(self) -> dict:
        return {"h": self.header.to_dict(), "t": self.term, "r": int(self.result),
                "ni": self.next_index, "fc": self.follower_commit,
                "mi": self.match_index, "hb": self.is_heartbeat}

    @staticmethod
    def from_dict(d: dict) -> "AppendEntriesReply":
        return AppendEntriesReply(RaftRpcHeader.from_dict(d["h"]), d["t"],
                                  AppendResult(d["r"]), d["ni"], d["fc"],
                                  d["mi"], d.get("hb", False))


class InstallSnapshotResult(enum.IntEnum):
    """InstallSnapshotReplyProto.InstallSnapshotResult (Raft.proto:225-233)."""

    SUCCESS = 0
    NOT_LEADER = 1
    IN_PROGRESS = 2
    ALREADY_INSTALLED = 3
    CONF_MISMATCH = 4
    SNAPSHOT_INSTALLED = 5
    SNAPSHOT_UNAVAILABLE = 6
    SNAPSHOT_EXPIRED = 7


@dataclasses.dataclass(frozen=True)
class FileChunk:
    """One chunk of one snapshot file (FileChunkProto:150-158)."""

    filename: str
    total_size: int
    file_digest: bytes
    chunk_index: int
    offset: int
    data: bytes
    done: bool

    def to_dict(self) -> dict:
        return {"f": self.filename, "ts": self.total_size, "dg": self.file_digest,
                "ci": self.chunk_index, "o": self.offset, "d": self.data,
                "dn": self.done}

    @staticmethod
    def from_dict(d: dict) -> "FileChunk":
        return FileChunk(d["f"], d["ts"], d["dg"], d["ci"], d["o"], d["d"], d["dn"])


@dataclasses.dataclass(frozen=True)
class InstallSnapshotRequest:
    header: RaftRpcHeader
    leader_term: int
    # chunked mode (SnapshotChunkProto:214-221)
    request_id: str = ""
    request_index: int = 0
    snapshot_term_index: Optional[TermIndex] = None
    chunks: tuple[FileChunk, ...] = ()
    total_size: int = 0
    done: bool = False
    # notification mode (NotificationProto:222-224): leader log purged; the
    # StateMachine fetches state out-of-band.
    notification_first_available: Optional[TermIndex] = None
    last_included: Optional[TermIndex] = None

    def is_notification(self) -> bool:
        return self.notification_first_available is not None

    def to_dict(self) -> dict:
        def ti(x):
            return None if x is None else [x.term, x.index]
        return {"h": self.header.to_dict(), "t": self.leader_term,
                "rid": self.request_id, "ridx": self.request_index,
                "sti": ti(self.snapshot_term_index),
                "ch": [c.to_dict() for c in self.chunks], "ts": self.total_size,
                "dn": self.done, "nfa": ti(self.notification_first_available),
                "lin": ti(self.last_included)}

    @staticmethod
    def from_dict(d: dict) -> "InstallSnapshotRequest":
        def ti(x):
            return None if x is None else TermIndex(x[0], x[1])
        return InstallSnapshotRequest(
            RaftRpcHeader.from_dict(d["h"]), d["t"], d.get("rid", ""),
            d.get("ridx", 0), ti(d.get("sti")),
            tuple(FileChunk.from_dict(c) for c in d.get("ch", ())),
            d.get("ts", 0), d.get("dn", False), ti(d.get("nfa")), ti(d.get("lin")))


@dataclasses.dataclass(frozen=True)
class InstallSnapshotReply:
    header: RaftRpcHeader
    term: int
    result: InstallSnapshotResult
    request_index: int = 0
    snapshot_index: int = -1

    def to_dict(self) -> dict:
        return {"h": self.header.to_dict(), "t": self.term, "r": int(self.result),
                "ri": self.request_index, "si": self.snapshot_index}

    @staticmethod
    def from_dict(d: dict) -> "InstallSnapshotReply":
        return InstallSnapshotReply(RaftRpcHeader.from_dict(d["h"]), d["t"],
                                    InstallSnapshotResult(d["r"]),
                                    d.get("ri", 0), d.get("si", -1))


@dataclasses.dataclass(frozen=True)
class ReadIndexRequest:
    header: RaftRpcHeader

    def to_dict(self) -> dict:
        return {"h": self.header.to_dict()}

    @staticmethod
    def from_dict(d: dict) -> "ReadIndexRequest":
        return ReadIndexRequest(RaftRpcHeader.from_dict(d["h"]))


@dataclasses.dataclass(frozen=True)
class ReadIndexReply:
    header: RaftRpcHeader
    ok: bool
    read_index: int = -1

    def to_dict(self) -> dict:
        return {"h": self.header.to_dict(), "ok": self.ok, "ri": self.read_index}

    @staticmethod
    def from_dict(d: dict) -> "ReadIndexReply":
        return ReadIndexReply(RaftRpcHeader.from_dict(d["h"]), d["ok"],
                              d.get("ri", -1))


@dataclasses.dataclass(frozen=True)
class StartLeaderElectionRequest:
    """Leader -> chosen follower during transfer leadership
    (StartLeaderElectionRequestProto)."""

    header: RaftRpcHeader
    leader_last_entry: TermIndex

    def to_dict(self) -> dict:
        return {"h": self.header.to_dict(), "lt": self.leader_last_entry.term,
                "li": self.leader_last_entry.index}

    @staticmethod
    def from_dict(d: dict) -> "StartLeaderElectionRequest":
        return StartLeaderElectionRequest(RaftRpcHeader.from_dict(d["h"]),
                                          TermIndex(d["lt"], d["li"]))


@dataclasses.dataclass(frozen=True)
class StartLeaderElectionReply:
    header: RaftRpcHeader
    accepted: bool

    def to_dict(self) -> dict:
        return {"h": self.header.to_dict(), "ok": self.accepted}

    @staticmethod
    def from_dict(d: dict) -> "StartLeaderElectionReply":
        return StartLeaderElectionReply(RaftRpcHeader.from_dict(d["h"]), d["ok"])


@dataclasses.dataclass(frozen=True)
class AppendEnvelope:
    """Multi-raft AppendEntries envelope: append traffic from EVERY group a
    server leads toward one destination server, folded into a single RPC —
    both idle heartbeats and pipelined entry batches.

    No reference analog — the reference runs one stream per (group,
    follower) (GrpcLogAppender.java:356) plus one heartbeat per group per
    interval, which is the O(groups) RPC wall this framework's multi-raft
    axis removes.  The envelope carries ordinary AppendEntriesRequests, so
    each group's semantics are exactly the unary path's; the receiver
    processes a group's items sequentially in order (RaftServer
    _handle_append_envelope), which preserves per-group FIFO.

    Sequenced append windows (round 9, raft.tpu.replication.window-depth):
    with per-group frame pipelining a group's items MAY be split across
    consecutive in-flight envelopes, so FIFO moves from the sender's busy
    latch to the wire — ``lane`` names one (sender, destination,
    loop-shard) lane instance (a fresh id per sender lifetime, so a
    restarted sender never collides with its predecessor's sequence
    space) and ``seq`` numbers the lane's frames from 0.  The receiver
    processes a lane's frames strictly in sequence (out-of-order arrivals
    briefly buffered, gaps rejected with a rewind hint — RaftServer's
    lane intake).  ``seq < 0`` = unsequenced legacy frame, processed
    immediately; a depth-1 sender emits only those, with bit-identical
    wire bytes to the pre-window protocol."""

    items: tuple[AppendEntriesRequest, ...]
    lane: int = 0
    seq: int = -1

    def to_dict(self) -> dict:
        d: dict = {"i": [r.to_dict() for r in self.items]}
        if self.seq >= 0:
            d["ln"] = self.lane
            d["sq"] = self.seq
        return d

    @staticmethod
    def from_dict(d: dict) -> "AppendEnvelope":
        return AppendEnvelope(
            tuple(AppendEntriesRequest.from_dict(x) for x in d["i"]),
            d.get("ln", 0), d.get("sq", -1))


# AppendEnvelopeReply.status codes (sequenced lanes)
ENV_OK = 0
# the frame broke the lane's sequence (gap past the reorder buffer, a
# duplicate, or a buffered wait that timed out): nothing was processed;
# ``hint`` carries the sequence the receiver expects next — the sender
# drops the lane's unacked frames and re-cuts on a fresh lane
ENV_OUT_OF_SEQUENCE = 1


@dataclasses.dataclass(frozen=True)
class AppendEnvelopeReply:
    """Per-item replies; None where the peer failed that group (e.g. it does
    not serve it) — the sender treats those as per-follower RPC errors.
    ``status != ENV_OK`` means the whole frame was refused unprocessed by
    the receiver's lane intake (items is empty then)."""

    items: tuple[Optional[AppendEntriesReply], ...]
    status: int = ENV_OK
    hint: int = -1

    def to_dict(self) -> dict:
        d: dict = {"i": [None if r is None else r.to_dict()
                         for r in self.items]}
        if self.status != ENV_OK:
            d["st"] = self.status
            d["hn"] = self.hint
        return d

    @staticmethod
    def from_dict(d: dict) -> "AppendEnvelopeReply":
        return AppendEnvelopeReply(
            tuple(None if x is None else AppendEntriesReply.from_dict(x)
                  for x in d["i"]),
            d.get("st", ENV_OK), d.get("hn", -1))


@dataclasses.dataclass(frozen=True)
class BulkHeartbeat:
    """Compact multi-raft heartbeat: ONE small message per server pair per
    interval carrying a fixed-width tuple per led group, instead of one full
    AppendEntries per (group, follower).

    No reference analog — the reference's per-group heartbeat volume
    (GrpcLogAppender heartbeat channel) is an O(groups) event-loop wall at
    thousands of co-hosted groups even when the RPCs are folded, because
    each heartbeat still costs a full AppendEntries build + handle + reply.
    The bulk item carries exactly what the idle happy path needs: leadership
    assertion (term), and safe commit propagation (leader commit + the term
    of the entry at that index, so the follower advances commit only when
    its own entry matches — the Log Matching property makes that
    sufficient).  Any anomaly (behind follower, term conflict) falls back to
    a full AppendEntries probe on the data path, with prev-check fidelity.

    items: (group_id_bytes, leader_term, leader_commit, commit_entry_term)
    """

    requestor_id: RaftPeerId
    reply_id: RaftPeerId
    items: tuple[tuple[bytes, int, int, int], ...]

    def to_dict(self) -> dict:
        return {"rq": self.requestor_id.id, "rp": self.reply_id.id,
                "i": [list(x) for x in self.items]}

    @staticmethod
    def from_dict(d: dict) -> "BulkHeartbeat":
        return BulkHeartbeat(RaftPeerId.value_of(d["rq"]),
                             RaftPeerId.value_of(d["rp"]),
                             tuple(tuple(x) for x in d["i"]))


# BulkHeartbeatReply item result codes
BULK_HB_OK = 0
BULK_HB_NOT_LEADER = 1
BULK_HB_UNKNOWN_GROUP = 2
# Receiver skipped the item because the division's append lock was held by
# an in-flight AppendEntries: that append itself resets the follower's
# election deadline, and the leader simply retries next sweep — so the
# sweep never waits on a contended division (no head-of-line blocking).
BULK_HB_BUSY = 3
# Follower accepted a hibernate request (a normal bulk item with a 5th
# flag field set): its election timer is DISARMED and the leader may stop
# heartbeating the group (idle-group quiescence,
# RaftServerConfigKeys.Hibernate).
BULK_HB_HIBERNATED = 4


@dataclasses.dataclass(frozen=True)
class BulkHeartbeatReply:
    """Aligned 1:1 with the request's items.

    items: (result_code, term, next_index, follower_commit, flush_index)
    """

    items: tuple[tuple[int, int, int, int, int], ...]

    def to_dict(self) -> dict:
        return {"i": [list(x) for x in self.items]}

    @staticmethod
    def from_dict(d: dict) -> "BulkHeartbeatReply":
        return BulkHeartbeatReply(tuple(tuple(x) for x in d["i"]))


# --- encode-once fast path ---------------------------------------------------
#
# The leader fans near-identical AppendEntries payloads to N followers (and
# re-sends them on window refills): at 5-peer x 10240 groups every entry's
# msgpack bytes were produced four times per replication round.  The fast
# path below serializes each piece ONCE and splices:
#
# - per-ENTRY wire bytes are memoized on the LogEntry object itself (frozen
#   dataclass, attribute set via object.__setattr__) — the dominant bytes of
#   any append, encoded once per entry lifetime, shared across followers,
#   envelopes, and resends;
# - the per-request SUFFIX (everything after the routing header — term,
#   prev, entries, commit, infos) is cached in a small LRU keyed by the
#   request's non-header fields, so fanning one batch to N followers packs
#   the suffix once and re-packs only the ~30-byte header per destination;
# - scaffolding (map/array headers, keys, ints) is written by a
#   msgpack-bit-compatible mini-packer into a POOLED bytearray, so the
#   output is byte-identical to ``msgpack.packb({"_": tag, "b": to_dict()},
#   use_bin_type=True)`` (asserted in tests/test_wire_fastpath.py) and no
#   per-call buffer is allocated.
#
# Any unexpected shape falls back to the generic packer (counted in
# FANOUT_STATS["fallback"]) — the fast path is an optimization, never a
# second wire format.

FANOUT_STATS = {"fast": 0, "suffix_hits": 0, "fallback": 0}

_SUFFIX_LRU: "dict[tuple, tuple[tuple, bytes]]" = {}
_SUFFIX_LRU_MAX = 512


def _pk_int(out: bytearray, v: int) -> None:
    if v >= 0:
        if v < 0x80:
            out.append(v)
        elif v <= 0xff:
            out.append(0xcc); out.append(v)  # noqa: E702
        elif v <= 0xffff:
            out.append(0xcd); out += v.to_bytes(2, "big")  # noqa: E702
        elif v <= 0xffffffff:
            out.append(0xce); out += v.to_bytes(4, "big")  # noqa: E702
        else:
            out.append(0xcf); out += v.to_bytes(8, "big")  # noqa: E702
    else:
        if v >= -32:
            out.append(0x100 + v)
        elif v >= -0x80:
            out.append(0xd0); out += v.to_bytes(1, "big", signed=True)  # noqa: E702
        elif v >= -0x8000:
            out.append(0xd1); out += v.to_bytes(2, "big", signed=True)  # noqa: E702
        elif v >= -0x80000000:
            out.append(0xd2); out += v.to_bytes(4, "big", signed=True)  # noqa: E702
        else:
            out.append(0xd3); out += v.to_bytes(8, "big", signed=True)  # noqa: E702


def _pk_str(out: bytearray, s: str) -> None:
    b = s.encode("utf-8")
    n = len(b)
    if n < 32:
        out.append(0xa0 | n)
    elif n <= 0xff:
        out.append(0xd9); out.append(n)  # noqa: E702
    elif n <= 0xffff:
        out.append(0xda); out += n.to_bytes(2, "big")  # noqa: E702
    else:
        out.append(0xdb); out += n.to_bytes(4, "big")  # noqa: E702
    out += b


def _pk_bin(out: bytearray, b: bytes) -> None:
    n = len(b)
    if n <= 0xff:
        out.append(0xc4); out.append(n)  # noqa: E702
    elif n <= 0xffff:
        out.append(0xc5); out += n.to_bytes(2, "big")  # noqa: E702
    else:
        out.append(0xc6); out += n.to_bytes(4, "big")  # noqa: E702
    out += b


def _pk_arr(out: bytearray, n: int) -> None:
    if n < 16:
        out.append(0x90 | n)
    elif n <= 0xffff:
        out.append(0xdc); out += n.to_bytes(2, "big")  # noqa: E702
    else:
        out.append(0xdd); out += n.to_bytes(4, "big")  # noqa: E702


def _pk_obj(out: bytearray, v) -> None:
    """Generic scalar/sequence packer (msgpack-bit-compatible) for the few
    loosely-typed fields (commit-info pairs, header ids)."""
    if v is None:
        out.append(0xc0)
    elif v is True:
        out.append(0xc3)
    elif v is False:
        out.append(0xc2)
    elif isinstance(v, int):
        _pk_int(out, v)
    elif isinstance(v, str):
        _pk_str(out, v)
    elif isinstance(v, (bytes, bytearray)):
        _pk_bin(out, bytes(v))
    elif isinstance(v, (list, tuple)):
        _pk_arr(out, len(v))
        for x in v:
            _pk_obj(out, x)
    else:
        raise TypeError(f"no fast packer for {type(v)}")


def entry_wire_bytes(e) -> bytes:
    """Wire bytes of one log entry (``msgpack.packb(e.to_dict())``),
    memoized ON the entry — encode-once across followers and resends."""
    w = e.__dict__.get("_wire")
    if w is None:
        w = msgpack.packb(e.to_dict(), use_bin_type=True)
        object.__setattr__(e, "_wire", w)
    return w


def _append_suffix(req: "AppendEntriesRequest") -> bytes:
    """The request body AFTER the "h" key/value: identical across the
    per-follower fan-out, cacheable."""
    out = bytearray()
    _pk_str(out, "t"); _pk_int(out, req.leader_term)  # noqa: E702
    prev = req.previous
    _pk_str(out, "pt"); _pk_int(out, -1 if prev is None else prev.term)  # noqa: E702
    _pk_str(out, "pi"); _pk_int(out, -1 if prev is None else prev.index)  # noqa: E702
    _pk_str(out, "e"); _pk_arr(out, len(req.entries))  # noqa: E702
    for e in req.entries:
        out += entry_wire_bytes(e)
    _pk_str(out, "lc"); _pk_int(out, req.leader_commit)  # noqa: E702
    _pk_str(out, "init")
    out.append(0xc3 if req.initializing else 0xc2)
    _pk_str(out, "ci"); _pk_arr(out, len(req.commit_infos))  # noqa: E702
    for pair in req.commit_infos:
        _pk_obj(out, list(pair))
    return bytes(out)


def _suffix_for(req: "AppendEntriesRequest") -> bytes:
    prev = req.previous
    key = (req.leader_term,
           -1 if prev is None else prev.term,
           -1 if prev is None else prev.index,
           req.leader_commit, req.initializing, req.commit_infos,
           tuple(map(id, req.entries)))
    hit = _SUFFIX_LRU.get(key)
    if hit is not None:
        FANOUT_STATS["suffix_hits"] += 1
        return hit[1]
    suf = _append_suffix(req)
    # The value PINS the entry objects, so the id()-based key stays valid
    # for exactly as long as it is in the cache.  Multi-MB suffixes are
    # not cached: 512 pinned 4MB batches would be ~2GB of heap, and a big
    # batch's encode is already amortized by the per-entry memo — the
    # cache's marginal win there is one memcpy.
    if len(suf) <= (256 << 10):
        _SUFFIX_LRU[key] = (req.entries, suf)
        if len(_SUFFIX_LRU) > _SUFFIX_LRU_MAX:
            _SUFFIX_LRU.pop(next(iter(_SUFFIX_LRU)))
    return suf


def _pk_append_request_body(out: bytearray,
                            req: "AppendEntriesRequest") -> None:
    out.append(0x88)  # fixmap(8): h t pt pi e lc init ci
    _pk_str(out, "h")
    h = req.header
    out.append(0x84)  # fixmap(4): rq rp g c
    _pk_str(out, "rq"); _pk_obj(out, h.requestor_id.id)  # noqa: E702
    _pk_str(out, "rp"); _pk_obj(out, h.reply_id.id)  # noqa: E702
    _pk_str(out, "g"); _pk_bin(out, h.group_id.to_bytes())  # noqa: E702
    _pk_str(out, "c"); _pk_int(out, h.call_id)  # noqa: E702
    out += _suffix_for(req)


_BUF_POOL: list[bytearray] = []


def _encode_append_fast(msg) -> bytes:
    buf = _BUF_POOL.pop() if _BUF_POOL else bytearray()
    try:
        buf.append(0x82)  # fixmap(2): _ b
        _pk_str(buf, "_")
        if type(msg) is AppendEnvelope:
            _pk_str(buf, "env_req")
            _pk_str(buf, "b")
            sequenced = msg.seq >= 0
            # fixmap(3): i ln sq (sequenced lane frame) / fixmap(1): i
            # (legacy frame — byte-identical to the pre-window protocol)
            buf.append(0x83 if sequenced else 0x81)
            _pk_str(buf, "i")
            _pk_arr(buf, len(msg.items))
            for req in msg.items:
                _pk_append_request_body(buf, req)
            if sequenced:
                _pk_str(buf, "ln"); _pk_int(buf, msg.lane)  # noqa: E702
                _pk_str(buf, "sq"); _pk_int(buf, msg.seq)  # noqa: E702
        else:
            _pk_str(buf, "append_req")
            _pk_str(buf, "b")
            _pk_append_request_body(buf, msg)
        FANOUT_STATS["fast"] += 1
        return bytes(buf)
    finally:
        buf.clear()
        if len(_BUF_POOL) < 8:
            _BUF_POOL.append(buf)


def _encode(msg) -> bytes:
    t = type(msg)
    if t is AppendEnvelope or t is AppendEntriesRequest:
        try:
            return _encode_append_fast(msg)
        except Exception:
            FANOUT_STATS["fallback"] += 1
    return msgpack.packb({"_": _TYPE_TAGS[t], "b": msg.to_dict()},
                         use_bin_type=True)


# --- generic envelope for transports ---------------------------------------

_MSG_TYPES: dict[str, type] = {
    "vote_req": RequestVoteRequest, "vote_rep": RequestVoteReply,
    "append_req": AppendEntriesRequest, "append_rep": AppendEntriesReply,
    "snap_req": InstallSnapshotRequest, "snap_rep": InstallSnapshotReply,
    "readidx_req": ReadIndexRequest, "readidx_rep": ReadIndexReply,
    "sle_req": StartLeaderElectionRequest, "sle_rep": StartLeaderElectionReply,
    "env_req": AppendEnvelope, "env_rep": AppendEnvelopeReply,
    "bulkhb_req": BulkHeartbeat, "bulkhb_rep": BulkHeartbeatReply,
}
_TYPE_TAGS = {v: k for k, v in _MSG_TYPES.items()}


def encode_rpc(msg) -> bytes:
    """Tagged msgpack envelope (cf. Netty.proto's request/reply union:31-48).

    Append traffic (AppendEntriesRequest / AppendEnvelope) takes the
    encode-once fast path above — bit-identical output, entry bytes and
    fan-out suffixes serialized once.  Host-path tracing samples the encode
    here (process-level span, ratis_tpu.trace STAGE_ENCODE, tag = wire
    bytes): the per-commit msgpack cost of the server-to-server plane,
    measured where it is paid — fast-path encodes record through the same
    stage, so coalesced/spliced frames stay attributed."""
    if TRACER.enabled:
        span = TRACER.begin(STAGE_ENCODE)
        b = b""
        try:
            b = _encode(msg)
            return b
        finally:
            if span is not None:
                TRACER.end(span, tag=len(b))
    return _encode(msg)


def decode_rpc(b: bytes):
    if TRACER.enabled:
        span = TRACER.begin(STAGE_DECODE)
        try:
            d = msgpack.unpackb(b, raw=False)
            return _MSG_TYPES[d["_"]].from_dict(d["b"])
        finally:
            if span is not None:
                TRACER.end(span, tag=len(b))
    d = msgpack.unpackb(b, raw=False)
    return _MSG_TYPES[d["_"]].from_dict(d["b"])
