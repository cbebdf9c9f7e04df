"""Log entry wire/storage representation.

Capability parity with the reference's LogEntryProto (Raft.proto:97-107) and
its three body cases: StateMachineLogEntryProto (client transaction,
Raft.proto:72-91), ConfigurationEntryProto (membership change, including the
joint-consensus oldPeers list), and MetadataProto (persisted commitIndex,
Raft.proto:93-95).  Serialization is msgpack (compact, schema-stable dicts)
rather than protobuf-java; the gRPC transport wraps the same bytes.
"""

from __future__ import annotations

import dataclasses
import enum
from typing import Optional

import msgpack

from ratis_tpu.protocol.ids import ClientId
from ratis_tpu.protocol.peer import RaftPeer
from ratis_tpu.protocol.termindex import TermIndex


class LogEntryKind(enum.IntEnum):
    STATE_MACHINE = 1
    CONFIGURATION = 2
    METADATA = 3


@dataclasses.dataclass(frozen=True)
class StateMachineLogEntry:
    """A client transaction: the logged payload plus the (clientId, callId)
    pair that keys the retry cache (reference StateMachineLogEntryProto)."""

    client_id: bytes = b""
    call_id: int = 0
    log_data: bytes = b""
    # State-machine data held OUTSIDE the log file when the StateMachine
    # provides a DataApi (reference SegmentedRaftLog stateMachineCachingEnabled,
    # SegmentedRaftLog.java:203).  On the wire, never in a segment file: the
    # record keeps ``data_size`` alone, and the log's cache lets the bytes go
    # once the entry is applied and replicated.  ``sm_data is None`` with
    # ``data_size > 0`` is such an entry: StateMachine.data_read has the bytes.
    sm_data: Optional[bytes] = None
    # True when this transaction was submitted by a DataStream CLOSE: every
    # replica must data_link the entry at apply, passing None when it holds
    # no local stream so the StateMachine can detect/repair the missing bytes
    # (reference passes a null stream for exactly this).
    is_datastream: bool = False
    data_size: int = 0

    def __post_init__(self) -> None:
        if self.sm_data is not None:
            object.__setattr__(self, "data_size", len(self.sm_data))

    def data_let_go(self) -> bool:
        """The entry carries state-machine data and does not hold it."""
        return self.sm_data is None and self.data_size > 0


@dataclasses.dataclass(frozen=True)
class ConfigurationEntry:
    peers: tuple[RaftPeer, ...] = ()
    old_peers: tuple[RaftPeer, ...] = ()  # non-empty == joint consensus phase
    listeners: tuple[RaftPeer, ...] = ()
    old_listeners: tuple[RaftPeer, ...] = ()


@dataclasses.dataclass(frozen=True)
class LogEntry:
    term: int
    index: int
    kind: LogEntryKind
    smlog: Optional[StateMachineLogEntry] = None
    conf: Optional[ConfigurationEntry] = None
    commit_index: int = -1  # METADATA body

    def term_index(self) -> TermIndex:
        return TermIndex(self.term, self.index)

    def is_config(self) -> bool:
        return self.kind == LogEntryKind.CONFIGURATION

    def is_metadata(self) -> bool:
        return self.kind == LogEntryKind.METADATA

    def serialized_size(self) -> int:
        return len(self.to_bytes())

    # -- codec ---------------------------------------------------------------

    def to_dict(self, include_sm_data: bool = True) -> dict:
        d: dict = {"t": self.term, "i": self.index, "k": int(self.kind)}
        if self.smlog is not None:
            s: dict = {"c": self.smlog.client_id, "id": self.smlog.call_id,
                       "d": self.smlog.log_data}
            if include_sm_data and self.smlog.sm_data is not None:
                s["sd"] = self.smlog.sm_data
            elif self.smlog.data_size:
                s["sx"] = self.smlog.data_size
            if self.smlog.is_datastream:
                s["ds"] = True
            d["s"] = s
        if self.conf is not None:
            d["cf"] = {
                "p": [p.to_dict() for p in self.conf.peers],
                "op": [p.to_dict() for p in self.conf.old_peers],
                "l": [p.to_dict() for p in self.conf.listeners],
                "ol": [p.to_dict() for p in self.conf.old_listeners],
            }
        if self.kind == LogEntryKind.METADATA:
            d["ci"] = self.commit_index
        return d

    @staticmethod
    def from_dict(d: dict) -> "LogEntry":
        smlog = None
        if "s" in d:
            s = d["s"]
            smlog = StateMachineLogEntry(
                client_id=s.get("c", b""), call_id=s.get("id", 0),
                log_data=s.get("d", b""), sm_data=s.get("sd"),
                is_datastream=s.get("ds", False), data_size=s.get("sx", 0))
        conf = None
        if "cf" in d:
            c = d["cf"]
            conf = ConfigurationEntry(
                peers=tuple(RaftPeer.from_dict(p) for p in c.get("p", ())),
                old_peers=tuple(RaftPeer.from_dict(p) for p in c.get("op", ())),
                listeners=tuple(RaftPeer.from_dict(p) for p in c.get("l", ())),
                old_listeners=tuple(RaftPeer.from_dict(p) for p in c.get("ol", ())))
        return LogEntry(term=d["t"], index=d["i"], kind=LogEntryKind(d["k"]),
                        smlog=smlog, conf=conf, commit_index=d.get("ci", -1))

    def to_bytes(self, include_sm_data: bool = True) -> bytes:
        return msgpack.packb(self.to_dict(include_sm_data), use_bin_type=True)

    @staticmethod
    def from_bytes(b: bytes) -> "LogEntry":
        return LogEntry.from_dict(msgpack.unpackb(b, raw=False))

    def without_sm_data(self) -> "LogEntry":
        """This entry as a segment file holds it: the header, and the size
        of the state-machine data that lives outside the log."""
        return dataclasses.replace(self, smlog=dataclasses.replace(
            self.smlog, sm_data=None))

    def with_sm_data(self, data: bytes) -> "LogEntry":
        """The entry with the bytes StateMachine.data_read gave back."""
        return dataclasses.replace(self, smlog=dataclasses.replace(
            self.smlog, sm_data=data))

    def __str__(self) -> str:
        body = self.kind.name
        if self.smlog is not None:
            body += f"[{len(self.smlog.log_data)}B]"
        return f"{self.term_index()}:{body}"


def make_transaction_entry(term: int, index: int, client_id: ClientId | bytes,
                           call_id: int, data: bytes,
                           sm_data: Optional[bytes] = None,
                           is_datastream: bool = False) -> LogEntry:
    cid = client_id.to_bytes() if isinstance(client_id, ClientId) else bytes(client_id)
    return LogEntry(term, index, LogEntryKind.STATE_MACHINE,
                    smlog=StateMachineLogEntry(cid, call_id, data, sm_data,
                                               is_datastream))


def make_config_entry(term: int, index: int, peers, old_peers=(),
                      listeners=(), old_listeners=()) -> LogEntry:
    return LogEntry(term, index, LogEntryKind.CONFIGURATION,
                    conf=ConfigurationEntry(tuple(peers), tuple(old_peers),
                                            tuple(listeners), tuple(old_listeners)))


def make_metadata_entry(term: int, index: int, commit_index: int) -> LogEntry:
    return LogEntry(term, index, LogEntryKind.METADATA, commit_index=commit_index)
