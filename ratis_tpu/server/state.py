"""ServerState: the durable per-division consensus variables.

Capability parity with the reference ServerState
(ratis-server/.../impl/ServerState.java:61): currentTerm / votedFor /
leaderId (:82-92), metadata persistence (persistMetadata:248), vote grant
bookkeeping (grantVote:259), log initialization (initRaftLog:172 — memory vs
segmented), candidate-vs-mine log comparison (compareLog:350), and the
configuration history (ConfigurationManager).
"""

from __future__ import annotations

import asyncio
from typing import Callable, Optional

from ratis_tpu.protocol.group import RaftGroup, RaftGroupMemberId
from ratis_tpu.protocol.ids import RaftPeerId
from ratis_tpu.protocol.logentry import LogEntry
from ratis_tpu.protocol.termindex import INVALID_LOG_INDEX, INVALID_TERM, TermIndex
from ratis_tpu.server.config import RaftConfiguration
from ratis_tpu.server.log.base import RaftLog
from ratis_tpu.server.log.memory import MemoryRaftLog


class ConfigurationManager:
    """Index -> configuration history with truncate rollback
    (reference ConfigurationManager, ratis-server/.../impl/)."""

    def __init__(self, initial: RaftConfiguration):
        self._initial = initial
        self._history: dict[int, RaftConfiguration] = {}

    def add(self, conf: RaftConfiguration) -> None:
        self._history[conf.log_index] = conf

    def current(self) -> RaftConfiguration:
        if not self._history:
            return self._initial
        return self._history[max(self._history)]

    def truncate(self, index: int) -> None:
        """Drop confs at log indexes >= index (log truncation rollback)."""
        for k in [k for k in self._history if k >= index]:
            del self._history[k]


class ServerState:
    def __init__(self, member_id: RaftGroupMemberId, group: RaftGroup,
                 log: Optional[RaftLog] = None,
                 metadata_io: Optional["MetadataIO"] = None):
        self.member_id = member_id
        self.current_term = 0
        self.voted_for: Optional[RaftPeerId] = None
        self.leader_id: Optional[RaftPeerId] = None
        self.log: RaftLog = log or MemoryRaftLog(f"log-{member_id}")
        self.conf_manager = ConfigurationManager(
            RaftConfiguration.from_peers(group.peers, log_index=INVALID_LOG_INDEX))
        self._metadata_io = metadata_io
        # Index of the newest entry known flushed (leader self-slot input).
        self.last_applied = TermIndex.INITIAL_VALUE

    @property
    def configuration(self) -> RaftConfiguration:
        return self.conf_manager.current()

    # -- term / vote ---------------------------------------------------------

    async def persist_metadata(self) -> None:
        """Durably record (term, votedFor) BEFORE replying to a vote or
        accepting a higher term (ServerState.persistMetadata:248)."""
        if self._metadata_io is not None:
            await self._metadata_io.persist(self.current_term, self.voted_for)

    async def update_current_term(self, term: int) -> bool:
        if term > self.current_term:
            self.current_term = term
            self.voted_for = None
            self.leader_id = None
            await self.persist_metadata()
            return True
        return False

    async def grant_vote(self, candidate: RaftPeerId) -> None:
        self.voted_for = candidate
        self.leader_id = None
        await self.persist_metadata()

    async def init_election_term(self) -> int:
        """Candidate entering a real election: term+1, vote self, persist."""
        self.current_term += 1
        self.voted_for = self.member_id.peer_id
        self.leader_id = None
        await self.persist_metadata()
        return self.current_term

    def set_leader(self, leader_id: Optional[RaftPeerId]) -> bool:
        changed = self.leader_id != leader_id
        self.leader_id = leader_id
        return changed

    # -- log comparison (ServerState.compareLog:350) -------------------------

    def is_log_up_to_date(self, candidate_last: TermIndex) -> bool:
        mine = self.log.get_last_entry_term_index()
        if mine is None:
            return True
        if candidate_last.term != mine.term:
            return candidate_last.term > mine.term
        return candidate_last.index >= mine.index

    # -- configuration -------------------------------------------------------

    def apply_log_entry_configuration(self, entry: LogEntry) -> None:
        if entry.is_config():
            self.conf_manager.add(RaftConfiguration.from_entry(entry))

    def truncate_configurations(self, index: int) -> None:
        self.conf_manager.truncate(index)


class MetadataIO:
    """Abstract persistence of (term, votedFor) and the configuration entry:
    per group directory the atomic files (``storage.FileMetadataIO``, cf.
    raft-meta, RaftStorageDirectoryImpl.java:41), on the shared log plane the
    shard's records (``storage.SharedMetadataIO``)."""

    async def persist(self, term: int, voted_for: Optional[RaftPeerId]) -> None:
        pass

    async def load(self) -> tuple[int, Optional[RaftPeerId]]:
        return 0, None

    async def persist_conf(self, entry) -> None:
        """The latest configuration entry (cf. raft-meta.conf)."""

    async def load_conf(self):
        return None
