"""StateMachine SPI: the application-extension interface.

Capability parity with the reference StateMachine
(ratis-server-api/src/main/java/org/apache/ratis/statemachine/StateMachine.java:57):
lifecycle (initialize:437 / pause:449 / reinitialize:456), queries (query:492,
queryStale:505), the transaction pipeline (startTransaction:520,
preAppendTransaction:546, applyTransaction:592), snapshotting
(takeSnapshot, getLatestSnapshot:487), and the optional event sub-APIs
(EventApi:158, LeaderEventApi:237, FollowerEventApi:271).  asyncio-native:
apply/query return awaitables so state machines can do real I/O.
"""

from __future__ import annotations

import asyncio
import dataclasses
import pathlib
from typing import Any, Iterable, Optional

from ratis_tpu.protocol.group import RaftGroup, RaftGroupMemberId
from ratis_tpu.protocol.ids import RaftGroupId, RaftPeerId
from ratis_tpu.protocol.logentry import LogEntry
from ratis_tpu.protocol.message import Message
from ratis_tpu.protocol.requests import RaftClientRequest
from ratis_tpu.protocol.termindex import INVALID_LOG_INDEX, INVALID_TERM, TermIndex
from ratis_tpu.util.lifecycle import LifeCycle, LifeCycleState


@dataclasses.dataclass(frozen=True)
class SnapshotFileInfo:
    """One file of a snapshot (path + MD5), cf. FileInfo in the reference."""

    path: str
    digest: bytes = b""


@dataclasses.dataclass(frozen=True)
class SnapshotInfo:
    """Term/index + files of one snapshot (reference SnapshotInfo /
    SingleFileSnapshotInfo / FileListSnapshotInfo)."""

    term_index: TermIndex
    files: tuple[SnapshotFileInfo, ...] = ()

    @property
    def index(self) -> int:
        return self.term_index.index


@dataclasses.dataclass
class TransactionContext:
    """Carries one transaction from startTransaction through apply
    (reference TransactionContextImpl, ratis-server/.../statemachine/impl/)."""

    client_request: Optional[RaftClientRequest] = None
    log_entry: Optional[LogEntry] = None
    state_machine_context: Any = None  # app-private scratch
    exception: Optional[Exception] = None
    # Data the SM wants logged (may differ from the request message)
    log_data: Optional[bytes] = None
    sm_data: Optional[bytes] = None
    should_commit: bool = True


class StateMachineStorage:
    """Where a state machine keeps its snapshots
    (reference StateMachineStorage / SimpleStateMachineStorage)."""

    SNAPSHOT_PREFIX = "snapshot"

    def __init__(self):
        self._dir: Optional[pathlib.Path] = None

    def init(self, sm_dir: "str | pathlib.Path") -> None:
        """Snapshots go to ``sm_dir``, made with the first of them (a group
        on the shared log plane has no directory until then)."""
        self._dir = pathlib.Path(sm_dir)

    @property
    def directory(self) -> Optional[pathlib.Path]:
        return self._dir

    def snapshot_path(self, term: int, index: int) -> pathlib.Path:
        # file pattern snapshot.<term>_<index>, cf. SimpleStateMachineStorage
        assert self._dir is not None, "storage not initialized"
        self._dir.mkdir(parents=True, exist_ok=True)
        return self._dir / f"{self.SNAPSHOT_PREFIX}.{term}_{index}"

    def find_latest_snapshot(self) -> Optional[SnapshotInfo]:
        if self._dir is None or not self._dir.exists():
            return None
        best: Optional[tuple[int, int, pathlib.Path]] = None
        for f in self._dir.iterdir():
            name = f.name
            if not name.startswith(self.SNAPSHOT_PREFIX + "."):
                continue
            try:
                term_s, index_s = name[len(self.SNAPSHOT_PREFIX) + 1:].split("_")
                term, index = int(term_s), int(index_s)
            except ValueError:
                continue
            if best is None or index > best[1]:
                best = (term, index, f)
        if best is None:
            return None
        return SnapshotInfo(TermIndex(best[0], best[1]),
                            (SnapshotFileInfo(str(best[2])),))

    def clean_old_snapshots(self, retention: int) -> None:
        if self._dir is None or retention < 0 or not self._dir.exists():
            return
        snaps = []
        for f in self._dir.iterdir():
            if f.name.startswith(self.SNAPSHOT_PREFIX + "."):
                try:
                    _, index_s = f.name[len(self.SNAPSHOT_PREFIX) + 1:].split("_")
                    snaps.append((int(index_s), f))
                except ValueError:
                    continue
        for _, f in sorted(snaps)[:-retention] if retention > 0 else []:
            f.unlink(missing_ok=True)


class DataChannel:
    """Destination of one DataStream's bytes
    (reference StateMachine.DataChannel:302 — a WritableByteChannel the SM
    owns, e.g. an open file).

    The stream plane hands each packet to ``submit_write`` and puts its
    copies on the successors' sockets without waiting for the write.  The
    default runs ``write`` behind the channel's previous one; a channel
    with an ordered writer of its own overrides it (FileStore's
    ``FileChunkChannel``)."""

    _last_write: Optional[asyncio.Future] = None

    async def write(self, data: bytes) -> int:
        raise NotImplementedError

    def submit_write(self, data: bytes) -> "asyncio.Future[int]":
        """Queue ``data`` behind this channel's earlier writes, not awaited:
        the future holds the bytes written or the error (an earlier
        write's, where that one failed)."""
        before = self._last_write

        async def _write() -> int:
            if before is not None:
                await before
            return await self.write(data)

        self._last_write = fut = asyncio.ensure_future(_write())
        return fut

    async def force(self, metadata: bool = False) -> None:
        """fsync-equivalent (DataChannel.force)."""

    async def close(self) -> None:
        pass


class DataStream:
    """One open stream handed out by :meth:`StateMachine.data_stream`
    (reference StateMachine.DataStream:338): the channel plus cleanup."""

    def __init__(self, channel: DataChannel, request=None) -> None:
        self.channel = channel
        self.request = request  # the header RaftClientRequest

    async def cleanup(self) -> None:
        """Discard resources after failure (DataStream.cleanUp)."""
        await self.channel.close()


class StateMachine:
    """Base class every application state machine extends.

    Matches the reference's contract: applyTransaction futures may complete
    out of band but MUST be applied in log order by the caller
    (StateMachineUpdater); query is only invoked on applied state.
    """

    def __init__(self):
        self.life_cycle = LifeCycle(type(self).__name__)
        self._storage = StateMachineStorage()
        self._last_applied: TermIndex = TermIndex.INITIAL_VALUE
        self.member_id: Optional[RaftGroupMemberId] = None

    # -- lifecycle (StateMachine.java:437-476) -------------------------------

    async def initialize(self, server, group_id: RaftGroupId,
                         storage_dir=None) -> None:
        """One SPI entry point for both durable and memory modes (the
        reference initializes the SM even with a memory log); storage_dir is
        None in memory mode and snapshot restore is skipped."""
        self.life_cycle.transition(LifeCycleState.STARTING)
        if storage_dir is not None:
            self._storage.init(pathlib.Path(storage_dir) / "sm")
            snapshot = self._storage.find_latest_snapshot()
            if snapshot is not None:
                await self.restore_from_snapshot(snapshot)
                self._last_applied = snapshot.term_index
        self.life_cycle.transition(LifeCycleState.RUNNING)

    async def pause(self) -> None:
        self.life_cycle.transition(LifeCycleState.PAUSING)
        self.life_cycle.transition(LifeCycleState.PAUSED)

    async def reinitialize(self) -> None:
        """Reload state after a snapshot was installed while paused."""
        self.life_cycle.transition(LifeCycleState.STARTING)
        snapshot = self._storage.find_latest_snapshot()
        if snapshot is not None:
            await self.restore_from_snapshot(snapshot)
            self._last_applied = snapshot.term_index
        self.life_cycle.transition(LifeCycleState.RUNNING)

    async def close(self) -> None:
        self.life_cycle.check_state_and_close(lambda: None)

    # -- storage / snapshot --------------------------------------------------

    def get_state_machine_storage(self) -> StateMachineStorage:
        return self._storage

    def get_latest_snapshot(self) -> Optional[SnapshotInfo]:
        return self._storage.find_latest_snapshot()

    async def take_snapshot(self) -> int:
        """Persist applied state; returns the snapshot's log index or
        INVALID_LOG_INDEX if unsupported (StateMachine.takeSnapshot)."""
        return INVALID_LOG_INDEX

    async def restore_from_snapshot(self, snapshot: SnapshotInfo) -> None:
        pass

    # -- applied-index bookkeeping ------------------------------------------

    def get_last_applied_term_index(self) -> TermIndex:
        return self._last_applied

    def set_last_applied_term_index(self, ti: TermIndex) -> None:
        self._last_applied = ti

    def update_last_applied_term_index(self, term: int, index: int) -> None:
        if index > self._last_applied.index:
            self._last_applied = TermIndex(term, index)

    # -- transaction pipeline (StateMachine.java:520-604) --------------------

    async def start_transaction(self, request: RaftClientRequest) -> TransactionContext:
        """Leader-side validation/transform of a client write before it is
        logged.  Default: log the message bytes verbatim."""
        return TransactionContext(client_request=request,
                                  log_data=request.message.content)

    async def pre_append_transaction(self, trx: TransactionContext) -> TransactionContext:
        return trx

    async def apply_transaction(self, trx: TransactionContext) -> Message:
        """Apply one committed entry; returns the reply message."""
        return Message.EMPTY

    async def apply_transaction_serial(self, trx: TransactionContext) -> TransactionContext:
        return trx

    async def notify_term_index_updated(self, term: int, index: int) -> None:
        pass

    # -- queries (StateMachine.java:492-516) ---------------------------------

    async def query(self, request: Message) -> Message:
        return Message.EMPTY

    async def query_stale(self, request: Message, min_index: int) -> Message:
        return await self.query(request)

    # -- event APIs (StateMachine.java:158-299), all optional ---------------

    async def notify_leader_changed(self, member_id: RaftGroupMemberId,
                                    leader_id: RaftPeerId) -> None:
        pass

    async def notify_follower_slowness(self, leader_info, slow_peer) -> None:
        pass

    async def notify_extended_no_leader(self, role_info) -> None:
        pass

    async def notify_log_failed(self, cause: Exception, entry: Optional[LogEntry]) -> None:
        pass

    async def notify_install_snapshot_from_leader(
            self, role_info, first_available: TermIndex) -> Optional[TermIndex]:
        """Notification-mode snapshot install: app fetches state out-of-band
        and returns the installed TermIndex (StateMachine.java:293)."""
        return None

    async def notify_snapshot_installed(self, snapshot: SnapshotInfo, peer) -> None:
        pass

    async def notify_configuration_changed(self, term: int, index: int,
                                           new_conf) -> None:
        pass

    async def notify_group_remove(self) -> None:
        pass

    async def notify_server_shutdown(self, role_info, all_groups: bool) -> None:
        pass

    async def notify_leader_ready(self) -> None:
        pass

    async def notify_not_leader(self, pending_requests: Iterable) -> None:
        pass

    # ------------------------------------------------------------- DataApi
    # Optional bulk-data sub-API (reference StateMachine.DataApi:69): stream
    # bytes AROUND the raft log into SM-owned storage, then `link` ties the
    # streamed data to the log entry at apply time (§3.5 of SURVEY.md).

    async def data_stream(self, request) -> DataStream:
        """Open a DataChannel for an incoming stream (DataApi.stream)."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support DataStream")

    async def data_link(self, stream: Optional[DataStream], entry) -> None:
        """Tie a completed stream's data to its committed log entry
        (DataApi.link); ``stream`` is None on peers that did not receive
        the stream (they must fetch via ordinary replication/recovery)."""
        raise NotImplementedError(
            f"{type(self).__name__} does not support DataStream")

    # The log-path half (DataApi.write / read / flush / truncate): an entry's
    # ``sm_data`` never reaches a segment file.  The log calls ``data_write``
    # when the entry is appended, on the leader and on every follower, and its
    # worker writes and fsyncs the entry's record only once the returned
    # awaitable has completed (upstream's
    # raft.server.log.statemachine.data.sync, default true: the log worker
    # waits for the data before its flush), so no crash leaves a durable
    # record without its data; once the entry is applied and replicated the
    # log's cache lets the bytes go, and whoever needs them again (an
    # appender, after a restart) calls ``data_read``.

    def data_write(self, entry):
        """Persist the state-machine data a log entry carries, outside the
        log (DataApi.write).  Called on the loop, in index order, as the
        entry is appended; returns an awaitable that completes when the
        bytes are written (and forced, where the state machine's request
        says so), or None when there is nothing to wait for.  The entry may
        be committed by the other replicas, and so applied here, before this
        replica's own write has completed: an apply that needs the bytes
        written waits for its own awaitable.  Default: no-op (the data is
        then held by nothing but the log's cache, which keeps it)."""
        return None

    def data_read(self, entry) -> bytes:
        """The state-machine data of ``entry`` (``entry.smlog.data_size``
        bytes), which the log no longer holds (DataApi.read).  Blocking:
        called off the loop."""
        raise NotImplementedError(
            f"{type(self).__name__} keeps no state-machine data to read back")

    async def data_flush(self, index: int) -> None:
        """Force state-machine data up to a log index (DataApi.flush);
        default no-op."""

    async def data_truncate(self, index: int) -> None:
        """The log dropped its entries from ``index`` on: drop their data
        (DataApi.truncate); default no-op."""

    def __str__(self) -> str:
        return f"{type(self).__name__}@{self.member_id}"


class BaseStateMachine(StateMachine):
    """Alias matching the reference's convenience base
    (ratis-server/.../statemachine/impl/BaseStateMachine.java); the tracking
    behavior already lives in StateMachine here."""
