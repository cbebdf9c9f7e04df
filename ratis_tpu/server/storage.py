"""RaftStorage: on-disk layout, lock, metadata, and conf files per division.

Capability parity with the reference storage layer
(ratis-server/.../storage/RaftStorageImpl.java, RaftStorageDirectoryImpl.java:40-98):

    <root>/<groupId-uuid>/
        in_use.lock              exclusive-use marker
        current/
            raft-meta            (term, votedFor) — atomic tmp+rename
            raft-meta.conf       latest committed RaftConfiguration entry
            log_<s>-<e>          closed log segments
            log_inprogress_<s>   the open segment
        sm/                      StateMachine snapshots
        tmp/                     staging (snapshot install, atomic writes)

Atomic writes follow the reference AtomicFileOutputStream (tmp + rename);
metadata is msgpack instead of the reference's java Properties text.

Under ``raft.tpu.log.shared`` a group has none of this of its own: its log,
term, vote and configuration are records of its shard's one segment sequence
(``server/log/shared.py``), read and written through
:class:`SharedGroupStorage` and :class:`SharedMetadataIO`; ``<root>/<uuid>/``
is made only when the group's state machine keeps something there (a
snapshot).
"""

from __future__ import annotations

import asyncio
import os
import pathlib
from typing import Optional

import msgpack

from ratis_tpu.protocol.exceptions import AlreadyClosedException, RaftException
from ratis_tpu.protocol.ids import RaftGroupId, RaftPeerId
from ratis_tpu.protocol.logentry import LogEntry
from ratis_tpu.server.log.shared import REC_CONF, REC_META
from ratis_tpu.server.state import MetadataIO
from ratis_tpu.trace.tracer import STAGE_LOG_META, TRACER


_TMP_IDS = __import__("itertools").count(1)


def atomic_write(path: pathlib.Path, data: bytes) -> None:
    """tmp + fsync + rename (reference AtomicFileOutputStream).  The tmp
    name is unique per call: two concurrent writers of the SAME target
    (mass step-downs persisting raft-meta from racing to_thread workers
    — found by the chaos campaign's leader-crash scenario at 1024
    groups) must degrade to last-rename-wins, not to one of them
    renaming the other's half-written (or already-consumed) tmp away."""
    tmp = path.with_name(f"{path.name}.tmp{os.getpid()}.{next(_TMP_IDS)}")
    with open(tmp, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


def lock_in_use(lock: pathlib.Path) -> None:
    """Exclusive-use marker (reference in_use.lock) at ``lock``, for a
    group's directory or a shared log's shard.  Single-process protection:
    O_EXCL create; stale locks from crashed processes are reclaimed when
    the recorded pid is dead.  Raises where a live process holds it, this
    one included."""
    try:
        fd = os.open(lock, os.O_CREAT | os.O_EXCL | os.O_WRONLY)
        os.write(fd, str(os.getpid()).encode())
        os.close(fd)
    except FileExistsError:
        try:
            pid = int(lock.read_text() or "0")
        except ValueError:
            pid = 0
        alive = False
        if pid > 0:
            if pid == os.getpid():
                alive = True  # another holder in THIS process
            else:
                try:
                    os.kill(pid, 0)
                    alive = True
                except OSError:
                    alive = False
        if alive:
            raise RaftException(
                f"storage {lock.parent} is locked by live pid {pid}")
        lock.write_text(str(os.getpid()))


class RaftStorageDirectory:
    META_FILE = "raft-meta"
    CONF_FILE = "raft-meta.conf"
    LOCK_FILE = "in_use.lock"

    def __init__(self, root: "str | pathlib.Path", group_id: RaftGroupId):
        self.root = pathlib.Path(root) / str(group_id.uuid)
        self.current = self.root / "current"
        self.sm_dir = self.root / "sm"
        self.tmp_dir = self.root / "tmp"
        self.group_id = group_id
        self._locked = False

    def format(self) -> None:
        for d in (self.current, self.sm_dir, self.tmp_dir):
            d.mkdir(parents=True, exist_ok=True)

    def lock(self) -> None:
        lock_in_use(self.root / self.LOCK_FILE)
        self._locked = True

    def unlock(self) -> None:
        if self._locked:
            (self.root / self.LOCK_FILE).unlink(missing_ok=True)
            self._locked = False

    # -- raft-meta ------------------------------------------------------------

    def persist_metadata(self, term: int, voted_for: Optional[RaftPeerId]) -> None:
        data = msgpack.packb({"t": term,
                              "v": None if voted_for is None else voted_for.id})
        atomic_write(self.current / self.META_FILE, data)

    def load_metadata(self) -> tuple[int, Optional[RaftPeerId]]:
        path = self.current / self.META_FILE
        if not path.exists():
            return 0, None
        d = msgpack.unpackb(path.read_bytes(), raw=False)
        v = d.get("v")
        return d.get("t", 0), None if v is None else RaftPeerId.value_of(v)

    # -- raft-meta.conf -------------------------------------------------------

    def persist_conf_entry(self, entry: LogEntry) -> None:
        atomic_write(self.current / self.CONF_FILE, entry.to_bytes())

    def load_conf_entry(self) -> Optional[LogEntry]:
        path = self.current / self.CONF_FILE
        if not path.exists():
            return None
        return LogEntry.from_bytes(path.read_bytes())

    def exists(self) -> bool:
        return self.current.exists()

    def metadata_io(self) -> "FileMetadataIO":
        return FileMetadataIO(self)


class FileMetadataIO(MetadataIO):
    """ServerState's (term, votedFor) persistence over RaftStorageDirectory.
    The blocking fsync runs in a thread so the event loop never stalls.

    Persists SERIALIZE per division and never regress the on-disk term:
    a vote handler and an append handler can both drive a term update in
    the same burst, and with unserialized to_thread workers the OLDER
    term could land last on disk — a durable term regression that lets a
    restarted node double-vote (found by the chaos campaign's election
    storms)."""

    def __init__(self, directory: RaftStorageDirectory):
        self.directory = directory
        self._lock = asyncio.Lock()
        self._last_term = -1

    async def persist(self, term: int, voted_for: Optional[RaftPeerId]) -> None:
        async with self._lock:
            if term < self._last_term:
                return  # stale writer lost the race; newer term is on disk
            self._last_term = term
            await asyncio.to_thread(self.directory.persist_metadata, term,
                                    voted_for)

    async def load(self) -> tuple[int, Optional[RaftPeerId]]:
        # (file reads, off the loop as the directories' making is)
        return await asyncio.to_thread(self.directory.load_metadata)

    async def persist_conf(self, entry: LogEntry) -> None:
        await asyncio.to_thread(self.directory.persist_conf_entry, entry)

    async def load_conf(self) -> Optional[LogEntry]:
        return await asyncio.to_thread(self.directory.load_conf_entry)


class SharedGroupStorage:
    """A group's storage on the shared log plane: its log (a
    ``SharedGroupLog``) holds its hard state too.  ``root`` is where the
    group's state machine may make a directory of its own; nothing here
    makes it."""

    def __init__(self, root: "str | pathlib.Path", group_id: RaftGroupId,
                 log) -> None:
        self.root = pathlib.Path(root) / str(group_id.uuid)
        self.sm_dir = self.root / "sm"
        self.tmp_dir = self.root / "tmp"
        self.group_id = group_id
        self.log = log

    def metadata_io(self) -> "SharedMetadataIO":
        return SharedMetadataIO(self.log)

    def mark_removed(self) -> None:
        """The group is being removed for good: its log's close writes the
        REMOVE record, and a restart no longer finds it."""
        self.log.removed = True

    def unlock(self) -> None:
        pass


class SharedMetadataIO(MetadataIO):
    """(term, votedFor) and the configuration entry as records of the
    group's shard (``SharedGroupLog.persist_meta`` / ``persist_conf``): a
    persist returns once the batch that carries its record is fsynced, as
    :class:`FileMetadataIO`'s ``atomic_write`` does.  Records are queued in
    call order, so the newest persist is the last record; as in
    :class:`FileMetadataIO` a persist older than one already made is
    dropped, and recovery keeps the highest term it finds besides."""

    def __init__(self, log) -> None:
        self.log = log
        self._last_term = -1

    async def persist(self, term: int, voted_for: Optional[RaftPeerId]) -> None:
        if term < self._last_term:
            return  # a newer term is already on its way to the disk
        self._last_term = term
        await self._wait(self.log.persist_meta(
            term, None if voted_for is None else voted_for.id), REC_META)

    async def load(self) -> tuple[int, Optional[RaftPeerId]]:
        h = self.log.hard_state()
        return h.term, (None if h.voted_for is None
                        else RaftPeerId.value_of(h.voted_for))

    async def persist_conf(self, entry: LogEntry) -> None:
        await self._wait(self.log.persist_conf(entry), REC_CONF)

    async def load_conf(self) -> Optional[LogEntry]:
        conf = self.log.hard_state().conf
        return None if conf is None else LogEntry.from_bytes(conf)

    @staticmethod
    async def _wait(queued, kind: int) -> None:
        # log.meta: the persist's call -> its record's fsync seen on the loop
        t0 = TRACER.now() if TRACER.enabled else 0
        await queued
        TRACER.interval(STAGE_LOG_META, t0, kind)


def scan_group_dirs(root: "str | pathlib.Path") -> list[RaftGroupId]:
    """Boot-time discovery of hosted groups (RaftServerProxy.initGroups:257)."""
    rootp = pathlib.Path(root)
    out = []
    if not rootp.exists():
        return out
    for child in rootp.iterdir():
        if not child.is_dir():
            continue
        try:
            gid = RaftGroupId.value_of(child.name)
        except ValueError:
            continue
        if (child / "current").exists():
            out.append(gid)
    return out
