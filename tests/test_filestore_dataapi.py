"""StateMachine.DataApi on the normal path: a WRITE's bytes go round the
raft log (header in ``log_data``, bytes in ``sm_data``, written beside the
log by ``data_write``, read back by ``data_read``), held against a plain
reference of the FileStore's semantics (tests/filestore_reference.py) on
seeded data."""

import asyncio
import pathlib

import msgpack
import pytest

from filestore_reference import FileStoreModel, seeded_requests
from minicluster import MiniCluster, run_with_new_cluster
from ratis_tpu.models.filestore import FileStoreStateMachine
from ratis_tpu.protocol.exceptions import RaftLogIOException
from ratis_tpu.protocol.logentry import LogEntry, make_transaction_entry
from ratis_tpu.server.log.base import DATA_CACHE_LAG
from ratis_tpu.server.log.memory import MemoryRaftLog
from ratis_tpu.server.log.segmented import (LogWorker, SegmentedRaftLog,
                                            read_records)
from ratis_tpu.server.log.shared import SharedGroupLog, SharedLogStore
from ratis_tpu.server.storage import RaftStorageDirectory
from ratis_tpu.trace import TRACER

TRANSPORTS = ("SIMULATED", "TCP")
KINDS = ("memory", "segmented", "shared")


def pack(request: dict) -> bytes:
    return msgpack.packb(request, use_bin_type=True)


async def send_all(client, requests, model: FileStoreModel) -> int:
    """Every request through the client; every reply against the model's.
    Returns the last acknowledged log index."""
    last = -1
    for req in requests:
        expected = model.write(req["path"], req["offset"], req["data"],
                               req["close"])
        reply = await client.io().send(pack(req))
        if expected is None:
            assert not reply.success, req["path"]
            continue
        assert reply.success, reply.exception
        got = msgpack.unpackb(reply.message.content, raw=False)
        assert {k: got[k] for k in expected} == expected
        last = reply.log_index
    return last


def files_of(sm: FileStoreStateMachine) -> dict[str, bytes]:
    """path -> bytes of every file a replica holds, open or closed."""
    out = {}
    root = sm.root
    for sub in (root / ".uc", root):
        for p in sub.rglob("*"):
            rel = p.relative_to(sub)
            if p.is_file() and rel.parts[0] not in (".uc", ".tmp"):
                out[str(rel)] = p.read_bytes()
    return out


def assert_replicas_equal_model(cluster, model: FileStoreModel) -> None:
    want = {p: bytes(b) for p, b in model.files.items()}
    for div in cluster.divisions():
        sm = div.state_machine
        assert files_of(sm) == want, div.member_id
        assert sorted(sm.files) == sorted(model.closed)
        assert sm.writes_committed == model.writes


async def settle(cluster, client, model: FileStoreModel) -> None:
    """One more write: the append behind the last entry tells the followers
    that it is committed."""
    req = {"op": "write", "path": "settle", "offset":
           len(model.files.get("settle", b"")), "close": False,
           "sync": False, "data": b"s"}
    index = await send_all(client, [req], model)
    await cluster.wait_applied(index)
    hb = await client.io().send(pack(dict(req, offset=req["offset"] + 1)))
    model.write("settle", req["offset"] + 1, b"s", False)
    await cluster.wait_applied(hb.log_index)


# ------------------------------------------------------------ end to end

@pytest.mark.parametrize("durable", (False, True), ids=("memory", "durable"))
@pytest.mark.parametrize("rpc", TRANSPORTS)
def test_replies_and_every_replicas_bytes_equal_the_reference(
        rpc, durable, tmp_path):
    model = FileStoreModel()

    async def t(cluster):
        await cluster.wait_for_leader()
        async with cluster.new_client() as client:
            await send_all(client, seeded_requests(11, 6), model)
            await settle(cluster, client, model)
        assert_replicas_equal_model(cluster, model)
        assert model.closed and len(model.files) > len(model.closed)

    run_with_new_cluster(3, t, rpc_type=rpc, sm_factory=FileStoreStateMachine,
                         storage_root=str(tmp_path) if durable else None)


@pytest.mark.parametrize("rpc", TRANSPORTS)
def test_segment_files_hold_the_headers_and_none_of_the_bytes(rpc, tmp_path):
    model = FileStoreModel()
    requests = seeded_requests(12, 4)

    async def t(cluster):
        await cluster.wait_for_leader()
        async with cluster.new_client() as client:
            await send_all(client, requests, model)
            await settle(cluster, client, model)

    run_with_new_cluster(3, t, rpc_type=rpc, sm_factory=FileStoreStateMachine,
                         storage_root=str(tmp_path))
    segments = list(pathlib.Path(tmp_path).rglob("log_*"))
    assert len(segments) == 3
    for seg in segments:
        raw = seg.read_bytes()
        payloads, _ = read_records(seg)
        headers = []
        for p in payloads:
            e = LogEntry.from_bytes(p)
            if e.smlog is not None:
                assert e.smlog.sm_data is None
                headers.append((msgpack.unpackb(e.smlog.log_data),
                                e.smlog.data_size))
        for req in requests:
            assert req["data"][:64] not in raw
            assert any(h["path"] == req["path"] and h["offset"] ==
                       req["offset"] and size == len(req["data"])
                       for h, size in headers), req["path"]


@pytest.mark.parametrize("rpc", TRANSPORTS)
def test_refused_writes_leave_the_file_as_the_reference_has_it(rpc):
    model = FileStoreModel()

    async def t(cluster):
        await cluster.wait_for_leader()
        a, b = b"a" * 2000, b"b" * 1500
        requests = [
            {"op": "write", "path": "x", "offset": 0, "close": False,
             "sync": False, "data": a},
            {"op": "write", "path": "x", "offset": 5, "close": False,
             "sync": False, "data": b},                 # inside the file
            {"op": "write", "path": "x", "offset": 4000, "close": False,
             "sync": False, "data": b},                 # past its end
            {"op": "write", "path": "x", "offset": 2000, "close": True,
             "sync": True, "data": b},
            {"op": "write", "path": "x", "offset": 3500, "close": True,
             "sync": False, "data": a},                 # after close
            {"op": "write", "path": "x", "offset": 0, "close": True,
             "sync": False, "data": a},                 # a closed path again
            {"op": "write", "path": "y", "offset": 7, "close": True,
             "sync": False, "data": a},                 # a new file, not at 0
        ]
        async with cluster.new_client() as client:
            await send_all(client, requests, model)
            await settle(cluster, client, model)
        assert model.files["x"] == a + b and "y" not in model.files
        assert_replicas_equal_model(cluster, model)

    run_with_new_cluster(3, t, rpc_type=rpc, sm_factory=FileStoreStateMachine)


def test_sync_forces_before_the_acknowledgement(tmp_path, monkeypatch):
    """A ``sync`` write is forced on a majority when its reply arrives; one
    without is written and not forced."""
    import os
    forced = []
    real = os.fsync

    def counting(fd):
        target = os.readlink(f"/proc/self/fd/{fd}")
        if "/.uc/" in target:
            forced.append(target)
        return real(fd)

    monkeypatch.setattr(os, "fsync", counting)

    async def t(cluster):
        await cluster.wait_for_leader()
        async with cluster.new_client() as client:
            r = await client.io().send(pack(
                {"op": "write", "path": "f", "offset": 0, "close": False,
                 "sync": False, "data": b"n" * 3000}))
            assert r.success and not forced
            r = await client.io().send(pack(
                {"op": "write", "path": "f", "offset": 3000, "close": False,
                 "sync": True, "data": b"y" * 3000}))
            assert r.success
            assert len(forced) >= 2     # the leader's and a follower's
            await cluster.wait_applied(r.log_index)

    run_with_new_cluster(3, t, sm_factory=FileStoreStateMachine,
                         storage_root=str(tmp_path))
    assert len(forced) == 3


@pytest.mark.parametrize("rpc", TRANSPORTS)
def test_a_restarted_follower_gets_the_bytes_through_data_read(rpc, tmp_path):
    """A follower stopped over more writes than the cache keeps for a
    laggard: the leader's cache has let their data go, the appender reads
    it back through data_read (the counter moves), the follower's files
    come out byte for byte."""
    model = FileStoreModel()
    reads = TRACER.counter("sm.data_reads")

    async def t(cluster):
        leader = await cluster.wait_for_leader()
        follower = next(d for d in cluster.divisions() if not d.is_leader())
        async with cluster.new_client() as client:
            await send_all(client, seeded_requests(13, 2), model)
            before = reads.n
            await cluster.kill_server(follower.member_id.peer_id)
            missed = seeded_requests(14, DATA_CACHE_LAG, prefix="m/f")
            assert len(missed) > DATA_CACHE_LAG
            last = await send_all(client, missed, model)
            await cluster.wait_applied(last)
            log = leader.state.log
            # bounded by what is not yet applied and replicated
            assert log.data_held <= DATA_CACHE_LAG
            stripped = log.get(last - DATA_CACHE_LAG - 1)
            assert stripped.smlog.sm_data is None \
                and stripped.smlog.data_size >= 1024
            await cluster.restart_server(follower.member_id.peer_id)
            await settle(cluster, client, model)
            assert reads.n > before
        assert_replicas_equal_model(cluster, model)

    run_with_new_cluster(3, t, rpc_type=rpc, sm_factory=FileStoreStateMachine,
                         storage_root=str(tmp_path))


def test_a_restarted_cluster_replays_and_serves_what_the_disk_holds(tmp_path):
    """Every server stopped and started again: the logs hold the headers
    alone, the state machines rebuild their files' metadata from them, and a
    write continues an open file where it stood."""
    model = FileStoreModel()
    requests = seeded_requests(15, 5)
    cut = len(requests) - 2          # the last file stays open over the restart
    assert not requests[cut - 1]["close"]

    async def main():
        cluster = MiniCluster(3, sm_factory=FileStoreStateMachine,
                              storage_root=str(tmp_path))
        await cluster.start()
        try:
            await cluster.wait_for_leader()
            async with cluster.new_client() as client:
                await send_all(client, requests[:cut], model)
                await settle(cluster, client, model)
            for peer in list(cluster.servers):
                await cluster.kill_server(peer)
            for peer in list(cluster._stopped):
                await cluster.restart_server(peer)
            await cluster.wait_for_leader()
            async with cluster.new_client() as client:
                await send_all(client, requests[cut:], model)
                await settle(cluster, client, model)
            assert_replicas_equal_model(cluster, model)
        finally:
            await cluster.close()

    asyncio.run(main())


@pytest.mark.parametrize("rpc", TRANSPORTS)
def test_a_leader_change_in_the_middle_of_a_file(rpc, tmp_path):
    model = FileStoreModel()
    requests = [r for r in seeded_requests(16, 8)]
    mid = next(i for i, r in enumerate(requests)
               if i > 3 and r["offset"] and not r["close"])

    async def t(cluster):
        leader = await cluster.wait_for_leader()
        async with cluster.new_client() as client:
            await send_all(client, requests[:mid], model)
            await cluster.kill_server(leader.member_id.peer_id)
            await cluster.wait_for_leader()
            await send_all(client, requests[mid:], model)
            await cluster.restart_server(leader.member_id.peer_id)
            await settle(cluster, client, model)
        assert_replicas_equal_model(cluster, model)

    run_with_new_cluster(3, t, rpc_type=rpc, sm_factory=FileStoreStateMachine,
                         storage_root=str(tmp_path))


def test_apply_waits_for_this_replicas_own_write(tmp_path, monkeypatch):
    """The followers commit a file's last write while the leader's own
    data_write is still out: the leader's apply must not close the file
    under it."""
    import time
    from ratis_tpu.models import filestore
    model = FileStoreModel()
    slow_peer = []
    real = filestore._UnderConstruction.write

    def write(self, offset, data, sync):
        if slow_peer and f"/{slow_peer[0]}/" in str(self.uc_path):
            time.sleep(0.15)
        return real(self, offset, data, sync)

    monkeypatch.setattr(filestore._UnderConstruction, "write", write)

    async def t(cluster):
        leader = await cluster.wait_for_leader()
        slow_peer.append(str(leader.member_id.peer_id))
        async with cluster.new_client() as client:
            await send_all(client, seeded_requests(18, 3), model)
            slow_peer.clear()
            await settle(cluster, client, model)
        assert not leader.state.log.failed
        assert_replicas_equal_model(cluster, model)

    run_with_new_cluster(3, t, sm_factory=FileStoreStateMachine,
                         storage_root=str(tmp_path))


@pytest.mark.parametrize("rpc", TRANSPORTS)
def test_a_follower_killed_before_its_data_is_written_holds_no_record_of_it(
        rpc, tmp_path, monkeypatch):
    """A follower whose data_write is held up while the other two commit
    the entry: the disk as a kill would leave it at that moment (copied
    then, put back before the restart) has no record of an entry whose
    bytes it lacks, so the restarted follower does not acknowledge what it
    does not hold, the leader sends the entries again, and its files come
    out byte for byte."""
    import shutil
    import threading
    from ratis_tpu.models import filestore
    model = FileStoreModel()
    held_peer, release = [], threading.Event()
    real = filestore._UnderConstruction.write

    def write(self, offset, data, sync):
        if held_peer and f"/{held_peer[0]}/" in str(self.uc_path):
            release.wait(20)
        return real(self, offset, data, sync)

    monkeypatch.setattr(filestore._UnderConstruction, "write", write)
    crashed = tmp_path / "crashed"

    async def t(cluster):
        await cluster.wait_for_leader()
        follower = next(d for d in cluster.divisions() if not d.is_leader())
        peer = follower.member_id.peer_id
        root = pathlib.Path(cluster.storage_root) / str(peer)
        async with cluster.new_client() as client:
            first = seeded_requests(19, 2)
            await send_all(client, first, model)
            await settle(cluster, client, model)
            before = follower.state.log.next_index
            held_peer.append(str(peer))
            try:
                held = [r for r in seeded_requests(20, 2, prefix="h/f")][:3]
                last = await send_all(client, held, model)  # 2 of 3 commit
                await turns()
                log = follower.state.log
                assert log.next_index > before      # appended in memory,
                assert log.flush_index < before     # not acknowledged,
                for seg in root.rglob("log_*"):     # and not on the disk
                    indexes = [LogEntry.from_bytes(p).index
                               for p in read_records(seg)[0]]
                    assert all(i < before for i in indexes), indexes
                # (less the lock, which names this process as its holder)
                await asyncio.to_thread(
                    shutil.copytree, root, crashed,
                    ignore=shutil.ignore_patterns(RaftStorageDirectory.LOCK_FILE))
            finally:
                held_peer.clear()
                release.set()
            await cluster.kill_server(peer)
            await asyncio.to_thread(shutil.rmtree, root)
            await asyncio.to_thread(shutil.copytree, crashed, root)
            await cluster.restart_server(peer)
            await settle(cluster, client, model)
            await cluster.wait_applied(last)
        assert_replicas_equal_model(cluster, model)

    run_with_new_cluster(3, t, rpc_type=rpc, sm_factory=FileStoreStateMachine,
                         storage_root=str(tmp_path / "servers"))


def test_a_close_cuts_off_what_a_crashed_life_wrote_past_the_commit(tmp_path):
    uc, target = tmp_path / "uc", tmp_path / "f"
    uc.write_bytes(b"committed" + b"stale tail")
    FileStoreStateMachine._move_into_place(uc, target, False, 9)
    assert target.read_bytes() == b"committed" and not uc.exists()


def test_memory_held_in_sm_data_is_bounded_after_a_settled_run():
    model = FileStoreModel()

    async def t(cluster):
        await cluster.wait_for_leader()
        async with cluster.new_client() as client:
            await send_all(client, seeded_requests(17, 12), model)
            await settle(cluster, client, model)
        for div in cluster.divisions():
            log = div.state.log
            # what is applied and replicated has let go: at the most the
            # settle round's entries still hold their bytes
            assert log.data_held <= 2, div.member_id
            held = sum(len(log.get(i).smlog.sm_data or b"")
                       for i in range(log.start_index, log.next_index)
                       if log.get(i).smlog is not None)
            assert held <= 2

    run_with_new_cluster(3, t, sm_factory=FileStoreStateMachine)


# -------------------------------------------------------------- the log

class GatedData:
    """A DataApi whose writes complete when the test says so."""

    def __init__(self) -> None:
        self.writes: dict[int, asyncio.Future] = {}
        self.truncated: list[int] = []
        self.read = 0

    def data_write(self, entry):
        fut = asyncio.get_running_loop().create_future()
        self.writes[entry.index] = fut
        return fut

    def data_read(self, entry) -> bytes:
        self.read += 1
        return b"d" * entry.smlog.data_size

    async def data_truncate(self, index: int) -> None:
        self.truncated.append(index)


def data_entry(index: int, term: int = 1, data: bytes = b"d" * 100):
    return make_transaction_entry(term, index, b"c" * 16, index, b"header",
                                  sm_data=data)


def plain_entry(index: int, term: int = 1):
    return make_transaction_entry(term, index, b"c" * 16, index, b"INCREMENT")


async def make_log(kind: str, tmp_path):
    if kind == "memory":
        log = MemoryRaftLog("t")
    elif kind == "segmented":
        log = SegmentedRaftLog("t", tmp_path / "current")
    else:
        log = SharedGroupLog("t", b"g" * 16, SharedLogStore(
            tmp_path / "shared", LogWorker(f"shared-{tmp_path.name}")))
    await log.open()
    api = GatedData()
    log.set_data_api(api)
    return log, api


def records_on_disk(log):
    """Indexes of the records the segmented log's files hold as a crash
    would leave them (what is written through to the OS); None for the log
    in memory."""
    if not isinstance(log, SegmentedRaftLog):
        return None
    return [LogEntry.from_bytes(p).index
            for seg in sorted(log.dir.glob("log_*"))
            for p in read_records(seg)[0]]


async def turns(n: int = 6) -> None:
    for _ in range(n):
        await asyncio.sleep(0.01)


@pytest.mark.parametrize("kind", KINDS)
def test_flush_index_waits_for_the_data_in_index_order(kind, tmp_path):
    async def t():
        log, api = await make_log(kind, tmp_path)
        seen = []
        log.set_flush_callbacks(seen.append, lambda e: None)
        await log.append_entry(plain_entry(0))
        await log.append_entry(data_entry(1), wait_flush=False)
        await log.append_entry(data_entry(2), wait_flush=False)
        await log.append_entry(plain_entry(3), wait_flush=False)
        await turns()
        assert log.flush_index == 0      # 1 is out
        assert records_on_disk(log) in (None, [0])
        api.writes[2].set_result(None)   # out of order: nothing moves
        await turns()
        assert log.flush_index == 0
        assert records_on_disk(log) in (None, [0])
        api.writes[1].set_result(None)
        await turns()
        assert log.flush_index == 3 and seen[-1] == 3
        assert records_on_disk(log) in (None, [0, 1, 2, 3])
        await log.close()

    asyncio.run(t())


def test_the_worker_records_what_the_data_held_its_batch_back(tmp_path):
    """``server.data_wait``: nothing for a record whose data came first, the
    wait for one whose data the batch was held for."""
    from ratis_tpu.trace import export

    async def t():
        log, api = await make_log("segmented", tmp_path)
        TRACER.configure(enabled=True, sample_every=1, ring_size=64)
        try:
            await log.append_entry(data_entry(0), wait_flush=False)
            api.writes[0].set_result(None)      # before the worker's turn
            await turns()
            await log.append_entry(data_entry(1), wait_flush=False)
            await asyncio.sleep(0.05)
            api.writes[1].set_result(None)
            await turns()
            assert log.flush_index == 1
            first, second = export.session_durations_ms("server.data_wait")
        finally:
            TRACER.configure(enabled=False)
        assert first == 0 and 40 <= second < 500
        await log.close()

    asyncio.run(t())


@pytest.mark.parametrize("kind", KINDS)
def test_a_followers_append_returns_once_the_data_is_written(kind, tmp_path):
    async def t():
        log, api = await make_log(kind, tmp_path)
        task = asyncio.ensure_future(
            log.append_entries_follower([data_entry(0), data_entry(1)]))
        await turns()
        assert not task.done() and log.flush_index == -1
        api.writes[0].set_result(None)
        api.writes[1].set_result(None)
        assert await task == 1 and log.flush_index == 1
        await log.close()

    asyncio.run(t())


@pytest.mark.parametrize("kind", KINDS)
def test_a_failed_data_write_is_a_failed_log_write(kind, tmp_path):
    async def t():
        log, api = await make_log(kind, tmp_path)
        failures = []
        log.set_flush_callbacks(lambda i: None, failures.append)
        await log.append_entry(data_entry(0), wait_flush=False)
        await log.append_entry(plain_entry(1), wait_flush=False)
        api.writes[0].set_exception(OSError("disk full"))
        await turns()
        assert log.failed and log.flush_index == -1
        assert isinstance(failures[0], OSError)
        # neither the record whose data failed nor one behind it is written
        assert records_on_disk(log) in (None, [])
        if kind != "memory":
            with pytest.raises(RaftLogIOException):
                await log.append_entry(plain_entry(2))
        await log.close()

    asyncio.run(t())


@pytest.mark.parametrize("kind", KINDS)
def test_truncation_waits_for_the_writes_and_tells_the_state_machine(
        kind, tmp_path):
    async def t():
        log, api = await make_log(kind, tmp_path)
        await log.append_entry(plain_entry(0), wait_flush=False)
        await log.append_entry(data_entry(1), wait_flush=False)
        await log.append_entry(data_entry(2), wait_flush=False)
        task = asyncio.ensure_future(log.append_entries_follower(
            [data_entry(1, term=2, data=b"n" * 10)]))
        await turns()
        assert not api.truncated         # the old writes are still out
        api.writes.pop(1).set_result(None)
        api.writes.pop(2).set_result(None)
        await turns()
        assert api.truncated == [1]
        api.writes[1].set_result(None)
        assert await task == 1
        assert log.flush_index == 1 and log.get(1).term == 2
        assert log.data_held == 1
        await log.close()

    asyncio.run(t())


@pytest.mark.parametrize("kind", KINDS)
def test_released_data_is_read_back_for_an_appender(kind, tmp_path):
    async def t():
        log, api = await make_log(kind, tmp_path)
        for i in range(4):
            await log.append_entry(
                data_entry(i) if i % 2 else plain_entry(i), wait_flush=False)
        for fut in api.writes.values():
            fut.set_result(None)
        await turns()
        assert log.release_data(2) == 1 and log.data_held == 1
        assert log.get(1).smlog.sm_data is None
        assert log.get(1).smlog.data_size == 100
        assert log.get(3).smlog.sm_data is not None
        assert log.is_resident(0) and not log.is_resident(1)
        # an entry is never shipped without the data it names
        assert [e.index for e in log.get_entries(0, 4)] == [0]
        await asyncio.to_thread(log.prefault, 1)
        assert api.read == 1 and log.is_resident(1)
        got = log.get_entries(0, 4)
        assert [e.index for e in got] == [0, 1, 2, 3]
        assert got[1].smlog.sm_data == b"d" * 100
        await log.close()

    asyncio.run(t())


def test_a_reopened_segmented_log_reads_its_data_back(tmp_path):
    async def t():
        log, api = await make_log("segmented", tmp_path)
        await log.append_entry(data_entry(0), wait_flush=False)
        api.writes[0].set_result(None)
        await turns()
        await log.close()
        log, api = await make_log("segmented", tmp_path)
        e = log.get(0)
        assert e.smlog.sm_data is None and e.smlog.data_size == 100
        assert not log.is_resident(0)
        await asyncio.to_thread(log.prefault, 0)
        assert log.get_entries(0, 1)[0].smlog.sm_data == b"d" * 100
        await log.close()

    asyncio.run(t())


def test_the_record_names_the_size_of_the_data_it_leaves_out():
    e = data_entry(7, data=b"x" * 4096)
    on_disk = LogEntry.from_bytes(e.to_bytes(include_sm_data=False))
    assert len(e.to_bytes(include_sm_data=False)) < 200
    assert on_disk.smlog.sm_data is None and on_disk.smlog.data_size == 4096
    on_wire = LogEntry.from_bytes(e.to_bytes())
    assert on_wire == e and on_wire.smlog.data_size == 4096
    assert e.without_sm_data() == on_disk
    assert on_disk.with_sm_data(b"x" * 4096) == e
    plain = plain_entry(8)
    assert "sx" not in plain.to_dict(False)["s"]
    assert LogEntry.from_bytes(plain.to_bytes(False)) == plain
