"""Durable log + storage tests.

Mirrors the reference coverage of TestSegmentedRaftLog, TestRaftLogReadWrite,
TestRaftStorage and ServerRestartTests (ratis-test/.../segmented/,
ratis-server/src/test): segment round-trip, corrupt-tail recovery, truncate,
purge, metadata persistence, full-cluster restart with durable state.
"""

import asyncio
import pathlib

import pytest

from ratis_tpu.protocol.ids import ClientId, RaftGroupId, RaftPeerId
from ratis_tpu.protocol.logentry import make_transaction_entry
from ratis_tpu.protocol.termindex import TermIndex
from ratis_tpu.server.log.segmented import (MAGIC, LogWorker,
                                            SegmentedRaftLog, read_records)
from ratis_tpu.server.storage import (RaftStorageDirectory, atomic_write,
                                      scan_group_dirs)
from tests.minicluster import MiniCluster


def entry(term, index, size=8):
    return make_transaction_entry(term, index, ClientId.random_id(), index,
                                  b"x" * size)


def run(coro):
    return asyncio.run(coro)


class TestSegmentedLog:
    def test_append_close_reopen(self, tmp_path):
        async def body():
            log = SegmentedRaftLog("t", tmp_path, worker=LogWorker("w1"))
            await log.open()
            for i in range(10):
                await log.append_entry(entry(1, i))
            assert log.flush_index == 9
            await log.close()

            log2 = SegmentedRaftLog("t", tmp_path, worker=LogWorker("w2"))
            await log2.open()
            assert log2.next_index == 10
            assert log2.get(5).term_index() == TermIndex(1, 5)
            assert log2.flush_index == 9
            await log2.close()

        run(body())

    def test_segment_rollover_and_recovery(self, tmp_path):
        async def body():
            log = SegmentedRaftLog("t", tmp_path, worker=LogWorker("w"),
                                   segment_size_max=256)
            await log.open()
            for i in range(30):
                await log.append_entry(entry(1, i, size=32))
            await log.close()
            files = sorted(p.name for p in tmp_path.iterdir())
            closed = [f for f in files if f.startswith("log_") and
                      "inprogress" not in f]
            assert len(closed) >= 2, files

            log2 = SegmentedRaftLog("t", tmp_path, worker=LogWorker("w2"))
            await log2.open()
            assert log2.next_index == 30
            assert all(log2.get(i) is not None for i in range(30))
            await log2.close()

        run(body())

    def test_corrupt_tail_truncated_on_recovery(self, tmp_path):
        async def body():
            log = SegmentedRaftLog("t", tmp_path, worker=LogWorker("w"))
            await log.open()
            for i in range(5):
                await log.append_entry(entry(1, i))
            await log.close()
            # simulate a torn write: garbage appended to the open segment
            open_seg = next(p for p in tmp_path.iterdir()
                            if p.name.startswith("log_inprogress_"))
            with open(open_seg, "ab") as f:
                f.write(b"\x13\x37GARBAGE")

            log2 = SegmentedRaftLog("t", tmp_path, worker=LogWorker("w2"))
            await log2.open()
            assert log2.next_index == 5  # garbage dropped, entries intact
            await log2.append_entry(entry(1, 5))  # and appendable again
            await log2.close()
            payloads, _ = read_records(open_seg)
            assert len(payloads) == 6

        run(body())

    def test_truncate_within_and_across_segments(self, tmp_path):
        async def body():
            log = SegmentedRaftLog("t", tmp_path, worker=LogWorker("w"),
                                   segment_size_max=256)
            await log.open()
            for i in range(20):
                await log.append_entry(entry(1, i, size=32))
            await log.truncate(7)
            assert log.next_index == 7
            assert log.get(7) is None and log.get(6) is not None
            # appends continue with a different term (conflict resolution)
            for i in range(7, 12):
                await log.append_entry(entry(2, i))
            await log.close()

            log2 = SegmentedRaftLog("t", tmp_path, worker=LogWorker("w2"))
            await log2.open()
            assert log2.next_index == 12
            assert log2.get(8).term == 2
            await log2.close()

        run(body())

    def test_purge_drops_whole_segments(self, tmp_path):
        async def body():
            log = SegmentedRaftLog("t", tmp_path, worker=LogWorker("w"),
                                   segment_size_max=200)
            await log.open()
            for i in range(30):
                await log.append_entry(entry(1, i, size=32))
            before = len(list(tmp_path.iterdir()))
            await log.purge(15)
            after = len(list(tmp_path.iterdir()))
            assert after < before
            assert log.start_index > 0
            assert log.get(log.start_index) is not None
            assert log.next_index == 30
            await log.close()

        run(body())

    def test_shared_worker_batches_fsync(self, tmp_path):
        async def body():
            w = LogWorker("shared")
            log_a = SegmentedRaftLog("a", tmp_path / "a", worker=w)
            log_b = SegmentedRaftLog("b", tmp_path / "b", worker=w)
            await log_a.open()
            await log_b.open()
            await asyncio.gather(*(
                log.append_entry(entry(1, i))
                for log in (log_a, log_b) for i in [0]))
            await asyncio.gather(log_a.append_entry(entry(1, 1)),
                                 log_b.append_entry(entry(1, 1)))
            assert w.metrics["writes"] >= 4
            # batching: fewer flush rounds than writes
            assert w.metrics["flushes"] <= w.metrics["writes"]
            await log_a.close()
            await log_b.close()

        run(body())


class TestRaftStorageDirectory:
    def test_metadata_roundtrip(self, tmp_path):
        gid = RaftGroupId.random_id()
        sd = RaftStorageDirectory(tmp_path, gid)
        sd.format()
        assert sd.load_metadata() == (0, None)
        sd.persist_metadata(7, RaftPeerId.value_of("s1"))
        assert sd.load_metadata() == (7, RaftPeerId.value_of("s1"))
        assert scan_group_dirs(tmp_path) == [gid]

    def test_lock_reclaims_stale(self, tmp_path):
        gid = RaftGroupId.random_id()
        sd = RaftStorageDirectory(tmp_path, gid)
        sd.format()
        (sd.root / "in_use.lock").write_text("999999")  # dead pid
        sd.lock()  # reclaims
        sd2 = RaftStorageDirectory(tmp_path, gid)
        with pytest.raises(Exception, match="locked by live pid"):
            sd2.lock()
        sd.unlock()


class TestDurableCluster:
    def test_full_cluster_restart_preserves_state(self, tmp_path):
        async def body():
            cluster = MiniCluster(3, storage_root=str(tmp_path))
            await cluster.start()
            try:
                await cluster.wait_for_leader()
                for _ in range(5):
                    assert (await cluster.send_write()).success
                term_before = max(d.state.current_term
                                  for d in cluster.divisions())
                # stop all, restart all — state must come back from disk
                for pid in list(cluster.servers):
                    await cluster.kill_server(pid)
                for pid in list(cluster._stopped):
                    await cluster.restart_server(pid)
                leader = await cluster.wait_for_leader()
                assert leader.state.current_term >= term_before
                last = leader.state.log.get_last_committed_index()
                reply = await cluster.send_read()
                assert reply.message.content == b"5"
                assert (await cluster.send_write()).message.content == b"6"
            finally:
                await cluster.close()

        run(body())

    def test_votes_survive_restart(self, tmp_path):
        async def body():
            cluster = MiniCluster(3, storage_root=str(tmp_path))
            await cluster.start()
            try:
                leader = await cluster.wait_for_leader()
                fid = next(d.member_id.peer_id for d in cluster.divisions()
                           if not d.is_leader())
                term = leader.state.current_term
                await cluster.kill_server(fid)
                server = await cluster.restart_server(fid)
                div = server.divisions[cluster.group.group_id]
                # restarted follower remembers the term it acked
                assert div.state.current_term >= term - 1
            finally:
                await cluster.close()

        run(body())


    def test_a_second_group_add_under_way_is_refused(self, tmp_path):
        """Review regression (PR 32): a durable group's directory is made
        and locked in a thread, so ``_add_division`` suspends between its
        check and the registration.  A retry of the same ``group_add``
        meanwhile is refused as one after it would be, and exactly one
        Division sits on the directory."""
        from ratis_tpu.protocol.exceptions import AlreadyExistsException
        from ratis_tpu.protocol.group import RaftGroup

        async def body():
            cluster = MiniCluster(1, storage_root=str(tmp_path))
            await cluster.start()
            try:
                server = next(iter(cluster.servers.values()))
                g2 = RaftGroup.value_of(RaftGroupId.random_id(),
                                        cluster.group.peers)
                got = await asyncio.gather(server.group_add(g2),
                                           server.group_add(g2),
                                           return_exceptions=True)
                assert sorted(type(r).__name__ for r in got) == [
                    "AlreadyExistsException", "Division"], got
                div = next(r for r in got
                           if not isinstance(r, Exception))
                assert server.divisions[g2.group_id] is div
                assert not server._adding
                with pytest.raises(AlreadyExistsException):
                    await server.group_add(g2)
            finally:
                await cluster.close()

        run(body())


class TestSnapshotBoundary:
    def test_empty_log_restarts_above_snapshot(self, tmp_path):
        """Review regression: snapshot at 100 + purged log must not restart
        the log at index 0."""
        async def body():
            log = SegmentedRaftLog("t", tmp_path, worker=LogWorker("w"))
            await log.open()
            log.set_snapshot_boundary(TermIndex(2, 100))
            assert log.next_index == 101
            assert log.start_index == 101
            assert log.get_last_entry_term_index() == TermIndex(2, 100)
            await log.append_entry(entry(2, 101))
            await log.close()

            log2 = SegmentedRaftLog("t", tmp_path, worker=LogWorker("w2"))
            await log2.open()
            assert log2.get(101) is not None
            await log2.close()

        run(body())


def test_log_factory_with_durable_storage_rejected(tmp_path):
    """Review regression: volatile injected log + durable metadata would lose
    acked entries across restarts — the combination must be refused."""
    from ratis_tpu.server.log.memory import MemoryRaftLog

    async def body():
        cluster = MiniCluster(1, storage_root=str(tmp_path),
                              log_factory=lambda s, g: MemoryRaftLog())
        with pytest.raises(ValueError, match="log_factory cannot be combined"):
            await cluster.start()
        await cluster.close()

    run(body())


class TestDecoupledFlush:
    def test_leader_append_returns_before_flush(self, tmp_path):
        """wait_flush=False returns after the in-memory append; flush_index
        catches up from the worker and fires the flush callback."""

        async def body():
            log = SegmentedRaftLog("t", tmp_path, worker=LogWorker("wd1"))
            flushed = []
            log.set_flush_callbacks(flushed.append, lambda e: None)
            await log.open()
            for i in range(5):
                await log.append_entry(entry(1, i), wait_flush=False)
            assert log.next_index == 5  # appended in memory
            deadline = asyncio.get_event_loop().time() + 5.0
            while log.flush_index < 4:
                assert asyncio.get_event_loop().time() < deadline
                await asyncio.sleep(0.01)
            assert flushed[-1] == 4
            await log.close()

        run(body())

    def test_failed_write_latches_log_dead(self, tmp_path, monkeypatch):
        """A failed fsync must latch the log: flush_index never advances past
        the hole even when LATER batches succeed, the error callback fires
        once, and further appends are refused (reference log worker
        terminates on IO failure)."""
        from ratis_tpu.protocol.exceptions import RaftLogIOException
        from ratis_tpu.server.log import segmented as seg_mod

        async def body():
            log = SegmentedRaftLog("t", tmp_path, worker=LogWorker("wd2"))
            errors = []
            log.set_flush_callbacks(lambda i: None, errors.append)
            await log.open()
            await log.append_entry(entry(1, 0))
            assert log.flush_index == 0

            real_fsync = seg_mod.os.fsync
            fail = {"on": True}

            def flaky_fsync(fd):
                if fail["on"]:
                    raise OSError(28, "No space left on device")
                real_fsync(fd)

            monkeypatch.setattr(seg_mod.os, "fsync", flaky_fsync)
            await log.append_entry(entry(1, 1), wait_flush=False)
            # let the failing batch complete
            deadline = asyncio.get_event_loop().time() + 5.0
            while not errors:
                assert asyncio.get_event_loop().time() < deadline
                await asyncio.sleep(0.01)
            fail["on"] = False  # disk "recovers" — must make no difference
            with pytest.raises(RaftLogIOException):
                await log.append_entry(entry(1, 2))
            assert log.flush_index == 0  # never advanced past the hole
            assert len(errors) == 1
            monkeypatch.setattr(seg_mod.os, "fsync", real_fsync)
            await log.close()

        run(body())


class TestCacheEviction:
    """SegmentedRaftLogCache parity (SegmentedRaftLogCache.java): closed
    segments past the cache budget drop their payloads once applied; reads
    below the eviction line come back through the file."""

    def test_evict_and_read_through(self, tmp_path):
        async def body():
            log = SegmentedRaftLog("t", tmp_path, worker=LogWorker("we"),
                                   segment_size_max=256,
                                   cache_segments_max=2)
            await log.open()
            for i in range(40):
                await log.append_entry(entry(1, i, size=32))
            closed = [s for s in log._segments if not s.is_open]
            assert len(closed) > 3  # several closed segments exist
            assert log.evict_cache(applied_index=-1) == 0  # nothing applied
            evicted = log.evict_cache(applied_index=39)
            assert evicted == len(closed) - 2
            assert log.cached_segments == 2
            # metadata stays resident: term/prev checks never fault
            assert log.get_term_index(1) == TermIndex(1, 1)
            # payload reads fault the segment in from disk
            e = log.get(1)
            assert e is not None and e.index == 1
            assert log.metrics.cache_miss_count.count >= 1
            # sequential scan (a lagging follower's catch-up batch) is served
            # from the single-slot read-through cache after the first miss
            first_seg = next(s for s in log._segments if not s.cached)
            entries = log.get_entries(first_seg.start, first_seg.end + 1)
            assert [e.index for e in entries] == list(
                range(first_seg.start, first_seg.end + 1))
            await log.close()

        run(body())

    def test_truncate_into_evicted_segment(self, tmp_path):
        async def body():
            log = SegmentedRaftLog("t", tmp_path, worker=LogWorker("wt"),
                                   segment_size_max=256,
                                   cache_segments_max=0)
            await log.open()
            for i in range(40):
                await log.append_entry(entry(1, i, size=32))
            log.evict_cache(applied_index=39)
            assert log.cached_segments == 0
            # truncate into an evicted segment: reloads, rewrites, stays open
            target = next(s for s in log._segments if not s.is_open)
            cut = target.start + 1
            await log.truncate(cut)
            assert log.next_index == cut
            for i in range(cut, cut + 3):
                await log.append_entry(entry(2, i, size=32))
            assert log.get(cut).term == 2
            assert log.get(cut - 1).term == 1
            await log.close()

        run(body())

    def test_lagging_follower_served_from_disk(self, tmp_path):
        """Cluster-level: a killed follower catches up from a leader whose
        log entries were evicted from memory (reads come through the file,
        not the snapshot path)."""

        async def body(cluster: MiniCluster):
            leader = await cluster.wait_for_leader()
            follower = next(d for d in cluster.divisions()
                            if not d.is_leader())
            fid = follower.member_id.peer_id
            await cluster.kill_server(fid)
            for _ in range(40):
                assert (await cluster.send_write()).success
            for d in cluster.divisions():
                d.state.log.evict_cache(d.applied_index)
                assert d.state.log.cached_segments <= 1
            await cluster.restart_server(fid)
            new_div = cluster.servers[fid].divisions[cluster.group.group_id]
            last = (await cluster.wait_for_leader()).state.log \
                .get_last_committed_index()
            await cluster.wait_applied(last, divisions=[new_div],
                                       timeout=20.0)
            assert new_div.state_machine.counter == 40

        from minicluster import run_with_new_cluster
        from ratis_tpu.conf import RaftProperties, RaftServerConfigKeys
        from tests.minicluster import fast_properties
        p = fast_properties()
        RaftServerConfigKeys.Log.set_use_memory(p, False)
        p.set(RaftServerConfigKeys.Log.SEGMENT_SIZE_MAX_KEY, "512")
        p.set(RaftServerConfigKeys.Log.SEGMENT_CACHE_NUM_MAX_KEY, "1")
        run_with_new_cluster(3, body, properties=p,
                             storage_root=str(tmp_path))
