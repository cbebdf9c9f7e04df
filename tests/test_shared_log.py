"""Shared multi-group log plane tests.

Coverage for ratis_tpu/server/log/shared.py: multi-group interleaving with
one fsync per drain sweep, tombstone-based rewind (shared bytes are never
rewritten), exact purge + sealed-segment compaction, the one-pass boot
scan (torn tails, tombstones, purge markers), and randomized equivalence
against the per-group segmented store on the RaftLog observables.
"""

import asyncio
import os
import random

import pytest

from ratis_tpu.protocol.exceptions import ChecksumException
from ratis_tpu.protocol.ids import ClientId
from ratis_tpu.protocol.logentry import make_transaction_entry
from ratis_tpu.protocol.termindex import TermIndex
from ratis_tpu.server.log.segmented import (MAGIC, LogWorker,
                                            SegmentedRaftLog, read_records)
from ratis_tpu.server.log.shared import (SharedGroupLog, SharedLogStore,
                                         shard_dir)
from tests.minicluster import MiniCluster, fast_properties

GID_A = b"A" * 16
GID_B = b"B" * 16
GID_C = b"C" * 16


def entry(term, index, size=8):
    return make_transaction_entry(term, index, ClientId.random_id(), index,
                                  b"x" * size)


def run(coro):
    return asyncio.run(coro)


def make_store(path, wname, **kw):
    kw.setdefault("name", f"store-{wname}")
    return SharedLogStore(path, LogWorker(wname), **kw)


class TestSharedStoreBasics:
    def test_multi_group_append_close_reopen(self, tmp_path):
        async def body():
            store = make_store(tmp_path, "w1")
            logs = [SharedGroupLog(f"g{i}", gid, store)
                    for i, gid in enumerate((GID_A, GID_B, GID_C))]
            for lg in logs:
                await lg.open()
            for i in range(20):
                for t, lg in enumerate(logs):
                    await lg.append_entry(entry(t + 1, i))
            for lg in logs:
                assert lg.flush_index == 19
                await lg.close()

            store2 = make_store(tmp_path, "w2")
            logs2 = [SharedGroupLog(f"g{i}", gid, store2)
                     for i, gid in enumerate((GID_A, GID_B, GID_C))]
            for t, lg in enumerate(logs2):
                await lg.open()
                assert lg.next_index == 20
                assert lg.flush_index == 19
                assert lg.get(7).term == t + 1
                assert lg.get_term_index(19) == TermIndex(t + 1, 19)
            for lg in logs2:
                await lg.close()

        run(body())

    def test_one_fsync_per_sweep(self, tmp_path):
        """The point of the shared plane: a burst of appends across many
        groups costs one fsync per worker drain, not one per group."""

        async def body():
            store = make_store(tmp_path, "wf")
            logs = [SharedGroupLog(f"g{i}", bytes([i]) * 16, store)
                    for i in range(16)]
            for lg in logs:
                await lg.open()
            for rnd in range(5):
                waits = [lg.append_entry(entry(1, rnd), wait_flush=True)
                         for lg in logs]
                await asyncio.gather(*waits)
            w = store.worker
            syncs = w.registry_metrics.sync_count.count
            batches = w.metrics["batched"]
            writes = w.metrics["writes"]
            assert writes == 16 * 5
            assert syncs == batches  # exactly one file fsynced per drain
            assert syncs <= 10  # gather batches whole sweeps together
            for lg in logs:
                await lg.close()

        run(body())

    def test_segment_roll_and_recovery(self, tmp_path):
        async def body():
            store = make_store(tmp_path, "wr", segment_size_max=512)
            lg = SharedGroupLog("g", GID_A, store)
            await lg.open()
            for i in range(40):
                await lg.append_entry(entry(1, i, size=32))
            await lg.close()
            names = sorted(p.name for p in tmp_path.iterdir())
            sealed = [n for n in names if n.startswith("shared_")
                      and "inprogress" not in n]
            assert len(sealed) >= 2, names

            store2 = make_store(tmp_path, "wr2")
            lg2 = SharedGroupLog("g", GID_A, store2)
            await lg2.open()
            assert lg2.next_index == 40
            assert all(lg2.get(i) is not None for i in range(40))
            await lg2.close()

        run(body())

    def test_rewind_is_logical_shared_bytes_never_rewritten(self, tmp_path):
        """Follower rewind appends a tombstone; the interleaved file only
        grows, so other groups' records are never rewritten."""

        async def body():
            store = make_store(tmp_path, "wt")
            la = SharedGroupLog("ga", GID_A, store)
            lb = SharedGroupLog("gb", GID_B, store)
            await la.open()
            await lb.open()
            for i in range(10):
                await la.append_entry(entry(1, i))
                await lb.append_entry(entry(1, i))
            open_seg = next(p for p in tmp_path.iterdir()
                            if p.name.startswith("shared_inprogress_"))
            size_before = open_seg.stat().st_size
            await la.truncate(4)
            assert open_seg.stat().st_size > size_before  # grew, not shrank
            assert la.next_index == 4
            for i in range(4, 8):
                await la.append_entry(entry(2, i))
            # B untouched by A's rewind
            assert lb.next_index == 10 and lb.get(9).term == 1
            await la.close()
            await lb.close()

            store2 = make_store(tmp_path, "wt2")
            la2 = SharedGroupLog("ga", GID_A, store2)
            lb2 = SharedGroupLog("gb", GID_B, store2)
            await la2.open()
            await lb2.open()
            assert la2.next_index == 8
            assert la2.get(3).term == 1 and la2.get(5).term == 2
            assert lb2.next_index == 10
            await la2.close()
            await lb2.close()

        run(body())

    def test_torn_final_record_truncated_on_boot_scan(self, tmp_path):
        async def body():
            store = make_store(tmp_path, "wc")
            la = SharedGroupLog("ga", GID_A, store)
            lb = SharedGroupLog("gb", GID_B, store)
            await la.open()
            await lb.open()
            for i in range(5):
                await la.append_entry(entry(1, i))
                await lb.append_entry(entry(1, i))
            await la.append_entry(entry(1, 5))  # the record we will tear
            await la.close()
            await lb.close()
            open_seg = next(p for p in tmp_path.iterdir()
                            if p.name.startswith("shared_inprogress_"))
            with open(open_seg, "r+b") as f:
                f.truncate(open_seg.stat().st_size - 3)  # torn mid-record

            store2 = make_store(tmp_path, "wc2")
            la2 = SharedGroupLog("ga", GID_A, store2)
            lb2 = SharedGroupLog("gb", GID_B, store2)
            await la2.open()
            await lb2.open()
            assert la2.next_index == 5  # torn tail dropped for its owner...
            assert lb2.next_index == 5  # ...other groups fully intact
            await la2.append_entry(entry(1, 5))
            assert la2.next_index == 6
            await la2.close()
            await lb2.close()

        run(body())

    def test_a_second_store_on_an_open_shard_is_refused(self, tmp_path):
        """One server a shard: a second store opening a shard that another
        holds open is refused (the per-group layout's in_use.lock), and
        takes it once the first has closed."""
        from ratis_tpu.protocol.exceptions import RaftException

        async def body():
            store = make_store(tmp_path, "wl1")
            la = SharedGroupLog("ga", GID_A, store)
            await la.open()
            assert (tmp_path / "in_use.lock").exists()
            other = make_store(tmp_path, "wl2")
            with pytest.raises(RaftException, match="locked"):
                other.open()
            await la.close()
            assert not (tmp_path / "in_use.lock").exists()
            other.open()
            assert other.take_recovered(GID_A).count == 0
            await other.close_if_idle()

        run(body())

    def test_corrupt_sealed_segment_raises(self, tmp_path):
        async def body():
            store = make_store(tmp_path, "ws", segment_size_max=256)
            lg = SharedGroupLog("g", GID_A, store)
            await lg.open()
            for i in range(30):
                await lg.append_entry(entry(1, i, size=32))
            await lg.close()
            sealed = sorted(p for p in tmp_path.iterdir()
                            if p.name.startswith("shared_")
                            and "inprogress" not in p.name)[0]
            with open(sealed, "r+b") as f:
                f.truncate(sealed.stat().st_size - 3)

            store2 = make_store(tmp_path, "ws2")
            lg2 = SharedGroupLog("g", GID_A, store2)
            with pytest.raises(ChecksumException):
                await lg2.open()

        run(body())

    def test_snapshot_boundary_round_trip(self, tmp_path):
        async def body():
            store = make_store(tmp_path, "wb")
            lg = SharedGroupLog("g", GID_A, store)
            await lg.open()
            lg.set_snapshot_boundary(TermIndex(2, 100))
            assert lg.next_index == 101
            assert lg.start_index == 101
            assert lg.get_last_entry_term_index() == TermIndex(2, 100)
            await lg.append_entry(entry(2, 101))
            await lg.close()

            store2 = make_store(tmp_path, "wb2")
            lg2 = SharedGroupLog("g", GID_A, store2)
            await lg2.open()
            assert lg2.start_index == 101
            assert lg2.get(101) is not None
            await lg2.close()

        run(body())

    def test_eviction_reads_through_file(self, tmp_path):
        async def body():
            store = make_store(tmp_path, "we")
            lg = SharedGroupLog("g", GID_A, store)
            await lg.open()
            for i in range(30):
                await lg.append_entry(entry(1, i, size=64))
            n = lg.evict_cache(29)
            assert n == 30
            misses0 = lg.metrics.cache_miss_count.count
            for i in range(30):
                e = lg.get(i)
                assert e is not None and e.index == i
            assert lg.metrics.cache_miss_count.count == misses0 + 30
            await lg.close()

        run(body())


class TestCompaction:
    def test_purge_triggers_compaction_and_reclaims(self, tmp_path):
        async def body():
            store = make_store(tmp_path, "wp", segment_size_max=2048,
                               compaction_dead_ratio=0.3)
            la = SharedGroupLog("ga", GID_A, store)
            lb = SharedGroupLog("gb", GID_B, store)
            await la.open()
            await lb.open()
            for i in range(60):
                await la.append_entry(entry(1, i, size=64))
                await lb.append_entry(entry(1, i, size=64))
            sealed_before = dict(store._sizes)
            assert sealed_before  # several sealed segments
            await la.purge(49)
            assert la.start_index == 50
            for _ in range(50):
                if store._compact_task is None or store._compact_task.done():
                    break
                await asyncio.sleep(0.02)
            if store._compact_task is not None:
                await store._compact_task
            reclaimed = store.metrics.compaction_reclaimed.count
            assert reclaimed > 0
            # survivors still served, from compacted files included
            la.evict_cache(60)
            lb.evict_cache(60)
            assert all(la.get(i) is not None for i in range(50, 60))
            assert all(lb.get(i) is not None for i in range(60))
            await la.close()
            await lb.close()

            # and the rewritten segment sequence recovers cleanly
            store2 = make_store(tmp_path, "wp2")
            la2 = SharedGroupLog("ga", GID_A, store2)
            lb2 = SharedGroupLog("gb", GID_B, store2)
            await la2.open()
            await lb2.open()
            assert la2.start_index == 50 and la2.next_index == 60
            assert lb2.start_index == 0 and lb2.next_index == 60
            assert lb2.get(5).index == 5
            await la2.close()
            await lb2.close()

        run(body())

    def test_compaction_under_concurrent_appends(self, tmp_path):
        async def body():
            store = make_store(tmp_path, "wcc", segment_size_max=1024,
                               compaction_dead_ratio=0.3)
            la = SharedGroupLog("ga", GID_A, store)
            lb = SharedGroupLog("gb", GID_B, store)
            await la.open()
            await lb.open()
            for i in range(40):
                await la.append_entry(entry(1, i, size=48))
                await lb.append_entry(entry(1, i, size=48))

            stop = asyncio.Event()

            async def writer():
                i = 40
                while not stop.is_set():
                    await lb.append_entry(entry(1, i, size=48))
                    i += 1
                    await asyncio.sleep(0)
                return i

            task = asyncio.create_task(writer())
            await la.purge(35)  # makes sealed segments mostly dead
            for _ in range(100):
                if store._compact_task is not None \
                        and store._compact_task.done():
                    break
                await asyncio.sleep(0.01)
            stop.set()
            last_b = await task
            if store._compact_task is not None:
                await store._compact_task
            assert store.metrics.compaction_count.count >= 1
            assert all(la.get(i) is not None for i in range(36, 40))
            assert all(lb.get(i) is not None for i in range(last_b))
            await la.close()
            await lb.close()

            store2 = make_store(tmp_path, "wcc2")
            lb2 = SharedGroupLog("gb", GID_B, store2)
            la2 = SharedGroupLog("ga", GID_A, store2)
            await lb2.open()
            await la2.open()
            assert lb2.next_index == last_b
            assert la2.start_index == 36 and la2.next_index == 40
            await lb2.close()
            await la2.close()

        run(body())


class TestEquivalence:
    """Randomized append/rewind/purge sequences replayed through BOTH
    stores must expose identical RaftLog observables.  (Purge is the one
    legal divergence: the per-group store purges at segment granularity,
    the shared store purges exactly — so shared's start_index may run
    ahead of segmented's and reads compare only above the higher.)"""

    @pytest.mark.parametrize("seed", [1, 2, 3])
    def test_randomized_observable_equivalence(self, tmp_path, seed):
        async def body():
            rng = random.Random(seed)
            store = make_store(tmp_path / "shared", f"weq{seed}",
                               segment_size_max=1024)
            pairs = []
            for i, gid in enumerate((GID_A, GID_B)):
                seg = SegmentedRaftLog(
                    f"seg{i}", tmp_path / f"pg{i}",
                    worker=LogWorker(f"weqpg{seed}{i}"), segment_size_max=1024)
                sh = SharedGroupLog(f"sh{i}", gid, store)
                await seg.open()
                await sh.open()
                pairs.append((seg, sh))

            term = 1
            for step in range(120):
                seg, sh = pairs[rng.randrange(len(pairs))]
                op = rng.random()
                nxt = sh.next_index
                if op < 0.70 or nxt == 0:
                    e = entry(term, nxt, size=rng.choice((8, 40, 120)))
                    await seg.append_entry(e, wait_flush=True)
                    await sh.append_entry(e, wait_flush=True)
                elif op < 0.85:
                    term += 1
                    cut = rng.randrange(max(sh.start_index, 1), nxt + 1)
                    if cut < nxt:
                        await seg.truncate(cut)
                        await sh.truncate(cut)
                elif nxt > sh.start_index:
                    cut = rng.randrange(sh.start_index, nxt)
                    await seg.purge(cut)
                    await sh.purge(cut)
                assert sh.next_index == seg.next_index
                assert sh.flush_index == seg.flush_index

            def check(seg, sh):
                assert sh.next_index == seg.next_index
                assert sh.flush_index == seg.flush_index
                assert sh.start_index >= seg.start_index
                lo = max(sh.start_index, seg.start_index)
                for i in range(lo, sh.next_index):
                    es, eh = seg.get(i), sh.get(i)
                    assert es is not None and eh is not None, i
                    assert es.term == eh.term and es.index == eh.index
                    assert seg.get_term_index(i) == sh.get_term_index(i)
                tis, tih = (seg.get_last_entry_term_index(),
                            sh.get_last_entry_term_index())
                assert (tis is None) == (tih is None)
                if tis is not None:
                    assert tis == tih

            for seg, sh in pairs:
                check(seg, sh)
                await seg.close()
                await sh.close()

            # both recover to the same observables
            store2 = make_store(tmp_path / "shared", f"weq{seed}b",
                               segment_size_max=1024)
            for i, gid in enumerate((GID_A, GID_B)):
                seg = SegmentedRaftLog(
                    f"seg{i}", tmp_path / f"pg{i}",
                    worker=LogWorker(f"weqpg{seed}{i}b"),
                    segment_size_max=1024)
                sh = SharedGroupLog(f"sh{i}", gid, store2)
                await seg.open()
                await sh.open()
                check(seg, sh)
                await seg.close()
                await sh.close()

        run(body())


class TestSharedDurableCluster:
    def _props(self):
        from ratis_tpu.conf import RaftServerConfigKeys
        p = fast_properties()
        RaftServerConfigKeys.Log.set_use_memory(p, False)
        RaftServerConfigKeys.TpuLog.set_shared(p, True)
        return p

    def test_full_cluster_restart_preserves_state(self, tmp_path):
        async def body():
            cluster = MiniCluster(3, properties=self._props(),
                                  storage_root=str(tmp_path))
            await cluster.start()
            try:
                await cluster.wait_for_leader()
                for _ in range(5):
                    assert (await cluster.send_write()).success
                # the interleaved store is in use, per-shard under the root
                # (the server roots storage at <dir>/<peer_id>, and the
                # cluster's dir is already <tmp>/<peer_id>)
                some_root = next(iter(cluster.servers))
                assert shard_dir(
                    f"{tmp_path}/{some_root}/{some_root}", 0).exists()
                for pid in list(cluster.servers):
                    await cluster.kill_server(pid)
                for pid in list(cluster._stopped):
                    await cluster.restart_server(pid)
                await cluster.wait_for_leader()
                reply = await cluster.send_read()
                assert reply.message.content == b"5"
                assert (await cluster.send_write()).message.content == b"6"
            finally:
                await cluster.close()

        run(body())

    def test_follower_crash_recovers_from_shared_scan(self, tmp_path):
        async def body():
            cluster = MiniCluster(3, properties=self._props(),
                                  storage_root=str(tmp_path))
            await cluster.start()
            try:
                await cluster.wait_for_leader()
                follower = next(d for d in cluster.divisions()
                                if not d.is_leader())
                fid = follower.member_id.peer_id
                await cluster.kill_server(fid)
                for _ in range(10):
                    assert (await cluster.send_write()).success
                await cluster.restart_server(fid)
                new_div = cluster.servers[fid].divisions[
                    cluster.group.group_id]
                last = (await cluster.wait_for_leader()).state.log \
                    .get_last_committed_index()
                await cluster.wait_applied(last, divisions=[new_div],
                                           timeout=20.0)
                assert new_div.state_machine.counter == 10
            finally:
                await cluster.close()

        run(body())

    def test_unset_key_keeps_per_group_layout(self, tmp_path):
        """raft.tpu.log.shared unset → per-group segment files, no
        _sharedlog directory anywhere (bit-for-bit today's store)."""

        async def body():
            cluster = MiniCluster(3, storage_root=str(tmp_path))
            await cluster.start()
            try:
                await cluster.wait_for_leader()
                for _ in range(3):
                    assert (await cluster.send_write()).success
                assert not list(tmp_path.glob("*/*/_sharedlog"))
                gid = cluster.group.group_id
                per_group = list(
                    tmp_path.glob(f"*/*/{gid.uuid}/current/log_*"))
                assert per_group
            finally:
                await cluster.close()

        run(body())

        async def body_shared():
            cluster = MiniCluster(3, properties=self._props(),
                                  storage_root=str(tmp_path / "sh"))
            await cluster.start()
            try:
                await cluster.wait_for_leader()
                for _ in range(3):
                    assert (await cluster.send_write()).success
                assert list(
                    (tmp_path / "sh").glob("*/*/_sharedlog/shard-*"))
                gid = cluster.group.group_id
                assert not list((tmp_path / "sh")
                                .glob(f"*/*/{gid.uuid}/current/log_*"))
            finally:
                await cluster.close()

        run(body_shared())


class TestSharedHardState:
    """A group's term, vote and configuration as records of its shard."""

    def test_a_stale_persist_does_not_overwrite_a_newer_term(self, tmp_path):
        from ratis_tpu.protocol.ids import RaftPeerId
        from ratis_tpu.server.storage import SharedMetadataIO

        async def body():
            store = make_store(tmp_path, "wm1")
            la = SharedGroupLog("ga", GID_A, store)
            meta = SharedMetadataIO(la)
            await meta.persist(5, RaftPeerId.value_of("s1"))
            await meta.persist(3, RaftPeerId.value_of("s2"))  # dropped
            # a record written late by another writer of the same group
            # regresses nothing at recovery either
            await la.persist_meta(4, "s2")
            await meta.persist(5, None)   # same term: the later one stands
            assert await meta.load() == (5, None)
            await la.close()

            store2 = make_store(tmp_path, "wm2")
            lb = SharedGroupLog("ga", GID_A, store2)
            h = lb.hard_state()
            assert (h.term, h.voted_for) == (5, None)
            await lb.close()

        run(body())

    def test_compaction_keeps_a_groups_latest_metadata_record(self,
                                                             tmp_path):
        from ratis_tpu.protocol.ids import RaftPeerId
        from ratis_tpu.server.config import RaftConfiguration
        from ratis_tpu.server.storage import SharedMetadataIO
        from tests.minicluster import MiniCluster as MC

        conf = RaftConfiguration.from_peers(MC(3).group.peers)

        async def body():
            store = make_store(tmp_path, "wc1", segment_size_max=2048,
                               compaction_dead_ratio=0.3)
            la = SharedGroupLog("ga", GID_A, store)
            lb = SharedGroupLog("gb", GID_B, store)
            meta = SharedMetadataIO(la)
            await meta.persist_conf(conf.to_entry(0, -1))
            await meta.persist(1, RaftPeerId.value_of("s1"))
            await meta.persist(2, RaftPeerId.value_of("s2"))
            await la.open()
            await lb.open()
            for i in range(60):
                await la.append_entry(entry(1, i, size=64))
                await lb.append_entry(entry(1, i, size=64))
            first_segment = min(store._sizes)
            size_before = store._sizes[first_segment]
            await la.purge(55)
            # compaction takes the worst segment at a time: until the first,
            # which holds the group's hard state, has been rewritten
            for _ in range(100):
                if store._compact_task is not None:
                    await store._compact_task
                if store._sizes[first_segment] < size_before:
                    break
                store.maybe_compact()
                await asyncio.sleep(0)
            assert store._sizes[first_segment] < size_before
            await la.close()
            await lb.close()

            store2 = make_store(tmp_path, "wc2")
            la2 = SharedGroupLog("ga", GID_A, store2)
            meta2 = SharedMetadataIO(la2)
            assert await meta2.load() == (2, RaftPeerId.value_of("s2"))
            loaded = await meta2.load_conf()
            assert loaded is not None and loaded.index == -1
            assert RaftConfiguration.from_entry(loaded).all_peers() == \
                conf.all_peers()
            assert sorted(store2.hosted_groups()) == [GID_A]
            await la2.open()
            assert la2.start_index == 56 and la2.next_index == 60
            await la2.close()

        run(body())

    def test_a_removed_group_does_not_come_back(self, tmp_path):
        from ratis_tpu.server.config import RaftConfiguration
        from tests.minicluster import MiniCluster as MC

        boot = RaftConfiguration.from_peers(MC(3).group.peers).to_entry(0, -1)

        async def body():
            store = make_store(tmp_path, "wr1")
            la = SharedGroupLog("ga", GID_A, store)
            lb = SharedGroupLog("gb", GID_B, store)
            for lg in (la, lb):
                await lg.persist_conf(boot)
                await lg.persist_meta(3, "s0")
                await lg.open()
                for i in range(5):
                    await lg.append_entry(entry(3, i))
            la.removed = True
            await la.close()
            # re-added while the store is open: a fresh group
            la_again = SharedGroupLog("ga", GID_A, store)
            assert la_again.hard_state().term == 0
            await la_again.open()
            assert la_again.next_index == 0
            await la_again.close()
            await lb.close()

            store2 = make_store(tmp_path, "wr2")
            store2.open()
            assert store2.hosted_groups() == [GID_B]
            assert store2.hard_state(GID_A).term == 0
            assert store2.take_recovered(GID_A).count == 0
            assert store2.take_recovered(GID_B).count == 5
            await store2.close_if_idle()

        run(body())


    def test_a_group_closed_and_added_again_keeps_its_log(self, tmp_path):
        """Closed without removal while another group holds the store open,
        a group added again finds its entries and hard state where they
        were."""
        async def body():
            store = make_store(tmp_path, "wa1")
            la = SharedGroupLog("ga", GID_A, store)
            lb = SharedGroupLog("gb", GID_B, store)
            await la.persist_meta(2, "s1")
            await la.open()
            await lb.open()
            for i in range(5):
                await la.append_entry(entry(2, i))
            await la.close()
            again = SharedGroupLog("ga", GID_A, store)
            await again.open()
            assert again.next_index == 5 and again.flush_index == 4
            again.evict_cache(5)
            assert again.get(3).index == 3
            assert again.hard_state().term == 2
            await again.close()
            await lb.close()

        run(body())


    def test_a_closed_groups_entries_move_with_a_compaction(self,
                                                            tmp_path):
        """A compaction while a group is closed (and the store open) moves
        the entries its kept index points at: added again, the group reads
        them from where they now are."""
        async def body():
            store = make_store(tmp_path, "wm1", segment_size_max=2048,
                               compaction_dead_ratio=0.3)
            la = SharedGroupLog("ga", GID_A, store)
            lb = SharedGroupLog("gb", GID_B, store)
            await la.open()
            await lb.open()
            for i in range(30):
                await la.append_entry(entry(1, i, size=64))
                await lb.append_entry(entry(1, i, size=64))
            await la.close()
            first_segment = min(store._sizes)
            size_before = store._sizes[first_segment]
            await lb.purge(25)
            for _ in range(100):
                if store._compact_task is not None:
                    await store._compact_task
                if store._sizes[first_segment] < size_before:
                    break
                store.maybe_compact()
                await asyncio.sleep(0)
            assert store._sizes[first_segment] < size_before
            again = SharedGroupLog("ga", GID_A, store)
            await again.open()
            assert again.next_index == 30
            for i in range(30):
                e = again.get(i)
                assert e is not None and e.index == i and e.term == 1
            await again.close()
            await lb.close()

        run(body())


def _peer_root(tmp_path, peer_id) -> "os.PathLike":
    # the server roots storage at <dir>/<peer_id>, and the cluster's dir is
    # already <tmp>/<peer_id>
    return tmp_path / str(peer_id) / str(peer_id)


def _bare_server(cluster, peer_id):
    """A server of the cluster's peer that is told of no group: what it
    hosts, it finds in its storage."""
    from ratis_tpu.conf import RaftServerConfigKeys
    from ratis_tpu.server.server import RaftServer
    props = cluster.properties.clone()
    RaftServerConfigKeys.set_storage_dir(
        props, f"{cluster.storage_root}/{peer_id}")
    return RaftServer(peer_id, cluster.group.get_peer(peer_id).address,
                      state_machine_registry=lambda gid: cluster.sm_factory(),
                      properties=props, transport_factory=cluster.factory)


class TestSharedHardStateCluster:
    def _props(self):
        from ratis_tpu.conf import RaftServerConfigKeys
        p = fast_properties()
        RaftServerConfigKeys.Log.set_use_memory(p, False)
        RaftServerConfigKeys.TpuLog.set_shared(p, True)
        return p

    def test_term_vote_and_conf_survive_restarts_with_no_group_directory(
            self, tmp_path):
        """A full-cluster restart and a follower's crash: each division
        comes back with the term, vote and configuration it had, read from
        its shard, and no peer's storage holds anything but the shards."""
        async def body():
            cluster = MiniCluster(3, properties=self._props(),
                                  storage_root=str(tmp_path))
            gid = cluster.group.group_id
            await cluster.start()
            try:
                await cluster.wait_for_leader()
                for _ in range(3):
                    assert (await cluster.send_write()).success
                before = {d.member_id.peer_id: (d.state.current_term,
                                                d.state.voted_for)
                          for d in cluster.divisions()}
                for pid in list(cluster.servers):
                    await cluster.kill_server(pid)
                for pid in before:
                    assert [p.name for p in _peer_root(tmp_path, pid)
                            .iterdir()] == ["_sharedlog"]
                for pid in list(cluster._stopped):
                    await cluster.restart_server(pid)
                for pid, (term, voted) in before.items():
                    d = cluster.servers[pid].divisions[gid]
                    assert d.state.current_term >= term
                    if d.state.current_term == term:
                        assert d.state.voted_for == voted
                    assert d.state.configuration.all_peers() == \
                        cluster.group.peers
                await cluster.wait_for_leader()
                assert (await cluster.send_write()).message.content == b"4"

                follower = next(d for d in cluster.divisions()
                                if not d.is_leader())
                fid = follower.member_id.peer_id
                term, voted = (follower.state.current_term,
                               follower.state.voted_for)
                await cluster.kill_server(fid)
                store = SharedLogStore(shard_dir(_peer_root(tmp_path, fid), 0),
                                       LogWorker(f"peek-{tmp_path.name}"))
                store.open()
                h = store.hard_state(gid.to_bytes())
                assert (h.term, h.voted_for) == (
                    term, None if voted is None else voted.id)
                assert h.conf is not None
                await store.close_if_idle()
                await cluster.restart_server(fid)
                d = cluster.servers[fid].divisions[gid]
                assert d.state.current_term >= term
                last = (await cluster.wait_for_leader()).state.log \
                    .get_last_committed_index()
                await cluster.wait_applied(last, divisions=[d], timeout=20.0)
                assert d.state_machine.counter == 4
            finally:
                await cluster.close()

        run(body())

    def test_a_servers_boot_finds_its_groups_in_the_shard_alone(self,
                                                               tmp_path):
        """Servers told of no group find theirs in their shards; a group
        removed with its directory is not found again."""
        from ratis_tpu.protocol.group import RaftGroup
        from ratis_tpu.protocol.ids import RaftGroupId

        async def body():
            cluster = MiniCluster(3, properties=self._props(),
                                  storage_root=str(tmp_path))
            gid = cluster.group.group_id
            gone = RaftGroup.value_of(RaftGroupId.random_id(),
                                      cluster.group.peers)
            await cluster.start()
            servers = []
            try:
                await cluster.wait_for_leader()
                assert (await cluster.send_write()).success
                for s in cluster.servers.values():
                    await s.group_add(gone)
                for s in cluster.servers.values():
                    await s.group_remove(gone.group_id,
                                         delete_directory=True)
                for pid in list(cluster.servers):
                    await cluster.kill_server(pid)
                servers = [_bare_server(cluster, pid)
                           for pid in cluster._stopped]
                await asyncio.gather(*(s.start() for s in servers))
                for s in servers:
                    assert s.group_ids() == [gid]
                    assert [p.name for p in _peer_root(tmp_path, s.peer_id)
                            .iterdir()] == ["_sharedlog"]
                for s in servers:
                    cluster.servers[s.peer_id] = s
                cluster._stopped.clear()
                await cluster.wait_for_leader()
                assert (await cluster.send_write()).message.content == b"2"
            finally:
                await cluster.close()

        run(body())

    def test_without_the_key_each_group_keeps_its_own_files(self, tmp_path):
        """The per-group layout as it is without raft.tpu.log.shared:
        lock, raft-meta, raft-meta.conf, sm/ and tmp/ in each group's
        directory, and no shard."""
        async def body():
            cluster = MiniCluster(3, storage_root=str(tmp_path))
            gid = cluster.group.group_id
            await cluster.start()
            try:
                await cluster.wait_for_leader()
                assert (await cluster.send_write()).success
                for pid in cluster.servers:
                    group_dir = _peer_root(tmp_path, pid) / str(gid.uuid)
                    assert sorted(p.name for p in group_dir.iterdir()) == [
                        "current", "in_use.lock", "sm", "tmp"]
                    names = {p.name for p in (group_dir / "current")
                             .iterdir()}
                    assert {"raft-meta", "raft-meta.conf"} <= names
                    assert not (_peer_root(tmp_path, pid)
                                / "_sharedlog").exists()
            finally:
                await cluster.close()

        run(body())
