"""The log worker by itself (server/log/segmented.py:LogWorker): a thread that
owns the way to the disk, the loops that own the logs, and the one call back
a batch where they meet.  Every test bounds its own waits, so a stuck thread
fails one test and not the run."""

import asyncio
import concurrent.futures
import os
import shutil
import sys
import threading

import pytest

from ratis_tpu.protocol.exceptions import RaftLogIOException
from ratis_tpu.protocol.logentry import LogEntry, make_transaction_entry
from ratis_tpu.server.log import segmented
from ratis_tpu.server.log.segmented import (MAGIC, LogWorker,
                                            SegmentedRaftLog, encode_record,
                                            read_records)
from ratis_tpu.trace import TRACER
from ratis_tpu.util import injection

WAIT = 10.0


def run(main, timeout: float = 30.0):
    return asyncio.run(asyncio.wait_for(main(), timeout))


async def stop(worker: LogWorker) -> None:
    await asyncio.wait_for(worker.release(), WAIT)
    assert worker._thread is None


class Told:
    """What a RaftLog is to the worker: it is told of flushes and failures."""

    def __init__(self) -> None:
        self.flushed: list[int] = []
        self.failed: list[BaseException] = []
        self.on: list[int] = []      # the thread each call came on

    def _on_record_flushed(self, index: int) -> None:
        self.flushed.append(index)
        self.on.append(threading.get_ident())

    def _failure(self, exc: BaseException) -> None:
        self.failed.append(exc)
        self.on.append(threading.get_ident())


class HeldFile:
    """A file whose ``write`` waits until the test lets it go: what is
    submitted meanwhile is the next batch."""

    def __init__(self, path) -> None:
        self.f = open(path, "ab")
        self.go = threading.Event()
        self.writes: list[bytes] = []

    def write(self, data: bytes) -> None:
        assert self.go.wait(WAIT)
        self.writes.append(data)
        self.f.write(data)

    def flush(self) -> None:
        self.f.flush()

    def fileno(self) -> int:
        return self.f.fileno()

    def close(self) -> None:
        self.f.close()


def entry(index: int, data: bytes = None):
    return make_transaction_entry(1, index, b"c" * 16, index, b"header",
                                  sm_data=data)


def indexes_on_disk(path) -> list[int]:
    return [LogEntry.from_bytes(p).index for p in read_records(path)[0]]


def loop_calls(worker: LogWorker) -> int:
    return TRACER.counter("log.loop_calls", worker.name).n


# ------------------------------------------------------- order and batches

def test_completions_come_in_submit_order_and_one_call_a_batch(tmp_path):
    """Two files, three batches: every log is told once a batch, of its
    highest index, in submit order; the counter reads one call a batch."""
    async def main():
        worker = LogWorker("t-order")
        worker.acquire()
        a, b = Told(), Told()
        fa, fb = HeldFile(tmp_path / "a"), open(tmp_path / "b", "ab")
        try:
            calls0 = loop_calls(worker)
            first = worker.submit(fa, b"a0", a, 0)      # batch 1, held
            while not worker._queue == []:
                await asyncio.sleep(0.001)
            recs = [worker.submit(fa, b"a1", a, 1),     # batch 2, as one
                    worker.submit(fb, b"b0", b, 0),
                    worker.submit(fa, b"a2", a, 2),
                    worker.submit(fb, b"b1", b, 1)]
            assert worker.metrics["batched"] == 1 and not first.done
            fa.go.set()
            await asyncio.wait_for(recs[-1], WAIT)
            assert all(r.done for r in [first] + recs)
            assert a.flushed == [0, 2] and b.flushed == [1]
            assert fa.writes == [b"a0", b"a1a2"]        # one write a file
            assert worker.metrics == {"flushes": 2, "writes": 5,
                                      "batched": 2}
            assert worker.sync_count == 3               # a | a, b
            assert loop_calls(worker) - calls0 == 2
            last = worker.submit(fb, b"b2", b, 2)       # batch 3
            await asyncio.wait_for(last, WAIT)
            assert b.flushed == [1, 2]
            assert loop_calls(worker) - calls0 == 3
        finally:
            fa.go.set()
            await stop(worker)
            fa.close()
            fb.close()
        assert (tmp_path / "a").read_bytes() == b"a0a1a2"
        assert (tmp_path / "b").read_bytes() == b"b0b1b2"

    run(main)


def test_flush_index_is_contiguous_across_batches_and_files(tmp_path):
    """Two real logs on one worker, appended without waiting: each log's
    flush_index only ever steps up, ends at its last entry, and what the
    division is told is what the files hold."""
    async def main():
        worker = LogWorker("t-contig")
        logs, seen = [], []
        for k in range(2):
            log = SegmentedRaftLog(f"l{k}", tmp_path / f"l{k}", worker=worker)
            await log.open()
            seen.append([])
            log.set_flush_callbacks(seen[k].append, None)
            logs.append(log)
        try:
            for i in range(40):
                for log in logs:
                    await log.append_entry(entry(i), wait_flush=False)
                if i % 7 == 0:
                    await asyncio.sleep(0.002)      # (several batches)
            await asyncio.wait_for(worker.drain(), WAIT)
            for log, told in zip(logs, seen):
                assert log.flush_index == 39
                assert told == sorted(set(told)) and told[-1] == 39
                assert indexes_on_disk(
                    log.dir / "log_inprogress_0") == list(range(40))
        finally:
            for log in logs:
                await asyncio.wait_for(log.close(), WAIT)

    run(main)


def test_drain_returns_after_the_fsync_and_the_thread_let_go(tmp_path,
                                                             monkeypatch):
    """drain() returns only once everything submitted before it is fsynced,
    its log told, and the thread done with the file: it is closed straight
    after, as a roll or a truncation does."""
    synced = []
    real_fsync = os.fsync

    def slow_fsync(fd):
        threading.Event().wait(0.05)
        real_fsync(fd)
        synced.append(fd)

    async def main():
        worker = LogWorker("t-drain")
        worker.acquire()
        log = Told()
        f = open(tmp_path / "f", "ab")
        fd = f.fileno()
        try:
            monkeypatch.setattr(segmented.os, "fsync", slow_fsync)
            for i in range(5):
                worker.submit(f, b"r%d" % i, log, i)
            await asyncio.wait_for(worker.drain(), WAIT)
            f.close()           # (a thread still in it would raise)
            assert synced and set(synced) == {fd}
            assert log.flushed[-1] == 4 and not log.failed
            assert worker._queue == []
            await asyncio.wait_for(worker.drain(), WAIT)    # nothing out
        finally:
            monkeypatch.setattr(segmented.os, "fsync", real_fsync)
            await stop(worker)
        assert (tmp_path / "f").read_bytes() == b"r0r1r2r3r4"

    run(main)


# ----------------------------------------------------------------- failures

class FullDisk:
    def __init__(self, path) -> None:
        self.f = open(path, "ab")

    def write(self, data: bytes) -> None:
        raise OSError("no space left on device")

    def flush(self) -> None:
        self.f.flush()

    def fileno(self) -> int:
        return self.f.fileno()


@pytest.mark.parametrize("fault", ["write", "fsync"])
def test_a_failed_batch_fails_every_record_and_latches_the_log(
        fault, tmp_path, monkeypatch):
    real_fsync = os.fsync

    def no_fsync(fd):
        raise OSError("fsync: input/output error")

    async def main():
        worker = LogWorker(f"t-fail-{fault}")
        log = SegmentedRaftLog("l", tmp_path / "l", worker=worker)
        await log.open()
        failures = []
        log.set_flush_callbacks(None, failures.append)
        other = Told()
        try:
            await asyncio.wait_for(log.append_entry(entry(0)), WAIT)
            assert log.flush_index == 0
            if fault == "write":
                log._open_file.close()
                log._open_file = FullDisk(tmp_path / "l" / "full")
            else:
                monkeypatch.setattr(segmented.os, "fsync", no_fsync)
            good = open(tmp_path / "good", "ab")
            held = HeldFile(tmp_path / "held")
            worker.submit(held, b"x")               # the batch before
            while worker._queue:
                await asyncio.sleep(0.001)
            await log.append_entry(entry(1), wait_flush=False)
            bystander = worker.submit(good, b"y", other, 7)
            awaited = asyncio.ensure_future(log.append_entry(entry(2)))
            await asyncio.sleep(0)
            held.go.set()
            with pytest.raises(OSError):
                await asyncio.wait_for(awaited, WAIT)
            with pytest.raises(OSError):            # the whole batch
                await asyncio.wait_for(bystander, WAIT)
            assert other.failed and not other.flushed
            assert log.failed and log.flush_index == 0
            assert len(failures) == 1               # latched once
            with pytest.raises(RaftLogIOException):     # later submits
                await log.append_entry(entry(3))
            good.close()
            held.close()
        finally:
            monkeypatch.setattr(segmented.os, "fsync", real_fsync)
            if fault == "write":
                log._open_file.f.close()
                log._open_file = None
            await asyncio.wait_for(log.close(), WAIT)

    run(main)


# -------------------------------------------------------------------- gates

class Gated:
    """A DataApi whose writes complete when the test says so, as the future
    of a writer thread or as one of the loop."""

    def __init__(self, kind: str) -> None:
        self.kind = kind
        self.writes: dict = {}

    def data_write(self, entry):
        fut = (concurrent.futures.Future() if self.kind == "thread"
               else asyncio.get_running_loop().create_future())
        self.writes[entry.index] = fut
        return fut

    def land(self, index: int) -> None:
        fut = self.writes[index]
        if self.kind == "thread":   # (as a writer thread would)
            t = threading.Thread(target=fut.set_result, args=(None,))
            t.start()
            t.join(WAIT)
        else:
            fut.set_result(None)

    async def data_truncate(self, index: int) -> None:
        pass


async def gated_log(kind, tmp_path, name):
    log = SegmentedRaftLog("l", tmp_path / "l", worker=LogWorker(name))
    await log.open()
    api = Gated(kind)
    log.set_data_api(api)
    return log, api, tmp_path / "l" / "log_inprogress_0"


async def until(cond) -> None:
    async def poll():
        while not cond():
            await asyncio.sleep(0.001)
    await asyncio.wait_for(poll(), WAIT)


@pytest.mark.parametrize("kind", ["thread", "loop"])
def test_a_gated_record_follows_its_data_and_holds_what_is_behind_it(
        kind, tmp_path):
    """Records before an unready gate go; the gated one and everything
    behind it stay off the disk (a copy of the file while the gate is held
    shows it) until the data has landed."""
    async def main():
        log, api, path = await gated_log(kind, tmp_path, f"t-gate-{kind}")
        try:
            await log.append_entry(entry(0), wait_flush=False)
            await log.append_entry(entry(1, b"d" * 64), wait_flush=False)
            await log.append_entry(entry(2), wait_flush=False)
            await log.append_entry(entry(3, b"d" * 64), wait_flush=False)
            await until(lambda: log.flush_index == 0)
            await asyncio.sleep(0.05)
            shutil.copy(path, tmp_path / "held")
            assert indexes_on_disk(tmp_path / "held") == [0]
            assert log.flush_index == 0 and len(log.worker._queue) == 3
            api.land(3)                     # not the head's: nothing moves
            await asyncio.sleep(0.05)
            assert indexes_on_disk(path) == [0] and log.flush_index == 0
            api.land(1)
            await until(lambda: log.flush_index == 3)
            assert indexes_on_disk(path) == [0, 1, 2, 3]
            assert not log.failed and not log._data_out
        finally:
            await asyncio.wait_for(log.close(), WAIT)

    run(main)


@pytest.mark.parametrize("how", ["failed", "cancelled"])
@pytest.mark.parametrize("kind", ["thread", "loop"])
def test_a_failed_or_cancelled_gate_kills_its_file(kind, how, tmp_path):
    """Neither the record whose data write failed nor a later one of its
    file is written; the log latches; another file's records go on."""
    async def main():
        log, api, path = await gated_log(kind, tmp_path,
                                         f"t-dead-{kind}-{how}")
        other, fo = Told(), open(tmp_path / "other", "ab")
        failures = []
        log.set_flush_callbacks(None, failures.append)
        try:
            await asyncio.wait_for(log.append_entry(entry(0)), WAIT)
            await log.append_entry(entry(1, b"d" * 64), wait_flush=False)
            await log.append_entry(entry(2), wait_flush=False)
            bystander = log.worker.submit(fo, b"o", other, 5)
            fut = api.writes[1]
            if how == "cancelled":
                assert fut.cancel()
            else:
                fut.set_exception(OSError("data: no space left"))
            await asyncio.wait_for(bystander, WAIT)
            assert other.flushed == [5]
            await until(lambda: log.failed)
            assert log.flush_index == 0 and failures
            late = log.worker.submit(log._open_file,
                                     encode_record(b"late"), None)
            with pytest.raises(RaftLogIOException):
                await asyncio.wait_for(late, WAIT)
            assert indexes_on_disk(path) == [0]
            assert path.read_bytes().startswith(MAGIC)
        finally:
            fo.close()
            await asyncio.wait_for(log.close(), WAIT)

    run(main)


# ------------------------------------------------------------------ release

def test_release_with_a_queue_neither_hangs_nor_loses_an_awaited_record(
        tmp_path):
    """What is ready is written before the thread ends; what a gate still
    holds is failed, not left pending; the thread is joined."""
    async def main():
        worker = LogWorker("t-release")
        worker.acquire()
        log = Told()
        held = HeldFile(tmp_path / "f")
        gate = concurrent.futures.Future()      # never completes
        thread = worker._thread
        assert thread.name == "log-worker-t-release" and thread.daemon
        worker.submit(held, b"r0", log, 0)
        awaited = worker.submit(held, b"r1", log, 1)
        behind = worker.submit(held, b"r2", log, 2, gate=gate)
        releasing = asyncio.ensure_future(worker.release())
        await asyncio.sleep(0.02)
        assert not releasing.done()
        held.go.set()
        await asyncio.wait_for(awaited, WAIT)
        with pytest.raises(RaftLogIOException):
            await asyncio.wait_for(behind, WAIT)
        await asyncio.wait_for(releasing, WAIT)
        assert not thread.is_alive() and worker._thread is None
        assert log.flushed == [1] and len(log.failed) == 1
        held.close()
        assert (tmp_path / "f").read_bytes() == b"r0r1"

    run(main)


# -------------------------------------------------------------- two loops

def test_a_worker_of_two_loops_calls_each_record_back_on_its_own(tmp_path):
    """Loop shards: one batch with records of two loops makes one call to
    each, and a log is told on the loop it lives on."""
    from ratis_tpu.server.shards import LoopShardPool

    async def main():
        pool = LoopShardPool("t-two", 2)
        pool.start()
        worker = LogWorker("t-two-loops")
        worker.acquire()
        held = HeldFile(tmp_path / "h")
        logs = [Told(), Told()]
        files = [open(tmp_path / f"f{k}", "ab") for k in range(2)]
        threads = []
        try:
            async def ident():
                return threading.get_ident()

            async def submit(k):
                for i in range(3):
                    rec = worker.submit(files[k], b"%d" % i, logs[k], i)
                return rec

            for k in range(2):
                threads.append(await pool.run_on(k, ident()))
            worker.submit(held, b"x")            # holds the thread
            await until(lambda: not worker._queue)
            calls0 = loop_calls(worker)
            recs = [await pool.run_on(k, submit(k)) for k in range(2)]
            held.go.set()
            for k in range(2):
                async def wait(rec=recs[k]):
                    await asyncio.wait_for(rec, WAIT)
                    await asyncio.wait_for(worker.drain(), WAIT)
                await pool.run_on(k, wait())
            for k in range(2):
                assert logs[k].flushed == [2]
                assert set(logs[k].on) == {threads[k]}
            assert threads[0] != threads[1]
            assert worker.metrics["batched"] == 2
            # (the held batch's call, then the one batch's: one a loop)
            assert loop_calls(worker) - calls0 == 1 + 2
        finally:
            held.go.set()
            await stop(worker)
            await pool.close()
            held.close()
            for f in files:
                f.close()

    run(main)


# ---------------------------------------------------------------- injection

@pytest.mark.parametrize("handler", ["sync", "async"])
def test_injection_points_fire_from_the_thread(handler, tmp_path):
    """RUN_LOG_WORKER once as the thread starts and LOG_SYNC once a batch,
    with the worker's name and the batch's distinct files; a sync handler
    runs on the worker's thread, an async one on the loop."""
    async def main():
        seen = []
        loop_thread = threading.get_ident()

        def note(point, args):
            seen.append((point, args, threading.get_ident() == loop_thread))

        if handler == "sync":
            def on_start(*args): note("start", args)
            def on_sync(*args): note("sync", args)
        else:
            async def on_start(*args): note("start", args)
            async def on_sync(*args): note("sync", args)
        injection.put(injection.RUN_LOG_WORKER, on_start)
        injection.put(injection.LOG_SYNC, on_sync)
        worker = LogWorker("t-inject")
        worker.acquire()
        files = [open(tmp_path / f"f{k}", "ab") for k in range(2)]
        try:
            rec = worker.submit(files[0], b"a")
            await asyncio.wait_for(rec, WAIT)
            on_loop = handler == "async"
            assert seen == [("start", ("t-inject", None), on_loop),
                            ("sync", ("t-inject", None, 1), on_loop)]
        finally:
            await stop(worker)
            for f in files:
                f.close()

    run(main)


# ------------------------------------------------------------------- stress

def test_many_submitters_lose_no_completion(tmp_path):
    """More loops than the box has cores for them, a shortened switch
    interval: every record is called back once, on its own loop, in order,
    and the files hold every byte."""
    from ratis_tpu.server.shards import LoopShardPool
    n_loops, n_records = 6, 300

    async def main():
        pool = LoopShardPool("t-stress", n_loops)
        pool.start()
        worker = LogWorker("t-stress")
        worker.acquire()
        logs = [Told() for _ in range(n_loops)]
        files = [open(tmp_path / f"f{k}", "ab") for k in range(n_loops)]
        old = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            async def submit(k):
                for i in range(n_records):
                    rec = worker.submit(files[k], b"%04d" % i, logs[k], i)
                    if i % 50 == 49:
                        await asyncio.wait_for(rec, WAIT)
                await asyncio.wait_for(worker.drain(), WAIT)

            await asyncio.gather(*(pool.run_on(k, submit(k))
                                   for k in range(n_loops)))
            for k in range(n_loops):
                told = logs[k].flushed
                assert told == sorted(set(told))
                assert told[-1] == n_records - 1 and not logs[k].failed
            assert worker.metrics["writes"] == n_loops * n_records
        finally:
            sys.setswitchinterval(old)
            await stop(worker)
            await pool.close()
            for f in files:
                f.close()
        want = b"".join(b"%04d" % i for i in range(n_records))
        for k in range(n_loops):
            assert (tmp_path / f"f{k}").read_bytes() == want

    run(main)
