"""The density deployment as a cell (``ratis-3x10k``: 3 peers x 10,240
durable counter groups on one shared log a server): the plain reference's
reading of a peer's shard against what the program wrote there, a flipped
byte, the cell rehearsed on the CPU at 3 x 64 groups with the storage it
leaves, its ``memory-log`` control, and the shared log plane's spans and
counters in a traced session."""

import asyncio
import json
import os
import subprocess
import sys
import uuid

import pytest

from ratis_tpu.conf import RaftServerConfigKeys
from ratis_tpu.protocol.group import RaftGroup
from ratis_tpu.protocol.ids import ClientId, RaftGroupId
from ratis_tpu.protocol.logentry import make_transaction_entry
from ratis_tpu.server.log.segmented import LogWorker
from ratis_tpu.server.log.shared import (SharedGroupLog, SharedLogStore,
                                         shard_dir)
from ratis_tpu.trace import get_tracer
from ratis_tpu.trace.tracer import STAGE_NAMES
from tests.minicluster import MiniCluster, fast_properties

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.reference import counter_shared as ref

CELL = "ratis-3x10k.write-closed"
NEEDLE = b"INCREMENT"
GIDS = [uuid.UUID(int=i + 1, version=4).bytes for i in range(3)]


@pytest.fixture(autouse=True)
def _tracer_reset():
    yield
    get_tracer().configure(enabled=False)


def _write(index: int, payload: bytes = NEEDLE):
    return make_transaction_entry(1, index, ClientId.random_id(), index,
                                  payload)


def _log_dir(peer_root, gid: bytes) -> str:
    """Where the harness asks: the group's per-group directory."""
    return os.path.join(peer_root, str(uuid.UUID(bytes=gid)), "current")


async def _fill(peer_root, segment_size_max: int = 32 << 20) -> None:
    """Group 0: writes 0..9, then 6..9 truncated and 6..7 written again;
    group 1: five writes and a GET-like entry; group 2: nothing."""
    store = SharedLogStore(shard_dir(peer_root, 0),
                           LogWorker(f"density-{peer_root.name}"),
                           segment_size_max=segment_size_max)
    a = SharedGroupLog("a", GIDS[0], store)
    b = SharedGroupLog("b", GIDS[1], store)
    await a.open()
    await b.open()
    for i in range(10):
        await a.append_entry(_write(i))
    await a.persist_meta(2, "s1")
    await a.truncate(6)
    for i in (6, 7):
        await a.append_entry(_write(i))
    for i in range(5):
        await b.append_entry(_write(i))
    await b.append_entry(_write(5, b"GET"))
    await a.close()
    await b.close()


def test_durable_writes_counts_what_a_shard_holds_and_not_a_truncated_entry(
        tmp_path):
    peer = tmp_path / "s0"
    asyncio.run(_fill(peer))
    ref._parsed.clear()
    assert ref.durable_writes(_log_dir(peer, GIDS[0]), NEEDLE) == 8
    assert ref.durable_writes(_log_dir(peer, GIDS[1]), NEEDLE) == 5
    assert ref.durable_writes(_log_dir(peer, GIDS[2]), NEEDLE) == 0
    assert ref.durable_writes(_log_dir(tmp_path / "absent", GIDS[0]),
                              NEEDLE) == 0
    # a torn record at the open segment's end is no write and no error
    (segment,) = (peer / "_sharedlog" / "shard-0").glob(
        "shared_inprogress_*")
    with open(segment, "ab") as f:
        f.write(b"\x40\x00\x00\x00torn")
    assert ref.peer_writes(str(peer), NEEDLE)[GIDS[0]] == 8
    # the judges are counter's own
    from benchmarks.reference import counter
    assert ref.judge_answers is counter.judge_answers
    assert ref.leader_commit is counter.leader_commit


def test_durable_writes_fails_on_a_flipped_byte_in_a_sealed_segment(tmp_path):
    peer = tmp_path / "s0"
    asyncio.run(_fill(peer, segment_size_max=512))
    shard = peer / "_sharedlog" / "shard-0"
    sealed = sorted(shard.glob("shared_[0-9]*"))
    assert sealed
    ref._parsed.clear()
    assert ref.durable_writes(_log_dir(peer, GIDS[0]), NEEDLE) == 8
    data = bytearray(sealed[0].read_bytes())
    data[len(ref.SEGMENT_MAGIC) + 12] ^= 1
    sealed[0].write_bytes(bytes(data))
    ref._parsed.clear()
    with pytest.raises(ValueError, match="bad checksum"):
        ref.durable_writes(_log_dir(peer, GIDS[0]), NEEDLE)


# ---------------------------------------------- the cell rehearsed on the CPU

_LISTING = """
import json, os, sys
run_py = os.path.join({root!r}, "benchmarks", "run.py")
sys.argv = [run_py] + {args!r}
sys.path.insert(0, {root!r})
import importlib.util
spec = importlib.util.spec_from_file_location("bench_run", run_py)
run = importlib.util.module_from_spec(spec)
spec.loader.exec_module(run)
from benchmarks.harness.cluster import run_storage_dir
remove = run.remove_storage
def listing_then_remove(config):
    path = run_storage_dir(run.CHECKOUT, config)
    seen = sorted(os.path.relpath(os.path.join(d, n), path)
                  for d, dirs, files in os.walk(path) for n in dirs + files)
    print("STORAGE " + json.dumps(seen), file=sys.stderr, flush=True)
    remove(config)
run.remove_storage = listing_then_remove
run.main()
"""


def _rehearse(*extra):
    """The cell at 3 x 64 groups; the result line and, listed just before
    the run removes it, what its storage held."""
    args = ["--workload", CELL, "--seed", "2147483999", "--seconds", "2",
            "--trace", "0", "--rehearse-cpu", "--groups", "64", *extra]
    p = subprocess.run(
        [sys.executable, "-c", _LISTING.format(root=ROOT, args=args)],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr[-3000:]
    listing = [json.loads(line[len("STORAGE "):])
               for line in p.stderr.splitlines()
               if line.startswith("STORAGE ")]
    return json.loads(p.stdout.strip().splitlines()[-1]), listing


def test_the_cell_rehearsed_on_the_cpu_agrees_with_the_plain_reference():
    result, (storage,) = _rehearse()
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["compared"]) == {
        "never_answered", "answers_wrong", "groups_short_of_replicas",
        "device_rows_differing", "device_quorum_rows_wrong",
        "device_commit_advance_wrong", "groups_short_of_durable"}
    for c in result["compared"].values():
        assert c["value"] == c["limit"] == 0
    # each peer holds its shard, locked, and nothing of a group's own
    peers = {"s0", "s1", "s2"}
    assert set(storage) == peers | {
        f"{p}/{x}" for p in peers
        for x in ("_sharedlog", "_sharedlog/shard-0",
                  "_sharedlog/shard-0/shared_inprogress_0",
                  "_sharedlog/shard-0/in_use.lock")}


def test_the_memory_log_control_fails_groups_short_of_durable():
    result, _ = _rehearse("--control", "memory-log")
    assert result["correct"] is False
    assert result["compared"]["groups_short_of_durable"]["value"] == 64
    others = dict(result["compared"])
    del others["groups_short_of_durable"]
    assert all(c["value"] == 0 for c in others.values()), others


# -------------------------------------- the shared log plane, traced

def test_a_traced_session_has_log_meta_group_add_and_the_shared_counters(
        tmp_path):
    from benchmarks.run import load_reader
    tracer = get_tracer()

    async def body():
        p = fast_properties()
        RaftServerConfigKeys.Log.set_use_memory(p, False)
        RaftServerConfigKeys.TpuLog.set_shared(p, True)
        tracer.configure(enabled=True)
        cluster = MiniCluster(3, properties=p, storage_root=str(tmp_path))
        await cluster.start()
        try:
            await cluster.wait_for_leader()
            more = RaftGroup.value_of(RaftGroupId.random_id(),
                                      cluster.group.peers)
            await asyncio.gather(*(s.group_add(more)
                                   for s in cluster.servers.values()))
            for _ in range(5):
                assert (await cluster.send_write()).success
        finally:
            await cluster.close()
        return tracer.session()

    sess = asyncio.run(body())
    records = sess["keyed"]["log.shared.records"]
    assert records["entry"] > 0 and records["meta"] > 0
    assert records["conf"] >= 6         # two groups' first conf, 3 servers
    assert sess["counters"]["log.shared.syncs"] > 0
    assert sess["counters"]["log.shared.sync_groups"] >= \
        sess["counters"]["log.shared.syncs"]
    meta = tracer.rows(STAGE_NAMES.index("log.meta"))
    assert {3, 4} <= set(meta[:, 3].tolist())    # tags: META, CONF
    assert (meta[:, 2] > 0).all()
    assert len(tracer.rows(STAGE_NAMES.index("server.group_add"))) >= 6
    read = load_reader("log_groups_per_fsync")
    assert read({}) >= 1.0
