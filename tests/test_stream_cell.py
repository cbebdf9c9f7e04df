"""The stream plane as a deployment (``ratis-filestore-stream-3x1k``): the
plain reference alone, the cell's operation, the system against the
reference's judgment at 3 peers x 4 groups on the CPU (answers, tables, the
bytes read back from every replica, a flipped byte), the stream plane's
stages and counters over one traced stream, and the cell rehearsed."""

import asyncio
import json
import os
import struct
import subprocess
import sys
import uuid
import zlib

import msgpack
import pytest

from ratis_tpu.models.filestore import FileStoreStateMachine
from ratis_tpu.protocol.group import RaftGroup
from ratis_tpu.protocol.ids import RaftGroupId
from ratis_tpu.trace import get_tracer
from ratis_tpu.trace.tracer import STAGE_KINDS, STAGE_NAMES
from tests.minicluster import MiniCluster, run_with_new_cluster

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.flip_byte import flip_one_byte
from benchmarks.harness.generator import load_op
from benchmarks.reference import filestore_stream as ref

CELL = "ratis-filestore-stream-3x1k.datastream-closed"
NEEDLE = b"datastream/"
MIB, PACKET = 1 << 20, 1 << 16
GROUP = "0b5f9a3e-1c2d-4e3f-8a9b-0c1d2e3f4a5b"


def _traffic(group_index: int) -> dict:
    with open(os.path.join(ROOT, "benchmarks", "traffic",
                           "datastream-closed.json")) as f:
        return dict(json.load(f), group_index=group_index)


@pytest.fixture(autouse=True)
def _tracer_sandbox():
    yield
    get_tracer().configure(enabled=False)


# ----------------------------------------------------------- the reference

def test_the_reference_answers_a_stream_once_and_refuses_its_path_again():
    r = ref.FileStoreStreamReference(2)
    assert r.apply(0, "STREAM datastream/s0 1048576 65536") == \
        b"OK datastream/s0 1048576"
    assert r.apply(0, "STREAM datastream/s0 1048576 65536") == b"REFUSED"
    assert r.apply(1, "STREAM datastream/s0 65536 65536") == \
        b"OK datastream/s0 65536"
    assert r.files == [{"datastream/s0": MIB}, {"datastream/s0": PACKET}]
    with pytest.raises(ValueError, match="no semantics"):
        r.apply(0, "WRITE datastream/s0 0 7 1")
    assert ref.leader_commit([7, 4, 2], 0, 6, [True, True, True]) == 4
    assert ref.replicas_holding([3, 4, 9], 3, 4) == 2


def test_the_reference_judges_replies_group_by_group():
    def part(rows):
        g, p, a = zip(*rows)
        return {"group": list(g), "payload": list(p), "answer": list(a)}
    s = "STREAM datastream/s{} 1048576 65536".format
    ok = "OK datastream/s{} 1048576".format
    j = ref.judge_answers(2, [part([(0, s(0), ok(0)), (1, s(0), ok(0))]),
                              part([(0, s(1), ok(1))])])
    assert (j["answers_wrong"], j["never_answered"]) == (0, 0)
    assert j["acked_per_group"] == [2, 1] == j["submitted_per_group"]
    assert "entries_per_part" not in j      # one entry an answered stream
    # another size or path is wrong; an answer that never came is counted
    # apart and takes nothing from those behind it (a path is its own)
    j = ref.judge_answers(2, [part([
        (0, s(0), "OK datastream/s0 65536"), (0, s(1), ok(0)),
        (1, s(0), None), (1, s(1), ok(1)), (1, s(1), ok(1))])])
    assert (j["answers_wrong"], j["never_answered"]) == (3, 1)
    assert j["acked_per_group"] == [2, 2]
    assert j["submitted_per_group"] == [2, 3]
    assert j["samples"][0] == {"group": 0, "answer": "OK datastream/s0 65536",
                               "reference": ok(0)}
    assert j["samples"][2]["reference"] == "REFUSED"


def _record(payload: bytes) -> bytes:
    return struct.pack("<II", len(payload), zlib.crc32(payload)) + payload


def _entry(index: int, path: str, size: int, pad: bytes = b"") -> bytes:
    header = {"op": "stream", "path": path, "size": size, "packet": PACKET}
    if pad:
        header["pad"] = pad
    return msgpack.packb({"t": 1, "i": index, "k": 1,
                          "s": {"c": b"c" * 16, "id": index,
                                "d": msgpack.packb(header)}})


def test_durable_writes_reads_headers_and_whole_files_by_itself(tmp_path):
    """A hand-made replica: two files in place, one still where it was
    streamed (its entry is in the log and not applied), a record torn after
    them; then a wrong byte, a short file, a missing one, and a record the
    size of a file's bytes."""
    group = tmp_path / "s0" / GROUP
    log_dir, files = group / "current", group / "sm" / "files"
    log_dir.mkdir(parents=True)
    (files / "datastream").mkdir(parents=True)
    (files / ".tmp").mkdir()
    whole = lambda path, size: ref.file_bytes(GROUP, path, size, PACKET)
    assert whole("datastream/s0", 3 * PACKET + 5) == \
        b"".join(ref.payload_bytes(GROUP, "datastream/s0", off, n)
                 for off, n in ((0, PACKET), (PACKET, PACKET),
                                (2 * PACKET, PACKET), (3 * PACKET, 5)))
    (files / "datastream" / "s0").write_bytes(whole("datastream/s0", MIB))
    (files / "datastream" / "r0").write_bytes(whole("datastream/r0", PACKET))
    (files / ".tmp" / "stream_77_3").write_bytes(
        whole("datastream/r1", PACKET))
    (files / ".tmp" / "stream_78_4").write_bytes(b"x" * PACKET)  # another's
    segment = ref.SEGMENT_MAGIC + _record(b"conf") \
        + _record(_entry(1, "datastream/r0", PACKET)) \
        + _record(_entry(2, "datastream/s0", MIB)) \
        + _record(_entry(3, "datastream/r1", PACKET))
    seg = log_dir / "log_inprogress_0"
    seg.write_bytes(segment + b"\x40\x00\x00\x00torn")
    (log_dir / "raft-meta").write_bytes(NEEDLE)             # not a segment
    assert [h["path"] for h in ref.stream_headers(str(seg), NEEDLE)] == [
        "datastream/r0", "datastream/s0", "datastream/r1"]
    assert ref.durable_writes(str(log_dir), NEEDLE) == 3
    assert ref.durable_writes(str(tmp_path / "absent"), NEEDLE) == 0
    # one wrong byte in the last packet of the MiB
    flipped = bytearray(whole("datastream/s0", MIB))
    flipped[-17] ^= 1
    (files / "datastream" / "s0").write_bytes(bytes(flipped))
    assert ref.durable_writes(str(log_dir), NEEDLE) == 2
    # a file one byte short, a streamed file that is gone
    (files / "datastream" / "s0").write_bytes(
        whole("datastream/s0", MIB)[:-1])
    (files / ".tmp" / "stream_77_3").unlink()
    assert ref.durable_writes(str(log_dir), NEEDLE) == 1
    # a record that could hold a packet is no header beside the data
    seg.write_bytes(ref.SEGMENT_MAGIC + _record(
        _entry(1, "datastream/r0", PACKET, pad=b"x" * 8192)))
    assert ref.stream_headers(str(seg), NEEDLE) == []


# ----------------------------------------------------------- the operation

class _Out:
    def __init__(self, log: dict) -> None:
        self.log = log

    async def write_async(self, data: bytes) -> None:
        self.log["packets"].append(data)

    async def close_async(self):
        size = sum(len(p) for p in self.log["packets"])
        return type("Reply", (), {
            "success": True, "exception": None,
            "message": type("M", (), {"content": msgpack.packb(
                {"ok": True, "size": size})})})


class _Client:
    """What the operation touches of a RaftClient."""

    def __init__(self, group: RaftGroup) -> None:
        self.group, self.group_id = group, group.group_id
        self.streams: list[dict] = []

    def data_stream(self):
        return self

    async def stream(self, header, routing_table=None, primary=None):
        self.streams.append({"header": msgpack.unpackb(header, raw=False),
                             "routing": routing_table, "primary": primary,
                             "packets": []})
        return _Out(self.streams[-1])


@pytest.mark.parametrize("group_index", [0, 1, 2, 1024 + 1])
def test_the_operation_streams_down_the_chain_from_the_groups_leader(
        group_index):
    """Header, 16 packets the reference computes again, the chain from
    server ``group_index mod 3``; the rounds' operation sends one packet to
    paths of its own; what was sent and the answer are ASCII."""
    cluster = MiniCluster(3)            # (never started: its peers only)
    peers = list(cluster.group.peers)
    guid = str(uuid.UUID(bytes=cluster.group.group_id.to_bytes()))
    lead = group_index % 3
    for op, letter, size in (("filestore-stream", "s", MIB),
                             ("filestore-stream-round", "r", PACKET)):
        client = _Client(cluster.group)
        send = load_op(ROOT, op).sender(client, _traffic(group_index))
        for k in range(2):
            ascii_sent, pending = send()
            assert ascii_sent == f"STREAM datastream/{letter}{k} {size} 65536"
            reply = asyncio.run(pending)
            assert reply.success and bytes(reply.message.content) == \
                f"OK datastream/{letter}{k} {size}".encode()
            s = client.streams[k]
            assert s["header"] == {"op": "stream", "size": size,
                                   "path": f"datastream/{letter}{k}",
                                   "packet": PACKET}
            assert s["primary"] == peers[lead]
            order = [peers[(lead + i) % 3].id for i in range(3)]
            assert s["routing"].get_successors(order[0]) == (order[1],)
            assert s["routing"].get_successors(order[1]) == (order[2],)
            assert s["routing"].get_successors(order[2]) == ()
            assert b"".join(s["packets"]) == ref.file_bytes(
                guid, f"datastream/{letter}{k}", size, PACKET)
            assert len(s["packets"]) == size // PACKET


# ---------------------------------- the system against the reference, 3 x 4

async def _leader_index(cluster: MiniCluster, group: RaftGroup) -> int:
    """The place among the group's peers of the peer that leads it."""
    peers = [p.id for p in group.peers]
    for _ in range(500):
        for pid, server in cluster.servers.items():
            d = server.divisions.get(group.group_id)
            if d is not None and d.is_leader() and d.leader_ctx is not None \
                    and d.leader_ctx.leader_ready.done():
                return peers.index(pid)
        await asyncio.sleep(0.02)
    raise TimeoutError(f"no ready leader of {group.group_id}")


def _log_dir(storage_root, peer: str, group: RaftGroup) -> str:
    """Where a MiniCluster's durable replica keeps its segment files: each
    server's storage directory is ``<root>/<peer>``, and under it
    RaftStorageDirectory's ``<peer>/<group uuid>/current``."""
    return os.path.join(str(storage_root), peer, peer,
                        str(uuid.UUID(bytes=group.group_id.to_bytes())),
                        "current")


async def _stream_once(send) -> tuple[str, str]:
    sent, pending = send()
    reply = await pending
    return sent, bytes(reply.message.content).decode("ascii")


async def _wait_streams(cluster: MiniCluster, group: RaftGroup, n: int
                        ) -> None:
    """Every replica of ``group`` has applied ``n`` streams."""
    sms = [s.divisions[group.group_id].state_machine
           for s in cluster.servers.values()]
    for _ in range(500):
        if all(sm.streams_committed >= n for sm in sms):
            return
        await asyncio.sleep(0.02)
    raise TimeoutError(f"{[sm.streams_committed for sm in sms]} of {n}")


def test_the_system_holds_against_the_references_judgment_at_3x4(tmp_path):
    """3 peers x 4 durable groups, chain routing from each group's leader,
    seeded bytes: every answer, every replica's table and the bytes read
    back from EVERY replica's files are the reference's; a second stream to
    a committed path is refused by both; a flipped byte is caught."""
    groups_n = 4
    stream_op, round_op = (load_op(ROOT, "filestore-stream"),
                           load_op(ROOT, "filestore-stream-round"))

    async def body(cluster: MiniCluster):
        groups = [cluster.group] + [
            RaftGroup.value_of(RaftGroupId.random_id(), cluster.group.peers)
            for _ in range(groups_n - 1)]
        for g in groups[1:]:
            await asyncio.gather(*(s.group_add(g)
                                   for s in cluster.servers.values()))
        rows = {"group": [], "payload": [], "answer": []}
        clients = []
        try:
            for i, g in enumerate(groups):
                client = cluster.new_client(group=g)
                clients.append(client)
                traffic = _traffic(await _leader_index(cluster, g))
                sends = [round_op.sender(client, traffic),
                         stream_op.sender(client, traffic)]
                # a round, i + 1 whole files, a round: as a run's parts
                for send in [sends[0]] + [sends[1]] * (i + 1) + [sends[0]]:
                    sent, answer = await _stream_once(send)
                    rows["group"].append(i)
                    rows["payload"].append(sent)
                    rows["answer"].append(answer)
            # the program refuses what the reference refuses
            again = stream_op.sender(clients[0], _traffic(
                await _leader_index(cluster, groups[0])))
            sent, pending = again()
            assert sent == rows["payload"][1]         # datastream/s0 again
            reply = await pending
            assert not reply.success and "is closed" in str(reply.exception)
            for i, g in enumerate(groups):
                await _wait_streams(cluster, g, i + 3)
        finally:
            for client in clients:
                await client.close()

        judged = ref.judge_answers(groups_n, [rows])
        assert judged["answers_wrong"] == judged["never_answered"] == 0
        assert judged["acked_per_group"] == [i + 3 for i in range(groups_n)]
        plain = ref.FileStoreStreamReference(groups_n)
        for g, payload in zip(rows["group"], rows["payload"]):
            plain.apply(g, payload)
        assert plain.apply(0, rows["payload"][1]) == b"REFUSED"
        for i, g in enumerate(groups):
            for pid, server in cluster.servers.items():
                sm = server.divisions[g.group_id].state_machine
                assert sm.files == plain.files[i], (i, pid)
                assert sm.streams_committed == i + 3
                assert ref.replicas_holding([sm.streams_committed],
                                            i + 3, i + 3) == 1
                log_dir = _log_dir(tmp_path, str(pid), g)
                assert ref.durable_writes(log_dir, NEEDLE) == i + 3, (i, pid)
        # one byte of one file of one replica
        log_dir = _log_dir(tmp_path, "s1", groups[2])
        flip_one_byte(log_dir)
        assert ref.durable_writes(log_dir, NEEDLE) == 2 + 3 - 1

    run_with_new_cluster(3, body, sm_factory=FileStoreStateMachine,
                         storage_root=str(tmp_path))


# ------------------------------------------- one traced stream: rows, counters

def _rows(tracer, name):
    return tracer.rows(STAGE_NAMES.index(name)).tolist()


def _one_stream(traced: bool) -> dict:
    """One 1 MiB stream down the chain of a 3-peer group, from the leader:
    every stage's rows and the session's counters."""
    tracer = get_tracer()
    out = {}

    async def body(cluster: MiniCluster):
        lead = await _leader_index(cluster, cluster.group)
        client = cluster.new_client()
        try:
            warm = load_op(ROOT, "filestore-stream-round").sender(
                client, _traffic(lead))
            await _stream_once(warm)
            # (the followers link the round's stream a heartbeat later)
            await _wait_streams(cluster, cluster.group, 1)
            if traced:
                tracer.configure(enabled=True, sample_every=1,
                                 ring_size=4096)
            else:
                tracer.reset()
            before = {k: c.n for k, c in tracer._counters.items()}
            send = load_op(ROOT, "filestore-stream").sender(
                client, _traffic(lead))
            _, answer = await _stream_once(send)
            assert answer == f"OK datastream/s0 {MIB}"
            await _wait_streams(cluster, cluster.group, 2)
            out["keyed"] = {k: c.n - before.get(k, 0)
                            for k, c in tracer._counters.items()
                            if k[0].startswith("stream.")}
            out["session"] = tracer.session()
            out["rows"] = {name: _rows(tracer, name) for name in STAGE_NAMES}
        finally:
            await client.close()

    run_with_new_cluster(3, body, sm_factory=FileStoreStateMachine)
    return out


@pytest.fixture(scope="module")
def traced_stream():
    out = _one_stream(traced=True)
    get_tracer().configure(enabled=False)
    return out


@pytest.mark.parametrize("stage, kind, rows, tags", [
    ("stream.header", "I", 3, {0: 3}),                  # one a peer
    ("stream.packet", "I", 48, {PACKET: 16, -PACKET: 32}),
    ("stream.write", "I", 48, {PACKET: 48}),
    ("stream.close", "I", 1, {0: 1}),                   # the primary's
    ("stream.force", "W", 3, {MIB: 3}),                 # bytes since the last
    ("stream.link", "I", 3, {0: 3}),                    # one a peer, at apply
])
def test_a_traced_stream_has_its_rows_and_tags(traced_stream, stage, kind,
                                               rows, tags):
    assert STAGE_KINDS[STAGE_NAMES.index(stage)] == kind
    got = traced_stream["rows"][stage]
    assert len(got) == rows
    seen: dict = {}
    for r in got:
        assert r[0] == 0 and r[2] >= 0          # process-level, a duration
        seen[r[3]] = seen.get(r[3], 0) + 1
    assert seen == tags


def test_a_traced_stream_counts_its_bytes_packets_and_connections(
        traced_stream):
    keyed = dict(traced_stream["keyed"])
    batches = {k: keyed.pop((n, k)) for n, k in list(keyed)
               if n == "stream.write_batches"}
    # the stream is pinned to one writer lane on every peer: its 48 writes
    # (and the three closes) went down in as many passes or fewer
    busy = [k for k, n in batches.items() if n]
    assert len(busy) == 1
    assert 0 < batches[busy[0]] <= 48 + 3
    # the stream plane's socket writes, by the side that writes: 18 frames
    # (HEADER, 16 packets, CLOSE) down each of the client's connection and
    # the two forwarding legs, 18 acks back up each of the three accepted
    # ones; fewer writes than frames (what a loop pass queues leaves in one)
    out = {(n, k): keyed.pop((n, k)) for n, k in list(keyed)
           if n in ("stream.frames_out", "stream.writes_out")}
    for side in ("server", "client"):
        assert out[("stream.frames_out", side)] == 3 * 18
        assert 0 < out[("stream.writes_out", side)] \
            < out[("stream.frames_out", side)]
    assert keyed == {
        ("stream.streams", ""): 1,
        ("stream.packets", "primary"): 16,
        ("stream.packets", "successor"): 32,
        ("stream.bytes", "primary"): MIB,
        ("stream.bytes", "successor"): 2 * MIB,
        # the client's and two forwarding legs, each counted at both ends
        ("stream.connects", "opened"): 2,
        ("stream.connects", "accepted"): 3}
    sess = traced_stream["session"]
    assert sess["keyed"]["stream.bytes"]["primary"] == MIB
    assert sess["counters"]["stream.connects"] == 5


def test_the_streams_raft_request_is_traced_like_a_client_request(
        traced_stream):
    """Its id is minted at the stream server's ingress; route to respond
    tile its server time, and the parts of replicate are there: the four
    accepted metrics that read them report in the stream cell."""
    rows = traced_stream["rows"]
    tids = {r[0] for r in rows["server.route"] if r[0]}
    assert len(tids) == 1
    tid = tids.pop()
    ends = {}
    for name in ("server.route", "server.txn_start", "server.append",
                 "server.replicate", "server.apply", "server.reply",
                 "server.respond"):
        mine = [r for r in rows[name] if r[0] == tid]
        assert len(mine) == 1, name
        ends[name] = (mine[0][1], mine[0][1] + mine[0][2])
    # each starts no earlier than the one before it ends (1 us of slack)
    order = list(ends.values())
    for (_, end), (start, _) in zip(order, order[1:]):
        assert start >= end - 1_000
    # the CLOSE span ends where the request's route begins
    close = rows["stream.close"][0]
    assert close[1] + close[2] <= ends["server.route"][0] + 1_000_000
    for name in ("server.quorum_wait", "server.apply_queue"):
        assert [r for r in rows[name] if r[0] == tid], name


def test_with_no_session_open_a_stream_leaves_no_row():
    out = _one_stream(traced=False)
    assert all(not rows for rows in out["rows"].values())
    assert not out["session"]["t_on"]
    # the counters are always on
    assert out["keyed"][("stream.bytes", "primary")] == MIB
    assert out["keyed"][("stream.streams", "")] == 1


# --------------------------- the cell against the plain reference, on the CPU

def test_the_cell_rehearsed_on_the_cpu_agrees_with_the_plain_reference():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "2147483999", "--seconds", "2",
         "--trace", "0", "--rehearse-cpu", "--groups", "4"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert p.returncode == 0, p.stderr[-3000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["compared"]) == {
        "never_answered", "answers_wrong", "groups_short_of_replicas",
        "device_rows_differing", "device_quorum_rows_wrong",
        "device_commit_advance_wrong", "groups_short_of_durable"}
    for c in result["compared"].values():
        assert c["value"] == c["limit"] == 0
    assert set(result["metrics"]) == {"commits_per_s", "commit_p50_ms",
                                      "commit_p75_ms", "setup_s"}
