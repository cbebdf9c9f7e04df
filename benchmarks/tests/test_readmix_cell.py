"""Tests of PR 35's part of the benchmark: the read mix's reference
(``counter_reads``), its operation, its cell (``ratis-3x1k.readmix-open``) on
the CPU with and without the planted stale read, and what the harness takes
from a reference and from a configuration since then: the entries an answer
added to the log, further compared numbers, the client's keys, peers with a
stream address.  Run with ``python -m pytest benchmarks/tests -q``; nothing
here touches the TPU library."""

import asyncio
import json
import os
import random
import subprocess
import sys
import uuid

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run
from benchmarks.harness import compare, generator
from benchmarks.reference import counter, counter_reads

CELL = "ratis-3x1k.readmix-open"
NEW_METRICS = ["read_server_ms", "read_groups_per_sweep"]
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "compared"]


def rehearse(*extra, seconds="3", timeout=180):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", CELL, "--seed", "2147483999", "--seconds", seconds,
         "--rehearse-cpu", "--groups", "16", "--rate", "100", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout)
    lines = p.stdout.strip().splitlines()
    return p, (json.loads(lines[-1]) if p.returncode == 0 and lines else None)


def failing(result) -> set:
    return {k for k, c in result["compared"].items()
            if c["value"] > c["limit"]}


# ------------------------------------------------------------- the reference

def part(*rows):
    """Rows ``(group, payload, sent, acked, answer)`` as the generator's
    columns."""
    cols = list(zip(*rows)) if rows else [[]] * 5
    return dict(zip(("group", "payload", "sent", "acked", "answer"),
                    (list(c) for c in cols)))


W, R = "INCREMENT", "GET"
WARM = part((0, W, 0.0, 0.1, "1"), (1, W, 0.0, 0.1, "1"))
SETTLE = part()


@pytest.mark.parametrize("name,window,not_linearizable,never,entries", [
    ("a linearizable history",
     [(0, W, 0.0, 1.0, "2"), (0, R, 1.5, 1.6, "2"), (0, W, 2.0, 3.0, "3"),
      (0, R, 2.5, 2.6, "2"), (0, R, 2.7, 3.5, "3"), (1, R, 0.2, 0.3, "1")],
     0, 0, [2, 0]),
    ("a stale read",            # 2 was acknowledged before it was sent
     [(0, W, 0.0, 1.0, "2"), (0, R, 1.5, 1.6, "1")], 1, 0, [1, 0]),
    ("a read from the future",  # no third INCREMENT was sent before its answer
     [(0, W, 0.0, 1.0, "2"), (0, R, 1.5, 1.6, "3"), (0, W, 2.0, 3.0, "3")],
     1, 0, [2, 0]),
    ("a read after an answered read that reads less",
     [(0, W, 0.0, 3.0, "2"), (0, R, 0.5, 1.0, "2"), (0, R, 1.5, 2.0, "1")],
     1, 0, [1, 0]),
    ("two overlapping reads in one order",
     [(0, W, 0.0, 3.0, "2"), (0, R, 0.5, 2.0, "2"), (0, R, 1.0, 1.5, "1")],
     0, 0, [1, 0]),
    ("two overlapping reads in the other order",
     [(0, W, 0.0, 3.0, "2"), (0, R, 0.5, 2.0, "1"), (0, R, 1.0, 1.5, "2")],
     0, 0, [1, 0]),
    ("an unanswered write before a read",   # it may or may not have applied
     [(0, W, 0.0, None, None), (0, R, 1.0, 1.1, "1"), (0, R, 1.2, 1.3, "2"),
      (1, W, 0.0, None, None), (1, R, 1.0, 1.1, "3")],
     1, 2, [0, 0]),
    ("reads add no entries, and one that never came is never answered",
     [(0, R, 0.0, 0.1, "1"), (0, R, 0.2, 0.3, "1"), (1, R, 0.0, None, None),
      (1, W, 0.5, 0.6, "2")],
     0, 1, [0, 1]),
    ("an answer that is no count",
     [(0, R, 0.0, 0.1, "one")], 1, 0, [0, 0]),
])
def test_counter_reads_judges_a_history(name, window, not_linearizable,
                                        never, entries):
    a = counter_reads.judge_answers(2, [WARM, part(*window), SETTLE])
    assert a["compared"] == {"reads_not_linearizable": not_linearizable}, name
    assert a["never_answered"] == never
    assert a["answers_wrong"] == 0
    assert a["entries_per_part"] == [[1, 1], entries, [0, 0]]
    # acked / submitted run over the writes alone, as counter's do
    writes = [r for r in window if r[1] == W]
    assert a["submitted_per_group"] == [1 + sum(r[0] == g for r in writes)
                                        for g in (0, 1)]
    assert a["acked_per_group"] == [
        1 + sum(r[0] == g and r[4] is not None for r in writes)
        for g in (0, 1)]
    reads = [r for r in window if r[1] == R and r[4] is not None]
    assert a["reads_compared"] == len(reads)
    assert len(a["samples"]) == not_linearizable


def test_counter_reads_judges_writes_as_counter_does_and_parts_in_order():
    # a wrong INCREMENT answer is counter's to catch, not a read's
    window = part((0, W, 0.0, 1.0, "3"), (0, R, 1.5, 1.6, "2"))
    a = counter_reads.judge_answers(2, [WARM, window, SETTLE])
    assert a["answers_wrong"] == 1
    assert a["compared"] == {"reads_not_linearizable": 0}
    # the warm-up's writes are before every window read, whatever its clock
    # says (the parts' clocks differ): reading 0 is stale
    a = counter_reads.judge_answers(
        2, [WARM, part((0, R, 0.0, 0.05, "0")), SETTLE])
    assert a["compared"] == {"reads_not_linearizable": 1}
    # a read of the settle part sees the window's writes as before it
    a = counter_reads.judge_answers(
        2, [WARM, part((0, W, 5.0, 6.0, "2")), part((0, R, 0.0, 0.1, "1"))])
    assert a["compared"] == {"reads_not_linearizable": 1}
    with pytest.raises(ValueError, match="no semantics"):
        counter_reads.judge_answers(2, [part((0, "PUT", 0.0, 0.1, "1"))])
    # the rest of the reference is counter's own
    for f in ("leader_commit", "replicas_holding", "durable_writes"):
        assert getattr(counter_reads, f) is getattr(counter, f)
    with open(counter_reads.__file__) as f:
        assert "ratis_tpu" not in f.read().replace(
            "Imports nothing of ratis_tpu", "")


def test_the_harness_takes_entries_and_numbers_from_the_reference():
    requests = {"group": [0, 0, 1], "answer": ["2", None, "1"]}
    # a reference that does not say: an answered request is one entry
    assert compare.window_entries({}, requests, 2) == [1, 1]
    assert compare.window_entries(
        counter.judge_answers(2, [part(), part(), part()]), requests, 2) \
        == [1, 1]
    # one that says is believed: here the window's part added nothing
    assert compare.window_entries(
        {"entries_per_part": [[1, 1], [0, 0], [1, 1]]}, requests, 2) == [0, 0]


# ------------------------------------------------------------- the operation

class _Io:
    def __init__(self):
        self.calls = []

    def send(self, payload):
        self.calls.append(("send", bytes(payload)))
        return "w"

    def send_read_only(self, payload):
        self.calls.append(("send_read_only", bytes(payload)))
        return "r"


class _Client:
    def __init__(self):
        self.api = _Io()

    def io(self):
        return self.api


def test_the_operation_decides_a_requests_kind_by_place_not_by_seed():
    op = generator.load_op(ROOT, "readmix")
    traffic = bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", "readmix-open.json"))
    kinds = {}
    for index in (0, 7):
        client = _Client()
        send = op.sender(client, dict(traffic, group_index=index))
        sent = [send() for _ in range(2000)]
        kinds[index] = [text for text, _ in sent]
        expect = ["GET" if random.Random(f"readmix:{index}:{k}").random()
                  < 0.95 else "INCREMENT" for k in range(2000)]
        assert kinds[index] == expect
        assert [p for _, p in sent] == ["r" if t == "GET" else "w"
                                        for t in expect]
        assert client.api.calls == [
            ("send_read_only", b"GET") if t == "GET"
            else ("send", b"INCREMENT") for t in expect]
        assert 0.93 < kinds[index].count("GET") / 2000 < 0.97
    assert kinds[0] != kinds[7]


# ------------------------------------------------------------------ the cell

def test_every_name_of_the_cell_resolves_to_its_files():
    m = bench_run.load_manifest()
    r = bench_run.resolve_cell(m, CELL)
    assert (r["cell"]["config"], r["cell"]["traffic"], r["cell"]["chips"]) \
        == ("ratis-3x1k", "readmix-open", 1)
    assert len(r["cell"]["why"]) <= 200
    assert bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "workloads", CELL + ".json")) \
        == {k: r["cell"][k] for k in ("config", "traffic", "chips", "why")}
    t = r["traffic"]
    assert (t["loop"], t["op"], t["round_op"], t["reference"]) \
        == ("open", "readmix", "write", "counter_reads")
    assert r["reference"] == "counter_reads"
    assert t["read_share"] == 0.95 and t["payload_ascii"] == "INCREMENT"
    # the rule that gave the rate stands beside it
    assert t["rate_per_s"] >= 400 and t["rate_per_s"] % 100 == 0
    assert "70 %" in t["rate_rule"]
    for op in (t["op"], t["round_op"]):
        assert callable(generator.load_op(ROOT, op).sender)
    # the configuration is the accepted one, its guarantees untouched: a
    # linearizable read with the lease at its default, off
    cfg = r["config"]
    assert cfg["name"] == "ratis-3x1k"
    assert cfg["guarantees"]["read_consistency"] == "linearizable"
    assert cfg["properties"]["raft.server.read.option"] == "LINEARIZABLE"
    assert not [k for k in cfg["properties"] if "lease" in k]
    # (the loop's ms a commit in the reads layer came later, with the other
    # layers')
    loop_reads = "loop_reads_ms_per_commit"
    listed = [x for x in m["per_layer"] if x.get("workloads") == [CELL]
              and x["name"] != loop_reads]
    assert [x["name"] for x in listed] == NEW_METRICS
    for x in listed:
        assert callable(bench_run.load_reader(x["name"]))
        assert (x["layer"], x["moves"], x["source"]) \
            == ("reads", "commit_p50_ms", "program_counter")
    assert {x["name"] for x in bench_run.metrics_of(m, "end_to_end", CELL)} \
        == {"commits_per_s", "commit_p50_ms", "commit_p75_ms", "setup_s"}
    everywhere = {x["name"] for x in m["per_layer"] if "workloads" not in x}
    assert "fsyncs_per_commit" in everywhere   # every cell runs that layer
    # (an open loop: the generator's lateness is reported here too)
    assert {x["name"] for x in bench_run.metrics_of(m, "per_layer", CELL)} \
        == everywhere | set(NEW_METRICS) | {"gen_late_p99_ms", loop_reads}


def test_the_readers_return_none_where_the_program_keeps_no_count():
    for name in NEW_METRICS:
        read = bench_run.load_reader(name)
        ctx = {"c0": {}, "c1": {}, "config": {"peers": 3}}
        assert read(ctx) is None                    # the parent's counters
        none = {"requests": 0, "total_s": 0.0, "sweeps": 0,
                "confirms_sent": 0}
        assert read(dict(ctx, c0={"reads": None}, c1={"reads": None})) is None
        assert read(dict(ctx, c0={"reads": none}, c1={"reads": none})) is None
    some = {"requests": 40, "total_s": 0.1, "sweeps": 10, "confirms_sent": 30}
    ctx = {"c0": {"reads": {"requests": 0, "total_s": 0.0, "sweeps": 0,
                            "confirms_sent": 0}},
           "c1": {"reads": some}, "config": {"peers": 3}}
    assert bench_run.load_reader("read_server_ms")(ctx) == pytest.approx(2.5)
    assert bench_run.load_reader("read_groups_per_sweep")(ctx) == \
        pytest.approx(1.5)


def test_a_rehearsal_is_correct_and_reports_the_new_metrics():
    p, result = rehearse("--trace", "1")
    assert p.returncode == 0, p.stderr[-2000:]
    assert list(result) == RESULT_KEYS      # and 'compared' comes last
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] == 300
    assert failing(result) == set()
    assert result["compared"]["reads_not_linearizable"] == {"value": 0,
                                                            "limit": 0}
    got = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW_METRICS) | {"gen_late_p99_ms"} <= set(got)
    assert got["read_server_ms"] > 0
    assert 1.0 <= got["read_groups_per_sweep"] <= 16
    # three fsyncs a write, none a read: three times the share of writes
    seen = json.loads([line for line in p.stdout.splitlines()
                       if line.startswith("RESULT ")][0][len("RESULT "):])
    reads = seen["counters"]["c1"]["reads"]["requests"] \
        - seen["counters"]["c0"]["reads"]["requests"]
    writes = 300 - seen["reads_compared"]
    assert 0 < writes < 40 and abs(reads - seen["reads_compared"]) <= 3
    assert got["fsyncs_per_commit"] == pytest.approx(3 * writes / 300,
                                                     abs=0.02)
    assert "compared reads_not_linearizable: 0 (limit 0)" in p.stderr
    assert p.stderr.strip().endswith("correct: True")


def test_an_untraced_rehearsal_keeps_the_four_end_to_end_metrics():
    p, result = rehearse("--trace", "0")
    assert p.returncode == 0, p.stderr[-2000:]
    assert result["correct"] is True
    assert set(result["metrics"]) == {"commits_per_s", "commit_p50_ms",
                                      "commit_p75_ms", "setup_s"}
    # an acknowledged operation counts, read or write: the fixed rate
    assert result["metrics"]["commits_per_s"]["value"] == pytest.approx(
        100.0, rel=0.02)


@pytest.mark.parametrize("switch,name,fails", [
    ("--fault", "stale-read", {"reads_not_linearizable"}),
    ("--control", "memory-log", {"groups_short_of_durable"}),
    ("--fault", "lossy-followers", {"groups_short_of_replicas"}),
    ("--fault", "frozen-device-step", {"device_rows_differing",
                                       "device_commit_advance_wrong"}),
])
def test_each_number_fails_alone(switch, name, fails):
    p, result = rehearse("--trace", "0", switch, name)
    assert p.returncode == 0, p.stderr[-2000:]
    assert result["correct"] is False
    assert failing(result) == fails


def test_reads_sent_through_the_log_fail_the_commit_advance(tmp_path):
    """A read never reaches the log, and a number holds it: an operation
    that sends its 'GET's as entries (here: INCREMENTs under the name GET,
    the counter's state machine takes no GET as a write) moves the commit
    index by more than the reference counts."""
    import shutil
    co = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), co / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    os.symlink(os.path.join(ROOT, "ratis_tpu"), co / "ratis_tpu")
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), co / "BENCHMARK.json")
    op = (co / "benchmarks/ops/readmix.py").read_text()
    assert 'api.send_read_only(b"GET")' in op
    (co / "benchmarks/ops/readmix.py").write_text(
        op.replace('api.send_read_only(b"GET")', "api.send(payload)"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(co / "benchmarks" / "run.py"), "--workload",
         CELL, "--seed", "77", "--seconds", "2", "--rehearse-cpu",
         "--groups", "16", "--rate", "100"], cwd=str(co), env=env,
        capture_output=True, text=True, timeout=180)
    assert p.returncode == 0, p.stderr[-2000:]
    result = json.loads(p.stdout.strip().splitlines()[-1])
    assert result["correct"] is False
    assert "device_commit_advance_wrong" in failing(result)


# ------------------------------------- what a configuration can ask for now

def test_the_generators_client_gets_the_configurations_client_keys_whole():
    assert bench_run.CLIENT_KEY_PREFIXES == (
        "raft.tpu.tcp.", "raft.grpc.", "raft.tpu.grpc.", "raft.client.",
        "raft.netty.")
    # no accepted configuration sets a key under the four new prefixes: every
    # cell's client is built from what it was built from; one asks for a
    # stream address a peer (the stream configuration), and that is all it
    # adds
    m = bench_run.load_manifest()
    streams = set()
    for c in m["configs"]:
        cfg = bench_run.load_json(os.path.join(ROOT, c["file"]))
        assert not [k for k in cfg["properties"]
                    if k.startswith(bench_run.CLIENT_KEY_PREFIXES[1:])], c
        if "datastream" in cfg:
            assert cfg["datastream"] is True, c
            streams.add(c["name"])
    assert streams == {"ratis-filestore-stream-3x1k"}


def test_a_configuration_can_ask_for_peers_with_a_stream_address(tmp_path):
    """``"datastream": true``: every peer gets a stream address of its own,
    the servers start their stream servers, the generator's peers carry the
    addresses, and a client's 64 KiB stream is answered."""
    from benchmarks.harness.cluster import Cluster
    config = bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "configs", "ratis-filestore-3x1k.json"))
    plain = Cluster(dict(config, groups=4), 11, str(tmp_path))
    assert plain.datastream_addresses == {}
    assert all(p.datastream_address is None
               for g in plain.groups for p in g.peers)
    config = dict(config, groups=4, datastream=True)

    async def drive():
        from ratis_tpu.client import RaftClient
        from ratis_tpu.protocol.ids import ClientId
        cluster = Cluster(config, 11, str(tmp_path))
        ports = set(cluster.datastream_addresses.values())
        assert len(ports) == 3 and not ports & {a for _, a in
                                                cluster.addresses}
        os.makedirs(cluster.storage_dir)
        await cluster.start()
        assert all(s.datastream is not None for s in cluster.servers)
        # the generator's peers, as build_senders makes them from the spec
        spec = {"peers": cluster.addresses,
                "datastream": cluster.datastream_addresses}
        group = cluster.groups[1]
        by_id = {p.id.id: p for p in group.peers}
        assert {pid: by_id[pid].datastream_address for pid, _ in
                spec["peers"]} == spec["datastream"]
        client = (RaftClient.builder().set_raft_group(group)
                  .set_client_id(ClientId.value_of(uuid.uuid4().bytes))
                  .set_leader_id(group.peers[cluster.leader_server(1)].id)
                  .set_transport(cluster.factory.new_client_transport(
                      cluster.properties))
                  .set_properties(cluster.properties).build())
        import msgpack
        data = random.Random(5).randbytes(65536)
        out = await client.data_stream().stream(msgpack.packb(
            {"op": "stream", "path": "streamed/f0"}, use_bin_type=True))
        await out.write_async(data)
        reply = await out.close_async()
        assert reply.success, reply.exception
        answer = msgpack.unpackb(bytes(reply.message.content), raw=False)
        leader = cluster.servers[cluster.leader_server(1)]
        held = leader.divisions[group.group_id].state_machine.resolve(
            "streamed/f0").read_bytes()
        await client.close()
        await asyncio.gather(*(s.close() for s in cluster.servers))
        return answer, held == data

    answer, held = asyncio.run(drive())
    assert answer == {"ok": True, "size": 65536} and held
