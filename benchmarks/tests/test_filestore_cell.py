"""Tests of the FileStore cell (``ratis-filestore-3x1k.loadgen-closed``): its
plain reference alone, the cell rehearsed on the CPU, and the planted fault.
Run with ``python -m pytest benchmarks/tests -q``; nothing here touches the
TPU library."""

import json
import os
import struct
import subprocess
import sys
import zlib

import msgpack
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run
from benchmarks.harness import generator
from benchmarks.reference import filestore as ref

CELL = "ratis-filestore-3x1k.loadgen-closed"
NEW_METRICS = {"sm_data_write_ms", "sm_data_wait_ms",
               "sm_data_fsyncs_per_commit", "payload_mb_per_s"}
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "compared"]
GROUP = "0b5f9a3e-1c2d-4e3f-8a9b-0c1d2e3f4a5b"


def rehearse(script, *extra, timeout=180):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", script),
         "--workload", CELL, "--seed", "2147483999", "--seconds", "2",
         "--rehearse-cpu", "--groups", "16", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout)
    lines = p.stdout.strip().splitlines()
    return p, (json.loads(lines[-1]) if p.returncode == 0 and lines else None)


# ----------------------------------------------------------- the reference

def test_the_reference_answers_writes_in_offset_order_and_refuses_the_rest():
    r = ref.FileStoreReference(2)
    assert r.apply(0, "WRITE loadgen/f0 0 100 0") == b"OK loadgen/f0 0 100"
    assert r.apply(0, "WRITE loadgen/f0 50 100 0") == b"REFUSED"
    assert r.apply(0, "WRITE loadgen/f0 200 100 0") == b"REFUSED"
    assert r.apply(1, "WRITE loadgen/f0 0 7 1") == b"OK loadgen/f0 0 7"
    assert r.apply(1, "WRITE loadgen/f0 7 7 0") == b"REFUSED"   # closed
    assert r.apply(0, "WRITE loadgen/f0 100 100 1") == \
        b"OK loadgen/f0 100 100"
    assert r.files == [{"loadgen/f0": [200, True]},
                       {"loadgen/f0": [7, True]}]
    assert r.writes == [2, 1]
    with pytest.raises(ValueError, match="no semantics"):
        r.apply(0, "INCREMENT x 0 0 0")


def test_the_reference_judges_replies_group_by_group():
    def part(rows):
        g, p, a = zip(*rows)
        return {"group": list(g), "payload": list(p), "answer": list(a)}
    w = "WRITE loadgen/f0 {} 10 0".format
    ok = "OK loadgen/f0 {} 10".format
    good = part([(0, w(0), ok(0)), (1, w(0), ok(0)), (0, w(10), ok(10))])
    j = ref.judge_answers(2, [good])
    assert (j["answers_wrong"], j["never_answered"]) == (0, 0)
    assert j["acked_per_group"] == [2, 1] == j["submitted_per_group"]
    # an answer for another offset is wrong; one that never came is counted
    # apart, and those behind it in its group are held to echo the request
    bad = part([(0, w(0), ok(10)), (1, w(0), None), (1, w(10), ok(10)),
                (1, w(20), ok(0))])
    j = ref.judge_answers(2, [bad])
    assert (j["answers_wrong"], j["never_answered"]) == (2, 1)
    assert j["acked_per_group"] == [1, 2]
    assert j["submitted_per_group"] == [1, 3]
    assert j["samples"][0] == {"group": 0, "answer": ok(10),
                               "reference": ok(0)}


def record(payload: bytes) -> bytes:
    return struct.pack("<II", len(payload), zlib.crc32(payload)) + payload


def entry(index: int, path: str, offset: int, length: int,
          data: bytes = None) -> bytes:
    s = {"c": b"c" * 16, "id": index,
         "d": msgpack.packb({"op": "write", "path": path, "offset": offset,
                             "length": length, "close": False,
                             "sync": True})}
    if data is None:
        s["sx"] = length
    else:
        s["sd"] = data
    return msgpack.packb({"t": 1, "i": index, "k": 1, "s": s})


def test_durable_writes_reads_headers_and_bytes_by_itself(tmp_path):
    """A hand-made replica: three writes whose bytes are on the disk (one in
    a closed file, two in the file under construction), a fourth with one
    wrong byte, a record torn after them, and a record that swallowed its
    payload."""
    group = tmp_path / "s0" / GROUP
    log_dir, files = group / "current", group / "sm" / "files"
    log_dir.mkdir(parents=True)
    (files / ".uc" / "loadgen").mkdir(parents=True)
    (files / "loadgen").mkdir()
    n = 4096
    data = lambda path, off: ref.payload_bytes(GROUP, path, off, n)
    (files / "loadgen" / "f0").write_bytes(data("loadgen/f0", 0))
    (files / ".uc" / "loadgen" / "f1").write_bytes(
        data("loadgen/f1", 0) + data("loadgen/f1", n))
    segment = ref.SEGMENT_MAGIC + record(b"conf") \
        + record(entry(1, "loadgen/f0", 0, n)) \
        + record(entry(2, "loadgen/f1", 0, n)) \
        + record(entry(3, "loadgen/f1", n, n))
    (log_dir / "log_inprogress_0").write_bytes(
        segment + b"\x40\x00\x00\x00torn")
    (log_dir / "raft-meta").write_bytes(b"loadgen/")        # not a segment
    needle = b"loadgen/"
    assert ref.durable_writes(str(log_dir), needle) == 3
    assert ref.durable_writes(str(tmp_path / "absent"), needle) == 0
    # a fourth header whose bytes differ by one: the lesser of the two counts
    wrong = bytearray(data("loadgen/f1", 2 * n))
    wrong[17] ^= 1
    with open(files / ".uc" / "loadgen" / "f1", "ab") as f:
        f.write(bytes(wrong))
    (log_dir / "log_inprogress_0").write_bytes(
        segment + record(entry(4, "loadgen/f1", 2 * n, n)))
    assert len(ref.write_headers(str(log_dir / "log_inprogress_0"),
                                 needle)) == 4
    assert ref.durable_writes(str(log_dir), needle) == 3
    # a header without its file at all
    (files / "loadgen" / "f0").unlink()
    assert ref.durable_writes(str(log_dir), needle) == 2
    # a record that holds the bytes is not a header beside the data
    (log_dir / "log_inprogress_0").write_bytes(
        ref.SEGMENT_MAGIC + record(entry(1, "loadgen/f1", 0, 8192,
                                         data=b"x" * 8192)))
    assert ref.write_headers(str(log_dir / "log_inprogress_0"), needle) == []
    assert ref.leader_commit([7, 4, 2], 0, 6, [True, True, True]) == 4
    assert ref.replicas_holding([3, 4, 9], 3, 4) == 2


def test_the_operation_walks_one_sequence_per_group():
    """Offsets, close on the last write of a file, bytes the reference
    computes again; what was sent is described as ASCII."""
    import uuid
    traffic = bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "traffic", "loadgen-closed.json"))
    assert (traffic["loop"], traffic["in_flight"]) == ("closed", 64)
    assert traffic["write"]["bytes"] == 65536
    assert traffic["write"]["file_bytes"] == 1048576
    assert traffic["write"]["sync"] is True
    assert traffic["target"] == {"dist": "uniform"}
    sent = []

    class Api:
        def send(self, payload):
            sent.append(msgpack.unpackb(payload, raw=False))
            return None         # (the reply is not awaited here)

    class Client:
        group_id = type("G", (), {"to_bytes":
                                  lambda self: uuid.UUID(GROUP).bytes})()

        def io(self):
            return Api()

    send = generator.load_op(ROOT, traffic["op"]).sender(Client(), traffic)
    texts = []
    for _ in range(18):
        text, pending = send()
        pending.close()
        texts.append(text)
    assert texts[0] == "WRITE loadgen/f0 0 65536 0"
    assert texts[15] == "WRITE loadgen/f0 983040 65536 1"
    assert texts[16] == "WRITE loadgen/f1 0 65536 0"
    assert [m["offset"] for m in sent[:3]] == [0, 65536, 131072]
    assert [m["close"] for m in sent[14:17]] == [False, True, False]
    assert all(m["sync"] is True and m["op"] == "write" for m in sent)
    assert sent[17]["data"] == ref.payload_bytes(GROUP, "loadgen/f1", 65536,
                                                 65536)
    r = ref.FileStoreReference(1)
    assert all(r.apply(0, t).startswith(b"OK ") for t in texts)


def test_the_configuration_states_what_the_issue_asks_for():
    m = bench_run.load_manifest()
    r = bench_run.resolve_cell(m, CELL)
    cfg = r["config"]
    assert r["reference"] == "filestore" and r["cell"]["chips"] == 1
    base = bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "configs", "ratis-3x1k.json"))
    inert = {"raft.tpu.tcp.flush-bytes", "raft.tpu.tcp.flush-micros"}
    assert cfg["properties"] == {k: v for k, v in base["properties"].items()
                                 if k not in inert}
    for key in ("peers", "groups", "transport", "engine", "prewarm",
                "bring_up_wave", "storage", "client", "leaders"):
        assert cfg[key] == base[key], key
    assert cfg["reduced"] == ["groups", "transport", "processes", "numFiles"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    assert {"--bufferSize 65536", "--sync 1"} <= set(cfg["assumed"])
    g = cfg["guarantees"]
    assert (g["replicas_acknowledging"], g["durable"], g["exactly_once"],
            g["read_consistency"]) == (2, True, True, "linearizable")
    listed = {x["name"] for x in m["per_layer"] if x.get("workloads") == [CELL]}
    assert listed == NEW_METRICS


# ------------------------------------------------------ the cell, rehearsed

@pytest.mark.parametrize("trace", ("0", "1"))
def test_the_cell_rehearsed_prints_the_contracts_last_line(trace):
    p, result = rehearse("run.py", "--trace", trace)
    assert p.returncode == 0, p.stderr[-2000:]
    assert list(result) == RESULT_KEYS      # and 'compared' comes last
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    m = bench_run.load_manifest()
    kind = "end_to_end" if trace == "0" else "per_layer"
    listed = {x["name"]: x for x in bench_run.metrics_of(m, kind, CELL)}
    assert set(result["metrics"]) <= set(listed)
    for name, got in result["metrics"].items():
        assert got["unit"] == listed[name]["unit"]
        assert isinstance(got["value"], (int, float))
        assert listed[name]["source"] != "device_trace"
    if trace == "0":
        assert set(result["metrics"]) == set(listed)
        assert result["metrics"]["commits_per_s"]["value"] > 0
    else:
        assert NEW_METRICS <= set(result["metrics"])
        got = {k: result["metrics"][k]["value"] for k in NEW_METRICS}
        assert 2.9 <= got["sm_data_fsyncs_per_commit"] <= 3.6
        assert got["payload_mb_per_s"] > 1 and got["sm_data_write_ms"] > 0
        assert got["sm_data_wait_ms"] >= 0
        # the bytes go over the wire twice a commit and never into the log
        assert result["metrics"]["wire_bytes_per_commit"]["value"] > 131072
    assert set(result["compared"]) == {
        "never_answered", "answers_wrong", "groups_short_of_replicas",
        "device_rows_differing", "device_quorum_rows_wrong",
        "device_commit_advance_wrong", "groups_short_of_durable"}
    for c in result["compared"].values():
        assert c["value"] <= c["limit"] == 0
    assert "compared groups_short_of_durable: 0 (limit 0)" in p.stderr
    assert p.stderr.strip().endswith("correct: True")


def test_a_byte_flipped_in_two_replicas_comes_out_not_correct():
    p, result = rehearse("flip_byte.py", "--trace", "0")
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stderr.count("flip_byte: ") == 2
    assert result["correct"] is False
    wrong = {k: c["value"] for k, c in result["compared"].items()
             if c["value"] > c["limit"]}
    assert wrong == {"groups_short_of_durable": 1}
