"""Tests of the stream cell (``ratis-filestore-stream-3x1k.datastream-closed``,
PR 36): the configuration beside its sibling, the cell rehearsed on the CPU
untraced and traced, the planted fault and the control each failing their own
number, and the readers on a program that lacks what they read.  The plain
reference alone, the operation and the system against the reference are held
by tier-1 (``tests/test_stream_cell.py``).  Run with ``python -m pytest
benchmarks/tests -q``; nothing here touches the TPU library."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run

CELL = "ratis-filestore-stream-3x1k.datastream-closed"
CONFIG = "ratis-filestore-stream-3x1k"
SIBLING = "ratis-filestore-3x1k"
NEW_METRICS = {"stream_mb_per_s", "stream_packet_ms", "stream_write_ms",
               "stream_close_ms", "stream_connects_per_commit"}
# the keys in which the configuration may differ from its sibling (ISSUE 36,
# Tentpole (1)), and the control a new file may carry
DIFFERS = {"name", "source", "source_defines", "datastream", "reference",
           "replica_state", "assumed", "controls"}
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "compared"]
COMPARED = {"never_answered", "answers_wrong", "groups_short_of_replicas",
            "device_rows_differing", "device_quorum_rows_wrong",
            "device_commit_advance_wrong", "groups_short_of_durable"}


def rehearse(script, *extra, timeout=240):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", script),
         "--workload", CELL, "--seed", "2147483999", "--seconds", "2",
         "--rehearse-cpu", "--groups", "16", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout)
    lines = p.stdout.strip().splitlines()
    return p, (json.loads(lines[-1]) if p.returncode == 0 and lines else None)


def wrong(result) -> dict:
    return {k: c["value"] for k, c in result["compared"].items()
            if c["value"] > c["limit"]}


# -------------------------------------------------------- the configuration

def config(name: str) -> dict:
    return bench_run.load_json(os.path.join(ROOT, "benchmarks", "configs",
                                            name + ".json"))


def test_the_configuration_is_its_sibling_but_for_the_listed_keys():
    cfg, sib = config(CONFIG), config(SIBLING)
    assert set(cfg) - set(sib) == {"datastream", "controls"}
    assert set(sib) <= set(cfg)
    for key in set(sib) - DIFFERS - {"guarantees", "reduced_why"}:
        assert cfg[key] == sib[key], key
    for key in ("name", "source", "source_defines", "reference",
                "replica_state", "assumed"):
        assert cfg[key] != sib[key], key
    assert cfg["datastream"] is True
    assert cfg["reference"] == "filestore_stream"
    assert cfg["replica_state"] == {"attribute": "streams_committed"}
    # the guarantees but for their meaning; the cuts but for numFiles
    g, gs = dict(cfg["guarantees"]), dict(sib["guarantees"])
    assert g.pop("meaning") != gs.pop("meaning") and g == gs
    assert (g["replicas_acknowledging"], g["durable"], g["exactly_once"],
            g["read_consistency"]) == (2, True, True, "linearizable")
    assert "3 of 3" in cfg["guarantees"]["meaning"]
    assert "2 of 3" in cfg["guarantees"]["meaning"]
    w, ws = dict(cfg["reduced_why"]), dict(sib["reduced_why"])
    assert w.pop("numFiles") != ws.pop("numFiles") and w == ws
    assert cfg["reduced"] == ["groups", "transport", "processes", "numFiles"]
    assert {"--bufferSize 65536", "--syncSize 1048576", "--type", "routing",
            "stream wire", "outstanding packets a stream"} \
        <= set(cfg["assumed"])
    assert "chain" in cfg["assumed"]["routing"]
    assert cfg["controls"] == config("ratis-3x1k")["controls"]


def test_the_manifest_gains_one_configuration_one_cell_and_five_metrics():
    m = bench_run.load_manifest()
    r = bench_run.resolve_cell(m, CELL)
    assert r["reference"] == "filestore_stream"
    assert r["cell"] == {"name": CELL, "config": CONFIG,
                         "traffic": "datastream-closed", "chips": 1,
                         "why": r["cell"]["why"]}
    assert len(r["cell"]["why"]) <= 200
    entry = [c for c in m["configs"] if c["name"] == CONFIG]
    assert len(entry) == 1 and entry[0]["source"] == r["config"]["source"]
    assert len(entry[0]["source"]) <= 200 and len(entry[0]["why"]) <= 200
    assert entry[0]["reduced"] == r["config"]["reduced"]
    assert entry[0]["source"] != config(SIBLING)["source"]
    assert [w["name"] for w in m["workloads"] if w["config"] == CONFIG] \
        == [CELL]
    # (the loop's ms a commit in the stream layer came later, with the
    # other layers')
    mine = [x for x in m["per_layer"] if CELL in x.get("workloads", ())
            and x["name"] != "loop_stream_ms_per_commit"]
    assert {x["name"] for x in mine} == NEW_METRICS
    at = m["per_layer"].index(mine[0])          # appended together, in order
    assert m["per_layer"][at:at + 5] == mine
    for x in mine:
        assert x["workloads"] == [CELL] and x["layer"] == "stream plane"
        assert os.path.isfile(os.path.join(ROOT, "benchmarks",
                                           "layer_metrics",
                                           x["name"] + ".py"))
    assert {x["name"]: x["moves"] for x in mine} == {
        "stream_mb_per_s": "commits_per_s",
        "stream_packet_ms": "commit_p50_ms",
        "stream_write_ms": "commit_p50_ms",
        "stream_close_ms": "commit_p50_ms",
        "stream_connects_per_commit": "commit_p50_ms"}
    # the cell reports every per-layer metric that has no list of its own
    reported = {x["name"] for x in bench_run.metrics_of(m, "per_layer", CELL)}
    assert {"ingress_to_append_ms", "apply_to_socket_ms",
            "log_flush_wait_ms", "quorum_to_apply_ms",
            "engine_step_roofline", "device_idle_pct"} <= reported
    assert "payload_mb_per_s" not in reported   # (the log path's, untouched)


def test_the_traffic_is_what_the_issue_names():
    t = bench_run.resolve_cell(bench_run.load_manifest(), CELL)["traffic"]
    assert (t["loop"], t["in_flight"]) == ("closed", 16)
    assert t["target"] == {"dist": "uniform"}
    assert (t["op"], t["round_op"]) == ("filestore-stream",
                                        "filestore-stream-round")
    assert t["stream"]["file_bytes"] == 1048576
    assert t["stream"]["packet_bytes"] == 65536
    assert "chain" in t["stream"]["routing"]
    assert (t["warmup_writes_per_group"], t["settle_writes_per_group"],
            t["drain_s"]) == (1, 1, 60)


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
        got = {k: v["value"] for k, v in result["metrics"].items()}
        assert NEW_METRICS <= set(got)
        # a file's bytes go round the log: its entry is a header, and the
        # raft request a stream ends in is traced from the stream server
        assert {"ingress_to_append_ms", "apply_to_socket_ms",
                "log_flush_wait_ms", "quorum_to_apply_ms"} <= set(got)
        assert got["wire_bytes_per_commit"] < 4096
        assert 2.9 <= got["fsyncs_per_commit"] <= 3.6
        assert got["stream_mb_per_s"] > 1
        assert got["stream_packet_ms"] >= got["stream_write_ms"] > 0
        assert got["stream_close_ms"] > 0
        # 5 a stream down a chain of three; a short window's edges add some
        assert 5 <= got["stream_connects_per_commit"] < 8
    assert set(result["compared"]) == COMPARED
    for c in result["compared"].values():
        assert c["value"] <= c["limit"] == 0
    assert "compared groups_short_of_durable: 0 (limit 0)" in p.stderr
    assert p.stderr.strip().endswith("correct: True")


def test_a_byte_flipped_in_two_replicas_comes_out_not_correct():
    p, result = rehearse("flip_byte.py", "--trace", "0")
    assert p.returncode == 0, p.stderr[-2000:]
    assert p.stderr.count("flip_byte: ") == 2
    assert result["correct"] is False and result["failed"] == 0
    assert wrong(result) == {"groups_short_of_durable": 1}


def test_the_memory_log_control_is_not_correct_by_durability_alone():
    p, result = rehearse("run.py", "--trace", "0", "--control", "memory-log")
    assert p.returncode == 0, p.stderr[-2000:]
    assert result["correct"] is False and result["failed"] == 0
    assert wrong(result) == {"groups_short_of_durable": 16}


# ------------------------------------------------- the readers on the parent

@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_a_reader_finds_nothing_on_a_program_without_its_span_or_counter(
        name, monkeypatch):
    """The driver runs this PR's readers over the parent's program too: no
    stage of that name, no counter of that name, and (no session) no rows:
    None, never a raise."""
    from ratis_tpu import trace
    from ratis_tpu.trace import get_tracer
    read = bench_run.load_reader(name)
    ctx = {"acked_in_window": 10}
    tracer = get_tracer()
    tracer.configure(enabled=False)
    assert read(ctx) is None                        # no session at all
    old = tuple(n for n in trace.STAGE_NAMES if not n.startswith("stream."))
    monkeypatch.setattr(trace, "STAGE_NAMES", old)
    tracer.configure(enabled=True, sample_every=1)
    try:
        counters = {k: v for k, v in tracer._counters.items()
                    if not k[0].startswith("stream.")}
        monkeypatch.setattr(tracer, "_counters", counters)
        tracer.configure(enabled=False)             # a closed, empty session
        assert read(ctx) is None
    finally:
        tracer.configure(enabled=False)
