"""Tests of the gRPC cell (``ratis-3x1k-grpc.write-closed``): its names
resolve to its files, its configuration is ``ratis-3x1k``'s but for the wire,
and a traced rehearsal on the CPU reports the two metrics that read the gRPC
transport's counters.  Run with ``python -m pytest benchmarks/tests -q``;
nothing here touches the TPU library."""

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run
from benchmarks.harness import generator

CELL = "ratis-3x1k-grpc.write-closed"
PAIR = "ratis-3x1k.write-closed"
NEW_METRICS = {"grpc_messages_per_commit", "grpc_chunks_per_message"}
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "compared"]


def rehearse(cell, *extra, timeout=180):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", cell, "--seed", "2147483999", "--seconds", "2",
         "--rehearse-cpu", "--groups", "16", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=timeout)
    lines = p.stdout.strip().splitlines()
    return p, (json.loads(lines[-1]) if p.returncode == 0 and lines else None)


def test_every_name_of_the_cell_resolves_to_its_files():
    m = bench_run.load_manifest()
    r = bench_run.resolve_cell(m, CELL)
    assert r["cell"] in m["workloads"]
    assert (r["cell"]["config"], r["cell"]["traffic"], r["cell"]["chips"]) \
        == ("ratis-3x1k-grpc", "write-closed", 1)
    assert bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "workloads", CELL + ".json")) \
        == {k: r["cell"][k] for k in ("config", "traffic", "chips", "why")}
    entry = {c["name"]: c for c in m["configs"]}["ratis-3x1k-grpc"]
    assert entry["name"] == r["config"]["name"] == "ratis-3x1k-grpc"
    assert entry["file"] == "benchmarks/configs/ratis-3x1k-grpc.json"
    assert entry["source"] == r["config"]["source"]
    assert len(entry["source"]) <= 200 and len(r["cell"]["why"]) <= 200
    assert entry["reduced"] == r["config"]["reduced"] == ["groups",
                                                          "processes"]
    assert r["reference"] == r["config"]["reference"] == "counter"
    assert os.path.exists(os.path.join(ROOT, "benchmarks", "reference",
                                       "counter.py"))
    assert r["traffic"]["name"] == "write-closed"
    assert callable(generator.load_op(ROOT, r["traffic"]["op"]).sender)
    listed = [x for x in m["per_layer"] if x.get("workloads") == [CELL]]
    # there, in the order they were appended in (later PRs append behind)
    assert [x["name"] for x in listed] == ["grpc_messages_per_commit",
                                           "grpc_chunks_per_message"]
    assert {x["name"] for x in listed} == NEW_METRICS
    for x in listed:
        assert callable(bench_run.load_reader(x["name"]))
        assert (x["layer"], x["moves"], x["source"]) \
            == ("wire", "commit_p50_ms", "program_counter")
    # the cell reports the four end-to-end metrics and every per-layer
    # metric that names no cells
    assert {x["name"] for x in bench_run.metrics_of(m, "end_to_end", CELL)} \
        == {"commits_per_s", "commit_p50_ms", "commit_p75_ms", "setup_s"}
    everywhere = {x["name"] for x in m["per_layer"] if "workloads" not in x}
    assert {x["name"] for x in bench_run.metrics_of(m, "per_layer", CELL)} \
        == everywhere | NEW_METRICS


def test_the_configuration_is_ratis_3x1k_but_for_the_wire():
    load = lambda name: bench_run.load_json(os.path.join(
        ROOT, "benchmarks", "configs", name + ".json"))
    cfg, base = load("ratis-3x1k-grpc"), load("ratis-3x1k")
    assert cfg["transport"] == "GRPC" and base["transport"] == "TCP"
    for key in base:
        if key not in ("name", "source", "source_defines", "deployment",
                       "transport", "properties", "reduced", "reduced_why",
                       "assumed"):
            assert cfg[key] == base[key], key
    assert cfg["guarantees"] == base["guarantees"]      # word for word
    assert cfg["controls"] == base["controls"]
    # no key of one wire in the other's file, every other property the same
    assert cfg["properties"] == {
        k: v for k, v in base["properties"].items()
        if not k.startswith("raft.tpu.tcp.")}
    assert not [k for k in cfg["properties"] if ".grpc." in k]
    assert cfg["reduced"] == ["groups", "processes"]
    assert set(cfg["reduced_why"]) == set(cfg["reduced"])
    assert cfg["reduced_why"] == {k: base["reduced_why"][k]
                                  for k in cfg["reduced"]}
    assert set(base["assumed"]) < set(cfg["assumed"])
    assert set(base["source_defines"]) < set(cfg["source_defines"])
    for word in ("GrpcConfigKeys.Server.setPort", "GrpcFactory"):
        assert word in cfg["source"]
        assert word in cfg["source_defines"]["transport"]
    assert cfg["source"] != base["source"]


def test_a_traced_rehearsal_reports_the_new_metrics_and_the_whole_wire():
    p, result = rehearse(CELL, "--trace", "1")
    assert p.returncode == 0, p.stderr[-2000:]
    assert list(result) == RESULT_KEYS      # and 'compared' comes last
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["platform"] == "cpu"
    m = bench_run.load_manifest()
    listed = {x["name"]: x for x in bench_run.metrics_of(m, "per_layer",
                                                         CELL)}
    assert set(result["metrics"]) <= set(listed)
    for name, got in result["metrics"].items():
        assert got["unit"] == listed[name]["unit"]
        assert isinstance(got["value"], (int, float))
        assert listed[name]["source"] != "device_trace"
    assert NEW_METRICS <= set(result["metrics"])
    got = {k: v["value"] for k, v in result["metrics"].items()}
    # nothing batches at flush-micros 0; a commit is at least two appends,
    # two acks, the client's request and its reply, written or read here
    assert got["grpc_chunks_per_message"] == 1.0
    assert got["grpc_messages_per_commit"] >= 2 * got["wire_frames_per_commit"] - 1
    # the same commits over TCP report the shared wire metrics and not
    # gRPC's own.  (Neither frames nor bytes are held to each other: since
    # PR 34 a gRPC lane keeps 4 frames unanswered where TCP's keeps 16, so it
    # sends fewer, fuller frames and fewer envelope headers a commit: 1.89
    # against 2.26 frames at 16 groups on the CPU, 1.94 against 1.79 at
    # 1,024 on the chip.)
    q, pair = rehearse(PAIR, "--trace", "1")
    assert q.returncode == 0, q.stderr[-2000:]
    tcp = {k: v["value"] for k, v in pair["metrics"].items()}
    assert NEW_METRICS.isdisjoint(tcp)
    assert tcp["wire_frames_per_commit"] > 0 < got["wire_frames_per_commit"]
    for c in result["compared"].values():
        assert c["value"] <= c["limit"] == 0


def test_an_untraced_rehearsal_keeps_the_contracts_last_line():
    p, result = rehearse(CELL, "--trace", "0")
    assert p.returncode == 0, p.stderr[-2000:]
    assert list(result) == RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"commits_per_s", "commit_p50_ms",
                                      "commit_p75_ms", "setup_s"}
    assert result["metrics"]["commits_per_s"]["value"] > 0
    assert p.stderr.strip().endswith("correct: True")
