"""Tests of the benchmark itself (the yardstick lives under ``benchmarks/``).
Run with ``python -m pytest benchmarks/tests -q`` on the CPU; nothing here
touches the TPU library."""

import asyncio
import json
import os
import re
import shutil
import struct
import subprocess
import sys
import zlib

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import run as bench_run
from benchmarks.harness import compare, generator, peaks, stats, trace_reduce
from benchmarks.reference import counter as ref

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
RESULT_KEYS = ["correct", "attempted", "failed", "metrics", "device",
               "compared"]
CELL1, CELL2 = "ratis-3x1k.write-open", "ratis-3x1k.write-closed"


def run_cell(*args, checkout=ROOT, timeout=120):
    """``benchmarks/run.py`` as the driver starts it, on the CPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(checkout, "benchmarks", "run.py"),
         *args], cwd=checkout, env=env, capture_output=True, text=True,
        timeout=timeout)
    lines = p.stdout.strip().splitlines()
    return p, (json.loads(lines[-1]) if p.returncode == 0 and lines else None)


def rehearse(cell, *extra, **kw):
    rate = ("--rate", "60") if cell == CELL1 else ()
    return run_cell("--workload", cell, "--seed", "2147483999", "--seconds",
                    "2", "--rehearse-cpu", "--groups", "16", *rate, *extra,
                    **kw)


# ------------------------------------------------------------------ manifest

def test_every_name_resolves_to_its_files():
    m = bench_run.load_manifest()
    assert m["paths"] == ["benchmarks"]
    files = set()
    for c in m["configs"]:
        assert c["file"].startswith("benchmarks/") and c["file"] not in files
        files.add(c["file"])
        cfg = bench_run.load_json(os.path.join(ROOT, c["file"]))
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "reference", cfg["reference"] + ".py"))
        assert cfg["source"].startswith("https://") and cfg["source_defines"]
        assert set(cfg["reduced"]) == set(cfg["reduced_why"])
        for key in ("replicas_acknowledging", "durable", "read_consistency"):
            assert key in cfg["guarantees"]
    for w in m["workloads"]:
        r = bench_run.resolve_cell(m, w["name"])
        assert r["traffic"]["name"] == w["traffic"]
        # the plain reference is the configuration's, unless the traffic
        # brings operations that need one of their own
        assert r["reference"] == r["traffic"].get("reference",
                                                  r["config"]["reference"])
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "reference", r["reference"] + ".py"))
        assert callable(generator.load_op(ROOT, r["traffic"]["op"]).sender)
        on_file = bench_run.load_json(os.path.join(
            ROOT, "benchmarks", "workloads", w["name"] + ".json"))
        assert on_file == {k: w[k] for k in ("config", "traffic", "chips",
                                             "why")}
        assert w["name"] == f"{w['config']}.{w['traffic']}"
    e2e = {e["name"] for e in m["end_to_end"]}
    cells = {w["name"] for w in m["workloads"]}
    for pl in m["per_layer"]:
        assert callable(bench_run.load_reader(pl["name"]))
        assert pl["moves"] in e2e
        assert set(pl.get("workloads", cells)) <= cells
    assert "setup_s" in e2e


def test_names_and_units_use_the_allowed_characters():
    m = bench_run.load_manifest()
    names = ([c["name"] for c in m["configs"]]
             + [w["name"] for w in m["workloads"]]
             + [w["traffic"] for w in m["workloads"]]
             + [k for c in m["configs"] for k in c["reduced"]])
    metrics = m["end_to_end"] + m["per_layer"]
    for n in names + [x["name"] for x in metrics]:
        assert NAME.match(n), n
    for x in metrics:
        assert UNIT.match(x["unit"]), x
        assert x["better"] in ("lower", "higher")
        assert x["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
    assert len({x["name"] for x in metrics}) == len(metrics)
    for e in m["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.25


def test_perf_md_states_the_bounds_that_the_manifest_holds():
    """BENCHMARK.json is the source; PERF.md section 2 repeats each bound in
    its table's third column and may not drift from it."""
    m = bench_run.load_manifest()
    if not os.path.exists(os.path.join(ROOT, "PERF.md")):
        pytest.skip("no PERF.md beside the benchmark")
    with open(os.path.join(ROOT, "PERF.md")) as f:
        rows = [[c.strip() for c in line.strip().strip("|").split("|")]
                for line in f if line.startswith("| `")]
    stated = {r[0].strip("`"): r[2] for r in rows if len(r) >= 4}
    for e in m["end_to_end"]:
        assert float(stated[e["name"]]) == e["bound"], e["name"]


# ------------------------------------------------------------- whole command

def test_without_a_chip_it_refuses_to_report():
    p, result = run_cell("--workload", CELL1, "--seed", "1", "--seconds", "2",
                         "--trace", "0")
    assert p.returncode != 0 and result is None
    assert p.stdout.strip() == ""
    assert "no accelerator" in p.stderr


@pytest.mark.parametrize("cell,trace", [(CELL1, "0"), (CELL1, "1"),
                                        (CELL2, "0")])
def test_rehearsal_prints_the_contracts_last_line(cell, trace):
    p, result = rehearse(cell, "--trace", trace)
    assert p.returncode == 0, p.stderr[-2000:]
    assert list(result) == RESULT_KEYS      # and 'compared' comes last
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert result["device"]["platform"] == "cpu"
    m = bench_run.load_manifest()
    kind = "end_to_end" if trace == "0" else "per_layer"
    listed = {x["name"]: x for x in bench_run.metrics_of(m, kind, cell)}
    assert set(result["metrics"]) <= set(listed)
    for name, got in result["metrics"].items():
        assert got["unit"] == listed[name]["unit"]
        assert isinstance(got["value"], (int, float))
        # a CPU run never writes under a device metric's name
        assert listed[name]["source"] != "device_trace"
    if trace == "0":
        assert set(result["metrics"]) == set(listed)
        assert result["metrics"]["commits_per_s"]["value"] > 0
    for c in result["compared"].values():
        assert c["value"] <= c["limit"] == 0
    # each number compared stands beside its limit at the end of stderr
    assert "compared answers_wrong: 0 (limit 0)" in p.stderr
    assert p.stderr.strip().endswith("correct: True")


@pytest.mark.parametrize("cell,switch,name,fails", [
    (CELL1, "--control", "memory-log", "groups_short_of_durable"),
    (CELL2, "--control", "memory-log", "groups_short_of_durable"),
    (CELL2, "--fault", "lossy-followers", "groups_short_of_replicas"),
    (CELL1, "--fault", "altered-answer", "answers_wrong"),
    (CELL1, "--fault", "frozen-device-step", "device_rows_differing"),
])
def test_controls_and_planted_faults_come_out_not_correct(cell, switch, name,
                                                          fails):
    p, result = rehearse(cell, "--trace", "0", switch, name)
    assert p.returncode == 0, p.stderr[-2000:]
    assert result["correct"] is False
    assert result["compared"][fails]["value"] > 0
    if name == "frozen-device-step":
        assert result["compared"]["device_commit_advance_wrong"]["value"] > 0


def test_a_cell_a_mix_an_operation_and_a_metric_are_added_by_files_alone(
        tmp_path):
    """A later PR adds files and entries, and edits no file that is there."""
    co = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), co / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    os.symlink(os.path.join(ROOT, "ratis_tpu"), co / "ratis_tpu")
    before = {p: p.read_bytes() for p in (co / "benchmarks").rglob("*")
              if p.is_file()}
    m = bench_run.load_manifest()
    (co / "benchmarks/traffic/write-open-slow.json").write_text(json.dumps(
        dict(bench_run.load_json(os.path.join(
            ROOT, "benchmarks/traffic/write-open.json")),
            name="write-open-slow", rate_per_s=40, op="write-async")))
    (co / "benchmarks/ops/write-async.py").write_text(
        "def sender(client, traffic):\n"
        "    api = client.async_api()\n"
        "    return lambda: ('INCREMENT', api.send(b'INCREMENT'))\n")
    cell = {"config": "ratis-3x1k", "traffic": "write-open-slow", "chips": 1,
            "why": "a test's cell"}
    (co / "benchmarks/workloads/ratis-3x1k.write-open-slow.json").write_text(
        json.dumps(cell))
    (co / "benchmarks/layer_metrics/answered_share.py").write_text(
        "def read(ctx):\n"
        "    r = ctx['requests']\n"
        "    return 100.0 * sum(a is not None for a in r['answer'])"
        " / len(r['answer'])\n")
    m["workloads"].append(dict(cell, name="ratis-3x1k.write-open-slow"))
    m["per_layer"].append({"name": "answered_share", "unit": "%",
                           "better": "higher", "source": "host_clock",
                           "layer": "load generator",
                           "moves": "commits_per_s",
                           "workloads": ["ratis-3x1k.write-open-slow"]})
    (co / "BENCHMARK.json").write_text(json.dumps(m))
    p, result = run_cell("--workload", "ratis-3x1k.write-open-slow", "--seed",
                         "5", "--seconds", "2", "--trace", "1",
                         "--rehearse-cpu", "--groups", "16",
                         checkout=str(co))
    assert p.returncode == 0, p.stderr[-2000:]
    assert result["correct"] is True and result["attempted"] == 80
    assert result["metrics"]["answered_share"] == {"value": 100.0,
                                                   "unit": "%"}
    assert all(p.read_bytes() == data for p, data in before.items())


def test_the_sweep_is_a_script_of_its_own_and_steps_one_parameter():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "sweep.py"),
         "--workload", CELL2, "--seed", "3", "--seconds", "1",
         "--rehearse-cpu", "--groups", "16", "--key", "in_flight",
         "--values", "2,6"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    last = p.stdout.strip().splitlines()[-1]
    assert last.startswith("SWEEP ")
    steps = json.loads(last[len("SWEEP "):])
    assert [s["in_flight"] for s in steps] == [2, 6]
    assert all(s["in_flight_at_close"] == s["in_flight"] and not s["failed"]
               for s in steps)
    # and the measured command knows nothing of it
    q, _ = run_cell("--workload", CELL2, "--seed", "1", "--seconds", "1",
                    "--sweep", "1,2")
    assert q.returncode == 2 and "unrecognized arguments" in q.stderr


# ----------------------------------------------------------------- generator

class _Reply:
    success = True

    def __init__(self, content):
        self.message = type("M", (), {"content": content})


def test_open_loop_times_from_the_due_time_and_keeps_its_schedule():
    """A server that stalls raises the tail and `failed`; it does not lower
    the load: every request still leaves when it was due."""
    import time
    traffic = {"rate_per_s": 200, "target": {"dist": "uniform"}}
    due, targets = generator.open_schedule(traffic, 4, 1.0, seed=3)

    async def drive(stalled):
        t0 = time.monotonic() + 0.05
        stall_from, stall_to = t0 + 0.6, t0 + 1.2

        def sender(group):
            async def reply():
                now = time.monotonic()
                if stalled and now >= stall_from:
                    if group == 3:
                        await asyncio.sleep(3600)     # never answers
                    await asyncio.sleep(stall_to - now)
                await asyncio.sleep(0.002)
                return _Reply(b"1")
            return lambda: ("INCREMENT", reply())

        rec = await generator.run_open([sender(g) for g in range(4)], due,
                                       targets, t0, drain_s=0.5)
        return rec.as_dict(t0)

    calm = asyncio.run(drive(False))
    stalled = asyncio.run(drive(True))
    for r in (calm, stalled):
        assert len(r["due"]) == 200
        # (the stall lasts 0.6 s: a generator that waited for it would be that late)
        assert max(s - d for s, d in zip(r["sent"], r["due"])) < 0.25
    hung = sum(1 for d, g in zip(due, targets) if g == 3 and d >= 0.6)
    assert hung > 5
    s_calm = bench_run.summarize(calm, 1.0, 0.5)
    s_stalled = bench_run.summarize(stalled, 1.0, 0.5)
    assert set(stalled["payload"]) == {"INCREMENT"}
    assert s_calm["failed"] == 0 and s_calm["commit_p99_ms"] < 300
    assert s_stalled["attempted"] == 200 and s_stalled["failed"] == hung
    assert s_stalled["commit_p99_ms"] == 1500.0   # among the missing
    assert s_stalled["commit_p50_ms"] < 300
    assert s_stalled["commits_per_s"] < 0.7 * s_calm["commits_per_s"]
    answered = [(a - d) * 1e3 for a, d in zip(stalled["acked"],
                                              stalled["due"]) if a is not None]
    assert max(answered) > 500        # timed from due, through the stall


def test_every_seed_sends_the_same_set_in_another_order():
    traffic = {"rate_per_s": 100, "target": {"dist": "uniform"}}
    due_a, tg_a = generator.open_schedule(traffic, 7, 3.0, seed=1)
    due_b, tg_b = generator.open_schedule(traffic, 7, 3.0, seed=2 ** 31 + 5)
    assert len(due_a) == len(due_b) == 300 and due_a[0] == 0.0
    assert due_a != due_b and tg_a != tg_b
    assert sorted(tg_a) == sorted(tg_b)
    assert max(tg_a.count(g) for g in range(7)) - min(
        tg_a.count(g) for g in range(7)) <= 1
    # (the last gap runs to the window's close)
    gaps = lambda d: sorted(round(y - x, 7) for x, y in zip(d, d[1:] + [3.0]))
    assert gaps(due_a) == gaps(due_b)
    assert all(0 <= t < 3.0 for t in due_a)
    first = generator.closed_targets({"target": {"dist": "uniform"}}, 5, 9)
    assert sorted(next(first) for _ in range(5)) == list(range(5))
    with pytest.raises(ValueError, match="unknown target distribution"):
        generator.open_schedule(dict(traffic, target={"dist": "zipf"}), 7,
                                3.0, seed=1)


# --------------------------------------------------------------------- stats

def test_percentile_and_the_missing_request_rule():
    v = [float(x) for x in range(1, 101)]
    assert stats.percentile(v, 0.50) == 50.0
    assert stats.percentile(v, 0.99) == 99.0
    assert stats.percentile(v, 1.0) == 100.0
    # one missing request among 100 sits at the top: p99 is the last answer
    assert stats.percentile(v[:99], 0.99, missing=1) == 99.0
    assert stats.percentile(v[:98], 0.99, missing=2) == float("inf")
    assert stats.percentile(v[:98], 0.50, missing=2) == 50.0
    with pytest.raises(ValueError):
        stats.percentile([], 0.5)
    s = stats.window_summary([10.0, 20.0, 30.0, 40.0], 0, 3, 2.0)
    assert s == {"commits_per_s": 1.5, "commit_p50_ms": 20.0,
                 "commit_p75_ms": 30.0, "commit_p90_ms": 40.0,
                 "commit_p95_ms": 40.0, "commit_p99_ms": 40.0}
    assert stats.spread([100.0, 101.0, 102.0, 103.0, 104.0, 105.0]) == \
        pytest.approx(3.5 / 102.5)
    assert stats.spread([1.0]) is None


# --------------------------------------------------------------------- trace

def test_trace_reduce_on_a_recorded_chip_trace():
    path = os.path.join(ROOT, "benchmarks", "testdata",
                        "ratis-3x1k.write-open.3s.xplane.pb")
    parsed = trace_reduce.load(path)
    assert list(parsed["devices"]) == ["/device:TPU:0"]
    window = [(s, e) for n, s, e in parsed["host_spans"]
              if n == trace_reduce.WINDOW_SPAN]
    assert len(window) == 1
    r = trace_reduce.reduce(parsed, window[0])
    assert r["busy_s"] == pytest.approx(0.000670082, rel=1e-6)
    assert r["window_s"] == pytest.approx(2.947553136, rel=1e-6)
    assert r["module_count"] == {"_unknown": 9, peaks.FAST_STEP: 3}
    assert 0 < r["module_time_s"][peaks.FAST_STEP] < r["busy_s"]
    assert len(r["device_ops"]) == 10
    assert r["device_ops"][0][0] == "engine_step_resident_fast/%fusion.7"
    idle = dict(r["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"],
                                               rel=1e-6)
    assert 0 < idle["host inside bench:engine_dispatch"] < 0.1
    # the readers that live on it
    ctx = {"trace": r, "device": {"kind": "TPU v5 lite"},
           "config": {"engine": {"max_groups": 1024, "max_peers": 8}}}
    idle_pct = bench_run.load_reader("device_idle_pct")(ctx)
    assert idle_pct == pytest.approx(99.97726649973444)
    roof = bench_run.load_reader("engine_step_roofline")(ctx)
    assert 0 < roof < 1.0
    assert bench_run.load_reader("device_idle_pct")({"trace": None}) is None
    assert bench_run.load_reader("engine_step_roofline")(
        dict(ctx, trace=dict(r, module_count={}, module_time_s={}))) is None


def test_union_and_gaps():
    assert trace_reduce.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert trace_reduce.union_ns([]) == 0
    assert trace_reduce._gaps([(5, 10), (8, 12), (20, 25)], 0, 30) == \
        [(0, 5), (12, 20), (25, 30)]
    assert trace_reduce.module_base_name(
        "jit_engine_step_resident_fast(1409)") == peaks.FAST_STEP
    assert trace_reduce.reduce({"devices": {}, "host_spans": []}) is None


# --------------------------------------------------------------------- peaks

def test_bytes_of_a_dispatch_from_shapes_alone():
    # 2 int32 [G,P] + 3 bool [G,P] + int8 [G] + 4 int32 [G] at 16,384 x 8
    assert peaks.device_state_bytes(16384, 8) == 1_720_320
    assert peaks.device_state_bytes(1024, 8) == 107_520
    assert peaks.fast_step_bytes(16384, 8) == (
        1_720_320 + 1_048_576 + 196_608 + 7 * 64 * 4 + 4 * 16384 * 4)
    assert peaks.refresh_step_bytes(1024, 8) > peaks.fast_step_bytes(1024, 8) \
        - 4 * 1024 * 4


def test_an_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks for device kind"):
        peaks.peaks_for("TPU v9 imaginary")


# ----------------------------------------------------------------- reference

def test_the_reference_reads_a_segment_file_by_itself(tmp_path):
    def record(payload):
        return struct.pack("<II", len(payload), zlib.crc32(payload)) + payload
    d = tmp_path / "current"
    d.mkdir()
    good = ref.SEGMENT_MAGIC + record(b"conf") + record(b"..INCREMENT..") \
        + record(b"xINCREMENT")
    (d / "log_inprogress_0").write_bytes(good + b"\x05\x00\x00\x00torn")
    (d / "raft-meta").write_bytes(b"INCREMENT")          # not a segment
    assert ref.durable_writes(str(d), b"INCREMENT") == 2
    assert ref.durable_writes(str(tmp_path / "absent"), b"INCREMENT") == 0
    (d / "log_0-9").write_bytes(b"garbage")
    assert ref.durable_writes(str(d), b"INCREMENT") == 2
    counters = ref.CounterReference(3)
    assert [counters.apply(g, "INCREMENT") for g in (0, 0, 2)] == \
        [b"1", b"2", b"1"]
    with pytest.raises(ValueError, match="no semantics"):
        counters.apply(1, "GET")
    assert ref.majority_min([5, 3, 9, 0, 0], [True, True, True, False,
                                              False]) == 5
    assert ref.leader_commit([7, 4, 2], 0, 6, [True, True, True]) == 4


def test_the_comparison_catches_wrong_and_missing_answers():
    def part(groups, answers):
        return {"group": groups, "payload": ["INCREMENT"] * len(groups),
                "answer": answers}
    warm = part([0, 1], ["1", "1"])
    a = ref.judge_answers(2, [warm, part([0, 0, 1], ["2", "3", "2"])])
    assert (a["answers_wrong"], a["never_answered"]) == (0, 0)
    assert a["acked_per_group"] == [3, 2]
    swapped = ref.judge_answers(2, [warm, part([0, 0, 1], ["3", "2", "2"])])
    assert swapped["answers_wrong"] == 2
    lost = ref.judge_answers(2, [warm, part([0, 0, 1], [None, "3", "2"])])
    assert (lost["answers_wrong"], lost["never_answered"]) == (0, 1)
    assert compare.replicas_short(ref, [[3, 3, 2], [2, 1, 1]], [3, 2], [3, 2],
                                  2, [0, 0]) == [1]
    # the settle round's write is applied by a follower only when something
    # comes behind it: one short of the count still holds, two short does not
    assert compare.replicas_short(ref, [[3, 2, 2], [2, 0, 0]], [3, 2], [3, 2],
                                  2, [1, 1]) == [1]
    assert compare.verdict({"x": (0, 0), "y": (1, 0)})[0] is False
