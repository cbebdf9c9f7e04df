"""The per-layer metrics that read the program's own trace session
(ratis_tpu/trace): each reader on an empty and on a hand-made session, and
the CPU rehearsal of each cell with and without ``--trace 1``.  Run with
``python -m pytest benchmarks/tests -q`` on the CPU."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks import idle_by_span
from benchmarks import run as bench_run

CELLS = ("ratis-3x1k.write-open", "ratis-3x1k.write-closed")
SESSION_METRICS = {
    "loop_busy_pct": "server edge and host event loop",
    "ingress_to_append_ms": "server edge and host event loop",
    "apply_to_socket_ms": "server edge and host event loop",
    "log_fsync_ms": "log",
    "log_flush_wait_ms": "log",
    "append_rtt_ms": "consensus",
    "quorum_to_apply_ms": "consensus",
    "engine_pack_ms": "engine host half",
    "engine_fetch_ms": "engine host half",
    "wire_frames_per_commit": "wire",
    "wire_bytes_per_commit": "wire",
}
ENGINE = {"engine_pack_ms", "engine_fetch_ms"}


def test_the_eleven_are_there_in_order_and_none_reads_the_device_trace():
    m = bench_run.load_manifest()
    tail = [x for x in m["per_layer"] if x["name"] in SESSION_METRICS]
    assert [x["name"] for x in tail] == list(SESSION_METRICS)
    for x in tail:
        assert x["layer"] == SESSION_METRICS[x["name"]]
        assert x["source"] in ("program_span", "program_counter")
        assert "workloads" not in x          # every cell reports them
        assert callable(bench_run.load_reader(x["name"]))


def rehearse(cell, trace, tmp_path):
    rate = ("--rate", "60") if cell == CELLS[0] else ()
    keep = str(tmp_path / "kept.xplane.pb")
    # through the builder's script, which runs benchmarks/run.py unchanged
    # and keeps the xplane the run would delete
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "idle_by_span.py"),
         "--keep", keep, "--", "--workload", cell, "--seed", "2147484001",
         "--seconds", "3", "--rehearse-cpu", "--groups", "16", *rate,
         "--trace", trace], cwd=ROOT, env=dict(os.environ,
                                               JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=180)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    return json.loads(lines[-1]), lines, keep


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_rehearsal_prints_the_session_metrics(cell, tmp_path):
    result, lines, keep = rehearse(cell, "1", tmp_path)
    assert result["correct"] is True
    got = {k: v["value"] for k, v in result["metrics"].items()
           if k in SESSION_METRICS}
    assert set(SESSION_METRICS) - ENGINE <= set(got), sorted(got)
    assert all(v > 0 for v in got.values()), got
    assert got["loop_busy_pct"] <= 100
    if ENGINE <= set(got) and "engine_dispatch_ms" in result["metrics"]:
        # (the timer's mean runs over the window, the rows over the session)
        assert got["engine_pack_ms"] + got["engine_fetch_ms"] <= \
            1.5 * result["metrics"]["engine_dispatch_ms"]["value"]
    # the kept xplane holds the program's spans on the profiler's clock,
    # and the builder's table splits the (here: whole) window by them
    table = [json.loads(line[len("IDLE_BY_SPAN "):]) for line in lines
             if line.startswith("IDLE_BY_SPAN ")][0]
    assert table["clock_marks"] == 1 and os.path.getsize(keep) > 0
    labels = table["idle_by_label_s"]
    assert "ratis:wire.flush" in labels and "ratis:tcp.read" in labels
    if cell == CELLS[0]:    # (64 callers on 16 groups leave the loop no wait)
        assert "ratis:loop.select" in labels
    assert table["spans_on_other_threads_s"]["ratis:log.fsync"] > 0
    assert sum(labels.values()) == pytest.approx(table["device_idle_s"],
                                                 rel=1e-6)
    # (coroutine steps outside the work spans lie between spans: PERF.md §5)
    assert table["under_select_or_named_span_pct"] > 20


@pytest.mark.parametrize("cell", CELLS)
def test_an_untraced_rehearsal_prints_none_of_them(cell, tmp_path):
    result, lines, keep = rehearse(cell, "0", tmp_path)
    assert result["correct"] is True
    assert not set(result["metrics"]) & set(SESSION_METRICS)
    assert not os.path.exists(keep)
    assert not any(line.startswith("IDLE_BY_SPAN") for line in lines)


# ------------------------------------------------------------- the readers

class FakeProfiler:
    """Stands where ``jax.profiler.TraceAnnotation`` does: switched on and
    off by hand, so ``Tracer.poll`` opens and closes a session as it does
    around a real profiler session."""
    on = False

    def __init__(self, *a, **kw):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    @classmethod
    def is_enabled(cls):
        return cls.on


@pytest.fixture
def tracer():
    from ratis_tpu.trace import TRACER
    TRACER.configure(enabled=False)
    TRACER.reset()
    TRACER._annotation = FakeProfiler
    yield TRACER
    FakeProfiler.on = False
    TRACER.poll()
    TRACER._annotation = None
    TRACER.configure(enabled=False)
    TRACER.reset()


CTX = {"acked_in_window": 10}


def test_every_reader_returns_none_on_an_empty_session(tracer):
    for name in SESSION_METRICS:
        assert bench_run.load_reader(name)(CTX) is None, name
    # ... and on a session in which nothing happened
    FakeProfiler.on = True
    tracer.poll()
    FakeProfiler.on = False
    tracer.poll()
    assert tracer.session()["t_off"] > 0
    for name in set(SESSION_METRICS) - {"loop_busy_pct"}:
        assert bench_run.load_reader(name)(CTX) is None, name


def test_every_reader_on_a_hand_made_session(tracer):
    from ratis_tpu.trace import STAGE_NAMES
    loop_a = (tracer.counter("loop.select_ns", "loop-a"),
              tracer.counter("loop.iterations", "loop-a"))
    loop_b = (tracer.counter("loop.select_ns", "loop-b"),
              tracer.counter("loop.iterations", "loop-b"))
    frames, nbytes = (tracer.counter("wire.frames", "loop-a"),
                      tracer.counter("wire.bytes", "loop-a"))
    frames.n += 1000                    # before the session: not counted
    tracer.sample_every = 1
    FakeProfiler.on = True
    tracer.poll()
    t_on = tracer.session()["t_on"]

    def row(name, tid, start_ms, dur_ms, tag=0):
        t0 = t_on + int(start_ms * 1e6)
        tracer.record(tid, STAGE_NAMES.index(name), t0,
                      t0 + int(dur_ms * 1e6), tag)

    for tid, scale in ((1, 1.0), (2, 2.0), (3, 3.0)):
        row("server.route", tid, 1, 0.1 * scale)
        row("server.txn_start", tid, 2, 0.2 * scale)
        row("server.append", tid, 3, 0.3 * scale)
        row("server.apply", tid, 9, 0.5 * scale)
        row("server.reply", tid, 10, 0.25 * scale)
        row("server.respond", tid, 11, 0.25 * scale)
        row("server.flush_wait", tid, 4, 5 * scale)
        row("server.apply_queue", tid, 8, 1 * scale)
        row("replicate.rtt", 0, 4, 7 * scale, tag=77)
    row("server.route", 4, 1, 9.0)      # a request that never appended
    row("log.fsync", 0, 5, 6.0, tag=3)  # three files: 2 ms each
    row("log.fsync", 0, 12, 2.0, tag=1)
    for k in range(2):
        row("engine.dispatch", 0, 20 + 10 * k, 4.0)
        row("engine.pack", 0, 20 + 10 * k, 0.5)
        row("engine.launch", 0, 20.5 + 10 * k, 1.0)
        row("engine.fetch", 0, 21.5 + 10 * k, 2.0)
    frames.n += 70
    nbytes.n += 9000
    FakeProfiler.on = False
    import time
    time.sleep(0.06)                    # (the rows above reach 32 ms in)
    tracer.poll()
    sess = tracer.session()
    length = sess["t_off"] - sess["t_on"]
    loop_a[0].n += 0                   # (closed: later adds do not count)
    # the loops' counters are made by hand to the session's length
    tracer._session["keyed"]["loop.select_ns"] = {"loop-a": length // 4,
                                                  "loop-b": length // 2,
                                                  "idle": 0}
    tracer._session["keyed"]["loop.iterations"] = {"loop-a": 9, "loop-b": 9,
                                                   "idle": 0}
    read = {n: bench_run.load_reader(n)(CTX) for n in SESSION_METRICS}
    assert read["loop_busy_pct"] == pytest.approx(75.0, abs=0.01)
    assert read["ingress_to_append_ms"] == pytest.approx(1.2)   # p50 of 3
    assert read["apply_to_socket_ms"] == pytest.approx(2.0)
    assert read["log_fsync_ms"] == pytest.approx(2.0)
    assert read["log_flush_wait_ms"] == pytest.approx(10.0)
    assert read["append_rtt_ms"] == pytest.approx(14.0)
    assert read["quorum_to_apply_ms"] == pytest.approx(3.0)     # p75 of 3
    assert read["engine_pack_ms"] == pytest.approx(1.5)
    assert read["engine_fetch_ms"] == pytest.approx(2.0)
    assert read["wire_frames_per_commit"] == pytest.approx(7.0)
    assert read["wire_bytes_per_commit"] == pytest.approx(900.0)
    # a row from before the session is not the session's
    row("replicate.rtt", 0, -50, 1000.0)
    assert bench_run.load_reader("append_rtt_ms")(CTX) == pytest.approx(14.0)


# ------------------------------------------------------ the builder's table

def test_idle_is_split_by_the_innermost_span():
    spans = [("ratis:loop.select", 0, 40), ("ratis:engine.dispatch", 50, 90),
             ("ratis:engine.pack", 50, 60), ("ratis:engine.fetch", 70, 90)]
    segs = idle_by_span.leaf_segments(spans, 0, 100)
    assert segs == [(0, 40, "ratis:loop.select"),
                    (40, 50, idle_by_span.BETWEEN),
                    (50, 60, "ratis:engine.pack"),
                    (60, 70, "ratis:engine.dispatch"),
                    (70, 90, "ratis:engine.fetch"),
                    (90, 100, idle_by_span.BETWEEN)]
    table = idle_by_span.split_idle({
        "device": [(75, 85)], "window": (0, 100), "clock_marks": 1,
        "threads": [[("ratis:log.fsync", 10, 30)], spans]})
    assert table["device_idle_s"] == pytest.approx(90e-9)
    assert table["idle_by_label_s"]["ratis:engine.fetch"] == \
        pytest.approx(10e-9)
    assert table["idle_by_label_s"]["ratis:loop.select"] == \
        pytest.approx(40e-9)
    assert table["under_select_or_named_span_pct"] == \
        pytest.approx(100 * 70 / 90)
    assert table["spans_on_other_threads_s"] == {"ratis:log.fsync": 20e-9}
    # the loop is the thread that holds ratis:loop.select, also where a log
    # worker's thread spent longer inside spans (slow fsyncs)
    slow = idle_by_span.split_idle({
        "device": [(75, 85)], "window": (0, 100), "clock_marks": 1,
        "threads": [[("ratis:log.fsync", 0, 99)], spans]})
    assert slow["idle_by_label_s"] == table["idle_by_label_s"]
    assert slow["spans_on_other_threads_s"] == {"ratis:log.fsync": 99e-9}
    assert "error" in idle_by_span.split_idle(
        {"device": [], "window": None, "threads": [], "clock_marks": 0})
