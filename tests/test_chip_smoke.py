"""chip_smoke.py on the CPU, and the failures it exists to catch.

The smoke's real run is on the chip (through the chip tool, one process).
Here: it refuses to run without an accelerator, its explicit tiny rehearsal
(5 peers x 64 groups, 4 virtual devices) passes every check including the
host/device agreement and zero-compiles-after-prewarm, and an engine whose
tick raises — or a ledger pass that raises — surfaces on the server's health
at once instead of leaving a server that serves and reports healthy.
"""

import asyncio
import json
import logging
import os
import shutil
import subprocess
import sys

from minicluster import MiniCluster, batched_properties, run_with_new_cluster

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run_smoke(*args, cwd=REPO, timeout=600):
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "PYTHONPATH")}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run([sys.executable, os.path.join(cwd, "chip_smoke.py"),
                           *args], cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=timeout)


def _result_lines(stdout: str) -> list:
    return [ln for ln in stdout.splitlines()
            if ln.startswith(("{", "RESULT "))]


def test_refuses_to_run_without_an_accelerator():
    proc = _run_smoke()
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr, proc.stderr[-2000:]
    # nothing was built and no result was printed
    assert proc.stdout == "", proc.stdout[-2000:]


def test_fails_alone_without_the_program(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    proc = _run_smoke("--rehearse-cpu", cwd=str(tmp_path))
    assert proc.returncode != 0
    assert "ratis_tpu" in proc.stderr, proc.stderr[-2000:]
    assert not _result_lines(proc.stdout)


def test_explicit_cpu_rehearsal_passes_every_check(tmp_path):
    out = tmp_path / "smoke.json"
    proc = _run_smoke("--rehearse-cpu", "--out", str(out))
    assert proc.returncode == 0, (proc.stdout[-3000:], proc.stderr[-3000:])
    lines = proc.stdout.splitlines()
    # the last line is the verdict alone, to the key: the device as JAX
    # reports it; everything that was seen is on the RESULT line before it
    verdict = json.loads(lines[-1])
    assert verdict == {"ok": True, "device": {
        "platform": "cpu", "kind": verdict["device"]["kind"], "count": 4}}
    assert isinstance(verdict["device"]["kind"], str)
    assert lines[-2].startswith("RESULT ")
    last = json.loads(lines[-2][len("RESULT "):])
    assert last == json.loads(out.read_text())
    assert last["ok"] is True and last["device"] == verdict["device"]
    assert last["rehearsal"] == "cpu" and last["device"]["platform"] == "cpu"
    for phase, mesh in (("served", 0), ("mesh", 4)):
        ph = last[phase]
        assert (ph["peers"], ph["groups"], ph["transport"]) == (5, 64, "tcp")
        assert ph["mesh_devices"] == mesh
        assert ph["compiles_after_prewarm"] == 0
        assert ph["writes"]["acknowledged"] == 64
        assert ph["voted_leaders"] == 32
        assert sum(ph["leaders_per_server"]) == 64
        assert len(ph["engines"]) == 5
        for eng in ph["engines"]:
            agree = eng["agreement"]
            assert agree["active_slots"] == 64
            assert [v for k, v in agree.items()
                    if k.endswith("_rows_differing")] == [0, 0, 0, 0]
            c = eng["counters_since_prewarm"]
            assert min(c["batched_dispatches"], c["fast_ticks"],
                       c["refresh_ticks"]) > 0
            assert eng["health"] == "ok"
            if mesh:
                assert eng["mesh_shard_devices"] == 4
    # the full resident batch even at the rehearsal's 64 groups
    assert last["served"]["engine_state_shape"] == [16384, 8]
    assert last["served"]["properties"]["raft.server.read.option"] \
        == "LINEARIZABLE"


def test_tick_that_raises_surfaces_on_health_at_once(caplog):
    """Commits advance inline at ack intake, so a server whose tick loop
    died keeps acknowledging writes: the death must be logged with its
    traceback when it happens and be on /health before the freshness bound
    (2 s) could notice."""

    async def body(cluster: MiniCluster):
        leader = await cluster.wait_for_leader()
        assert (await cluster.send_write()).success
        server = cluster.servers[leader.member_id.peer_id]
        engine = server.engine
        assert engine.tick_alive and engine.failure is None
        assert server.health_info()["status"] == "ok"

        def refused(acks, now):
            raise RuntimeError("program refused by the compiler (injected)")

        engine._tick_batched_dispatch = refused
        engine.state.mark_dirty(leader.engine_slot)
        engine.notify()
        loop = asyncio.get_running_loop()
        t0 = loop.time()
        while engine.failure is None and loop.time() - t0 < 2.0:
            await asyncio.sleep(0.005)
        seen_after = loop.time() - t0
        assert isinstance(engine.failure, RuntimeError), \
            "tick loop death never surfaced"
        assert not engine.tick_alive
        health = server.health_info()
        assert health["status"] == "degraded", health
        assert "injected" in health["engine"]["failure"]
        assert seen_after < health["engine"]["freshBoundS"]
        # the condition this guards against: the server still serves
        assert (await cluster.send_write()).success
        assert server.health_info()["status"] == "degraded"

    with caplog.at_level(logging.ERROR, logger="ratis_tpu.engine.engine"):
        run_with_new_cluster(3, body, properties=batched_properties())
    died = [r for r in caplog.records if "tick loop died" in r.getMessage()]
    assert len(died) == 1 and died[0].exc_info is not None


def test_ledger_pass_that_raises_surfaces_on_health(monkeypatch):
    async def body(cluster: MiniCluster):
        import ratis_tpu.engine.ledger as ledger_mod
        leader = await cluster.wait_for_leader()
        server = cluster.servers[leader.member_id.peer_id]
        ledger = server.engine.ledger
        ledger.sample()
        assert ledger.failure is None

        def broken(width, mesh=None):
            def run(*_a):
                raise RuntimeError("ledger pass failed on device (injected)")
            return run

        with monkeypatch.context() as m:
            m.setattr(ledger_mod, "_jitted_pass", broken)
            try:
                ledger.sample()
            except RuntimeError:
                pass
            else:
                raise AssertionError("the failing pass did not raise")
            health = server.health_info()
            assert health["status"] == "degraded", health
            assert "ledger pass failed" in health["engine"]["failure"]
        ledger.sample()  # a pass that succeeds clears it
        assert server.health_info()["status"] == "ok"

    run_with_new_cluster(3, body, properties=batched_properties())
