"""The loop's busy time by layer (ratis_tpu/trace/tracer.py: LoopClock): while
a trace session is open, a timed loop charges every callback and every work
span to exactly one layer; outside a session the loop runs untouched."""

import asyncio
import collections
import importlib.util
import os
import pkgutil
import sys
import threading
import time

import pytest

import ratis_tpu
from minicluster import MiniCluster, batched_properties, run_with_new_cluster
from ratis_tpu.trace.tracer import (LAYER_CONSENSUS, LAYER_NAMES, LAYER_OTHER,
                                    LAYER_SM, STAGE_LAYERS, STAGE_NAMES,
                                    STAGE_SWEEP, TRACER, instrument_loop,
                                    loop_clock, loop_key, module_layer)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _tracer_sandbox():
    yield
    TRACER.configure(enabled=False)


def _burn(ms: float) -> None:
    end = time.perf_counter() + ms / 1000
    while time.perf_counter() < end:
        pass


def _layer_ns(sess: dict, key: str) -> dict:
    keyed = sess["keyed"].get("loop.layer_ns", {})
    return {layer: keyed.get(f"{key}/{layer}", 0) for layer in LAYER_NAMES}


def _reader(name: str):
    path = os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


READERS = [f"loop_{layer}_ms_per_commit" for layer in LAYER_NAMES]

# milliseconds burnt a round: the wire callback before and after the handler
# it starts, the handler's eager first step and its later one, and a
# callback of a state machine's module
WIRE_MS, HANDLER_MS, SM_MS = (8, 6), (10, 12), 15
ROUNDS = 4


async def _synthetic() -> tuple[dict, str]:
    """A wire callback that eagerly starts a handler dispatched to consensus
    inside it (as transport/tcp.py:_Accepted._frame does), and a state
    machine's callback, ROUNDS times under one session: the session and the
    loop's key."""
    loop = asyncio.get_running_loop()
    instrument_loop(loop)
    done = []

    async def handler():
        TRACER.dispatch(LAYER_CONSENSUS)
        _burn(HANDLER_MS[0])
        await asyncio.sleep(0)
        _burn(HANDLER_MS[1])
        done.append(1)

    def on_read():
        _burn(WIRE_MS[0])
        clock = loop_clock()
        layer = clock.cur if clock is not None else 0
        asyncio.Task(handler(), loop=loop, eager_start=True)
        if clock is not None:
            clock.switch(layer)
        _burn(WIRE_MS[1])

    def on_apply():
        _burn(SM_MS)

    on_read.__module__ = "ratis_tpu.transport.synthetic"
    on_apply.__module__ = "ratis_tpu.models.synthetic"
    await asyncio.sleep(0)
    TRACER.configure(enabled=True)
    for _ in range(ROUNDS):
        loop.call_soon(on_read)
        loop.call_soon(on_apply)
        await asyncio.sleep(0.004)
    deadline = time.monotonic() + 10
    while len(done) < ROUNDS and time.monotonic() < deadline:
        await asyncio.sleep(0.001)
    TRACER.configure(enabled=False)
    return TRACER.session(), loop_key(loop)


def test_each_layer_is_charged_what_its_callbacks_burnt():
    sess, key = asyncio.run(_synthetic())
    got = _layer_ns(sess, key)
    want = {"wire": sum(WIRE_MS), "consensus": sum(HANDLER_MS),
            "sm": SM_MS}
    for layer, ms in want.items():
        assert got[layer] / 1e6 == pytest.approx(ROUNDS * ms, rel=0.1), \
            (layer, got)


def test_layers_and_selector_add_up_to_the_session():
    sess, key = asyncio.run(_synthetic())
    length = sess["t_off"] - sess["t_on"]
    charged = sum(_layer_ns(sess, key).values())
    select = sess["keyed"]["loop.select_ns"][key]
    assert charged + select == pytest.approx(length, rel=0.01)
    # and the readers split the busy share the way loop_busy_pct reads it
    ctx = {"acked_in_window": 10}
    total = sum(_reader(name)(ctx) for name in READERS)
    busy_pct = _reader("loop_busy_pct")(ctx)
    assert total == pytest.approx(busy_pct / 100 * length / 1e6 / 10,
                                  rel=0.01)


def test_outside_a_session_the_loop_is_untouched():
    async def body():
        loop = asyncio.get_running_loop()
        instrument_loop(loop)
        key = loop_key(loop)
        moved = {k: c.n for k, c in TRACER._counters.items()
                 if k[0] == "loop.layer_ns"}
        for _ in range(20):
            loop.call_soon(_burn, 0.1)
            await asyncio.sleep(0)
        assert type(loop._ready) is collections.deque
        assert loop_clock() is None
        assert moved == {k: c.n for k, c in TRACER._counters.items()
                         if k[0] == "loop.layer_ns"}
        TRACER.configure(enabled=True)
        assert type(loop._ready) is not collections.deque
        await asyncio.sleep(0)
        TRACER.configure(enabled=False)
        for _ in range(3):      # the first selector wait after the close
            await asyncio.sleep(0.001)
        assert type(loop._ready) is collections.deque
        assert loop_clock() is None
        after = {k: c.n for k, c in TRACER._counters.items()
                 if k[0] == "loop.layer_ns" and k[1].startswith(key)}
        for _ in range(20):
            loop.call_soon(_burn, 0.1)
            await asyncio.sleep(0)
        assert after == {k: c.n for k, c in TRACER._counters.items()
                         if k[0] == "loop.layer_ns"
                         and k[1].startswith(key)}

    asyncio.run(body())


def test_no_callback_from_another_thread_is_lost_across_swaps():
    """Threads keep calling the loop back while sessions open and close
    (each swaps the loop's ready queue): every callback runs."""
    threads_n, per_thread = 12, 2000

    async def body():
        loop = asyncio.get_running_loop()
        instrument_loop(loop)
        got = []

        def feed():
            for _ in range(per_thread):
                loop.call_soon_threadsafe(got.append, 1)

        threads = [threading.Thread(target=feed) for _ in range(threads_n)]
        switch = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for t in threads:
                t.start()
            while any(t.is_alive() for t in threads):
                TRACER.configure(enabled=True)
                await asyncio.sleep(0)
                TRACER.configure(enabled=False)
                await asyncio.sleep(0)
            for t in threads:
                t.join(10)
                assert not t.is_alive()
        finally:
            sys.setswitchinterval(switch)
        deadline = time.monotonic() + 10
        while len(got) < threads_n * per_thread \
                and time.monotonic() < deadline:
            await asyncio.sleep(0.001)
        assert len(got) == threads_n * per_thread

    asyncio.run(body())


def test_a_tcp_cluster_names_most_of_its_loop(tmp_path):
    """A few hundred writes over real TCP to durable logs: the edge, the
    wire, consensus and the log each have loop time, and what no program
    code owns is under half of it."""
    out = {}

    async def body(cluster: MiniCluster):
        await cluster.wait_for_leader()
        client = cluster.new_client()
        try:
            api = client.async_api()
            for _ in range(4):
                assert (await api.send(b"INCREMENT")).success
            TRACER.configure(enabled=True, sample_every=16)
            for _ in range(10):
                replies = await asyncio.gather(
                    *(api.send(b"INCREMENT") for _ in range(30)))
                assert all(r.success for r in replies)
            TRACER.configure(enabled=False)
        finally:
            await client.close()
        out["sess"] = TRACER.session()
        out["key"] = loop_key()

    run_with_new_cluster(3, body, properties=batched_properties(),
                         rpc_type="TCP", storage_root=str(tmp_path))
    got = _layer_ns(out["sess"], out["key"])
    for layer in ("edge", "wire", "consensus", "log"):
        assert got[layer] > 0, got
    assert got["other"] < 0.5 * sum(got.values()), got


def test_the_readers_read_nothing_without_a_session():
    TRACER.configure(enabled=False)
    TRACER.reset()
    for name in READERS:
        assert _reader(name)({"acked_in_window": 100}) is None, name


def test_every_module_and_every_stage_has_a_layer():
    for info in pkgutil.walk_packages(ratis_tpu.__path__, "ratis_tpu."):
        assert module_layer(info.name) != LAYER_OTHER, info.name
    assert module_layer("asyncio.base_events") == LAYER_OTHER
    assert len(STAGE_LAYERS) == len(STAGE_NAMES)
    unowned = {n for n, layer in zip(STAGE_NAMES, STAGE_LAYERS)
               if layer is None}
    assert unowned == {"loop.select"}
    assert set(STAGE_LAYERS) - {None} <= set(LAYER_NAMES)


async def _session_of(body) -> tuple[dict, str]:
    """Run ``body`` in a task on an instrumented loop under one session:
    the session and the loop's key."""
    loop = asyncio.get_running_loop()
    instrument_loop(loop)
    await asyncio.sleep(0)
    TRACER.configure(enabled=True)
    await asyncio.sleep(0.001)
    await loop.create_task(body())
    TRACER.configure(enabled=False)
    return TRACER.session(), loop_key(loop)


def test_a_work_span_charges_its_stages_layer():
    """A work span inside a state machine's callback: its length is its
    stage's layer's (replicate.sweep: consensus), the rest the callback's."""
    async def body():
        def on_apply():
            _burn(12)
            span = TRACER.begin(STAGE_SWEEP, always=True)
            _burn(18)
            TRACER.end(span)
            _burn(8)

        on_apply.__module__ = "ratis_tpu.models.synthetic"
        for _ in range(ROUNDS):
            asyncio.get_running_loop().call_soon(on_apply)
            await asyncio.sleep(0.002)

    sess, key = asyncio.run(_session_of(body))
    got = _layer_ns(sess, key)
    assert got["consensus"] / 1e6 == pytest.approx(ROUNDS * 18, rel=0.1), got
    assert got["sm"] / 1e6 == pytest.approx(ROUNDS * 20, rel=0.1), got


def test_a_state_machine_call_is_charged_to_sm_up_to_its_suspension():
    """Tracer.enter_layer / leave_layer around an awaited state machine
    call in a task dispatched to consensus (as division.py's apply does):
    the call's time is sm's up to its first suspension; after it the task
    is its dispatch's layer again."""
    async def body():
        TRACER.dispatch(LAYER_CONSENSUS)
        for _ in range(ROUNDS):
            _burn(10)
            entered = TRACER.enter_layer(LAYER_SM)
            assert entered is not None
            _burn(14)
            await asyncio.sleep(0)
            _burn(6)
            TRACER.leave_layer(entered)
            _burn(8)

    sess, key = asyncio.run(_session_of(body))
    got = _layer_ns(sess, key)
    assert got["sm"] / 1e6 == pytest.approx(ROUNDS * 14, rel=0.1), got
    assert got["consensus"] / 1e6 == pytest.approx(ROUNDS * 24, rel=0.1), got
