"""Test environment: the CPU platform with 8 virtual devices (the fleet the
``mesh``-marked tests shard over), pinned before anything initialises a JAX
backend.  Tier-1 runs here on the CPU; the chip is reached only through
``chip_smoke.py`` and the measurement entries, never from pytest."""

import os

from ratis_tpu.util.jaxenv import pin_cpu

pin_cpu(virtual_devices=8)

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "mp: spawns a real multi-process cluster (slower)")
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 `-m 'not slow'` run")
    config.addinivalue_line(
        "markers", "chaos: deterministic fault-scenario gate "
                   "(ratis_tpu.chaos); fast scenarios run in tier-1, the "
                   "long campaign also carries `slow`")
    config.addinivalue_line(
        "markers", "mesh: needs the multi-(virtual-)device fleet "
                   "(XLA_FLAGS --xla_force_host_platform_device_count=8, "
                   "set in-process above); tier-1 — mesh-vs-single-device "
                   "bit-identity is a correctness gate, not a perf rung")


def pytest_collection_modifyitems(config, items):
    """`mesh` tests assert their device fleet up front: if the in-process
    XLA flag was lost (stale interpreter, ambient override), fail loudly
    at the marked tests instead of skipping the bit-identity gate."""
    if not any(item.get_closest_marker("mesh") for item in items):
        return
    flags = os.environ.get("XLA_FLAGS", "")
    assert "xla_force_host_platform_device_count" in flags, \
        "mesh marker requires the conftest-set XLA_FLAGS device fleet"


@pytest.fixture(autouse=True)
def _clear_injections():
    yield
    from ratis_tpu.chaos.link import link_faults
    from ratis_tpu.util import injection
    injection.clear()
    link_faults().heal_all()


# ------------------------------------------------------------ task hygiene
#
# The PeerSender/LogAppender inflight-task bookkeeping grows with the
# round-9 append windows: a leak there (a task created but never awaited,
# cancelled, or tracked through close()) would silently accumulate across
# a long-lived server.  Every test therefore asserts that cluster teardown
# left no lingering asyncio task behind: after ``asyncio.run`` returns,
# any task that is still pending on a CLOSED loop can never run again —
# a definite leak.  Tasks whose cancellation was at least REQUESTED
# (``cancel()`` called, loop gone before it could unwind) are tolerated:
# they were tracked and asked to die; the loop's death froze them.

_reported_leaks = None  # lazy WeakSet: a leak fails exactly one test


def _pending_leaked_tasks() -> list:
    import asyncio.tasks as _tasks
    global _reported_leaks
    if _reported_leaks is None:
        import weakref
        _reported_leaks = weakref.WeakSet()
    leaked = []
    # Python 3.12 keeps every live task in these two sets, whatever loop it
    # belongs to (asyncio.all_tasks() only filters them by loop — useless
    # here, the loops in question are closed)
    for t in [*_tasks._scheduled_tasks, *_tasks._eager_tasks]:
        try:
            if t.done() or not t.get_loop().is_closed():
                continue
            if getattr(t, "_must_cancel", False):
                continue  # cancel() was requested; the loop died first
            if t in _reported_leaks:
                continue  # already failed an earlier test for this task
        except Exception:
            continue
        _reported_leaks.add(t)
        leaked.append(t)
    return leaked


@pytest.fixture(autouse=True)
def _no_lingering_tasks():
    yield
    leaked = _pending_leaked_tasks()
    if leaked:
        names = []
        for t in leaked:
            try:
                names.append(t.get_coro().__qualname__)
            except Exception:
                names.append(repr(t))
        pytest.fail(
            f"{len(leaked)} asyncio task(s) leaked past cluster teardown "
            f"(pending on a closed loop, never cancelled): {names}")
