"""Named fault-injection points compiled into production code paths.

Capability parity with the reference's CodeInjectionForTesting
(ratis-common/src/main/java/org/apache/ratis/util/CodeInjectionForTesting.java:29-60):
production code calls ``execute(point, local_id, *args)`` at named points;
tests register sync or async callbacks to block/delay/fail those points.
No-op (one dict lookup) when nothing is registered.
"""

from __future__ import annotations

import asyncio
import inspect
from typing import Any, Callable, Optional

# Well-known injection point names (mirroring the reference's usage sites).
APPEND_TRANSACTION = "append_transaction"       # RaftServerImpl.java:822
LOG_SYNC = "log_sync"                           # RaftServerImpl.java:1620
RUN_LOG_WORKER = "run_log_worker"               # SegmentedRaftLogWorker.java:70
REQUEST_VOTE = "request_vote"
APPEND_ENTRIES = "append_entries"
INSTALL_SNAPSHOT = "install_snapshot"

_injections: dict[str, Callable[..., Any]] = {}


def put(point: str, code: Callable[..., Any]) -> None:
    _injections[point] = code


def remove(point: str) -> None:
    _injections.pop(point, None)


def clear() -> None:
    _injections.clear()


def is_registered(point: str) -> bool:
    return point in _injections


async def execute(point: str, local_id: Any = None, remote_id: Any = None,
                  *args: Any) -> bool:
    """Run the injected code if any; returns True iff an injection ran.
    Sync and async callbacks are both supported."""
    code = _injections.get(point)
    if code is None:
        return False
    result = code(local_id, remote_id, *args)
    if inspect.isawaitable(result):
        await result
    return True


def execute_from_thread(loop, point: str, local_id: Any = None,
                        remote_id: Any = None, *args: Any) -> bool:
    """``execute`` for code on a thread of its own (the log worker): a sync
    callback runs on that thread, an async one on ``loop``, and the thread
    waits for it."""
    code = _injections.get(point)
    if code is None:
        return False
    result = code(local_id, remote_id, *args)
    if inspect.isawaitable(result):
        async def waited():
            await result
        asyncio.run_coroutine_threadsafe(waited(), loop).result()
    return True


def execute_sync(point: str, local_id: Any = None, remote_id: Any = None,
                 *args: Any) -> bool:
    code = _injections.get(point)
    if code is None:
        return False
    code(local_id, remote_id, *args)
    return True
