"""The comparison that decides ``correct``: what the timed window itself
produced (every answer, every replica's state machine, the durable logs, the
device state after a drained dispatch) against the plain reference
(``benchmarks/reference/<name>.py``, named by the configuration or, where
the traffic's operations need another, by the traffic file).  The reference
judges the answers itself (``judge_answers``); what is here asks it the
rest.  Every comparison is exact, so every limit is 0."""

from __future__ import annotations

import importlib
from typing import Sequence


def load_reference(name: str):
    return importlib.import_module(f"benchmarks.reference.{name}")


def replicas_short(ref, replica_values: Sequence[Sequence[int]],
                   acked: Sequence[int], submitted: Sequence[int],
                   need: int, unsettled: Sequence[int]) -> list[int]:
    """Groups in which fewer than ``need`` replicas hold the value the
    reference holds.  Where every submitted write was acknowledged that is
    the count, less at the most the group's ``unsettled`` last writes (those
    of the settle round: a follower applies an entry once it hears that it
    is committed, which the append behind it tells it, and nothing comes
    behind the last).  A group with unanswered writes may hold any value up
    to what was submitted."""
    short = []
    for g, values in enumerate(replica_values):
        top = acked[g] if acked[g] == submitted[g] else submitted[g]
        if ref.replicas_holding(values, acked[g] - unsettled[g],
                                top) < need:
            short.append(g)
    return short


def durable_short(ref, log_dirs: Sequence[Sequence[str]],
                  acked: Sequence[int], need: int, needle: bytes
                  ) -> list[int]:
    """Groups in which fewer than ``need`` replicas' segment files hold
    every acknowledged write."""
    short = []
    for g, dirs in enumerate(log_dirs):
        holding = sum(1 for d in dirs
                      if ref.durable_writes(d, needle) >= acked[g])
        if holding < need:
            short.append(g)
    return short


WINDOW_PART = 1     # of the run's parts: warm-up, window, settle


def window_entries(answers: dict, requests: dict, groups: int) -> list[int]:
    """Log entries the window added to each group.  The reference says, under
    ``entries_per_part`` of what its ``judge_answers`` returns (a read adds
    none, a request may add several); one that does not say holds every
    answered request to be one entry."""
    per_part = answers.get("entries_per_part")
    if per_part is not None:
        return list(per_part[WINDOW_PART])
    out = [0] * groups
    for g, answer in zip(requests["group"], requests["answer"]):
        out[g] += answer is not None
    return out


FAILING_SHOWN = 8   # groups a failing device comparison describes


def check_device(ref, snapshots: Sequence[dict], at_start: Sequence[dict],
                 at_close: Sequence[dict], appointees: Sequence[int],
                 acked_since_baseline: Sequence[int]) -> dict:
    """The device state after a drained dispatch, three ways:

    - ``device_rows_differing``: active rows where the device arrays differ
      from the host mirror (the program's two implementations of one step);
    - ``device_quorum_rows_wrong``: groups whose compared row's device commit
      index is not what the reference's commit rule gives from the device's
      own match and flush indexes;
    - ``device_commit_advance_wrong``: groups whose compared row's commit
      index is not the baseline (where that server's log ended before the
      window) plus exactly the writes acknowledged since.

    A group's compared row is that of the server that led it when the window
    opened (``at_start[g]``: ``server``, ``slot``, ``term``, ``role``,
    ``leads``, ``last_index``), not its appointee's: a leadership that an
    election moved during the warm-up is held where it is.  ``at_close[g]``
    is the same server's standing at the drained snapshot.  A group is
    skipped on both (``device_commit_skipped``) only where nobody led it at
    the start, or where that server no longer leads it in the same term: a
    new term carries an extra entry, and a new leader keeps the commit index
    it had while its followers' match indexes start again from nothing, so
    the commit rule holds for it only once it has committed in its term; a
    server that stepped down learns of the last commit only with the next
    append.  Up to ``FAILING_SHOWN`` groups that fail either of the last two
    are described under ``device_groups_failing``."""
    import numpy as np
    differing = 0
    for snap in snapshots:
        act = snap["active"]
        bad = np.zeros(act.size, bool)
        for f, d in snap["device"].items():
            h = snap["mirror"][f]
            bad |= np.any((d[act] != h[act]).reshape(act.size, -1), axis=1)
        differing += int(bad.sum())
    quorum_wrong = advance_wrong = skipped = 0
    failing = []
    for g, (start, close) in enumerate(zip(at_start, at_close)):
        if not (start["leads"] and close["leads"]
                and close["term"] == start["term"]):
            skipped += 1
            continue
        dev = snapshots[start["server"]]["device"]
        slot = start["slot"]
        commit = int(dev["commit_index"][slot])
        self_slot = int(np.argmax(dev["self_mask"][slot]))
        rule = ref.leader_commit(dev["match_index"][slot].tolist(), self_slot,
                                 int(dev["flush_index"][slot]),
                                 dev["conf_cur"][slot].tolist())
        quorum = rule != commit
        advance = commit - start["last_index"] != acked_since_baseline[g]
        quorum_wrong += quorum
        advance_wrong += advance
        if (quorum or advance) and len(failing) < FAILING_SHOWN:
            failing.append({
                "group": g, "appointee": appointees[g],
                "server": start["server"],
                "at_start": [start["role"], start["term"]],
                "at_close": [close["role"], close["term"]],
                "last_index_at_start": start["last_index"],
                "device_commit": commit, "quorum_rule": rule,
                "acked_in_window": acked_since_baseline[g]})
    return {"device_rows_differing": differing,
            "device_quorum_rows_wrong": quorum_wrong,
            "device_commit_advance_wrong": advance_wrong,
            "device_commit_skipped": skipped,
            "leaders_away_at_window_start": sum(
                1 for s, a in zip(at_start, appointees)
                if s["leads"] and s["server"] != a),
            "leaderless_at_window_start": sum(
                1 for s in at_start if not s["leads"]),
            "device_rows_compared": sum(int(s["active"].size)
                                        for s in snapshots),
            "device_groups_failing": failing}


def verdict(numbers: dict) -> tuple[bool, dict]:
    """``numbers`` maps a compared number's name to (value, limit); correct
    only if every value is within its limit."""
    compared = {k: {"value": v, "limit": lim}
                for k, (v, lim) in numbers.items()}
    return all(v <= lim for v, lim in numbers.values()), compared
