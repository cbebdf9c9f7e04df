"""The comparison that decides ``correct``: what the timed window itself
produced (every answer, every replica's state machine, the durable logs, the
device state after a drained dispatch) against the plain reference
(``benchmarks/reference/<name>.py``, named by the configuration or, where
the traffic's operations need another, by the traffic file).  The reference
judges the answers itself (``judge_answers``); what is here asks it the
rest.  Every comparison is exact, so every limit is 0."""

from __future__ import annotations

import importlib
from typing import Optional, Sequence


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


def check_device(ref, snapshots: Sequence[dict], leader_server: Sequence[int],
                 leader_slot: Sequence[int],
                 commit_baseline: Optional[Sequence[int]],
                 acked_since_baseline: Sequence[int],
                 term_unchanged: Sequence[bool]) -> dict:
    """The device state after a drained dispatch, three ways:

    - ``device_rows_differing``: active rows where the device arrays differ
      from the host mirror (the program's two implementations of one step);
    - ``device_quorum_rows_wrong``: leader rows whose device commit index is
      not what the reference's commit rule gives from the device's own match
      and flush indexes;
    - ``device_commit_advance_wrong``: groups whose leader row's commit
      index is not the baseline (where the leader's log ended before the
      window) plus exactly the writes acknowledged since (groups whose term
      moved since then carry an extra entry and are skipped)."""
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
    commits = []
    for g, (srv, slot) in enumerate(zip(leader_server, leader_slot)):
        dev = snapshots[srv]["device"]
        commit = int(dev["commit_index"][slot])
        commits.append(commit)
        self_slot = int(np.argmax(dev["self_mask"][slot]))
        rule = ref.leader_commit(dev["match_index"][slot].tolist(), self_slot,
                                 int(dev["flush_index"][slot]),
                                 dev["conf_cur"][slot].tolist())
        if rule != commit:
            quorum_wrong += 1
        if commit_baseline is not None:
            if not term_unchanged[g]:
                skipped += 1
            elif commit - commit_baseline[g] != acked_since_baseline[g]:
                advance_wrong += 1
    return {"device_rows_differing": differing,
            "device_quorum_rows_wrong": quorum_wrong,
            "device_commit_advance_wrong": advance_wrong,
            "device_commit_skipped": skipped,
            "device_rows_compared": sum(int(s["active"].size)
                                        for s in snapshots),
            "leader_commit_index": commits}


def verdict(numbers: dict) -> tuple[bool, dict]:
    """``numbers`` maps a compared number's name to (value, limit); correct
    only if every value is within its limit."""
    compared = {k: {"value": v, "limit": lim}
                for k, (v, lim) in numbers.items()}
    return all(v <= lim for v, lim in numbers.values()), compared
