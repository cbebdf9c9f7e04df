"""Tests of the device check (``compare.check_device``) on synthetic
snapshots, with no cluster: a group is held on the row of the server that
led it when the window opened, not on its appointee's; the check is not
loosened by that; a group whose leader moved during the window is skipped
and counted; a failing group says why.  Then the planted ``leader-moved``
fault, rehearsed on the CPU.  Run with ``python -m pytest benchmarks/tests
-q``; nothing here touches the TPU library."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import compare
from benchmarks.reference import counter as ref

PEERS = 3
LAST = 10       # every server's log ends here when the window opens
ACKED = 5       # writes the window acknowledged


def snapshot(server: int, commits: list[int]) -> dict:
    """One server's engine after a drained dispatch: row g is group g, its
    device and mirror alike, and every row obeys the commit rule (every
    match index and the flush index at the row's commit)."""
    n = len(commits)
    c = np.array(commits, np.int64)
    device = {"commit_index": c,
              "match_index": np.repeat(c[:, None], PEERS, axis=1),
              "flush_index": c.copy(),
              "self_mask": np.eye(PEERS, dtype=bool)[[server] * n],
              "conf_cur": np.ones((n, PEERS), bool)}
    return {"active": np.arange(n),
            "device": device,
            "mirror": {k: v.copy() for k, v in device.items()}}


def standing(server: int, role: str = "LEADER", term: int = 2,
             slot: int = 0) -> dict:
    return {"server": server, "slot": slot, "term": term, "role": role,
            "leads": role == "LEADER", "last_index": LAST}


def check(commits_by_server, at_start, at_close, appointees=(0,)):
    snaps = [snapshot(s, commits_by_server[s]) for s in range(PEERS)]
    return compare.check_device(ref, snaps, at_start, at_close,
                                list(appointees), [ACKED] * len(at_start))


# Each case: one group whose appointee is server 0.  The device's commit on
# each server's row; who led at the window's start; where that server stands
# at the close; then (advance wrong, skipped, leaders away).
CASES = {
    # the density run refused before: an election during the warm-up left
    # server 1 leading; the appointee is a follower one entry short (it
    # learns of the last commit with the next append), the leader's row
    # advanced by what was acked
    "leader-elsewhere-held-on-its-row": (
        [LAST + ACKED - 1, LAST + ACKED, LAST + ACKED - 1],
        standing(1), standing(1), (0, 0, 1)),
    # not loosened: the leader's own row one short, or one over
    "leader-row-one-short": (
        [LAST + ACKED - 1, LAST + ACKED - 1, LAST + ACKED - 1],
        standing(1), standing(1), (1, 0, 1)),
    "leader-row-one-over": (
        [LAST + ACKED + 1, LAST + ACKED + 1, LAST + ACKED + 1],
        standing(0), standing(0), (1, 0, 0)),
    # the compared server's term moved in the window: a new term's startup
    # entry is in the log, so the advance is not the window's writes alone
    "term-moved-skipped": (
        [LAST + ACKED + 1, LAST + ACKED + 1, LAST + ACKED + 1],
        standing(0), standing(0, term=3), (0, 1, 0)),
    # it stepped down at the same term (nobody has won since)
    "stepped-down-skipped": (
        [LAST + ACKED - 1, LAST + ACKED - 1, LAST + ACKED - 1],
        standing(1), standing(1, role="FOLLOWER"), (0, 1, 1)),
    # no leader at the start even after the grace: skipped and counted
    "leaderless-at-start-skipped": (
        [LAST, LAST, LAST],
        standing(0, role="CANDIDATE"), standing(0), (0, 1, 0)),
}


@pytest.mark.parametrize("case", list(CASES))
def test_a_group_is_held_on_the_row_of_its_leader_at_the_start(case):
    commits, at_start, at_close, (wrong, skipped, away) = CASES[case]
    dev = check([[c] for c in commits], [at_start], [at_close])
    assert dev["device_rows_differing"] == 0
    assert dev["device_quorum_rows_wrong"] == 0
    assert dev["device_commit_advance_wrong"] == wrong
    assert dev["device_commit_skipped"] == skipped
    assert dev["leaders_away_at_window_start"] == away
    assert dev["leaderless_at_window_start"] == (not at_start["leads"])
    assert len(dev["device_groups_failing"]) == wrong


def test_the_appointees_row_is_what_read_one_short():
    """The same device state as the first case, held on the appointee's row
    as the check did before (the appointee taken to lead): one group
    wrong."""
    commits, _start, _close, _ = CASES["leader-elsewhere-held-on-its-row"]
    dev = check([[c] for c in commits], [standing(0)], [standing(0)])
    assert dev["device_commit_advance_wrong"] == 1


def test_a_leader_elected_again_in_the_window_is_skipped_on_both():
    """A density run's group 1003: its leader at the start (server 1, term
    1) lost the group and won it back (term 3) just before the close.  A new
    leader keeps its commit index while its followers' match indexes start
    again from nothing, so its row is not yet under the commit rule: the
    group is skipped and counted, not held to a rule that does not apply."""
    snaps = [snapshot(s, [LAST + ACKED]) for s in range(PEERS)]
    snaps[1]["device"]["match_index"][0] = -1
    snaps[1]["mirror"]["match_index"] = \
        snaps[1]["device"]["match_index"].copy()
    dev = snaps[1]["device"]
    assert ref.leader_commit(dev["match_index"][0].tolist(), 1,
                             int(dev["flush_index"][0]),
                             dev["conf_cur"][0].tolist()) == -1
    got = compare.check_device(ref, snaps, [standing(1, term=1)],
                               [standing(1, term=3)], [1], [ACKED])
    assert (got["device_quorum_rows_wrong"],
            got["device_commit_advance_wrong"],
            got["device_commit_skipped"]) == (0, 0, 1)
    assert got["device_groups_failing"] == []


def test_a_failing_group_says_why():
    # group 0: its leader (server 1, not the appointee) one short on the
    # advance; group 1: the quorum rule broken on its leader's row
    commits = [[LAST + ACKED - 1, LAST + ACKED],
               [LAST + ACKED - 1, LAST + ACKED],
               [LAST + ACKED - 1, LAST + ACKED]]
    snaps = [snapshot(s, commits[s]) for s in range(PEERS)]
    snaps[0]["device"]["match_index"][1] = LAST
    snaps[0]["device"]["flush_index"][1] = LAST
    for f in ("match_index", "flush_index"):
        snaps[0]["mirror"][f] = snaps[0]["device"][f].copy()
    at_start = [standing(1, slot=0), standing(0, slot=1)]
    at_close = [standing(1, slot=0, term=2), standing(0, slot=1)]
    dev = compare.check_device(ref, snaps, at_start, at_close, [0, 1],
                               [ACKED, ACKED])
    assert dev["device_commit_advance_wrong"] == 1
    assert dev["device_quorum_rows_wrong"] == 1
    assert dev["device_groups_failing"] == [
        {"group": 0, "appointee": 0, "server": 1,
         "at_start": ["LEADER", 2], "at_close": ["LEADER", 2],
         "last_index_at_start": LAST, "device_commit": LAST + ACKED - 1,
         "quorum_rule": LAST + ACKED - 1, "acked_in_window": ACKED},
        {"group": 1, "appointee": 1, "server": 0,
         "at_start": ["LEADER", 2], "at_close": ["LEADER", 2],
         "last_index_at_start": LAST, "device_commit": LAST + ACKED,
         "quorum_rule": LAST, "acked_in_window": ACKED}]


def test_no_more_than_eight_failing_groups_are_described():
    n = 12
    commits = [[LAST] * n for _ in range(PEERS)]      # none advanced
    dev = check(commits, [standing(0, slot=g) for g in range(n)],
                [standing(0, slot=g) for g in range(n)], appointees=[0] * n)
    assert dev["device_commit_advance_wrong"] == n
    assert [f["group"] for f in dev["device_groups_failing"]] \
        == list(range(compare.FAILING_SHOWN))


def test_a_leadership_moved_before_the_window_is_held_and_correct():
    """The planted ``leader-moved`` fault on the CPU: group 0's leadership
    goes to another peer through the program's own transfer after the
    warm-up.  The run is correct, and the group is either held on its new
    leader's row or, where the leadership came home in the window,
    skipped, and counted either way."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "ratis-3x1k.write-closed", "--seed", "2147483999",
         "--seconds", "2", "--trace", "0", "--rehearse-cpu", "--groups",
         "16", "--fault", "leader-moved"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=180)
    assert p.returncode == 0, p.stderr[-2000:]
    lines = p.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    seen = json.loads(lines[-2][len("RESULT "):])
    assert result["correct"] is True, result["compared"]
    assert seen["leaders_away_at_window_start"] \
        + seen["device_commit_skipped"] >= 1
    assert seen["device_groups_failing"] == []
    assert list(seen)[-5:] == [
        "window_start_wait_s", "leaders_away_at_window_start",
        "leaderless_at_window_start", "device_commit_skipped",
        "device_groups_failing"]
