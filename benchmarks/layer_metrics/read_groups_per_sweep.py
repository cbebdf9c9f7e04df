"""Reads: groups whose leadership one confirmation sweep proved.  A
linearizable read with the lease off costs its group one readIndex
confirmation by a majority (Raft section 6.4); the batched scheduler
(``server/serving/readbatch.py:ReadIndexScheduler``) sends one zero-entry
envelope a follower for every group of a loop pass that has a read waiting.
Its own counts: ``sweeps`` (rounds fired, ``_fire``) and ``confirm_sent``
(a group's confirmation sent to a follower, ``_sweep``), the latter over the
followers of a group.  1.0: every read paid a round of its own.  None where
no sweep was fired or the program keeps no such count."""


def read(ctx):
    a, b = ctx["c0"].get("reads"), ctx["c1"].get("reads")
    if not a or not b or b["sweeps"] <= a["sweeps"]:
        return None
    followers = int(ctx["config"]["peers"]) - 1
    return ((b["confirms_sent"] - a["confirms_sent"]) / followers
            / (b["sweeps"] - a["sweeps"]))
