"""Plain reference of the counter deployment under a mix of INCREMENTs and
linearizable GETs, independent of the program.  Writes are judged as
``counter`` judges them, the reads left out of their count: a group's k-th
INCREMENT answers k.  A read goes through no log, so nothing orders it but
the clock of the one process that sent everything, and it is judged by that:

- a GET of group g, sent at s and answered at a with the value v, is
  linearizable only if (INCREMENTs of g acknowledged before s) <= v <=
  (INCREMENTs of g sent before a);
- a GET sent after another GET of its group was answered never reads less
  than that one did.  Reads do not go through the client's ordered window, so
  two that overlap may answer in either order, and only reads that do not
  overlap are held to each other.

The run's parts (warm-up, window, settle) follow one another: everything of
an earlier part is before everything of a later one, and within a part the
generator's ``sent`` and ``acked`` columns say what came before what.  A
write is stamped as sent before it leaves and as acknowledged after its reply
is in, so a bound is never tighter than what happened.

A read adds no entry to the log: ``entries_per_part`` counts the answered
INCREMENTs alone, and the harness holds the device's commit index to it.
Imports nothing of ratis_tpu."""

from __future__ import annotations

from typing import Sequence

from benchmarks.reference import counter
from benchmarks.reference.counter import (INCREMENT, durable_writes,  # noqa: F401
                                          leader_commit, replicas_holding)

GET = "GET"


def _rows(part: dict, keep) -> dict:
    """The part's rows whose payload ``keep`` admits, column by column."""
    at = [i for i, p in enumerate(part["payload"]) if keep(p)]
    return {k: [col[i] for i in at] for k, col in part.items()
            if isinstance(col, list) and len(col) == len(part["payload"])}


def judge_answers(groups: int, parts: Sequence[dict]) -> dict:
    """Every request of the run's ``parts`` in order (each the generator's
    rows ``group`` / ``payload`` / ``sent`` / ``acked`` / ``answer``).  What
    ``counter.judge_answers`` returns, over the writes alone, with the reads
    that never got an answer added to ``never_answered``; the log entries
    each part added to each group (``entries_per_part``); and the reads that
    no linearizable history explains (``compared``: every limit 0)."""
    for part in parts:
        for p in part["payload"]:
            if p not in (INCREMENT, GET):
                raise ValueError(f"the counter reference has no semantics "
                                 f"for the payload {p!r}")
    writes = [_rows(part, lambda p: p == INCREMENT) for part in parts]
    reads = [_rows(part, lambda p: p == GET) for part in parts]
    out = counter.judge_answers(groups, writes)
    entries = []
    for part in writes:
        per_group = [0] * groups
        for g, ans in zip(part["group"], part["answer"]):
            per_group[g] += ans is not None
        entries.append(per_group)

    # INCREMENTs of each group in each part: when sent, when acknowledged
    # (None: never), and the counts that all earlier parts leave behind
    sent_at = [[[] for _ in range(groups)] for _ in parts]
    acked_at = [[[] for _ in range(groups)] for _ in parts]
    for k, part in enumerate(writes):
        for g, s, a, ans in zip(part["group"], part["sent"], part["acked"],
                                part["answer"]):
            sent_at[k][g].append(s)
            if a is not None and ans is not None:
                acked_at[k][g].append(a)
    acked_before = [[0] * groups]
    sent_before = [[0] * groups]
    for k in range(len(parts)):
        acked_before.append([n + len(acked_at[k][g])
                             for g, n in enumerate(acked_before[-1])])
        sent_before.append([n + len(sent_at[k][g])
                            for g, n in enumerate(sent_before[-1])])

    not_linearizable = never = compared = 0
    samples = []
    answered: list[list] = [[] for _ in range(groups)]  # (part, acked, value)
    for k, part in enumerate(reads):
        for g, s, a, ans in zip(part["group"], part["sent"], part["acked"],
                                part["answer"]):
            if a is None or ans is None:
                never += 1
                continue
            compared += 1
            low = acked_before[k][g] + sum(1 for t in acked_at[k][g] if t < s)
            high = sent_before[k][g] + sum(1 for t in sent_at[k][g] if t <= a)
            value = int(ans) if ans.isdigit() else -1
            floor = max((v for q, t, v in answered[g]
                         if q < k or (q == k and t < s)), default=0)
            answered[g].append((k, a, value))
            if not max(low, floor) <= value <= high:
                not_linearizable += 1
                if len(samples) < 4:
                    samples.append({"group": g, "read": ans, "sent": s,
                                    "acked": a, "at_least": low,
                                    "at_most": high,
                                    "an_earlier_read": floor})
    out["never_answered"] += never
    out["answers_compared"] += compared
    out["samples"] = out["samples"] + samples
    out["reads_compared"] = compared
    out["entries_per_part"] = entries
    out["compared"] = {"reads_not_linearizable": not_linearizable}
    return out
