"""Reads: mean time a read spends in its division, from the request's
arrival there to its reply made (``division.py:_read_async``: the readIndex
confirmation by a majority, the wait for the state machine to have applied
that index, the query), over the reads served inside the window: the
divisions' ``readRequestLatency`` timers, delta of the sum over delta of the
count.  What the client's latency holds above it is the wire and the
client.  None where no read was served or the program keeps no such timer."""


def read(ctx):
    a, b = ctx["c0"].get("reads"), ctx["c1"].get("reads")
    if not a or not b or b["requests"] <= a["requests"]:
        return None
    return (b["total_s"] - a["total_s"]) / (b["requests"] - a["requests"]) \
        * 1e3
