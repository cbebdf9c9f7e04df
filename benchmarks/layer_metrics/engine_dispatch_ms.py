"""Engine, host half: mean wall time of one batched dispatch (pack + upload
+ kernel + fetch) over the window, from the engines' dispatchLatency timers:
delta of the sum over delta of the count, mean over the engines that
dispatched."""


def read(ctx):
    means = []
    for a, b in zip(ctx["c0"]["engines"], ctx["c1"]["engines"]):
        n = b["dispatch_count"] - a["dispatch_count"]
        if n > 0:
            means.append((b["dispatch_total_s"] - a["dispatch_total_s"])
                         / n * 1e3)
    return sum(means) / len(means) if means else None
