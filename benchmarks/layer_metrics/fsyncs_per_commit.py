"""Log: fsyncs issued by every LogWorker during the window per acknowledged
operation (all replicas together): three a write in a durable cell, none a
read, so in a cell that mixes them it reads three times the share of
writes.  Durable configurations only: without a durable log there is
nothing to read."""


def read(ctx):
    if not ctx["config"]["guarantees"]["durable"] or not ctx["acked_in_window"]:
        return None
    return (ctx["c1"]["fsyncs"] - ctx["c0"]["fsyncs"]) / ctx["acked_in_window"]
