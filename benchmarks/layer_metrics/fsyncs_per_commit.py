"""Log: fsyncs issued by every LogWorker during the window per acknowledged
write (all replicas together).  Durable cells only: without a durable log
there is nothing to read."""


def read(ctx):
    if not ctx["config"]["guarantees"]["durable"] or not ctx["acked_in_window"]:
        return None
    return (ctx["c1"]["fsyncs"] - ctx["c0"]["fsyncs"]) / ctx["acked_in_window"]
