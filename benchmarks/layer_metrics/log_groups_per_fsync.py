"""Log: groups the shared log plane made durable with one fsync during the
trace session: Σ ``log.shared.sync_groups`` (the distinct groups whose
records one shard fsync covered) / Σ ``log.shared.syncs`` (the shard fsyncs;
server/log/segmented.py:LogWorker, a worker of server/log/shared.py's
stores).  1.0 is what per-group files give; the more groups one drain
carries, the higher.  None where the program keeps neither counter or the
window made no shard fsync."""


def read(ctx):
    from ratis_tpu.trace import TRACER
    if not hasattr(TRACER, "session"):
        return None
    sess = TRACER.session()
    if not sess["t_on"]:
        return None
    syncs = sess["counters"].get("log.shared.syncs", 0)
    groups = sess["counters"].get("log.shared.sync_groups", 0)
    return groups / syncs if syncs and groups else None
