"""Per-division metric facades over the registry.

Capability parity with the reference server metric impls
(ratis-server/src/main/java/org/apache/ratis/server/metrics/ and
impl/StateMachineMetrics.java): ``RaftServerMetrics`` (retry-cache
hit/miss, request queue size, watch/read timers, commitInfo gauges),
``LeaderElectionMetrics`` (election count/time, last leader elapsed),
``SegmentedRaftLogMetrics`` (flush/sync timers + queue gauges),
``LogAppenderMetrics`` (per-follower next/match/rpcTime gauges),
``StateMachineMetrics`` (appliedIndex gauge, takeSnapshot timer).
Metric names follow the catalog in
ratis-docs/src/site/markdown/metrics.md:19-140 so dashboards written for
the reference carry over.
"""

from __future__ import annotations

import time
from typing import Callable, Optional

from ratis_tpu.metrics.registry import (Counter, MetricRegistries,
                                        MetricRegistryInfo,
                                        RatisMetricRegistry)

RATIS_APPLICATION_NAME = "ratis"


def _create(component: str, prefix: str, name: str) -> RatisMetricRegistry:
    info = MetricRegistryInfo(prefix=prefix,
                              application=RATIS_APPLICATION_NAME,
                              component=component, name=name)
    return MetricRegistries.global_registries().create(info)


class _MetricsBase:
    component = "server"
    name = "metrics"

    def __init__(self, member_id) -> None:
        self.registry = _create(self.component, str(member_id), self.name)

    def unregister(self) -> None:
        MetricRegistries.global_registries().remove(self.registry.info)

    def snapshot(self) -> dict:
        return self.registry.snapshot()


class RaftServerMetrics(_MetricsBase):
    """server component catalog (metrics.md "server" table)."""

    component = "server"
    name = "raft_server"

    def __init__(self, member_id) -> None:
        super().__init__(member_id)
        r = self.registry
        self.retry_cache_hit = r.counter("numRetryCacheHits")
        self.retry_cache_miss = r.counter("numRetryCacheMisses")
        self.num_requests = r.counter("numRaftClientRequests")
        self.num_failed = r.counter("numFailedClientRequests")
        self.watch_timer = r.timer("watchRequestLatency")
        self.read_timer = r.timer("readRequestLatency")
        self.write_timer = r.timer("writeRequestLatency")
        self.follower_append_timer = r.timer("follower_append_entry_latency")

    def add_commit_info_gauge(self, supplier: Callable[[], dict]) -> None:
        self.registry.gauge("commitInfos", supplier)

    def add_queue_gauge(self, supplier: Callable[[], int]) -> None:
        self.registry.gauge("numPendingRequestInQueue", supplier)


class LeaderElectionMetrics(_MetricsBase):
    component = "leader_election"
    name = "leader_election"

    def __init__(self, member_id, activity: Optional[Counter] = None
                 ) -> None:
        """``activity``: the server's count of its divisions' elections
        and election timeouts together (the watchdog's churn input)."""
        super().__init__(member_id)
        r = self.registry
        self.election_count = r.counter("electionCount", activity)
        # election timeouts
        self.timeout_count = r.counter("timeoutCount", activity)
        self.election_timer = r.timer("electionTime")
        self.transfer_count = r.counter("transferLeadershipCount")
        # timeout_count ← Division.on_election_timeout;
        # transfer_count ← server.admin.transfer_leadership
        self._last_leader_time: Optional[float] = None
        r.gauge("lastLeaderElapsedTime", self._elapsed_since_leader)

    def on_new_leader_elected(self) -> None:
        self._last_leader_time = time.monotonic()

    def _elapsed_since_leader(self) -> float:
        if self._last_leader_time is None:
            return -1.0
        return time.monotonic() - self._last_leader_time


class LogWorkerMetrics(_MetricsBase):
    """Shared per-storage-device worker catalog
    (metrics.md log_worker table: flushTime/flushCount/syncTime)."""

    component = "log_worker"
    name = "log_worker"

    def __init__(self, member_id) -> None:
        super().__init__(member_id)
        r = self.registry
        self.flush_timer = r.timer("flushTime")
        self.flush_count = r.counter("flushCount")
        self.sync_timer = r.timer("syncTime")
        # actual fsync() calls — flushCount is per drain batch; with many
        # files per batch the two diverge, and syncCount/commits is the
        # fsyncs-per-commit figure the shared log plane exists to shrink
        self.sync_count = r.counter("syncCount")

    def add_queue_gauges(self, pending_supplier: Callable[[], int]) -> None:
        self.registry.gauge("numPendingIO", pending_supplier)

    def add_sweep_gauge(self, supplier: Callable[[], float]) -> None:
        """Decayed average of fsyncs issued per drain sweep (1.0 when every
        division shares one segment file, ~N with per-group files)."""
        self.registry.gauge("fsyncsPerSweep", supplier)


class SharedLogMetrics(_MetricsBase):
    """Per-shard shared-log store catalog (segment footprint, flush
    backlog, compaction reclaim)."""

    component = "log_worker"
    name = "shared_log"

    def __init__(self, member_id) -> None:
        super().__init__(member_id)
        r = self.registry
        self.compaction_count = r.counter("compactionCount")
        self.compaction_reclaimed = r.counter("compactionReclaimedBytes")

    def add_store_gauges(self, bytes_supplier: Callable[[], int],
                         pending_supplier: Callable[[], int]) -> None:
        self.registry.gauge("sharedSegmentBytes", bytes_supplier)
        self.registry.gauge("logPendingFlushDepth", pending_supplier)


class SegmentedRaftLogMetrics(_MetricsBase):
    """Per-division segmented-log catalog (append/truncate/purge)."""

    component = "log_worker"
    name = "segmented_raft_log"

    def __init__(self, member_id) -> None:
        super().__init__(member_id)
        r = self.registry
        self.append_timer = r.timer("appendEntryLatency")
        self.truncate_count = r.counter("truncateLogCount")
        self.purge_count = r.counter("purgeLogCount")
        # entry-cache eviction + read-through (reference raft_log cache
        # hit/miss counters, SegmentedRaftLogMetrics.java)
        self.cache_hit_count = r.counter("cacheHitCount")
        self.cache_miss_count = r.counter("cacheMissCount")
        self.cache_evict_count = r.counter("cacheEvictCount")


class LogAppenderMetrics(_MetricsBase):
    component = "log_appender"
    name = "log_appender"

    def add_follower_gauges(self, peer_id, next_index: Callable[[], int],
                            match_index: Callable[[], int],
                            rpc_elapsed: Callable[[], float]) -> None:
        self.registry.gauge(f"follower_{peer_id}_next_index", next_index)
        self.registry.gauge(f"follower_{peer_id}_match_index", match_index)
        self.registry.gauge(f"follower_{peer_id}_rpc_elapsed_s", rpc_elapsed)

    def remove_follower_gauges(self, peer_id) -> None:
        for suffix in ("next_index", "match_index", "rpc_elapsed_s"):
            self.registry.remove(f"follower_{peer_id}_{suffix}")


class DataStreamMetrics(_MetricsBase):
    """DataStream server packet/stream counters + latency (reference
    NettyServerStreamRpcMetrics, ratis-netty/.../metrics/)."""

    component = "datastream"
    name = "netty_stream_server"

    def __init__(self, member_id) -> None:
        super().__init__(member_id)
        r = self.registry
        self.request_timer = r.timer("streamRequestLatency")
        self.num_requests = r.counter("numRequests")
        self.num_failed = r.counter("numFailedRequests")
        self.bytes_written = r.counter("numBytesWritten")
        self.streams_started = r.counter("numStreamsStarted")
        self.streams_closed = r.counter("numStreamsClosed")


class StateMachineMetrics(_MetricsBase):
    component = "state_machine"
    name = "state_machine"

    def __init__(self, member_id) -> None:
        super().__init__(member_id)
        r = self.registry
        self.snapshot_timer = r.timer("takeSnapshot")
        self.applied_count = r.counter("appliedTransactionCount")

    def add_applied_index_gauge(self, supplier: Callable[[], int]) -> None:
        self.registry.gauge("appliedIndex", supplier)
