"""Config key catalogs with defaults.

Capability parity with the reference's *ConfigKeys interfaces
(ratis-server-api/.../RaftServerConfigKeys.java:43-961, RaftClientConfigKeys,
RaftConfigKeys): PREFIX-composed dotted keys with typed defaults.  Layout
follows the reference's nested namespaces (Rpc, Log, Log.Appender, Snapshot,
Read, Write, Watch, RetryCache, LeaderElection, Notification, ThreadPool),
plus a new `Engine` namespace for the TPU batched-quorum engine.
"""

from __future__ import annotations

from ratis_tpu.conf.properties import RaftProperties
from ratis_tpu.util.timeduration import TimeDuration


class RaftConfigKeys:
    PREFIX = "raft"

    class Rpc:
        TYPE_KEY = "raft.rpc.type"
        TYPE_DEFAULT = "SIMULATED"  # transports: SIMULATED | GRPC

        @staticmethod
        def type(p: RaftProperties) -> str:
            return p.get(RaftConfigKeys.Rpc.TYPE_KEY, RaftConfigKeys.Rpc.TYPE_DEFAULT).upper()

        @staticmethod
        def set_type(p: RaftProperties, t: str) -> None:
            p.set(RaftConfigKeys.Rpc.TYPE_KEY, t.upper())


class RaftServerConfigKeys:
    PREFIX = "raft.server"

    STORAGE_DIR_KEY = "raft.server.storage.dir"
    STORAGE_DIR_DEFAULT = "/tmp/ratis-tpu"
    STORAGE_FREE_SPACE_MIN_KEY = "raft.server.storage.free-space.min"
    STORAGE_FREE_SPACE_MIN_DEFAULT = "0MB"
    # setConfiguration staging: a bootstrapping peer is "caught up" once it is
    # within this many entries of the leader's last index (reference
    # RaftServerConfigKeys stagingCatchupGap, used by LeaderStateImpl
    # checkStaging:828).
    STAGING_CATCHUP_GAP_KEY = "raft.server.staging.catchup.gap"
    STAGING_CATCHUP_GAP_DEFAULT = 1000

    # Host-runtime loop sharding (no reference analog; the closest shape is
    # Netty's NioEventLoopGroup): run this many worker event loops per
    # RaftServer and hash-pin each Division — its request handling,
    # appenders, heartbeat sweep share, and outbound transport connections —
    # to one of them.  1 (the default) = the single-loop runtime, with no
    # dispatch indirection anywhere.  The traced decomposition that
    # motivates >1 is in docs/perf.md ("Per-stage residual": ready-callback
    # queueing on one saturated loop dominates the north-star shape).
    LOOP_SHARDS_KEY = "raft.tpu.server.loop-shards"
    LOOP_SHARDS_DEFAULT = 1

    @staticmethod
    def loop_shards(p: RaftProperties) -> int:
        return p.get_int(RaftServerConfigKeys.LOOP_SHARDS_KEY,
                         RaftServerConfigKeys.LOOP_SHARDS_DEFAULT)

    @staticmethod
    def storage_dirs(p: RaftProperties) -> list[str]:
        v = p.get(RaftServerConfigKeys.STORAGE_DIR_KEY,
                  RaftServerConfigKeys.STORAGE_DIR_DEFAULT)
        return [s.strip() for s in v.split(",") if s.strip()]

    @staticmethod
    def set_storage_dir(p: RaftProperties, dirs: "list[str] | str") -> None:
        if isinstance(dirs, list):
            dirs = ",".join(dirs)
        p.set(RaftServerConfigKeys.STORAGE_DIR_KEY, dirs)

    class Rpc:
        # Election timeout bounds; each follower randomizes in [min, max)
        # (reference Rpc.TIMEOUT_MIN/MAX, RaftServerConfigKeys.java).
        TIMEOUT_MIN_KEY = "raft.server.rpc.timeout.min"
        TIMEOUT_MIN_DEFAULT = TimeDuration.millis(150)
        TIMEOUT_MAX_KEY = "raft.server.rpc.timeout.max"
        TIMEOUT_MAX_DEFAULT = TimeDuration.millis(300)
        REQUEST_TIMEOUT_KEY = "raft.server.rpc.request.timeout"
        REQUEST_TIMEOUT_DEFAULT = TimeDuration.millis(3000)
        SLEEP_TIME_KEY = "raft.server.rpc.sleep.time"
        SLEEP_TIME_DEFAULT = TimeDuration.millis(25)
        SLOWNESS_TIMEOUT_KEY = "raft.server.rpc.slowness.timeout"
        SLOWNESS_TIMEOUT_DEFAULT = TimeDuration.valueOf("60s")

        @staticmethod
        def timeout_min(p: RaftProperties) -> TimeDuration:
            return p.get_time_duration(RaftServerConfigKeys.Rpc.TIMEOUT_MIN_KEY,
                                       RaftServerConfigKeys.Rpc.TIMEOUT_MIN_DEFAULT)

        @staticmethod
        def timeout_max(p: RaftProperties) -> TimeDuration:
            return p.get_time_duration(RaftServerConfigKeys.Rpc.TIMEOUT_MAX_KEY,
                                       RaftServerConfigKeys.Rpc.TIMEOUT_MAX_DEFAULT)

        @staticmethod
        def request_timeout(p: RaftProperties) -> TimeDuration:
            return p.get_time_duration(RaftServerConfigKeys.Rpc.REQUEST_TIMEOUT_KEY,
                                       RaftServerConfigKeys.Rpc.REQUEST_TIMEOUT_DEFAULT)

        @staticmethod
        def slowness_timeout(p: RaftProperties) -> TimeDuration:
            return p.get_time_duration(RaftServerConfigKeys.Rpc.SLOWNESS_TIMEOUT_KEY,
                                       RaftServerConfigKeys.Rpc.SLOWNESS_TIMEOUT_DEFAULT)

        @staticmethod
        def set_timeout(p: RaftProperties, tmin, tmax) -> None:
            p.set_time_duration(RaftServerConfigKeys.Rpc.TIMEOUT_MIN_KEY, tmin)
            p.set_time_duration(RaftServerConfigKeys.Rpc.TIMEOUT_MAX_KEY, tmax)

    class Log:
        USE_MEMORY_KEY = "raft.server.log.use.memory"
        USE_MEMORY_DEFAULT = False
        SEGMENT_SIZE_MAX_KEY = "raft.server.log.segment.size.max"
        SEGMENT_SIZE_MAX_DEFAULT = "8MB"
        PREALLOCATED_SIZE_KEY = "raft.server.log.preallocated.size"
        PREALLOCATED_SIZE_DEFAULT = "4MB"
        WRITE_BUFFER_SIZE_KEY = "raft.server.log.write.buffer.size"
        WRITE_BUFFER_SIZE_DEFAULT = "64KB"
        FORCE_SYNC_NUM_KEY = "raft.server.log.force.sync.num"
        FORCE_SYNC_NUM_DEFAULT = 128
        UNSAFE_FLUSH_ENABLED_KEY = "raft.server.log.unsafe-flush.enabled"
        UNSAFE_FLUSH_ENABLED_DEFAULT = False
        PURGE_GAP_KEY = "raft.server.log.purge.gap"
        PURGE_GAP_DEFAULT = 1024
        PURGE_UPTO_SNAPSHOT_INDEX_KEY = "raft.server.log.purge.upto.snapshot.index"
        PURGE_UPTO_SNAPSHOT_INDEX_DEFAULT = False
        SEGMENT_CACHE_NUM_MAX_KEY = "raft.server.log.segment.cache.num.max"
        SEGMENT_CACHE_NUM_MAX_DEFAULT = 6
        QUEUE_ELEMENT_LIMIT_KEY = "raft.server.log.queue.element-limit"
        QUEUE_ELEMENT_LIMIT_DEFAULT = 4096
        QUEUE_BYTE_LIMIT_KEY = "raft.server.log.queue.byte-limit"
        QUEUE_BYTE_LIMIT_DEFAULT = "64MB"

        @staticmethod
        def use_memory(p: RaftProperties) -> bool:
            return p.get_boolean(RaftServerConfigKeys.Log.USE_MEMORY_KEY,
                                 RaftServerConfigKeys.Log.USE_MEMORY_DEFAULT)

        @staticmethod
        def set_use_memory(p: RaftProperties, v: bool) -> None:
            p.set_boolean(RaftServerConfigKeys.Log.USE_MEMORY_KEY, v)

        @staticmethod
        def segment_size_max(p: RaftProperties) -> int:
            return p.get_size(RaftServerConfigKeys.Log.SEGMENT_SIZE_MAX_KEY,
                              RaftServerConfigKeys.Log.SEGMENT_SIZE_MAX_DEFAULT)

        @staticmethod
        def segment_cache_num_max(p: RaftProperties) -> int:
            return p.get_int(
                RaftServerConfigKeys.Log.SEGMENT_CACHE_NUM_MAX_KEY,
                RaftServerConfigKeys.Log.SEGMENT_CACHE_NUM_MAX_DEFAULT)

        @staticmethod
        def force_sync_num(p: RaftProperties) -> int:
            return p.get_int(RaftServerConfigKeys.Log.FORCE_SYNC_NUM_KEY,
                             RaftServerConfigKeys.Log.FORCE_SYNC_NUM_DEFAULT)

        @staticmethod
        def purge_gap(p: RaftProperties) -> int:
            return p.get_int(RaftServerConfigKeys.Log.PURGE_GAP_KEY,
                             RaftServerConfigKeys.Log.PURGE_GAP_DEFAULT)

        class Appender:
            BUFFER_BYTE_LIMIT_KEY = "raft.server.log.appender.buffer.byte-limit"
            BUFFER_BYTE_LIMIT_DEFAULT = "4MB"
            BUFFER_ELEMENT_LIMIT_KEY = "raft.server.log.appender.buffer.element-limit"
            BUFFER_ELEMENT_LIMIT_DEFAULT = 0  # 0 = unlimited
            SNAPSHOT_CHUNK_SIZE_MAX_KEY = "raft.server.log.appender.snapshot.chunk.size.max"
            SNAPSHOT_CHUNK_SIZE_MAX_DEFAULT = "16MB"
            INSTALL_SNAPSHOT_ENABLED_KEY = "raft.server.log.appender.install.snapshot.enabled"
            INSTALL_SNAPSHOT_ENABLED_DEFAULT = True
            PIPELINE_WINDOW_KEY = "raft.server.log.appender.pipeline.window"
            PIPELINE_WINDOW_DEFAULT = 16  # in-flight AppendEntries per follower
            WAIT_TIME_MIN_KEY = "raft.server.log.appender.wait-time.min"
            WAIT_TIME_MIN_DEFAULT = TimeDuration.millis(10)
            # Data-path coalescing (no reference analog — the reference runs
            # one stream per (group, follower), GrpcLogAppender.java:356):
            # fold every group's append batches toward one destination into
            # a single AppendEnvelope RPC per flush.  Disabled = one unary
            # RPC per batch (the reference's cost shape).
            COALESCING_ENABLED_KEY = "raft.server.log.appender.coalescing.enabled"
            COALESCING_ENABLED_DEFAULT = True
            # Envelopes unanswered per (peer, loop-shard) lane.  On the
            # sequenced path (raft.tpu.replication.window-depth > 1) this
            # is the lane's whole window where the follower's transport
            # takes a lane's frames in turn (gRPC), and is multiplied by
            # the depth (cap 64) where frames are worked on as they
            # arrive (TCP, simulated): server/replication.py:PeerSender.
            ENVELOPE_INFLIGHT_KEY = "raft.server.log.appender.envelope.inflight"
            ENVELOPE_INFLIGHT_DEFAULT = 4
            ENVELOPE_BYTE_LIMIT_KEY = "raft.server.log.appender.envelope.byte-limit"
            ENVELOPE_BYTE_LIMIT_DEFAULT = "8MB"

            @staticmethod
            def buffer_byte_limit(p: RaftProperties) -> int:
                return p.get_size(
                    RaftServerConfigKeys.Log.Appender.BUFFER_BYTE_LIMIT_KEY,
                    RaftServerConfigKeys.Log.Appender.BUFFER_BYTE_LIMIT_DEFAULT)

            @staticmethod
            def install_snapshot_enabled(p: RaftProperties) -> bool:
                return p.get_boolean(
                    RaftServerConfigKeys.Log.Appender.INSTALL_SNAPSHOT_ENABLED_KEY,
                    RaftServerConfigKeys.Log.Appender.INSTALL_SNAPSHOT_ENABLED_DEFAULT)

            @staticmethod
            def pipeline_window(p: RaftProperties) -> int:
                return p.get_int(
                    RaftServerConfigKeys.Log.Appender.PIPELINE_WINDOW_KEY,
                    RaftServerConfigKeys.Log.Appender.PIPELINE_WINDOW_DEFAULT)

            @staticmethod
            def coalescing_enabled(p: RaftProperties) -> bool:
                return p.get_boolean(
                    RaftServerConfigKeys.Log.Appender.COALESCING_ENABLED_KEY,
                    RaftServerConfigKeys.Log.Appender.COALESCING_ENABLED_DEFAULT)

            @staticmethod
            def envelope_inflight(p: RaftProperties) -> int:
                return p.get_int(
                    RaftServerConfigKeys.Log.Appender.ENVELOPE_INFLIGHT_KEY,
                    RaftServerConfigKeys.Log.Appender.ENVELOPE_INFLIGHT_DEFAULT)

            @staticmethod
            def envelope_byte_limit(p: RaftProperties) -> int:
                return p.get_size(
                    RaftServerConfigKeys.Log.Appender.ENVELOPE_BYTE_LIMIT_KEY,
                    RaftServerConfigKeys.Log.Appender.ENVELOPE_BYTE_LIMIT_DEFAULT)

    class Snapshot:
        AUTO_TRIGGER_ENABLED_KEY = "raft.server.snapshot.auto.trigger.enabled"
        AUTO_TRIGGER_ENABLED_DEFAULT = False
        AUTO_TRIGGER_THRESHOLD_KEY = "raft.server.snapshot.auto.trigger.threshold"
        AUTO_TRIGGER_THRESHOLD_DEFAULT = 400000
        CREATION_GAP_KEY = "raft.server.snapshot.creation.gap"
        CREATION_GAP_DEFAULT = 1024
        RETENTION_FILE_NUM_KEY = "raft.server.snapshot.retention.file.num"
        RETENTION_FILE_NUM_DEFAULT = -1

        @staticmethod
        def auto_trigger_enabled(p: RaftProperties) -> bool:
            return p.get_boolean(RaftServerConfigKeys.Snapshot.AUTO_TRIGGER_ENABLED_KEY,
                                 RaftServerConfigKeys.Snapshot.AUTO_TRIGGER_ENABLED_DEFAULT)

        @staticmethod
        def auto_trigger_threshold(p: RaftProperties) -> int:
            return p.get_int(RaftServerConfigKeys.Snapshot.AUTO_TRIGGER_THRESHOLD_KEY,
                             RaftServerConfigKeys.Snapshot.AUTO_TRIGGER_THRESHOLD_DEFAULT)

        @staticmethod
        def creation_gap(p: RaftProperties) -> int:
            return p.get_int(RaftServerConfigKeys.Snapshot.CREATION_GAP_KEY,
                             RaftServerConfigKeys.Snapshot.CREATION_GAP_DEFAULT)

        @staticmethod
        def retention_file_num(p: RaftProperties) -> int:
            return p.get_int(RaftServerConfigKeys.Snapshot.RETENTION_FILE_NUM_KEY,
                             RaftServerConfigKeys.Snapshot.RETENTION_FILE_NUM_DEFAULT)

    class Read:
        class Option:
            DEFAULT = "DEFAULT"  # reads served from leader state directly
            LINEARIZABLE = "LINEARIZABLE"  # readIndex protocol

        OPTION_KEY = "raft.server.read.option"
        OPTION_DEFAULT = "DEFAULT"
        TIMEOUT_KEY = "raft.server.read.timeout"
        TIMEOUT_DEFAULT = TimeDuration.valueOf("10s")
        LEADER_LEASE_ENABLED_KEY = "raft.server.read.leader.lease.enabled"
        LEADER_LEASE_ENABLED_DEFAULT = False
        LEADER_LEASE_TIMEOUT_RATIO_KEY = "raft.server.read.leader.lease.timeout.ratio"
        LEADER_LEASE_TIMEOUT_RATIO_DEFAULT = 0.9
        READ_AFTER_WRITE_CONSISTENT_TIMEOUT_KEY = \
            "raft.server.read.read-after-write-consistent.write-index-cache.expiry-time"
        READ_AFTER_WRITE_CONSISTENT_TIMEOUT_DEFAULT = TimeDuration.valueOf("60s")

        @staticmethod
        def option(p: RaftProperties) -> str:
            return p.get(RaftServerConfigKeys.Read.OPTION_KEY,
                         RaftServerConfigKeys.Read.OPTION_DEFAULT).upper()

        @staticmethod
        def timeout(p: RaftProperties) -> TimeDuration:
            return p.get_time_duration(RaftServerConfigKeys.Read.TIMEOUT_KEY,
                                       RaftServerConfigKeys.Read.TIMEOUT_DEFAULT)

        @staticmethod
        def leader_lease_enabled(p: RaftProperties) -> bool:
            return p.get_boolean(RaftServerConfigKeys.Read.LEADER_LEASE_ENABLED_KEY,
                                 RaftServerConfigKeys.Read.LEADER_LEASE_ENABLED_DEFAULT)

        @staticmethod
        def leader_lease_timeout_ratio(p: RaftProperties) -> float:
            return p.get_float(RaftServerConfigKeys.Read.LEADER_LEASE_TIMEOUT_RATIO_KEY,
                               RaftServerConfigKeys.Read.LEADER_LEASE_TIMEOUT_RATIO_DEFAULT)

    class Write:
        ELEMENT_LIMIT_KEY = "raft.server.write.element-limit"
        ELEMENT_LIMIT_DEFAULT = 4096
        BYTE_LIMIT_KEY = "raft.server.write.byte-limit"
        BYTE_LIMIT_DEFAULT = "64MB"
        FOLLOWER_GAP_RATIO_MAX_KEY = "raft.server.write.follower.gap.ratio.max"
        FOLLOWER_GAP_RATIO_MAX_DEFAULT = -1.0

        @staticmethod
        def element_limit(p: RaftProperties) -> int:
            return p.get_int(RaftServerConfigKeys.Write.ELEMENT_LIMIT_KEY,
                             RaftServerConfigKeys.Write.ELEMENT_LIMIT_DEFAULT)

        @staticmethod
        def byte_limit(p: RaftProperties) -> int:
            return p.get_size(RaftServerConfigKeys.Write.BYTE_LIMIT_KEY,
                              RaftServerConfigKeys.Write.BYTE_LIMIT_DEFAULT)

    class Watch:
        ELEMENT_LIMIT_KEY = "raft.server.watch.element-limit"
        ELEMENT_LIMIT_DEFAULT = 65536
        TIMEOUT_KEY = "raft.server.watch.timeout"
        TIMEOUT_DEFAULT = TimeDuration.valueOf("10s")
        TIMEOUT_DENOMINATION_KEY = "raft.server.watch.timeout.denomination"
        TIMEOUT_DENOMINATION_DEFAULT = TimeDuration.valueOf("1s")

        @staticmethod
        def timeout(p: RaftProperties) -> TimeDuration:
            return p.get_time_duration(RaftServerConfigKeys.Watch.TIMEOUT_KEY,
                                       RaftServerConfigKeys.Watch.TIMEOUT_DEFAULT)

        @staticmethod
        def element_limit(p: RaftProperties) -> int:
            return p.get_int(RaftServerConfigKeys.Watch.ELEMENT_LIMIT_KEY,
                             RaftServerConfigKeys.Watch.ELEMENT_LIMIT_DEFAULT)

    class RetryCache:
        EXPIRY_TIME_KEY = "raft.server.retrycache.expiry-time"
        EXPIRY_TIME_DEFAULT = TimeDuration.valueOf("60s")
        STATISTICS_EXPIRY_TIME_KEY = "raft.server.retrycache.statistics.expiry-time"
        STATISTICS_EXPIRY_TIME_DEFAULT = TimeDuration.valueOf("100us")

        @staticmethod
        def expiry_time(p: RaftProperties) -> TimeDuration:
            return p.get_time_duration(RaftServerConfigKeys.RetryCache.EXPIRY_TIME_KEY,
                                       RaftServerConfigKeys.RetryCache.EXPIRY_TIME_DEFAULT)

    class LeaderElection:
        LEADER_STEP_DOWN_WAIT_TIME_KEY = "raft.server.leaderelection.leader.step-down.wait-time"
        LEADER_STEP_DOWN_WAIT_TIME_DEFAULT = TimeDuration.valueOf("10s")
        PRE_VOTE_KEY = "raft.server.leaderelection.pre-vote"
        PRE_VOTE_DEFAULT = True
        MEMBER_MAJORITY_ADD_KEY = "raft.server.leaderelection.member.majority.add"
        MEMBER_MAJORITY_ADD_DEFAULT = False

        @staticmethod
        def pre_vote(p: RaftProperties) -> bool:
            return p.get_boolean(RaftServerConfigKeys.LeaderElection.PRE_VOTE_KEY,
                                 RaftServerConfigKeys.LeaderElection.PRE_VOTE_DEFAULT)

        @staticmethod
        def step_down_wait_time(p: RaftProperties) -> TimeDuration:
            return p.get_time_duration(
                RaftServerConfigKeys.LeaderElection.LEADER_STEP_DOWN_WAIT_TIME_KEY,
                RaftServerConfigKeys.LeaderElection.LEADER_STEP_DOWN_WAIT_TIME_DEFAULT)

    class Heartbeat:
        """Multi-raft bulk heartbeats (no reference analog — removes the
        reference's O(groups) per-interval heartbeat volume): the sweep
        ships ONE compact BulkHeartbeat per destination server per interval
        instead of one AppendEntries per (group, follower).  Disabled =
        unary per-group heartbeats, the reference's cost shape."""

        COALESCING_ENABLED_KEY = "raft.tpu.heartbeat.coalescing.enabled"
        COALESCING_ENABLED_DEFAULT = True

        @staticmethod
        def coalescing_enabled(p: RaftProperties) -> bool:
            return p.get_boolean(
                RaftServerConfigKeys.Heartbeat.COALESCING_ENABLED_KEY,
                RaftServerConfigKeys.Heartbeat.COALESCING_ENABLED_DEFAULT)

    class Hibernate:
        """Idle-group quiescence (no reference analog; the multi-raft
        production pattern TiKV calls hibernate regions): a leader whose
        group has no pending work and fully-synced followers stops
        heartbeating it, and its followers disarm their election timers —
        an idle group costs ZERO background traffic.  Any contact (client
        request, append, vote) wakes the group; the availability trade is
        that a leader dying while hibernated is only detected at the next
        contact.  Requires heartbeat coalescing (the hibernate handshake
        rides the compact bulk items); OFF by default."""

        ENABLED_KEY = "raft.tpu.hibernate.enabled"
        ENABLED_DEFAULT = False
        # quiet sweeps before a group hibernates
        AFTER_SWEEPS_KEY = "raft.tpu.hibernate.after-sweeps"
        AFTER_SWEEPS_DEFAULT = 4
        # Dead-leader backstop: a hibernated follower arms this (long)
        # election deadline instead of disarming outright, and the sleeping
        # leader sends ONE hibernate-flagged heartbeat per backstop/4 to
        # keep refreshing it.  A dead leader stops refreshing, so the group
        # becomes electable again within ~backstop even with zero client
        # traffic.  "0s" restores the round-4 full-disarm behavior.
        BACKSTOP_KEY = "raft.tpu.hibernate.backstop"
        BACKSTOP_DEFAULT = "60s"

        @staticmethod
        def enabled(p: RaftProperties) -> bool:
            return p.get_boolean(
                RaftServerConfigKeys.Hibernate.ENABLED_KEY,
                RaftServerConfigKeys.Hibernate.ENABLED_DEFAULT)

        @staticmethod
        def after_sweeps(p: RaftProperties) -> int:
            return p.get_int(
                RaftServerConfigKeys.Hibernate.AFTER_SWEEPS_KEY,
                RaftServerConfigKeys.Hibernate.AFTER_SWEEPS_DEFAULT)

        @staticmethod
        def backstop(p: RaftProperties):
            return p.get_time_duration(
                RaftServerConfigKeys.Hibernate.BACKSTOP_KEY,
                RaftServerConfigKeys.Hibernate.BACKSTOP_DEFAULT)

    class Upkeep:
        """Vectorized upkeep plane (server/upkeep.py): per-loop-shard
        packed deadline arrays replace the per-group Python walk in the
        heartbeat sweep, hibernation backstop, retry-cache/write-index
        expiry, client-window sweep, and watch-frontier refresh.  OFF by
        default; unset reproduces the per-group paths bit-for-bit."""

        ENABLED_KEY = "raft.tpu.upkeep.enabled"
        ENABLED_DEFAULT = False
        # Full-walk resync cadence (sweeps): every N sweeps the plane
        # re-derives every registered division's deadlines from scratch —
        # an O(G) backstop against a missed re-arm hook.  At the default
        # 64 sweeps (~5s at the 75ms sweep cadence) the amortized cost is
        # negligible; 0 disables the resync.
        RESYNC_SWEEPS_KEY = "raft.tpu.upkeep.resync-sweeps"
        RESYNC_SWEEPS_DEFAULT = 64

        @staticmethod
        def enabled(p: RaftProperties) -> bool:
            return p.get_boolean(
                RaftServerConfigKeys.Upkeep.ENABLED_KEY,
                RaftServerConfigKeys.Upkeep.ENABLED_DEFAULT)

        @staticmethod
        def resync_sweeps(p: RaftProperties) -> int:
            return p.get_int(
                RaftServerConfigKeys.Upkeep.RESYNC_SWEEPS_KEY,
                RaftServerConfigKeys.Upkeep.RESYNC_SWEEPS_DEFAULT)

    class Metrics:
        """Per-server introspection endpoint (the cluster observability
        plane's scrape surface; no 1:1 reference analog — the reference
        exposes dropwizard reporters, operators today scrape Prometheus).
        When the port key is SET the server serves ``GET /metrics``
        (Prometheus text), ``/health`` (liveness + engine tick freshness),
        ``/divisions`` (per-division introspection JSON), and ``/events``
        (the stall watchdog's journal) on 127.0.0.1.  ``0`` binds an
        ephemeral port (the multi-process bench children use it and report
        the bound port to the parent); UNSET (the default) opens no
        listener socket and leaves the request hot paths untouched."""

        HTTP_PORT_KEY = "raft.tpu.metrics.http-port"

        @staticmethod
        def http_port(p: RaftProperties) -> "int | None":
            v = p.get(RaftServerConfigKeys.Metrics.HTTP_PORT_KEY)
            return None if v in (None, "") else int(v)

    class Watchdog:
        """Stall watchdog (ratis_tpu.server.watchdog; no reference analog —
        the closest shape is Borgmon-style derived alerting): a per-server
        sampling task detecting commit-stall (commitIndex flat while
        pending requests > 0), election churn, and follower lag beyond a
        threshold.  Detections append structured events to a bounded ring
        journal served at ``GET /events`` and surfaced by the shell's
        ``health`` subcommand.  Pure background sampling — nothing on the
        request path."""

        ENABLED_KEY = "raft.tpu.watchdog.enabled"
        ENABLED_DEFAULT = True
        INTERVAL_KEY = "raft.tpu.watchdog.interval"
        INTERVAL_DEFAULT = TimeDuration.valueOf("1s")
        JOURNAL_SIZE_KEY = "raft.tpu.watchdog.journal-size"
        JOURNAL_SIZE_DEFAULT = 256
        # follower match-index lag (entries behind the leader commit)
        # beyond which a follower-lag event is journaled
        FOLLOWER_LAG_KEY = "raft.tpu.watchdog.follower-lag-threshold"
        FOLLOWER_LAG_DEFAULT = 4096
        # election timeouts + started elections per sampling interval
        # (server-wide) beyond which an election-churn event is journaled
        CHURN_KEY = "raft.tpu.watchdog.churn-threshold"
        CHURN_DEFAULT = 8

        @staticmethod
        def enabled(p: RaftProperties) -> bool:
            return p.get_boolean(
                RaftServerConfigKeys.Watchdog.ENABLED_KEY,
                RaftServerConfigKeys.Watchdog.ENABLED_DEFAULT)

        @staticmethod
        def interval(p: RaftProperties) -> TimeDuration:
            return p.get_time_duration(
                RaftServerConfigKeys.Watchdog.INTERVAL_KEY,
                RaftServerConfigKeys.Watchdog.INTERVAL_DEFAULT)

        @staticmethod
        def journal_size(p: RaftProperties) -> int:
            return p.get_int(
                RaftServerConfigKeys.Watchdog.JOURNAL_SIZE_KEY,
                RaftServerConfigKeys.Watchdog.JOURNAL_SIZE_DEFAULT)

        @staticmethod
        def follower_lag_threshold(p: RaftProperties) -> int:
            return p.get_int(
                RaftServerConfigKeys.Watchdog.FOLLOWER_LAG_KEY,
                RaftServerConfigKeys.Watchdog.FOLLOWER_LAG_DEFAULT)

        @staticmethod
        def churn_threshold(p: RaftProperties) -> int:
            return p.get_int(
                RaftServerConfigKeys.Watchdog.CHURN_KEY,
                RaftServerConfigKeys.Watchdog.CHURN_DEFAULT)

    class Telemetry:
        """Continuous telemetry (ratis_tpu.metrics.timeseries /
        ratis_tpu.metrics.flight; reference analog: the per-server
        rate/percentile registries of ratis-metrics,
        RaftServerMetricsImpl — operators see trends, not samples).  A
        per-server background sampler takes registry deltas at a fixed
        cadence into bounded ring buffers, derives rates (commits/s,
        acks/s, rewinds/s, engine occupancy) and log2-bucket latency
        quantiles, and tracks a space-saving top-k hot-group sketch
        (commits + pending per group) served at ``GET /timeseries``
        (``?since=`` incremental) and ``GET /hotgroups``.  The flight
        recorder keeps the last window of samples + watchdog events +
        recent trace spans and dumps a replayable JSON artifact on
        watchdog degradation, chaos scenario failure, SIGTERM, or
        explicit request (``GET /flightrecorder``).  With ``enabled``
        unset (the default) no sampler task is created and every
        request path is untouched."""

        ENABLED_KEY = "raft.tpu.telemetry.enabled"
        ENABLED_DEFAULT = False
        INTERVAL_KEY = "raft.tpu.telemetry.interval"
        INTERVAL_DEFAULT = TimeDuration.valueOf("1s")
        # ring window: samples retained = window / interval (bounded)
        WINDOW_KEY = "raft.tpu.telemetry.window"
        WINDOW_DEFAULT = TimeDuration.valueOf("120s")
        # space-saving sketch size: top-k hot groups tracked exactly
        # enough (error bound <= total/k rides along in the payload)
        HOT_GROUPS_KEY = "raft.tpu.telemetry.hot-groups"
        HOT_GROUPS_DEFAULT = 16
        # flight-recorder artifacts land here; "" = serve /flightrecorder
        # on request but never write dump files on triggers
        FLIGHT_DIR_KEY = "raft.tpu.telemetry.flight-dir"
        FLIGHT_DIR_DEFAULT = ""

        @staticmethod
        def enabled(p: RaftProperties) -> bool:
            return p.get_boolean(
                RaftServerConfigKeys.Telemetry.ENABLED_KEY,
                RaftServerConfigKeys.Telemetry.ENABLED_DEFAULT)

        @staticmethod
        def interval(p: RaftProperties) -> TimeDuration:
            return p.get_time_duration(
                RaftServerConfigKeys.Telemetry.INTERVAL_KEY,
                RaftServerConfigKeys.Telemetry.INTERVAL_DEFAULT)

        @staticmethod
        def window(p: RaftProperties) -> TimeDuration:
            return p.get_time_duration(
                RaftServerConfigKeys.Telemetry.WINDOW_KEY,
                RaftServerConfigKeys.Telemetry.WINDOW_DEFAULT)

        @staticmethod
        def hot_groups(p: RaftProperties) -> int:
            return p.get_int(
                RaftServerConfigKeys.Telemetry.HOT_GROUPS_KEY,
                RaftServerConfigKeys.Telemetry.HOT_GROUPS_DEFAULT)

        @staticmethod
        def flight_dir(p: RaftProperties) -> str:
            return p.get(RaftServerConfigKeys.Telemetry.FLIGHT_DIR_KEY,
                         RaftServerConfigKeys.Telemetry.FLIGHT_DIR_DEFAULT)

    class Serving:
        """Production serving plane (ratis_tpu.server.serving; reference
        analogs: RaftServerImpl's pending-request element/byte limits and
        resource checks, ReadRequests' readIndex machinery).  Two halves:
        admission control bounds the pending intake per loop shard (count
        and bytes) and sheds overflow with a typed
        ResourceUnavailableException carrying a retry-after hint, so a
        saturated shard degrades into fast typed rejections instead of a
        p99 collapse; the batched-read scheduler coalesces the readIndex
        leadership-confirmation round across every group with pending
        linearizable reads on a shard into one zero-entry append envelope
        per destination peer, amortizing the per-group heartbeat round the
        same way the quorum engine amortizes per-group math.  Admission is
        off by default (every request admitted); read batching is on by
        default and falls back to the scalar per-group confirmation when
        disabled."""

        ADMISSION_ENABLED_KEY = "raft.tpu.serving.admission.enabled"
        ADMISSION_ENABLED_DEFAULT = False
        # per-loop-shard bounds on requests admitted but not yet replied
        PENDING_ELEMENT_LIMIT_KEY = "raft.tpu.serving.admission.pending.element-limit"
        PENDING_ELEMENT_LIMIT_DEFAULT = 8192
        PENDING_BYTE_LIMIT_KEY = "raft.tpu.serving.admission.pending.byte-limit"
        PENDING_BYTE_LIMIT_DEFAULT = "64MB"
        # base retry-after hint carried in shed replies; scaled by overshoot
        RETRY_AFTER_KEY = "raft.tpu.serving.admission.retry-after"
        RETRY_AFTER_DEFAULT = TimeDuration.valueOf("200ms")
        READ_BATCH_ENABLED_KEY = "raft.tpu.serving.read-batch.enabled"
        READ_BATCH_ENABLED_DEFAULT = True
        # extra coalescing delay before a confirmation sweep fires; 0 =
        # coalesce only what arrives in the same event-loop pass
        READ_BATCH_WINDOW_KEY = "raft.tpu.serving.read-batch.window"
        READ_BATCH_WINDOW_DEFAULT = TimeDuration.valueOf("0ms")
        # sustained shed rate (sheds/s over a watchdog interval) above
        # which an overload event is journaled and health degrades
        OVERLOAD_SHED_RATE_KEY = "raft.tpu.serving.overload.shed-rate"
        OVERLOAD_SHED_RATE_DEFAULT = 50.0

        @staticmethod
        def admission_enabled(p: RaftProperties) -> bool:
            return p.get_boolean(
                RaftServerConfigKeys.Serving.ADMISSION_ENABLED_KEY,
                RaftServerConfigKeys.Serving.ADMISSION_ENABLED_DEFAULT)

        @staticmethod
        def pending_element_limit(p: RaftProperties) -> int:
            return p.get_int(
                RaftServerConfigKeys.Serving.PENDING_ELEMENT_LIMIT_KEY,
                RaftServerConfigKeys.Serving.PENDING_ELEMENT_LIMIT_DEFAULT)

        @staticmethod
        def pending_byte_limit(p: RaftProperties) -> int:
            return p.get_size(
                RaftServerConfigKeys.Serving.PENDING_BYTE_LIMIT_KEY,
                RaftServerConfigKeys.Serving.PENDING_BYTE_LIMIT_DEFAULT)

        @staticmethod
        def retry_after(p: RaftProperties) -> TimeDuration:
            return p.get_time_duration(
                RaftServerConfigKeys.Serving.RETRY_AFTER_KEY,
                RaftServerConfigKeys.Serving.RETRY_AFTER_DEFAULT)

        @staticmethod
        def read_batch_enabled(p: RaftProperties) -> bool:
            return p.get_boolean(
                RaftServerConfigKeys.Serving.READ_BATCH_ENABLED_KEY,
                RaftServerConfigKeys.Serving.READ_BATCH_ENABLED_DEFAULT)

        @staticmethod
        def read_batch_window(p: RaftProperties) -> TimeDuration:
            return p.get_time_duration(
                RaftServerConfigKeys.Serving.READ_BATCH_WINDOW_KEY,
                RaftServerConfigKeys.Serving.READ_BATCH_WINDOW_DEFAULT)

        @staticmethod
        def overload_shed_rate(p: RaftProperties) -> float:
            return p.get_float(
                RaftServerConfigKeys.Serving.OVERLOAD_SHED_RATE_KEY,
                RaftServerConfigKeys.Serving.OVERLOAD_SHED_RATE_DEFAULT)

    class Lag:
        """Lag & health ledger (ratis_tpu.engine.ledger; reference analog:
        RaftServerMetrics' per-follower lag gauges on the Metrics SPI,
        here batched over the ``[G, P]`` arrays into one fused pass per
        telemetry tick).  ``threshold`` is the follower-lag line in
        entries-behind-commit shared by the watchdog detector and the
        grey classifier; ``up-window`` separates *grey* (slow but acking)
        from *down* (not acking at all).  The ``grey.*`` knobs shape the
        grey-follower episode detector: a peer is grey when at least
        ``grey.fraction`` of its active links (up links of groups that
        advanced commit this pass, at least ``grey.min-groups`` of them)
        are past the threshold for ``grey.rounds`` consecutive watchdog
        samples while none of its links are down."""

        THRESHOLD_KEY = "raft.tpu.lag.threshold"
        THRESHOLD_DEFAULT = 64
        UP_WINDOW_KEY = "raft.tpu.lag.up-window"
        UP_WINDOW_DEFAULT = TimeDuration.valueOf("3s")
        GREY_FRACTION_KEY = "raft.tpu.lag.grey.fraction"
        GREY_FRACTION_DEFAULT = 0.6
        GREY_MIN_GROUPS_KEY = "raft.tpu.lag.grey.min-groups"
        GREY_MIN_GROUPS_DEFAULT = 4
        GREY_ROUNDS_KEY = "raft.tpu.lag.grey.rounds"
        GREY_ROUNDS_DEFAULT = 2
        # laggard-group list size in GET /lag (and shell lag)
        TOP_GROUPS_KEY = "raft.tpu.lag.top-groups"
        TOP_GROUPS_DEFAULT = 8

        @staticmethod
        def threshold(p: RaftProperties) -> int:
            return p.get_int(RaftServerConfigKeys.Lag.THRESHOLD_KEY,
                             RaftServerConfigKeys.Lag.THRESHOLD_DEFAULT)

        @staticmethod
        def up_window(p: RaftProperties) -> TimeDuration:
            return p.get_time_duration(
                RaftServerConfigKeys.Lag.UP_WINDOW_KEY,
                RaftServerConfigKeys.Lag.UP_WINDOW_DEFAULT)

        @staticmethod
        def grey_fraction(p: RaftProperties) -> float:
            return p.get_float(
                RaftServerConfigKeys.Lag.GREY_FRACTION_KEY,
                RaftServerConfigKeys.Lag.GREY_FRACTION_DEFAULT)

        @staticmethod
        def grey_min_groups(p: RaftProperties) -> int:
            return p.get_int(
                RaftServerConfigKeys.Lag.GREY_MIN_GROUPS_KEY,
                RaftServerConfigKeys.Lag.GREY_MIN_GROUPS_DEFAULT)

        @staticmethod
        def grey_rounds(p: RaftProperties) -> int:
            return p.get_int(
                RaftServerConfigKeys.Lag.GREY_ROUNDS_KEY,
                RaftServerConfigKeys.Lag.GREY_ROUNDS_DEFAULT)

        @staticmethod
        def top_groups(p: RaftProperties) -> int:
            return p.get_int(
                RaftServerConfigKeys.Lag.TOP_GROUPS_KEY,
                RaftServerConfigKeys.Lag.TOP_GROUPS_DEFAULT)

    class Placement:
        """Placement controller (ratis_tpu.placement; reference analog:
        TiKV's Placement Driver pattern over exactly this shape —
        telemetry-scored leadership transfers and read steering on a
        multi-raft host).  ``enabled`` unset (the default) creates
        nothing: no loop, no registry, identical request paths.  When
        on, one scoring pass per ``interval`` consumes the
        already-fetched ledger/sketch data (O(servers + k) python, no
        divisions walk), emits an explainable plan, and actuates it
        rate-limited: at most ``max-transfers-per-round`` leadership
        transfers, each group then held out for ``cooldown``;
        ``hysteresis`` is the extra hot-leads margin a server must
        exceed over its fair share before it sheds (the anti-ping-pong
        band).  ``hot-share`` is the sketch share_min floor for a group
        to count as hot; peers scoring under ``grey-score`` (or inside
        a watchdog grey episode) are steered away from as readIndex
        confirmation targets for ``steer-ttl`` per actuation."""

        ENABLED_KEY = "raft.tpu.placement.enabled"
        ENABLED_DEFAULT = False
        INTERVAL_KEY = "raft.tpu.placement.interval"
        INTERVAL_DEFAULT = TimeDuration.valueOf("2s")
        MAX_TRANSFERS_KEY = "raft.tpu.placement.max-transfers-per-round"
        MAX_TRANSFERS_DEFAULT = 2
        COOLDOWN_KEY = "raft.tpu.placement.cooldown"
        COOLDOWN_DEFAULT = TimeDuration.valueOf("30s")
        HYSTERESIS_KEY = "raft.tpu.placement.hysteresis"
        HYSTERESIS_DEFAULT = 1.0
        HOT_SHARE_KEY = "raft.tpu.placement.hot-share"
        HOT_SHARE_DEFAULT = 0.2
        GREY_SCORE_KEY = "raft.tpu.placement.grey-score"
        GREY_SCORE_DEFAULT = 0.5
        STEER_TTL_KEY = "raft.tpu.placement.steer-ttl"
        STEER_TTL_DEFAULT = TimeDuration.valueOf("10s")
        TRANSFER_TIMEOUT_KEY = "raft.tpu.placement.transfer-timeout"
        TRANSFER_TIMEOUT_DEFAULT = TimeDuration.valueOf("3s")

        @staticmethod
        def enabled(p: RaftProperties) -> bool:
            return p.get_boolean(
                RaftServerConfigKeys.Placement.ENABLED_KEY,
                RaftServerConfigKeys.Placement.ENABLED_DEFAULT)

        @staticmethod
        def interval(p: RaftProperties) -> TimeDuration:
            return p.get_time_duration(
                RaftServerConfigKeys.Placement.INTERVAL_KEY,
                RaftServerConfigKeys.Placement.INTERVAL_DEFAULT)

        @staticmethod
        def max_transfers(p: RaftProperties) -> int:
            return p.get_int(
                RaftServerConfigKeys.Placement.MAX_TRANSFERS_KEY,
                RaftServerConfigKeys.Placement.MAX_TRANSFERS_DEFAULT)

        @staticmethod
        def cooldown(p: RaftProperties) -> TimeDuration:
            return p.get_time_duration(
                RaftServerConfigKeys.Placement.COOLDOWN_KEY,
                RaftServerConfigKeys.Placement.COOLDOWN_DEFAULT)

        @staticmethod
        def hysteresis(p: RaftProperties) -> float:
            return p.get_float(
                RaftServerConfigKeys.Placement.HYSTERESIS_KEY,
                RaftServerConfigKeys.Placement.HYSTERESIS_DEFAULT)

        @staticmethod
        def hot_share(p: RaftProperties) -> float:
            return p.get_float(
                RaftServerConfigKeys.Placement.HOT_SHARE_KEY,
                RaftServerConfigKeys.Placement.HOT_SHARE_DEFAULT)

        @staticmethod
        def grey_score(p: RaftProperties) -> float:
            return p.get_float(
                RaftServerConfigKeys.Placement.GREY_SCORE_KEY,
                RaftServerConfigKeys.Placement.GREY_SCORE_DEFAULT)

        @staticmethod
        def steer_ttl(p: RaftProperties) -> TimeDuration:
            return p.get_time_duration(
                RaftServerConfigKeys.Placement.STEER_TTL_KEY,
                RaftServerConfigKeys.Placement.STEER_TTL_DEFAULT)

        @staticmethod
        def transfer_timeout(p: RaftProperties) -> TimeDuration:
            return p.get_time_duration(
                RaftServerConfigKeys.Placement.TRANSFER_TIMEOUT_KEY,
                RaftServerConfigKeys.Placement.TRANSFER_TIMEOUT_DEFAULT)

    class Chaos:
        """Chaos campaign subsystem (ratis_tpu.chaos; reference analogs:
        RaftExceptionBaseTest, the kill/restart suites over simulated RPC,
        CodeInjectionForTesting): deterministic, seed-replayable fault
        scenarios — link partitions/latency/drop via the transport shim,
        crash/restart with tail truncation, slow-disk/slow-follower
        injection, leader-churn storms — each asserting recovery SLOs and
        journaling every injected fault through the watchdog ``/events``
        plane.  With ``enabled`` unset (the default) no transport ever
        consults the link-fault table and the request paths are
        untouched."""

        ENABLED_KEY = "raft.tpu.chaos.enabled"
        ENABLED_DEFAULT = False
        SEED_KEY = "raft.tpu.chaos.seed"
        SEED_DEFAULT = 0
        # re-election convergence SLO: after a fault heals, every affected
        # group must have a ready leader within this bound
        CONVERGENCE_TIMEOUT_KEY = "raft.tpu.chaos.convergence-timeout"
        CONVERGENCE_TIMEOUT_DEFAULT = TimeDuration.valueOf("30s")
        # post-heal quiesce SLO: replication + apply must drain (commit ==
        # applied on every live replica) within this bound
        RECOVERY_TIMEOUT_KEY = "raft.tpu.chaos.recovery-timeout"
        RECOVERY_TIMEOUT_DEFAULT = TimeDuration.valueOf("120s")
        # failing scenarios write their (seed, scenario, journal) replay
        # artifact here; "" = don't write artifacts
        ARTIFACT_DIR_KEY = "raft.tpu.chaos.artifact-dir"
        ARTIFACT_DIR_DEFAULT = ""

        @staticmethod
        def enabled(p: RaftProperties) -> bool:
            return p.get_boolean(
                RaftServerConfigKeys.Chaos.ENABLED_KEY,
                RaftServerConfigKeys.Chaos.ENABLED_DEFAULT)

        @staticmethod
        def seed(p: RaftProperties) -> int:
            return p.get_int(RaftServerConfigKeys.Chaos.SEED_KEY,
                             RaftServerConfigKeys.Chaos.SEED_DEFAULT)

        @staticmethod
        def convergence_timeout(p: RaftProperties) -> TimeDuration:
            return p.get_time_duration(
                RaftServerConfigKeys.Chaos.CONVERGENCE_TIMEOUT_KEY,
                RaftServerConfigKeys.Chaos.CONVERGENCE_TIMEOUT_DEFAULT)

        @staticmethod
        def recovery_timeout(p: RaftProperties) -> TimeDuration:
            return p.get_time_duration(
                RaftServerConfigKeys.Chaos.RECOVERY_TIMEOUT_KEY,
                RaftServerConfigKeys.Chaos.RECOVERY_TIMEOUT_DEFAULT)

        @staticmethod
        def artifact_dir(p: RaftProperties) -> str:
            return p.get(RaftServerConfigKeys.Chaos.ARTIFACT_DIR_KEY,
                         RaftServerConfigKeys.Chaos.ARTIFACT_DIR_DEFAULT)

    class PauseMonitor:
        """Event-loop pause monitor (reference JvmPauseMonitor.java:38)."""

        ENABLED_KEY = "raft.server.pause.monitor.enabled"
        ENABLED_DEFAULT = True
        INTERVAL_KEY = "raft.server.pause.monitor.interval"
        INTERVAL_DEFAULT = TimeDuration.millis(100)
        WARN_KEY = "raft.server.pause.monitor.warn.threshold"
        WARN_DEFAULT = TimeDuration.millis(500)

        @staticmethod
        def enabled(p: RaftProperties) -> bool:
            return p.get_boolean(
                RaftServerConfigKeys.PauseMonitor.ENABLED_KEY,
                RaftServerConfigKeys.PauseMonitor.ENABLED_DEFAULT)

        @staticmethod
        def interval(p: RaftProperties) -> TimeDuration:
            return p.get_time_duration(
                RaftServerConfigKeys.PauseMonitor.INTERVAL_KEY,
                RaftServerConfigKeys.PauseMonitor.INTERVAL_DEFAULT)

        @staticmethod
        def warn_threshold(p: RaftProperties) -> TimeDuration:
            return p.get_time_duration(
                RaftServerConfigKeys.PauseMonitor.WARN_KEY,
                RaftServerConfigKeys.PauseMonitor.WARN_DEFAULT)

    class Gc:
        """Heap discipline for multi-raft hosts (ratis_tpu.util.gcdiscipline;
        no reference analog — CPython's gen-2 collector over a 10k-group
        heap measured a 52s pause, enough for the pause monitor to depose
        every leader on the server).  Opt-in: tunes GC thresholds at
        server start and, once the group set has been idle for
        ``freeze-idle``, runs one deliberate full collection and freezes
        the surviving heap out of the collector."""

        DISCIPLINE_KEY = "raft.tpu.gc.discipline"
        DISCIPLINE_DEFAULT = False
        FREEZE_IDLE_KEY = "raft.tpu.gc.freeze-idle"
        FREEZE_IDLE_DEFAULT = TimeDuration.valueOf("10s")
        # Steady-state re-seal cadence (0 = off).  A loaded multi-raft host
        # accretes long-lived objects (log entries) that are never garbage
        # but are walked by every young-gen pass: measured at 5-peer x
        # 10240 groups, gen-1 collections burned 0.3-0.5s each COLLECTING
        # ZERO.  Periodic re-freezing moves the accreted live set out of
        # the collector.  Trade (document before enabling): frozen objects
        # are never reclaimed, so workloads that DROP long-lived state
        # (log purge after snapshot) leak it until close.
        REFREEZE_INTERVAL_KEY = "raft.tpu.gc.refreeze-interval"
        REFREEZE_INTERVAL_DEFAULT = TimeDuration.valueOf("0s")

        @staticmethod
        def discipline(p: RaftProperties) -> bool:
            return p.get_boolean(
                RaftServerConfigKeys.Gc.DISCIPLINE_KEY,
                RaftServerConfigKeys.Gc.DISCIPLINE_DEFAULT)

        @staticmethod
        def freeze_idle(p: RaftProperties) -> TimeDuration:
            return p.get_time_duration(
                RaftServerConfigKeys.Gc.FREEZE_IDLE_KEY,
                RaftServerConfigKeys.Gc.FREEZE_IDLE_DEFAULT)

        @staticmethod
        def refreeze_interval(p: RaftProperties) -> TimeDuration:
            return p.get_time_duration(
                RaftServerConfigKeys.Gc.REFREEZE_INTERVAL_KEY,
                RaftServerConfigKeys.Gc.REFREEZE_INTERVAL_DEFAULT)

    class Trace:
        """Host-path tracing (ratis_tpu.trace; no reference analog — the
        reference leans on JVM profilers): per-stage request->commit spans
        recorded into fixed-size ring buffers, exportable as a percentile
        decomposition table and Chrome trace-event JSON (Perfetto).  OFF by
        default; a session is open while ``enabled`` is set or while a
        jax.profiler session is open in the process.  In a session every
        ``sample-every``-th client request is traced end to end and
        process-level stages (rpc codec, sweeps, log batches) sample every
        ``sample-every``-th occurrence of their own."""

        ENABLED_KEY = "raft.tpu.trace.enabled"
        ENABLED_DEFAULT = False
        SAMPLE_EVERY_KEY = "raft.tpu.trace.sample-every"
        SAMPLE_EVERY_DEFAULT = 16
        RING_SIZE_KEY = "raft.tpu.trace.ring-size"
        RING_SIZE_DEFAULT = 4096

        @staticmethod
        def enabled(p: RaftProperties) -> bool:
            return p.get_boolean(
                RaftServerConfigKeys.Trace.ENABLED_KEY,
                RaftServerConfigKeys.Trace.ENABLED_DEFAULT)

        @staticmethod
        def sample_every(p: RaftProperties) -> int:
            return p.get_int(
                RaftServerConfigKeys.Trace.SAMPLE_EVERY_KEY,
                RaftServerConfigKeys.Trace.SAMPLE_EVERY_DEFAULT)

        @staticmethod
        def ring_size(p: RaftProperties) -> int:
            return p.get_int(
                RaftServerConfigKeys.Trace.RING_SIZE_KEY,
                RaftServerConfigKeys.Trace.RING_SIZE_DEFAULT)

    class Notification:
        NO_LEADER_TIMEOUT_KEY = "raft.server.notification.no-leader.timeout"
        NO_LEADER_TIMEOUT_DEFAULT = TimeDuration.valueOf("60s")

        @staticmethod
        def no_leader_timeout(p: RaftProperties) -> TimeDuration:
            return p.get_time_duration(
                RaftServerConfigKeys.Notification.NO_LEADER_TIMEOUT_KEY,
                RaftServerConfigKeys.Notification.NO_LEADER_TIMEOUT_DEFAULT)

    class Replication:
        """Replication-plane batching knobs (new; no reference analog —
        the reference schedules one GrpcLogAppender daemon per (group,
        follower)).  The sweep discipline converts the replication hot
        path from per-request/per-group scheduling to batched sweeps:
        one drain pass per (destination, loop-shard) collects due
        AppendEntries across ALL co-hosted groups, follower ack frames
        batch-decode into one packed engine intake, and commit fan-out
        resolves client waiters through a per-division waterline with one
        scheduled callback per connection instead of one wakeup chain per
        request."""

        # Master switch.  0 reproduces the exact per-request paths of the
        # pre-sweep runtime: per-appender wake->collect->schedule flush
        # loops, scalar QuorumEngine.on_ack per follower reply, and
        # per-request reply-future wakeup chains.
        SWEEP_KEY = "raft.tpu.replication.sweep"
        SWEEP_DEFAULT = 1
        # Commit fan-out collapse (requires sweep=1): resolve client
        # waiters via the per-division commit waterline and deliver
        # replies through the transport's per-connection batcher (one
        # scheduled callback per connection per batch).  0 keeps the
        # per-request reply-future chain while the append sweep and
        # packed ack intake stay on.
        REPLY_FANOUT_KEY = "raft.tpu.replication.reply-fanout"
        REPLY_FANOUT_DEFAULT = 1
        # Pin DataStream packet handling (stream accept/packet-read work)
        # to the owning division's loop shard instead of the primary loop
        # (the attributed structural cause of mixed-rung stream
        # starvation, docs/perf.md).  Only meaningful with
        # raft.tpu.server.loop-shards > 1; 0 keeps the primary-loop path.
        STREAM_SHARDS_KEY = "raft.tpu.replication.stream-shards"
        STREAM_SHARDS_DEFAULT = 1
        # Sequenced append-window pipelining (round 9, reference analog:
        # GrpcLogAppender's per-follower sliding window,
        # GrpcLogAppender.java:343-381, batched across groups): a group may
        # contribute entries to up to this many consecutive in-flight
        # multi-group frames per (destination, loop-shard) lane.  Frames
        # carry lane/sequence numbers and the follower's sweep intake
        # processes them in lane order, so per-group FIFO no longer needs
        # the one-frame-per-group busy latch.  1 = exactly the latched
        # (stop-and-wait per group) behavior — the deterministic fallback
        # and the scalar-reference cost shape.  Only effective with
        # sweep=1 and appender coalescing on.  The depth also multiplies
        # the lane's envelope slots (envelope.inflight above), but only
        # where the follower works on a lane's frames side by side: a
        # transport that takes them in turn would only queue the rest.
        WINDOW_DEPTH_KEY = "raft.tpu.replication.window-depth"
        WINDOW_DEPTH_DEFAULT = 4
        # Follower-side lane intake: frames parked past a sequence HOLE
        # (a lower seq never arrived) are briefly buffered — up to this
        # many per lane — waiting for the gap to fill; beyond it (or
        # after the gap wait times out) the frame is rejected with a
        # rewind hint and the sender re-cuts the lane.  In-order frames
        # queued behind a busy predecessor are ordinary pipelining,
        # bounded separately (RaftServer._LANE_QUEUE_MAX).
        REORDER_BUFFER_KEY = "raft.tpu.replication.reorder-buffer"
        REORDER_BUFFER_DEFAULT = 8

        @staticmethod
        def sweep(p: RaftProperties) -> bool:
            return p.get_int(
                RaftServerConfigKeys.Replication.SWEEP_KEY,
                RaftServerConfigKeys.Replication.SWEEP_DEFAULT) > 0

        @staticmethod
        def reply_fanout(p: RaftProperties) -> bool:
            return p.get_int(
                RaftServerConfigKeys.Replication.REPLY_FANOUT_KEY,
                RaftServerConfigKeys.Replication.REPLY_FANOUT_DEFAULT) > 0

        @staticmethod
        def stream_shards(p: RaftProperties) -> bool:
            return p.get_int(
                RaftServerConfigKeys.Replication.STREAM_SHARDS_KEY,
                RaftServerConfigKeys.Replication.STREAM_SHARDS_DEFAULT) > 0

        @staticmethod
        def window_depth(p: RaftProperties) -> int:
            return max(1, p.get_int(
                RaftServerConfigKeys.Replication.WINDOW_DEPTH_KEY,
                RaftServerConfigKeys.Replication.WINDOW_DEPTH_DEFAULT))

        @staticmethod
        def reorder_buffer(p: RaftProperties) -> int:
            return max(1, p.get_int(
                RaftServerConfigKeys.Replication.REORDER_BUFFER_KEY,
                RaftServerConfigKeys.Replication.REORDER_BUFFER_DEFAULT))

    class TpuLog:
        """Shared log plane (new; no reference analog — the reference gives
        every group its own segment files).  With ``raft.tpu.log.shared``
        on, all divisions pinned to a loop shard interleave into one
        per-shard segment sequence so a replication sweep costs one
        buffered write + one fsync regardless of group count.  Unset
        keeps the per-group segmented store bit-for-bit."""

        SHARED_KEY = "raft.tpu.log.shared"
        SHARED_DEFAULT = 0
        # Roll the interleaved segment at this size.  Larger than the
        # per-group default (8MB): one shard file absorbs every co-hosted
        # group's traffic.
        SHARED_SEGMENT_SIZE_MAX_KEY = "raft.tpu.log.shared.segment.size.max"
        SHARED_SEGMENT_SIZE_MAX_DEFAULT = "32MB"
        # Rewrite a sealed segment once at least this fraction of its bytes
        # is dead (tombstoned / purged / overwritten records).
        COMPACTION_DEAD_RATIO_KEY = "raft.tpu.log.shared.compaction.dead-ratio"
        COMPACTION_DEAD_RATIO_DEFAULT = 0.5

        @staticmethod
        def shared(p: RaftProperties) -> bool:
            return p.get_int(
                RaftServerConfigKeys.TpuLog.SHARED_KEY,
                RaftServerConfigKeys.TpuLog.SHARED_DEFAULT) > 0

        @staticmethod
        def set_shared(p: RaftProperties, v: bool) -> None:
            p.set_int(RaftServerConfigKeys.TpuLog.SHARED_KEY, 1 if v else 0)

        @staticmethod
        def shared_segment_size_max(p: RaftProperties) -> int:
            return p.get_size(
                RaftServerConfigKeys.TpuLog.SHARED_SEGMENT_SIZE_MAX_KEY,
                RaftServerConfigKeys.TpuLog.SHARED_SEGMENT_SIZE_MAX_DEFAULT)

        @staticmethod
        def compaction_dead_ratio(p: RaftProperties) -> float:
            return min(1.0, max(0.05, p.get_float(
                RaftServerConfigKeys.TpuLog.COMPACTION_DEAD_RATIO_KEY,
                RaftServerConfigKeys.TpuLog.COMPACTION_DEAD_RATIO_DEFAULT)))

    class Engine:
        """TPU batched-quorum engine knobs (new; no reference analog — this
        replaces the reference's thread-per-division daemons)."""

        TICK_INTERVAL_KEY = "raft.tpu.engine.tick-interval"
        TICK_INTERVAL_DEFAULT = TimeDuration.millis(2)
        MAX_GROUPS_KEY = "raft.tpu.engine.max-groups"
        MAX_GROUPS_DEFAULT = 1024
        MAX_PEERS_KEY = "raft.tpu.engine.max-peers"
        MAX_PEERS_DEFAULT = 8
        SCALAR_FALLBACK_THRESHOLD_KEY = "raft.tpu.engine.scalar-fallback-threshold"
        SCALAR_FALLBACK_THRESHOLD_DEFAULT = 16  # below this many groups, skip device dispatch
        # Shard the resident engine state over this many local devices
        # (jax.sharding.Mesh over the group axis; ratis_tpu.parallel.mesh).
        # 0 = single-device.  Each device owns one contiguous slice of the
        # group batch and receives only its slice's packed events; group
        # capacity is auto-padded up to the next mesh multiple (padded
        # rows stay masked invalid), so any max-groups value is legal.
        MESH_DEVICES_KEY = "raft.tpu.engine.mesh-devices"
        MESH_DEVICES_DEFAULT = 0
        # When set, the server runs inside a jax.profiler session written
        # to this directory from start() to close() (XLA device ops, and on
        # the host plane the program's own ratis:* spans: a profiler session
        # is a trace session, ratis_tpu.trace.profile_dir_session).  Empty =
        # no profiling.  SURVEY §5 tracing.
        PROFILE_DIR_KEY = "raft.tpu.engine.profile-dir"
        PROFILE_DIR_DEFAULT = ""

        @staticmethod
        def tick_interval(p: RaftProperties) -> TimeDuration:
            return p.get_time_duration(RaftServerConfigKeys.Engine.TICK_INTERVAL_KEY,
                                       RaftServerConfigKeys.Engine.TICK_INTERVAL_DEFAULT)

        @staticmethod
        def max_groups(p: RaftProperties) -> int:
            return p.get_int(RaftServerConfigKeys.Engine.MAX_GROUPS_KEY,
                             RaftServerConfigKeys.Engine.MAX_GROUPS_DEFAULT)

        @staticmethod
        def max_peers(p: RaftProperties) -> int:
            return p.get_int(RaftServerConfigKeys.Engine.MAX_PEERS_KEY,
                             RaftServerConfigKeys.Engine.MAX_PEERS_DEFAULT)

        @staticmethod
        def mesh_devices(p: RaftProperties) -> int:
            return p.get_int(RaftServerConfigKeys.Engine.MESH_DEVICES_KEY,
                             RaftServerConfigKeys.Engine.MESH_DEVICES_DEFAULT)

        @staticmethod
        def profile_dir(p: RaftProperties) -> str:
            return p.get(RaftServerConfigKeys.Engine.PROFILE_DIR_KEY,
                         RaftServerConfigKeys.Engine.PROFILE_DIR_DEFAULT)


class GrpcConfigKeys:
    """gRPC transport keys (reference GrpcConfigKeys, ratis-grpc/.../
    GrpcConfigKeys.java; TLS block maps GrpcTlsConfig)."""

    PREFIX = "raft.grpc"

    # Separate client/admin plane endpoint (reference GrpcConfigKeys.Client/
    # Admin port split, GrpcServicesImpl.java:197): when set, client requests
    # are served on this port while server-to-server RPC stays on the main
    # address. "" = share the main port.
    CLIENT_PORT_KEY = "raft.grpc.client.port"

    # Dedicated ADMIN endpoint (the reference optionally runs THREE gRPC
    # servers — server/client/admin — each with its own TLS,
    # GrpcServicesImpl.java:56,197-224).  When set, admin request types are
    # served on this port (and ONLY admin types; data-plane requests are
    # rejected there).  "" = admin shares the client (or main) endpoint.
    ADMIN_PORT_KEY = "raft.grpc.admin.port"

    @staticmethod
    def client_port(p: RaftProperties):
        v = p.get(GrpcConfigKeys.CLIENT_PORT_KEY)
        return int(v) if v else None

    @staticmethod
    def admin_port(p: RaftProperties):
        v = p.get(GrpcConfigKeys.ADMIN_PORT_KEY)
        return int(v) if v else None

    class Tls:
        ENABLED_KEY = "raft.grpc.tls.enabled"
        ENABLED_DEFAULT = False
        CERT_CHAIN_KEY = "raft.grpc.tls.cert.chain.path"
        PRIVATE_KEY_KEY = "raft.grpc.tls.private.key.path"
        TRUST_ROOT_KEY = "raft.grpc.tls.trust.root.path"
        MUTUAL_AUTH_KEY = "raft.grpc.tls.mutual.auth.enabled"
        MUTUAL_AUTH_DEFAULT = False
        NAME_OVERRIDE_KEY = "raft.grpc.tls.target.name.override"

        @staticmethod
        def enabled(p: RaftProperties) -> bool:
            return p.get_boolean(GrpcConfigKeys.Tls.ENABLED_KEY,
                                 GrpcConfigKeys.Tls.ENABLED_DEFAULT)

        @staticmethod
        def cert_chain(p: RaftProperties):
            return p.get(GrpcConfigKeys.Tls.CERT_CHAIN_KEY)

        @staticmethod
        def private_key(p: RaftProperties):
            return p.get(GrpcConfigKeys.Tls.PRIVATE_KEY_KEY)

        @staticmethod
        def trust_root(p: RaftProperties):
            return p.get(GrpcConfigKeys.Tls.TRUST_ROOT_KEY)

        @staticmethod
        def mutual_auth(p: RaftProperties) -> bool:
            return p.get_boolean(GrpcConfigKeys.Tls.MUTUAL_AUTH_KEY,
                                 GrpcConfigKeys.Tls.MUTUAL_AUTH_DEFAULT)

        @staticmethod
        def name_override(p: RaftProperties):
            return p.get(GrpcConfigKeys.Tls.NAME_OVERRIDE_KEY)

    class AdminTls:
        """Admin-endpoint TLS override (the reference's admin server takes
        its own GrpcTlsConfig, GrpcServicesImpl.java:56,219-224).  When not
        enabled, the admin endpoint inherits the main Tls block."""

        ENABLED_KEY = "raft.grpc.admin.tls.enabled"
        ENABLED_DEFAULT = False
        CERT_CHAIN_KEY = "raft.grpc.admin.tls.cert.chain.path"
        PRIVATE_KEY_KEY = "raft.grpc.admin.tls.private.key.path"
        TRUST_ROOT_KEY = "raft.grpc.admin.tls.trust.root.path"
        MUTUAL_AUTH_KEY = "raft.grpc.admin.tls.mutual.auth.enabled"
        MUTUAL_AUTH_DEFAULT = False

        @staticmethod
        def enabled(p: RaftProperties) -> bool:
            return p.get_boolean(GrpcConfigKeys.AdminTls.ENABLED_KEY,
                                 GrpcConfigKeys.AdminTls.ENABLED_DEFAULT)

        @staticmethod
        def cert_chain(p: RaftProperties):
            return p.get(GrpcConfigKeys.AdminTls.CERT_CHAIN_KEY)

        @staticmethod
        def private_key(p: RaftProperties):
            return p.get(GrpcConfigKeys.AdminTls.PRIVATE_KEY_KEY)

        @staticmethod
        def trust_root(p: RaftProperties):
            return p.get(GrpcConfigKeys.AdminTls.TRUST_ROOT_KEY)

        @staticmethod
        def mutual_auth(p: RaftProperties) -> bool:
            return p.get_boolean(GrpcConfigKeys.AdminTls.MUTUAL_AUTH_KEY,
                                 GrpcConfigKeys.AdminTls.MUTUAL_AUTH_DEFAULT)


class WireConfigKeys:
    """Wire hot-path write coalescing of the gRPC transport (no reference
    analog — the reference pays one HTTP2 flush per message and amortizes
    via one stream per (group, follower), GrpcLogAppender.java:343-381;
    this framework folds RPCs instead).  The TCP transport has no key: it
    writes what a loop pass queued on a connection in one socket write
    (transport/tcp.py)."""

    class Grpc:
        """Stream-framing coalescing for the grpc.aio transport: one bidi
        stream message carries up to ``flush-chunks`` append/request chunks
        (VERDICT r5 item 6 — grpc.aio's per-message Python+C-core cost was
        the residual gap vs TCP), gathered for at most ``flush-micros``.
        0µs = coalescing off: one chunk per stream message, the wire shape
        of previous rounds."""

        FLUSH_MICROS_KEY = "raft.tpu.grpc.flush-micros"
        FLUSH_MICROS_DEFAULT = 0
        FLUSH_CHUNKS_KEY = "raft.tpu.grpc.flush-chunks"
        FLUSH_CHUNKS_DEFAULT = 64

        @staticmethod
        def flush_micros(p: RaftProperties) -> int:
            return p.get_int(WireConfigKeys.Grpc.FLUSH_MICROS_KEY,
                             WireConfigKeys.Grpc.FLUSH_MICROS_DEFAULT)

        @staticmethod
        def flush_chunks(p: RaftProperties) -> int:
            return p.get_int(WireConfigKeys.Grpc.FLUSH_CHUNKS_KEY,
                             WireConfigKeys.Grpc.FLUSH_CHUNKS_DEFAULT)


class NettyConfigKeys:
    """Raw-TCP (netty-analog) transport keys (reference NettyConfigKeys,
    ratis-netty/.../NettyConfigKeys.java; the TLS block mirrors what the
    reference's gRPC transport gets from GrpcTlsConfig — the netty analog
    here supports TLS so no transport is plaintext-only)."""

    PREFIX = "raft.netty"

    class Tls:
        ENABLED_KEY = "raft.netty.tls.enabled"
        ENABLED_DEFAULT = False
        CERT_CHAIN_KEY = "raft.netty.tls.cert.chain.path"
        PRIVATE_KEY_KEY = "raft.netty.tls.private.key.path"
        TRUST_ROOT_KEY = "raft.netty.tls.trust.root.path"
        MUTUAL_AUTH_KEY = "raft.netty.tls.mutual.auth.enabled"
        MUTUAL_AUTH_DEFAULT = False

        @staticmethod
        def enabled(p: RaftProperties) -> bool:
            return p.get_boolean(NettyConfigKeys.Tls.ENABLED_KEY,
                                 NettyConfigKeys.Tls.ENABLED_DEFAULT)

        @staticmethod
        def cert_chain(p: RaftProperties):
            return p.get(NettyConfigKeys.Tls.CERT_CHAIN_KEY)

        @staticmethod
        def private_key(p: RaftProperties):
            return p.get(NettyConfigKeys.Tls.PRIVATE_KEY_KEY)

        @staticmethod
        def trust_root(p: RaftProperties):
            return p.get(NettyConfigKeys.Tls.TRUST_ROOT_KEY)

        @staticmethod
        def mutual_auth(p: RaftProperties) -> bool:
            return p.get_boolean(NettyConfigKeys.Tls.MUTUAL_AUTH_KEY,
                                 NettyConfigKeys.Tls.MUTUAL_AUTH_DEFAULT)

    class DataStreamTls:
        """TLS for the DataStream transport (reference NettyServerStreamRpc
        takes its own TlsConfig, ratis-netty/.../NettyServerStreamRpc.java);
        separate block because the stream plane often terminates TLS
        differently from the RPC plane."""

        ENABLED_KEY = "raft.datastream.tls.enabled"
        ENABLED_DEFAULT = False
        CERT_CHAIN_KEY = "raft.datastream.tls.cert.chain.path"
        PRIVATE_KEY_KEY = "raft.datastream.tls.private.key.path"
        TRUST_ROOT_KEY = "raft.datastream.tls.trust.root.path"
        MUTUAL_AUTH_KEY = "raft.datastream.tls.mutual.auth.enabled"
        MUTUAL_AUTH_DEFAULT = False

        @staticmethod
        def enabled(p: RaftProperties) -> bool:
            return p.get_boolean(NettyConfigKeys.DataStreamTls.ENABLED_KEY,
                                 NettyConfigKeys.DataStreamTls.ENABLED_DEFAULT)

        @staticmethod
        def cert_chain(p: RaftProperties):
            return p.get(NettyConfigKeys.DataStreamTls.CERT_CHAIN_KEY)

        @staticmethod
        def private_key(p: RaftProperties):
            return p.get(NettyConfigKeys.DataStreamTls.PRIVATE_KEY_KEY)

        @staticmethod
        def trust_root(p: RaftProperties):
            return p.get(NettyConfigKeys.DataStreamTls.TRUST_ROOT_KEY)

        @staticmethod
        def mutual_auth(p: RaftProperties) -> bool:
            return p.get_boolean(
                NettyConfigKeys.DataStreamTls.MUTUAL_AUTH_KEY,
                NettyConfigKeys.DataStreamTls.MUTUAL_AUTH_DEFAULT)

        @staticmethod
        def tls_config(p):
            """Build the stream-plane TLS config (or None when disabled);
            the single source both the server (DataStreamManagement) and
            the client (DataStreamOutput) construct from."""
            if p is None or not NettyConfigKeys.DataStreamTls.enabled(p):
                return None
            from ratis_tpu.transport.tcp import TcpTlsConfig
            K = NettyConfigKeys.DataStreamTls
            return TcpTlsConfig(cert_chain_path=K.cert_chain(p),
                                private_key_path=K.private_key(p),
                                trust_root_path=K.trust_root(p),
                                mutual_auth=K.mutual_auth(p))


class RaftClientConfigKeys:
    PREFIX = "raft.client"

    class Rpc:
        REQUEST_TIMEOUT_KEY = "raft.client.rpc.request.timeout"
        REQUEST_TIMEOUT_DEFAULT = TimeDuration.valueOf("3s")
        WATCH_REQUEST_TIMEOUT_KEY = "raft.client.rpc.watch.request.timeout"
        WATCH_REQUEST_TIMEOUT_DEFAULT = TimeDuration.valueOf("10s")

        @staticmethod
        def request_timeout(p: RaftProperties) -> TimeDuration:
            return p.get_time_duration(RaftClientConfigKeys.Rpc.REQUEST_TIMEOUT_KEY,
                                       RaftClientConfigKeys.Rpc.REQUEST_TIMEOUT_DEFAULT)

        @staticmethod
        def watch_request_timeout(p: RaftProperties) -> TimeDuration:
            return p.get_time_duration(
                RaftClientConfigKeys.Rpc.WATCH_REQUEST_TIMEOUT_KEY,
                RaftClientConfigKeys.Rpc.WATCH_REQUEST_TIMEOUT_DEFAULT)

    class Async:
        OUTSTANDING_REQUESTS_MAX_KEY = "raft.client.async.outstanding-requests.max"
        OUTSTANDING_REQUESTS_MAX_DEFAULT = 100

        @staticmethod
        def outstanding_requests_max(p: RaftProperties) -> int:
            return p.get_int(RaftClientConfigKeys.Async.OUTSTANDING_REQUESTS_MAX_KEY,
                             RaftClientConfigKeys.Async.OUTSTANDING_REQUESTS_MAX_DEFAULT)

    class MessageStream:
        SUBMESSAGE_SIZE_KEY = "raft.client.message-stream.submessage-size"
        SUBMESSAGE_SIZE_DEFAULT = "1MB"

        @staticmethod
        def submessage_size(p: RaftProperties) -> int:
            return p.get_size(RaftClientConfigKeys.MessageStream.SUBMESSAGE_SIZE_KEY,
                              RaftClientConfigKeys.MessageStream.SUBMESSAGE_SIZE_DEFAULT)
