"""Environment-driven configuration flags.

TPU-native analogue of the reference engine's env-flag system
(reference: bodo/__init__.py:109-236 — ~30 BODO_* flags read once at import
into module globals). We keep the same "read once, module-global" model but
expose a typed dataclass so tests can override via `set_config`.
"""

from __future__ import annotations

import os
from dataclasses import dataclass, field, fields


def _env_bool(name: str, default: bool) -> bool:
    v = os.environ.get(name)
    if v is None:
        return default
    return v.strip().lower() not in ("0", "false", "no", "off", "")


def _env_int(name: str, default: int) -> int:
    v = os.environ.get(name)
    return int(v) if v not in (None, "") else default


def _env_float(name: str, default: float) -> float:
    v = os.environ.get(name)
    return float(v) if v not in (None, "") else default


def _env_str(name: str, default: str) -> str:
    return os.environ.get(name, default)


@dataclass
class Config:
    # -- execution -----------------------------------------------------------
    # Rows per streaming batch fed through the pipeline executor (analogue of
    # the reference's bodosql_streaming_batch_size, bodo/__init__.py:114).
    streaming_batch_size: int = field(
        default_factory=lambda: _env_int("BODO_TPU_STREAMING_BATCH_SIZE", 1 << 22)
    )
    # Streaming batch executor: batch-at-a-time pipelines with bounded
    # device memory (plan/streaming.py). Off by default; whole-table
    # execution is faster when everything fits in device memory.
    stream_exec: bool = field(
        default_factory=lambda: _env_bool("BODO_TPU_STREAM_EXEC", False)
    )
    # Whole-stage fusion (plan/fusion.py): compile maximal chains of
    # adjacent pipeline-compatible plan nodes (filter/project, with an
    # optional dense-aggregate root) into ONE jitted/shard_map program,
    # so intermediate tables never materialize and per-node host syncs
    # (filter count reads, rebuckets) collapse into a single group-exit
    # sync. Off → every node dispatches its own kernel (pre-fusion
    # behavior, also the fallback for non-fusable expressions).
    fusion: bool = field(
        default_factory=lambda: _env_bool("BODO_TPU_FUSION", True)
    )
    # Fused join groups (plan/fusion_join.py): extend whole-stage fusion
    # across join-probe and shuffle boundaries — the probe (and any
    # filter/project chain around it, plus an optional terminal dense
    # aggregate) compiles into ONE jit/shard_map program over a
    # device-resident build-side hash table, with the bucket shuffle's
    # lax.all_to_all traced INSIDE the program. Off → joins dispatch
    # per-operator (pre-PR-12 behavior); requires `fusion` too.
    fusion_join: bool = field(
        default_factory=lambda: _env_bool("BODO_TPU_FUSION_JOIN", True)
    )
    # Pad table capacities up to a multiple of this (TPU lane friendliness).
    capacity_round: int = field(
        default_factory=lambda: _env_int("BODO_TPU_CAPACITY_ROUND", 128)
    )
    # Mesh axis used for row sharding.
    data_axis: str = field(default_factory=lambda: _env_str("BODO_TPU_DATA_AXIS", "d"))
    # Skew headroom factor for all_to_all shuffle bucket capacity.
    shuffle_skew_factor: float = field(
        default_factory=lambda: _env_float("BODO_TPU_SHUFFLE_SKEW", 2.0)
    )
    # Dense (sort-free) groupby: when the exact product of key ranges is at
    # most this many slots, every row gets a dense slot and the
    # aggregations run per slot (no lax.sort). How they run is
    # relational.dense_route's choice by shape, not a setting: masked
    # reductions over the rows up to relational.DENSE_REDUCE_MAX_SLOTS
    # slots, segment scatters above (~4M slots * 8B * a few columns of
    # transient dense arrays), the MXU one-hot matmul for float32 values
    # with Pallas on.
    dense_groupby_max_slots: int = field(
        default_factory=lambda: _env_int("BODO_TPU_DENSE_GROUPBY_SLOTS",
                                         1 << 22)
    )
    # Scatter-claim hash groupby/join (ops/hashtable.py): sort-free
    # group ids / join LUTs at arbitrary key cardinality.
    hash_groupby: bool = field(
        default_factory=lambda: _env_bool("BODO_TPU_HASH_GROUPBY", True)
    )
    hash_join: bool = field(
        default_factory=lambda: _env_bool("BODO_TPU_HASH_JOIN", True)
    )
    # Broadcast-join threshold: a sharded build side of at most this many
    # rows, under a probe of more than four times as many, is replicated
    # instead of hash-shuffled (analogue of broadcast join, reference
    # bodo/libs/_shuffle.h:153-210). Replicated means `Table.gather`
    # today: every column copied device to host, repacked with numpy,
    # and put back on the default device (no `all_gather`; only a fused
    # join group gathers a build inside its program,
    # plan/fusion_join.py). `bodo:exchange.broadcast` times it.
    bcast_join_threshold: int = field(
        default_factory=lambda: _env_int("BODO_TPU_BCAST_JOIN_THRESHOLD", 1 << 20)
    )
    # Sources with fewer rows stay replicated (broadcast-join heuristic);
    # larger ones are row-sharded over the mesh.
    shard_min_rows: int = field(
        default_factory=lambda: _env_int("BODO_TPU_SHARD_MIN_ROWS", 100_000)
    )
    # -- pipelined I/O (runtime/io_pool.py) ----------------------------------
    # Batches decoded ahead of the consumer by the streaming sources'
    # Prefetcher (batch k+1 decodes on a host thread while batch k runs
    # on the device). 0 disables prefetching entirely. The effective
    # depth derates under memory-governor pressure (depth x batch bytes
    # is admission-charged against the derived budget).
    prefetch_depth: int = field(
        default_factory=lambda: _env_int("BODO_TPU_PREFETCH_DEPTH", 2)
    )
    # Workers in the shared I/O thread pool used for parallel parquet
    # row-group decode and CSV chunk parse. <= 0 means auto:
    # min(8, cpu_count), at least 2 (Arrow releases the GIL, so decode
    # overlaps file I/O even on one core).
    io_threads: int = field(
        default_factory=lambda: _env_int("BODO_TPU_IO_THREADS", 0)
    )
    # Device-side parquet decode (io/device_decode.py): pool workers
    # ship raw page bytes and jitted XLA programs decode PLAIN
    # fixed-width / dictionary / RLE-bool pages and definition levels
    # directly into device buffers. Columns whose encoding the device
    # programs don't cover (DELTA_*, BYTE_STREAM_SPLIT, non-dict
    # strings, nested) transparently fall back to the host pyarrow
    # decode per column. Off -> every page decodes on host (pre-PR 9
    # behavior).
    device_decode: bool = field(
        default_factory=lambda: _env_bool("BODO_TPU_DEVICE_DECODE", True)
    )
    # Minimum estimated decoded size (uncompressed bytes, from footer
    # row-group metadata) before a read takes the device route. Tiny
    # reads decode faster on host than the program dispatch costs, and
    # every distinct page shape pins an XLA executable — not worth it
    # below ~1 MiB. 0 -> always take the device route when enabled.
    device_decode_min_bytes: int = field(
        default_factory=lambda: _env_int(
            "BODO_TPU_DEVICE_DECODE_MIN_BYTES", 1 << 20)
    )
    # -- observability -------------------------------------------------------
    # 0 = silent, 1 = pushdown/fallback notices, 2 = plan dumps, 3 = kernel trace
    # (analogue of bodo.set_verbose_level, bodo/user_logging.py:1-40).
    verbose_level: int = field(
        default_factory=lambda: _env_int("BODO_TPU_VERBOSE_LEVEL", 0)
    )
    tracing_level: int = field(
        default_factory=lambda: _env_int("BODO_TPU_TRACING_LEVEL", 0)
    )
    # Ring-buffer capacity for trace events (drop-oldest beyond this;
    # dropped events are counted — long-running sessions can't leak).
    trace_events_max: int = field(
        default_factory=lambda: _env_int("BODO_TPU_TRACE_EVENTS_MAX",
                                         100_000)
    )
    # When set, gang runs write the merged multi-rank chrome trace here
    # (trace_gang_<ts>.json); also inherited by spawned workers.
    trace_dir: str = field(
        default_factory=lambda: _env_str("BODO_TPU_TRACE_DIR", "")
    )
    # Communication observatory (parallel/comm.py): per-collective
    # bytes/wall/peer-wait accounting at every host-level dispatch site.
    # On by default — the accounting is a dict update per DISPATCH (not
    # per element).
    comm_accounting: bool = field(
        default_factory=lambda: _env_bool("BODO_TPU_COMM_ACCOUNTING",
                                          True)
    )
    # -- telemetry / flight recorder (runtime/telemetry.py) ------------------
    # Background sampler: one daemon thread snapshotting subsystem stats
    # (governor occupancy, io queue depth, fusion cache, lockstep head,
    # heartbeat age, RSS) into a bounded ring every interval. The knob
    # gates whether ensure_sampler() actually starts the thread; it is
    # called from init_runtime(), spawned workers, and serve().
    telemetry: bool = field(
        default_factory=lambda: _env_bool("BODO_TPU_TELEMETRY", True)
    )
    telemetry_interval_s: float = field(
        default_factory=lambda: _env_float("BODO_TPU_TELEMETRY_INTERVAL",
                                           1.0)
    )
    # Ring capacity (samples kept in memory; 600 x 1s = 10 min window).
    telemetry_ring: int = field(
        default_factory=lambda: _env_int("BODO_TPU_TELEMETRY_RING", 600)
    )
    # HTTP endpoint port for /metrics + /healthz + /debug/flightrecorder
    # (-1 = no server; 0 = bind an ephemeral port). The server is
    # started by telemetry.serve() / init_runtime(), never at import.
    telemetry_port: int = field(
        default_factory=lambda: _env_int("BODO_TPU_TELEMETRY_PORT", -1)
    )
    # Flight recorder: dump a self-contained diagnostic bundle on gang
    # failure, LockstepError, or SIGUSR1.
    flight_recorder: bool = field(
        default_factory=lambda: _env_bool("BODO_TPU_FLIGHT_RECORDER",
                                          True)
    )
    # Bundle destination; empty -> <tempdir>/bodo_tpu_flightrec.
    flight_dir: str = field(
        default_factory=lambda: _env_str("BODO_TPU_FLIGHT_DIR", "")
    )
    # -- numerics ------------------------------------------------------------
    # Pack small-range multi-key groupby/sort keys into one int64 (big
    # sort/shuffle win; disable to force the general lexicographic path).
    pack_keys: bool = field(
        default_factory=lambda: _env_bool("BODO_TPU_PACK_KEYS", True)
    )
    # Streaming device-state budget in MiB (0 = unbounded). When a
    # streaming sort/join's accumulated device state exceeds this, the
    # state is sorted/parked to the spillable host pool via the
    # comptroller (larger-than-HBM streaming; reference analogue:
    # OperatorBufferPool spill thresholds, bodo/libs/_operator_pool.h).
    stream_device_budget_mb: int = field(
        default_factory=lambda: _env_int(
            "BODO_TPU_STREAM_DEVICE_BUDGET_MB", 0)
    )
    # Memory governor (runtime/memory_governor.py): derive a real device
    # budget at mesh init and govern every state-materializing operator
    # against it — admission control, forced spill mode, OOM-retry.
    # When stream_device_budget_mb is set it wins (exact legacy
    # behavior); the governor is the default when nothing is pinned.
    mem_governor: bool = field(
        default_factory=lambda: _env_bool("BODO_TPU_MEM_GOVERNOR", True)
    )
    # Fraction of the probed device memory reserved as headroom (XLA
    # scratch, fragmentation, transient shuffle buffers).
    mem_headroom_frac: float = field(
        default_factory=lambda: _env_float("BODO_TPU_MEM_HEADROOM", 0.15)
    )
    # Largest slice of the derived budget a single operator may hold as
    # device-resident state before its grant forces partitioned/spill
    # mode (the reference's per-operator budget negotiation).
    mem_op_fraction: float = field(
        default_factory=lambda: _env_float("BODO_TPU_MEM_OP_FRACTION", 0.5)
    )
    # -- adaptive query execution (plan/adaptive.py) -------------------------
    # Observe actual cardinalities at stage boundaries and correct the
    # remaining plan: broadcast promote/demote against governor budgets,
    # hot-key splits before all_to_all shuffles, undersized streaming-batch
    # coalescing, and mid-plan join re-ordering on observed rows.
    aqe: bool = field(
        default_factory=lambda: _env_bool("BODO_TPU_AQE", True)
    )
    # Broadcast-join byte budget: replicating a build side is allowed while
    # its observed device bytes stay under this fraction of the governor's
    # derived per-device budget. Larger builds demote to a shuffle join;
    # smaller ones promote to broadcast even when the rows-based
    # bcast_join_threshold planned a shuffle.
    aqe_bcast_frac: float = field(
        default_factory=lambda: _env_float("BODO_TPU_AQE_BCAST_FRAC", 0.05)
    )
    # A sampled join/shuffle key owning at least this fraction of rows is
    # "hot": its rows split off and broadcast-join so the all_to_all only
    # carries the cold remainder.
    aqe_skew_frac: float = field(
        default_factory=lambda: _env_float("BODO_TPU_AQE_SKEW_FRAC", 0.3)
    )
    # Probe sides smaller than this skip skew detection (sampling costs
    # more than any skew it could find).
    aqe_skew_min_rows: int = field(
        default_factory=lambda: _env_int("BODO_TPU_AQE_SKEW_MIN_ROWS",
                                         100_000)
    )
    # Persistent runtime-stats store directory (runtime/stats_store.py):
    # observed cardinalities keyed by normalized subplan fingerprints, so
    # repeated queries start from observed rather than guessed stats.
    # Empty = in-process observations only (no persistence).
    stats_store_dir: str = field(
        default_factory=lambda: _env_str("BODO_TPU_STATS_DIR", "")
    )
    # SQL plan cache directory (analogue BODO_SQL_PLAN_CACHE_DIR).
    sql_plan_cache_dir: str = field(
        default_factory=lambda: _env_str("BODO_TPU_SQL_PLAN_CACHE_DIR", "")
    )
    # -- semantic result cache (runtime/result_cache.py) ---------------------
    # Cache executed results keyed by (structural plan fingerprint,
    # dataset signature) and maintain them incrementally under
    # append-only dataset growth. Off -> no cross-query result reuse at
    # all (per-plan node memoization still applies).
    result_cache: bool = field(
        default_factory=lambda: _env_bool("BODO_TPU_RESULT_CACHE", True)
    )
    # Device-byte budget for cached results. 0 = auto: a fraction of
    # the memory governor's derived device budget (floor 64 MiB).
    result_cache_bytes: int = field(
        default_factory=lambda: _env_int("BODO_TPU_RESULT_CACHE_BYTES", 0)
    )
    # Host-side spill tier: entries evicted under device pressure move
    # to host pandas instead of being dropped, and rehydrate on hit.
    result_cache_host_spill: bool = field(
        default_factory=lambda: _env_bool(
            "BODO_TPU_RESULT_CACHE_HOST_SPILL", True)
    )
    # -- query serving (runtime/scheduler.py, bodo_tpu.serve) ----------------
    # Worker threads draining the per-session queues onto the gang. One
    # worker serializes queries (an SPMD gang runs one program at a
    # time anyway); more overlap host-side planning/IO of one query
    # with device execution of another.
    serve_workers: int = field(
        default_factory=lambda: _env_int("BODO_TPU_SERVE_WORKERS", 1)
    )
    # Per-session bounded queue depth; overflow is a typed Overloaded
    # rejection with a retry-after hint, never an unbounded buffer.
    serve_queue_depth: int = field(
        default_factory=lambda: _env_int("BODO_TPU_SERVE_QUEUE_DEPTH", 32)
    )
    # Total queued requests across all sessions before global shedding.
    serve_max_pending: int = field(
        default_factory=lambda: _env_int("BODO_TPU_SERVE_MAX_PENDING",
                                         256)
    )
    # Admission control from live health/metrics signals (off = every
    # submit is admitted; bounded queues still backpressure).
    serve_admission: bool = field(
        default_factory=lambda: _env_bool("BODO_TPU_SERVE_ADMISSION",
                                          True)
    )
    # Governor occupancy (granted / derived budget) at which new work
    # is shed with Overloaded instead of risking OOM.
    serve_shed_occupancy: float = field(
        default_factory=lambda: _env_float(
            "BODO_TPU_SERVE_SHED_OCCUPANCY", 0.92)
    )
    # Gang comm wait fraction above which comm-wait-dominated sessions
    # (their own EWMA also above this) are backed off.
    serve_comm_wait_frac: float = field(
        default_factory=lambda: _env_float(
            "BODO_TPU_SERVE_COMM_WAIT_FRAC", 0.5)
    )
    # Priority aging rate: every this-many seconds a session's head
    # request has waited discounts one second of its accrued virtual
    # time, bounding starvation of low-weight sessions.
    serve_aging_s: float = field(
        default_factory=lambda: _env_float("BODO_TPU_SERVE_AGING_S", 5.0)
    )
    # Base retry-after hint (seconds) attached to typed rejections
    # (scaled up by rejection severity and measured queue wait).
    serve_retry_after_s: float = field(
        default_factory=lambda: _env_float("BODO_TPU_SERVE_RETRY_AFTER",
                                           0.25)
    )
    # -- fleet serving (runtime/fleet.py, bodo_tpu.fleet) ---------------------
    # Stable identity of THIS gang process within a fleet. Set by the
    # fleet controller in each gang's environment; empty outside fleet
    # mode. Exported on set_config so result-cache ownership, metric
    # labels and flight-recorder manifests all see the same id.
    gang_id: str = field(
        default_factory=lambda: _env_str("BODO_TPU_GANG_ID", "")
    )
    # TCP port for the controller's client listener (-1 = in-process
    # controller only, no listener; 0 = ephemeral).
    fleet_port: int = field(
        default_factory=lambda: _env_int("BODO_TPU_FLEET_PORT", -1)
    )
    # Default gang count for fleet.start() when none is given.
    fleet_gangs: int = field(
        default_factory=lambda: _env_int("BODO_TPU_FLEET_GANGS", 2)
    )
    # Hard cap on a single wire-protocol frame body; an oversized
    # header is a typed ProtocolError, never an attempted allocation.
    fleet_frame_max: int = field(
        default_factory=lambda: _env_int("BODO_TPU_FLEET_FRAME_MAX",
                                         64 << 20)
    )
    # Per-session in-flight quota at the controller; overflow is a
    # typed Overloaded(reason="session_quota"), not an unbounded pile.
    fleet_session_quota: int = field(
        default_factory=lambda: _env_int("BODO_TPU_FLEET_SESSION_QUOTA",
                                         64)
    )
    # -- materialized views (runtime/views.py) -------------------------------
    # Base signature-watcher poll interval for continuous queries; a
    # subscription's max_staleness_s tightens the effective interval
    # (poll at most every max_staleness_s/4, floored at 50 ms).
    view_poll_s: float = field(
        default_factory=lambda: _env_float("BODO_TPU_VIEW_POLL_S", 1.0)
    )
    # Weighted-fair priority of the system maintenance session view
    # refreshes run under (tenants are not billed for shared refreshes;
    # < 1.0 keeps maintenance from starving interactive traffic).
    view_maintenance_weight: float = field(
        default_factory=lambda: _env_float("BODO_TPU_VIEW_MAINT_WEIGHT",
                                           0.5)
    )
    # -- resilience (runtime/resilience.py) ----------------------------------
    # Armed fault-injection spec (see resilience module docstring for the
    # grammar, e.g. "io.read=raise:OSError,collective=raise:Internal:1:0").
    # set_config(faults=...) arms in-process AND exports BODO_TPU_FAULTS
    # so spawned workers inherit the same chaos.
    faults: str = field(
        default_factory=lambda: _env_str("BODO_TPU_FAULTS", "")
    )
    # Retry envelope: attempts / base backoff / overall deadline for
    # transient errors (coordination-service init, filesystem flake,
    # RESOURCE_EXHAUSTED outside the stage envelope).
    retry_attempts: int = field(
        default_factory=lambda: _env_int("BODO_TPU_RETRY_ATTEMPTS", 3)
    )
    retry_base_s: float = field(
        default_factory=lambda: _env_float("BODO_TPU_RETRY_BASE_S", 0.05)
    )
    retry_deadline_s: float = field(
        default_factory=lambda: _env_float("BODO_TPU_RETRY_DEADLINE_S",
                                           30.0)
    )
    # Graceful degradation: when a sharded collective fails with a
    # non-OOM internal error, re-execute the stage replicated (gather
    # inputs, run the REP kernel path) instead of failing the query.
    degrade_replicated: bool = field(
        default_factory=lambda: _env_bool("BODO_TPU_DEGRADE_REPLICATED",
                                          True)
    )
    # Spawn supervision: worker heartbeat cadence and the staleness
    # window after which a silent-but-alive rank is declared hung.
    spawn_hb_interval_s: float = field(
        default_factory=lambda: _env_float("BODO_TPU_SPAWN_HB_INTERVAL",
                                           0.5)
    )
    spawn_hb_timeout_s: float = field(
        default_factory=lambda: _env_float("BODO_TPU_SPAWN_HB_TIMEOUT",
                                           15.0)
    )
    # Gang-level retries of run_spmd when ALL failing ranks look
    # transient (coordination-service init flake).
    spawn_gang_retries: int = field(
        default_factory=lambda: _env_int("BODO_TPU_SPAWN_GANG_RETRIES", 1)
    )
    # -- elastic gangs (runtime/elastic.py) ----------------------------------
    # Master switch for stage-checkpointed shrink-grow recovery: stage
    # boundaries register checkpoints, a lost rank shrinks the mesh and
    # resumes the plan suffix, and the scheduler resumes (not fails)
    # queries that raise a RankLost. set_config(elastic=...) exports
    # BODO_TPU_ELASTIC so spawned workers inherit the posture.
    elastic: bool = field(
        default_factory=lambda: _env_bool("BODO_TPU_ELASTIC", True)
    )
    # Shared checkpoint/control directory for elastic gang runs (the
    # launcher points this at each gang's temp dir; empty = the run's
    # own gang dir only).
    elastic_dir: str = field(
        default_factory=lambda: _env_str("BODO_TPU_ELASTIC_DIR", "")
    )
    # Checkpoint-store byte bound per process (shards beyond the
    # committed frontier are pruned after every commit; resident bytes
    # are charged to the memory governor through an advisory grant).
    elastic_ckpt_bytes: int = field(
        default_factory=lambda: _env_int("BODO_TPU_ELASTIC_CKPT_BYTES",
                                         256 << 20)
    )
    # How many shrinks one gang run may absorb, and the smallest mesh
    # recovery may shrink to before falling back to gang-level retry.
    elastic_max_shrinks: int = field(
        default_factory=lambda: _env_int("BODO_TPU_ELASTIC_MAX_SHRINKS", 2)
    )
    elastic_min_ranks: int = field(
        default_factory=lambda: _env_int("BODO_TPU_ELASTIC_MIN_RANKS", 1)
    )
    # Whole-gang retries after elastic recovery itself fails (a fault
    # during re-mesh must fall back to the existing gang-level retry).
    elastic_gang_retries: int = field(
        default_factory=lambda: _env_int("BODO_TPU_ELASTIC_GANG_RETRIES",
                                         1)
    )
    # Straggler-eviction policy: a rank whose checkpoint frontier trails
    # its peers and has not advanced for this long is evicted like a
    # dead one (0 = never evict stragglers). Attribution prefers the
    # comm observatory's lockstep arrival stamps when available.
    elastic_straggler_s: float = field(
        default_factory=lambda: _env_float("BODO_TPU_ELASTIC_STRAGGLER_S",
                                           0.0)
    )
    # Grace given to an evicted-but-alive rank to exit clean before the
    # parent tears it down (its state stays "evicted" either way).
    elastic_evict_grace_s: float = field(
        default_factory=lambda: _env_float(
            "BODO_TPU_ELASTIC_EVICT_GRACE_S", 2.0)
    )
    # Background grow path: re-admit replacement capacity (a joiner
    # rank at the next stage boundary of a shrunk run; full width at
    # the next query boundary in serving).
    elastic_grow: bool = field(
        default_factory=lambda: _env_bool("BODO_TPU_ELASTIC_GROW", True)
    )
    # Re-form the jax.distributed cluster on the post-shrink mesh (real
    # pods). Off by default: the recovery shuffle moves state through
    # the shared gang dir and must not depend on collectives; the CPU
    # backend has no cross-process collectives to re-form anyway.
    elastic_remesh_distributed: bool = field(
        default_factory=lambda: _env_bool(
            "BODO_TPU_ELASTIC_REMESH_DISTRIBUTED", False)
    )
    # -- shardcheck / SPMD safety (analysis/) --------------------------------
    # Validate every logical plan against the distribution/shape
    # invariants before execution (analysis/plan_validator.py).
    # Violations raise PlanInvariantError instead of wrong answers or a
    # wedged gang; cost is one host-side DFS per plan.
    plan_validate: bool = field(
        default_factory=lambda: _env_bool("BODO_TPU_PLAN_VALIDATE", True)
    )
    # Lockstep debug mode (analysis/lockstep.py): fingerprint every
    # host-level collective dispatch and cross-check sequence/site
    # against peer processes, converting divergent control flow into a
    # structured LockstepError in seconds instead of a gang hang.
    # set_config(lockstep=...) exports BODO_TPU_LOCKSTEP so spawned
    # workers inherit the mode.
    lockstep: bool = field(
        default_factory=lambda: _env_bool("BODO_TPU_LOCKSTEP", False)
    )
    # Shared directory for the per-rank dispatch logs (spawn.py points
    # this at each gang's fresh temp dir; multi-process runs without it
    # disable checking with a warning).
    lockstep_dir: str = field(
        default_factory=lambda: _env_str("BODO_TPU_LOCKSTEP_DIR", "")
    )
    # How long a rank waits for its peers to reach the same dispatch
    # sequence number before declaring divergence.
    lockstep_timeout_s: float = field(
        default_factory=lambda: _env_float("BODO_TPU_LOCKSTEP_TIMEOUT",
                                           10.0)
    )
    # progcheck (analysis/progcheck.py): jaxpr-level verification of
    # every registered program — collective-manifest extraction +
    # rank-invariance, donation/aliasing audit, static HBM peak
    # estimation. Default on (one trace walk per distinct program);
    # violations warn-and-record unless progcheck_enforce raises them
    # as ProgramInvariantError at registration. set_config exports both
    # so spawned workers inherit the posture.
    progcheck: bool = field(
        default_factory=lambda: _env_bool("BODO_TPU_PROGCHECK", True)
    )
    progcheck_enforce: bool = field(
        default_factory=lambda: _env_bool("BODO_TPU_PROGCHECK_ENFORCE",
                                          False)
    )


config = Config()


def set_config(**kwargs) -> None:
    """Override config values at runtime (tests / notebooks)."""
    valid = {f.name for f in fields(Config)}
    for k, v in kwargs.items():
        if k not in valid:
            raise ValueError(f"unknown config key: {k}")
        setattr(config, k, v)
        if k == "faults":
            # arm in-process AND export to the environment so spawned
            # workers (which copy os.environ) inherit the same chaos
            from bodo_tpu.runtime import resilience
            resilience.arm(v or "")
            if v:
                os.environ["BODO_TPU_FAULTS"] = v
            else:
                os.environ.pop("BODO_TPU_FAULTS", None)
        if k == "io_threads":
            # drop the shared executor so the next I/O rebuilds it at
            # the new width
            from bodo_tpu.runtime import io_pool
            io_pool.reset_pool()
        if k in ("result_cache", "result_cache_bytes",
                 "result_cache_host_spill"):
            # re-apply budgets to a live cache (lazy: never imports the
            # module just to reconfigure it); disabling drops entries
            import sys as _sys
            rc = _sys.modules.get("bodo_tpu.runtime.result_cache")
            if rc is not None:
                rc.reconfigure()
        if k.startswith("serve_"):
            # re-size a live scheduler's worker pool / drop its signal
            # snapshot (lazy: never imports the module to reconfigure)
            import sys as _sys
            sch = _sys.modules.get("bodo_tpu.runtime.scheduler")
            if sch is not None:
                sch.reconfigure()
        if k == "gang_id":
            # export like faults so result-cache ownership, metric
            # labels and spawned sub-workers see the same identity
            if v:
                os.environ["BODO_TPU_GANG_ID"] = v
            else:
                os.environ.pop("BODO_TPU_GANG_ID", None)
        if k.startswith("elastic"):
            # export like faults/lockstep so spawned gang workers
            # inherit the recovery posture and checkpoint budget
            env_name = "BODO_TPU_" + k.upper()
            if isinstance(v, bool):
                os.environ[env_name] = "1" if v else "0"
            elif v in ("", None):
                os.environ.pop(env_name, None)
            else:
                os.environ[env_name] = str(v)
        if k == "stats_store_dir":
            # flush + drop the open store so the next lookup re-binds to
            # the new directory
            from bodo_tpu.runtime import stats_store
            stats_store.reset_store()
        if k in ("lockstep", "lockstep_dir", "lockstep_timeout_s"):
            # drop the live checker so the next dispatch rebinds to the
            # new mode/dir; export the env (like faults) so spawned
            # workers inherit the debug mode
            from bodo_tpu.analysis import lockstep as _lockstep
            _lockstep.reset()
            if k == "lockstep":
                if v:
                    os.environ["BODO_TPU_LOCKSTEP"] = "1"
                else:
                    os.environ.pop("BODO_TPU_LOCKSTEP", None)
            if k == "lockstep_dir":
                if v:
                    os.environ["BODO_TPU_LOCKSTEP_DIR"] = v
                else:
                    os.environ.pop("BODO_TPU_LOCKSTEP_DIR", None)
        if k in ("progcheck", "progcheck_enforce"):
            # export so spawned workers inherit the verification
            # posture ("0", not unset: progcheck defaults to on)
            os.environ["BODO_TPU_" + k.upper()] = "1" if v else "0"
        if k == "trace_events_max":
            # rebuild the ring buffer at the new capacity (keeps the
            # newest events)
            from bodo_tpu.utils import tracing
            tracing.resize_events_buffer()
        if k == "trace_dir":
            # export like faults/lockstep so spawned workers inherit it
            if v:
                os.environ["BODO_TPU_TRACE_DIR"] = v
            else:
                os.environ.pop("BODO_TPU_TRACE_DIR", None)
        if k in ("telemetry", "telemetry_interval_s", "flight_recorder",
                 "flight_dir"):
            # export like faults/lockstep/trace_dir so spawned workers
            # inherit the telemetry + flight-recorder posture
            env_name = {
                "telemetry": "BODO_TPU_TELEMETRY",
                "telemetry_interval_s": "BODO_TPU_TELEMETRY_INTERVAL",
                "flight_recorder": "BODO_TPU_FLIGHT_RECORDER",
                "flight_dir": "BODO_TPU_FLIGHT_DIR",
            }[k]
            if isinstance(v, bool):
                os.environ[env_name] = "1" if v else "0"
            elif v in ("", None):
                os.environ.pop(env_name, None)
            else:
                os.environ[env_name] = str(v)
            if k in ("telemetry", "telemetry_interval_s"):
                # rebind a live sampler to the new gate/period (lazy:
                # never imports the module just to reconfigure it)
                import sys as _sys
                tl = _sys.modules.get("bodo_tpu.runtime.telemetry")
                if tl is not None:
                    tl.reconfigure()


def set_verbose_level(level: int) -> None:
    config.verbose_level = int(level)
