"""Env-var driven system configuration.

TPU-native analog of the reference SystemConfig singleton
(include/faabric/util/config.h:12-70, src/util/config.cpp:19-97): a
re-readable (``reset()``) process-wide config sourced from environment
variables, printable for debugging, with test overrides.
"""

from __future__ import annotations

import dataclasses
import os
import threading


def _env(name: str, default: str) -> str:
    return os.environ.get(name, default)


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, default))
    except ValueError:
        return default


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, default))
    except ValueError:
        return default


@dataclasses.dataclass
class SystemConfig:
    # Logging
    log_level: str = "info"
    log_file: str = "off"

    # State
    state_mode: str = "inmemory"  # inmemory | file (shm) | redis
    state_dir: str = "/dev/shm/faabric_tpu_state"
    # Synchronous backups per in-memory state key (ISSUE 19). 1 = every
    # key gets a planner-placed backup host and masters forward dirty
    # chunks before acking; 0 = seed-era single-master semantics (no
    # backups, no epochs on the wire, no fencing).
    state_replicas: int = 1
    # THREADS batches whose snapshots declare merge regions promise their
    # writes stay inside them: trackers then baseline/compare only those
    # pages (writes outside the hints go undetected — opt-in)
    dirty_region_hints: bool = False
    redis_state_host: str = "redis"
    redis_queue_host: str = "redis"
    redis_port: int = 6379

    # Scheduling
    batch_scheduler_mode: str = "bin-pack"  # bin-pack | compact | spot
    # Gang-schedule MPI batches (ISSUE 9): bin-pack consults the world's
    # prospective Topology and prefers FILLING a host with the world's
    # ranks (best fit among hosts that hold the whole remainder) before
    # spilling — fewest hosts, co-located ranks, so the hierarchical
    # collectives get their shm tier. Off → the capacity-blind
    # larger-first order also applies to MPI worlds.
    gang_schedule_mpi: bool = True
    override_cpu_count: int = 0
    override_free_cpu_start: int = 0
    default_mpi_world_size: int = 5

    # Timeouts (seconds)
    global_message_timeout: float = 60.0
    bound_timeout: float = 30.0
    reaper_interval_secs: float = 30.0

    # Endpoint
    endpoint_interface: str = ""
    endpoint_host: str = ""
    endpoint_port: int = 8080
    endpoint_num_threads: int = 4

    # RPC server worker threads per plane
    function_server_threads: int = 2
    state_server_threads: int = 2
    snapshot_server_threads: int = 2
    point_to_point_server_threads: int = 8

    # Dirty tracking: the reference uses mprotect/SIGSEGV, soft-dirty PTEs or
    # userfaultfd on guest memory (src/util/dirty.cpp). Executor memory here
    # is host numpy / device HBM, so tracking is hash-page compare ("hash"),
    # full compare ("compare"), native C++ page compare ("native"), or "none"
    # (everything dirty).
    dirty_tracking_mode: str = "hash"
    diffing_mode: str = "xor"
    delta_snapshot_encoding: str = "pages=4096;xor;zlib=1"

    # Planner
    planner_host: str = "localhost"
    planner_port: int = 8011
    # Hosts expire if they miss keep-alives for this long (reference
    # PlannerConfig.hostTimeout; workers re-register every half-timeout)
    planner_host_timeout: float = 30.0
    # Recovery: per-app requeue budget when a host dies or a dispatch
    # fails, and the base of the exponential requeue backoff
    planner_max_requeues: int = 3
    planner_requeue_backoff: float = 0.2
    # Crash safety (ISSUE 4): directory for the planner's write-ahead
    # journal (empty → journaling disabled, allocation-free no-op), the
    # fsync batching interval, the record count that triggers snapshot
    # compaction, and how long a restarted planner waits for hosts to
    # re-register before requeueing their replayed in-flight messages
    # (0 → defaults to planner_host_timeout)
    planner_journal_dir: str = ""
    planner_journal_fsync_interval: float = 0.05
    planner_journal_compact_records: int = 20000
    planner_reconcile_grace: float = 0.0
    # High-QPS invocation ingress (ISSUE 8): batched scheduling tick
    # period; admission-queue bound (messages); per-source credit cap
    # (outstanding queued messages per source before that source sheds);
    # and how long a queued invocation may wait for capacity before it
    # is failed back to the caller
    planner_tick_ms: float = 5.0
    ingress_queue_max: int = 20000
    ingress_source_credits: int = 8192
    ingress_queue_timeout: float = 30.0

    # MPI fault propagation: while a recv on a watched (MPI) group
    # blocks, the expected sender's host is probed every this many
    # seconds; a refused connection aborts the world within ~one probe
    # interval instead of hanging to the socket timeout
    mpi_abort_check_seconds: float = 2.0

    # Transport
    serialisation: str = "json"

    @classmethod
    def from_env(cls) -> "SystemConfig":
        """Build a config populated from the environment. A plain
        ``SystemConfig(...)`` keeps its constructor arguments / dataclass
        defaults untouched (explicit kwargs are never silently overwritten
        by the environment)."""
        conf = cls()
        conf.reset()
        return conf

    def reset(self) -> None:
        """Re-read every knob from the environment."""
        self.log_level = _env("LOG_LEVEL", "info")
        self.log_file = _env("LOG_FILE", "off")

        self.state_mode = _env("STATE_MODE", "inmemory")
        self.state_dir = _env("STATE_DIR", "/dev/shm/faabric_tpu_state")
        self.state_replicas = _env_int("FAABRIC_STATE_REPLICAS", 1)
        self.redis_state_host = _env("REDIS_STATE_HOST", "redis")
        self.redis_queue_host = _env("REDIS_QUEUE_HOST", "redis")
        self.redis_port = _env_int("REDIS_PORT", 6379)

        self.batch_scheduler_mode = _env("BATCH_SCHEDULER_MODE", "bin-pack")
        self.gang_schedule_mpi = _env(
            "FAABRIC_GANG_SCHEDULE", "1").lower() not in ("0", "false", "off")
        self.override_cpu_count = _env_int("OVERRIDE_CPU_COUNT", 0)
        self.override_free_cpu_start = _env_int("OVERRIDE_FREE_CPU_START", 0)
        self.default_mpi_world_size = _env_int("DEFAULT_MPI_WORLD_SIZE", 5)

        self.global_message_timeout = _env_int("GLOBAL_MESSAGE_TIMEOUT", 60000) / 1000.0
        self.bound_timeout = _env_int("BOUND_TIMEOUT", 30000) / 1000.0
        self.reaper_interval_secs = _env_int("REAPER_INTERVAL_SECS", 30)

        self.endpoint_interface = _env("ENDPOINT_INTERFACE", "")
        self.endpoint_host = _env("ENDPOINT_HOST", "")
        self.endpoint_port = _env_int("ENDPOINT_PORT", 8080)
        self.endpoint_num_threads = _env_int("ENDPOINT_NUM_THREADS", 4)

        self.function_server_threads = _env_int("FUNCTION_SERVER_THREADS", 2)
        self.state_server_threads = _env_int("STATE_SERVER_THREADS", 2)
        self.snapshot_server_threads = _env_int("SNAPSHOT_SERVER_THREADS", 2)
        self.point_to_point_server_threads = _env_int("POINT_TO_POINT_SERVER_THREADS", 8)

        # native (C++ memcmp) brackets a 128 MiB image in ~75 ms vs
        # compare ~170 ms and hash ~300 ms (2-core CPU container);
        # hash still wins when baseline MEMORY matters (8 B/page)
        self.dirty_tracking_mode = _env("DIRTY_TRACKING_MODE", "native")
        self.dirty_region_hints = _env("DIRTY_REGION_HINTS", "0") in (
            "1", "true", "on")
        self.diffing_mode = _env("DIFFING_MODE", "xor")
        self.delta_snapshot_encoding = _env(
            "DELTA_SNAPSHOT_ENCODING", "pages=4096;xor;zlib=1"
        )

        self.planner_host = _env("PLANNER_HOST", "localhost")
        self.planner_port = _env_int("PLANNER_PORT", 8011)
        self.planner_host_timeout = _env_float("PLANNER_HOST_TIMEOUT", 30.0)
        self.planner_max_requeues = _env_int("PLANNER_MAX_REQUEUES", 3)
        self.planner_requeue_backoff = _env_float(
            "PLANNER_REQUEUE_BACKOFF", 0.2)
        self.planner_journal_dir = _env("FAABRIC_PLANNER_JOURNAL_DIR", "")
        self.planner_journal_fsync_interval = _env_float(
            "FAABRIC_PLANNER_JOURNAL_FSYNC_INTERVAL", 0.05)
        self.planner_journal_compact_records = _env_int(
            "FAABRIC_PLANNER_JOURNAL_COMPACT_RECORDS", 20000)
        self.planner_reconcile_grace = _env_float(
            "FAABRIC_PLANNER_RECONCILE_GRACE", 0.0)
        self.planner_tick_ms = _env_float("FAABRIC_PLANNER_TICK_MS", 5.0)
        self.ingress_queue_max = _env_int("FAABRIC_INGRESS_QUEUE_MAX", 20000)
        self.ingress_source_credits = _env_int(
            "FAABRIC_INGRESS_SOURCE_CREDITS", 8192)
        self.ingress_queue_timeout = _env_float(
            "FAABRIC_INGRESS_QUEUE_TIMEOUT", 30.0)
        self.mpi_abort_check_seconds = _env_float(
            "MPI_ABORT_CHECK_SECONDS", 2.0)

        self.serialisation = _env("SERIALISATION", "json")

    def print(self) -> str:
        lines = ["--- System config ---"]
        for f in dataclasses.fields(self):
            lines.append(f"{f.name:<32}{getattr(self, f.name)}")
        out = "\n".join(lines)
        return out

    def get_usable_cores(self) -> int:
        if self.override_cpu_count > 0:
            return self.override_cpu_count
        return os.cpu_count() or 1


_conf: SystemConfig | None = None
_conf_lock = threading.Lock()


def get_system_config() -> SystemConfig:
    global _conf
    if _conf is None:
        with _conf_lock:
            if _conf is None:
                _conf = SystemConfig.from_env()
    return _conf
