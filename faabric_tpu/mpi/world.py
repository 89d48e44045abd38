"""MpiWorld: MPI semantics on the framework's group substrate.

Reference analog: src/mpi/MpiWorld.cpp (2132 lines) and
include/faabric/mpi/MpiWorld.h. One world per app; rank 0 creates the
world by chaining (size-1) functions through the planner
(MpiWorld.cpp:157-226); other ranks join from their dispatched message.

Transport split, re-designed TPU-first:
- **Host path** (this file): rank↔host routing comes from the PTP group
  mappings; send/recv/sendrecv/isend/irecv and the collectives ride the
  PTP broker — same-host ranks through in-process queues, cross-host over
  the PTP RPC plane. Collectives keep the reference's locality-aware
  local-leader trees (broadcast :786-853, reduce :1127-1249, gather
  two-step :917-1080) so cross-host traffic is one leg per host, not per
  rank.
- **Device path** (``device_collectives()``): when buffers are
  device-resident, collectives compile to ``jax.lax`` ops over a
  ``jax.sharding.Mesh`` built from the chips the planner pinned each rank
  to (decision device ids → mesh positions) — see
  parallel/collectives.py. This replaces the reference's per-rank-pair
  TCP mesh (initSendRecvSockets :1789-1934): on TPU the rank mesh IS the
  ICI topology and XLA owns the schedule.
"""

from __future__ import annotations

import os
import threading
import time
from typing import Optional, Sequence

import numpy as np

from faabric_tpu.mpi.types import (
    MpiMessageType,
    MpiOp,
    MpiStatus,
    MpiWirePayload,
    UserOp,
    apply_op,
    apply_op_inplace,
    mpi_dtype_for,
    pack_mpi_payload,
    unpack_mpi_payload,
)
from faabric_tpu.faults import fault_point, faults_enabled
from faabric_tpu.mpi.quant import (
    ALLREDUCE_QUANT,
    leader_ring_codec,
    resolve_quant_mode,
)
from faabric_tpu.telemetry import (
    NULL_SPAN,
    get_collective_profiler,
    get_metrics,
    span,
    tracing_enabled,
)
from faabric_tpu.transport.point_to_point import GroupAbortedError
from faabric_tpu.util.logging import get_logger

logger = get_logger(__name__)

MAIN_RANK = 0

# The MPI-facing name for a group abort: recv/barrier/collectives raise
# this within ~one liveness-check interval (mpi_abort_check_seconds)
# when a peer's host is dead or a send to it failed terminally — the
# transport layer detects and broadcasts the abort (point_to_point.py),
# this is simply its MPI-domain name.
MpiWorldAborted = GroupAbortedError

_FAULTS = faults_enabled()
_FP_COLLECTIVE = fault_point("mpi.collective")

# Ring collectives stream each per-rank segment as a pipeline of
# chunk-sized messages (one bulk frame each): while a rank folds chunk k
# its predecessor already has chunk k+1 on the wire and its successor is
# folding chunk k-1 — serialize/wire/deserialize overlap across hops the
# way HiCCL's pipelined collectives overlap channel stages. This
# replaced the PR 1 RING_MSG_CAP skip-to-fallback: oversized segments
# now chunk instead of bailing to the root-serialized tree. 2 MiB rides
# comfortably inside the shm rings / kernel socket buffers that carry
# the cross-process legs; measured against 4/8 MiB it holds the same
# throughput while cutting blocked-recv (enqueue_wait) time by ~35%.
RING_CHUNK_BYTES = int(os.environ.get("FAABRIC_RING_CHUNK_BYTES",
                                      2 * 1024 * 1024))

# Hierarchical topology-composed collectives (ISSUE 9): compose
# allreduce/reduce_scatter/allgather over the Topology — shm
# reduce-scatter within each host, a cross-host ring over the per-host
# LEADERS only on the striped bulk plane, then redistribution back down
# — so an N-rank world on H hosts puts ~1/(ranks-per-host) of the flat
# ring's bytes on the wire. Values: on (default; composes only when the
# hosts span real machines — see _hier_wins), "force" (compose even
# when every host resolves to this machine: the simulated-host dist
# tests/benches that measure the composition itself), off (flat paths
# always; the A/B baseline). It must agree across every process of a
# world or algorithm choice desyncs and the collective hangs, hence
# env-level with a per-world override that tests set identically on
# all sides.
_hier_env = os.environ.get("FAABRIC_HIER_COLLECTIVES", "1").lower()
HIER_COLLECTIVES = ("force" if _hier_env == "force"
                    else _hier_env not in ("0", "false", "off"))

# Collective schedule compiler (ISSUE 13, mpi/schedule.py): lower
# alltoall/scatter/scatterv/scan into verified step programs executed
# by the generic runner, selected per topology + measured link
# bandwidth. Values: on (default), off (the seed-era hand-written
# paths; the A/B baseline), "force" (compose hierarchically even when
# every host resolves to this machine — the simulated-host dist
# tests/benches). Like FAABRIC_HIER_COLLECTIVES it must agree across
# every process of a world: a desynced schedule choice mismatches the
# message pattern and hangs the collective. The world-level attribute
# ``sched_enabled`` overrides per world (tests set it identically on
# all sides).
_sched_env = os.environ.get("FAABRIC_SCHED_COLLECTIVES", "1").lower()
SCHED_COLLECTIVES = ("force" if _sched_env == "force"
                     else _sched_env not in ("0", "false", "off"))

# Device collective plane (ISSUE 10, faabric_tpu/device_plane/): the
# rung ABOVE the whole host ladder. Routing is opt-in per world — a
# world only has the rung after every rank ran the
# activate_device_plane handshake — so this knob exists for A/B runs
# and emergency disable: "0"/"off" makes activation refuse everywhere
# (must agree across the world's processes like the knobs above).
DEVICE_PLANE_ENABLED = os.environ.get(
    "FAABRIC_DEVICE_PLANE", "1").lower() not in ("0", "false", "off")

_metrics = get_metrics()
_coll_total: dict = {}
_coll_bytes: dict = {}

# Collective phase fold-in (ISSUE 12): every rank records its round
# entry stamp, per-phase durations and total into the collective
# profiler — the store behind /perf's critical-path decomposition and
# the straggler detector. Shared no-op when metrics/profiling are off.
_PROFILER = get_collective_profiler()


def _count_collective(op: str, nbytes: int) -> None:
    if _FAULTS:
        # One chaos choke point covering every host-path collective:
        # delay rules add straggler latency, raise rules fail the rank
        _FP_COLLECTIVE.fire(op=op, bytes=nbytes)
    c = _coll_total.get(op)
    b = _coll_bytes.get(op)
    if c is None or b is None:
        # Both setdefaults run unconditionally: ranks are concurrent
        # threads, and observing one dict populated must not imply the
        # other is (the registry dedupes handles, so racers agree)
        c = _coll_total.setdefault(op, _metrics.counter(
            "faabric_mpi_collectives_total",
            "Host-path collective invocations (per participating rank)",
            op=op))
        b = _coll_bytes.setdefault(op, _metrics.counter(
            "faabric_mpi_collective_bytes_total",
            "Per-rank payload bytes entering host-path collectives",
            op=op))
    c.inc()
    b.inc(nbytes)


class _SendWorker:
    """Daemon FIFO worker for one rank's remote async sends. Daemon so a
    transfer wedged on a dead peer can never hang interpreter exit; FIFO
    so a rank's sends to any one destination stay in order."""

    # _closed orders submits against shutdown's sentinel (see submit);
    # the SimpleQueue itself is internally synchronized
    GUARDS = {"_closed": "_state_lock"}

    def __init__(self, name: str) -> None:
        import queue as _queue

        self._q: "_queue.SimpleQueue" = _queue.SimpleQueue()
        self._closed = False
        self._state_lock = threading.Lock()
        self._t = threading.Thread(target=self._loop, name=name, daemon=True)
        self._t.start()

    def submit(self, fn):
        from concurrent.futures import Future

        fut: Future = Future()
        # Lock orders submits against shutdown's sentinel: either this
        # lands in the FIFO before the None (worker runs it) or _closed
        # is already visible and the future FAILS — never runs inline
        # (inline would reorder past still-queued sends and could block
        # the caller on a wedged peer) and never silently drops (which
        # would hang await_async forever)
        with self._state_lock:
            if self._closed:
                fut.set_exception(RuntimeError(
                    "MPI world closed while async send pending"))
            else:
                self._q.put((fn, fut))
        return fut

    def _loop(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            fn, fut = item
            try:
                fut.set_result(fn())
            except BaseException as e:  # noqa: BLE001 — delivered at wait()
                fut.set_exception(e)

    def shutdown(self) -> None:
        with self._state_lock:
            self._closed = True
            self._q.put(None)


class _LocalMpiPayload:
    """Same-host MPI message: the array object itself rides the queue.
    ``shared`` marks fan-out buffers delivered to several receivers (a
    consumer must copy before exposing them writable)."""

    __slots__ = ("msg_type", "data", "shared", "owned")

    def __init__(self, msg_type: MpiMessageType, data: np.ndarray,
                 shared: bool = False, owned: bool = False) -> None:
        self.msg_type = msg_type
        self.data = data
        self.shared = shared
        # owned=True: the sender TRANSFERRED the buffer — the receiver
        # may fold into it in place. This must ride the payload, not the
        # numpy writeable flag: flags live on the shared array object
        # and a sender restoring its view's writability would race the
        # receiver's flags-based ownership check
        self.owned = owned

    def to_bytes(self) -> bytes:
        """Late wire conversion if routing sends this remote after all
        (e.g. a live-migration remap between send and delivery)."""
        return pack_mpi_payload(self.msg_type, self.data)

    def __len__(self) -> int:
        return len(MpiWirePayload(self.msg_type, self.data))

    def buffers(self) -> list:
        return MpiWirePayload(self.msg_type, self.data).buffers()


class MpiWorld:
    # Concurrency contract (tools/concheck.py): rank bookkeeping and
    # topology caches mutate under the world RLock — collectives on N
    # rank threads share them. Deliberately unlisted: record_exec_graph
    # (configured before traffic starts), _in_send_pool (thread-local),
    # _send_workers (per-rank entries created under _lock in
    # _send_worker(); reads are GIL-atomic dict hits on an add-only
    # dict), _split_seq (only mutated under _lock in _split_draw).
    GUARDS = {
        "_requests": "_lock",
        "_next_request_id": "_lock",
        "_rank_hosts": "_lock",
        "_rank_devices": "_lock",
        "_topology_cache": "_lock",
        "_same_machine_cache": "_lock",
        "_topology_gen": "_lock",
        "_msg_count_to_rank": "_lock",
        "_msg_type_count": "_lock",
        "_device_collectives": "_lock",
        "_device_plane": "_lock",
        "_sched_seen": "_lock",
    }

    def __init__(self, broker, world_id: int, size: int, group_id: int,
                 user: str = "", function: str = "") -> None:
        self.broker = broker
        self.id = world_id
        self.size = size
        self.group_id = group_id
        self.user = user
        self.function = function

        self._lock = threading.RLock()
        # Per-rank async request bookkeeping (reference MpiRankState)
        self._requests: dict[int, dict[int, tuple]] = {}
        self._next_request_id = 1

        # rank → host cache (initLocalRemoteLeaders, MpiWorld.cpp:318-366)
        # and the immutable Topology derived from it (mpi/topology.py);
        # the cache object itself is lock-free to read once handed out
        self._rank_hosts: dict[int, str] = {}
        self._rank_devices: dict[int, int] = {}
        self._topology_cache = None
        self._same_machine_cache: bool | None = None
        self._topology_gen = 0  # bumped by refresh_rank_hosts

        # Hierarchical collective composition (module knob; tests/bench
        # override per world — identically on every process of the world)
        self.hier_enabled = HIER_COLLECTIVES
        # Leader-ring wire quantization (mpi/quant.py): "" or "int8".
        # World-level override of FAABRIC_ALLREDUCE_QUANT — like
        # hier_enabled it must agree across every process of the world
        self.allreduce_quant = ALLREDUCE_QUANT

        # Collective schedule compiler (ISSUE 13): the per-world
        # verified-schedule cache (keys carry the topology generation,
        # so migration remaps invalidate naturally) and the per-RANK
        # selection-round ledger — a rank joins the world-wide
        # selection broadcast exactly when ITS call sequence first
        # meets a key, which is identical on every rank because every
        # rank executes the same collective sequence (see
        # _sched_family). sched_reductions opts the hierarchical
        # reduction LOWERINGS in (with sched_enabled == "force"): the
        # hand-written zero-copy paths stay the tuned default
        # executors; the lowerings exist to prove IR coverage and are
        # bitwise-pinned against them in tests.
        from faabric_tpu.mpi.schedule import ScheduleCache

        self.sched_enabled = SCHED_COLLECTIVES
        self.sched_reductions = False
        self._sched_cache = ScheduleCache()
        self._sched_seen: dict[int, set] = {}

        # Exec-graph accounting (MpiWorld.h:13-18)
        self._msg_count_to_rank: dict[int, int] = {}
        self._msg_type_count: dict[tuple[int, int], int] = {}
        self.record_exec_graph = False

        self._device_collectives = None
        # The device collective plane (faabric_tpu/device_plane/):
        # None until activate_device_plane's handshake resolves the
        # world onto one mesh; cleared on migration remaps
        self._device_plane = None
        self._send_workers: dict[int, _SendWorker] = {}
        self._in_send_pool = threading.local()
        self._split_seq = 0  # split-generation draws (see _split_draw)

        # Bounded-time failure propagation: register with the broker so
        # recvs blocked on this world probe peer liveness and raise
        # MpiWorldAborted instead of hanging to the socket timeout
        # (guarded: some unit tests drive worlds with stub brokers)
        watch = getattr(broker, "watch_group", None)
        if watch is not None:
            watch(group_id)

    def abort(self, reason: str = "MPI_Abort") -> None:
        """Abort the world: every rank's blocked/future recv, barrier or
        collective on it raises MpiWorldAborted. Idempotent; callable
        from any rank or from the runtime when it learns a peer died."""
        abort = getattr(self.broker, "abort_group", None)
        if abort is not None:
            abort(self.group_id, reason)

    # ------------------------------------------------------------------
    # Topology
    # ------------------------------------------------------------------
    def refresh_rank_hosts(self) -> None:
        self.broker.wait_for_mappings(self.group_id)
        # Stub brokers in unit tests may not expose device mappings
        get_dev = getattr(self.broker, "get_device_for_idx", None)
        with self._lock:
            rank_hosts = {
                idx: self.broker.get_host_for_receiver(self.group_id, idx)
                for idx in range(self.size)
            }
            rank_devices = (
                {idx: get_dev(self.group_id, idx)
                 for idx in range(self.size)}
                if get_dev is not None else {})
            # Every local rank refreshes on joining (GuestContext.
            # mpi_world): only a placement that CHANGED is a new
            # generation. Bumping on an identical re-read let a late
            # joiner invalidate the device-plane handshake its siblings
            # were already in — they refused the plane, it did not
            if (rank_hosts, rank_devices) == (self._rank_hosts,
                                              self._rank_devices):
                return
            self._rank_hosts = rank_hosts
            self._rank_devices = rank_devices
            self._topology_cache = None
            self._same_machine_cache = None
            self._topology_gen += 1

    def topology(self):
        """The world's Topology (mpi/topology.py): immutable once built,
        rebuilt lazily after refresh_rank_hosts / migration remaps. The
        collectives' hierarchy decisions and the exported scheduler view
        both read this one object.

        Check-completeness and build-cache happen under ONE lock
        acquisition: a migration remap between them would cache a
        Topology built from a cleared/partial rank map (the same race
        class _all_hosts_same_machine guards with its gen check). A
        remap racing the out-of-lock refresh just sends us around the
        loop again."""
        from faabric_tpu.mpi.topology import Topology

        while True:
            with self._lock:
                if self._topology_cache is not None:
                    return self._topology_cache
                if len(self._rank_hosts) == self.size:
                    devices = (dict(self._rank_devices)
                               if any(d >= 0 for d in
                                      self._rank_devices.values())
                               else None)
                    self._topology_cache = Topology(dict(self._rank_hosts),
                                                    rank_devices=devices)
                    return self._topology_cache
            # Broker RPCs — must not run under _lock
            self.refresh_rank_hosts()

    def host_for_rank(self, rank: int) -> str:
        with self._lock:
            if rank not in self._rank_hosts:
                self.refresh_rank_hosts()
            return self._rank_hosts[rank]

    def ranks_on_host(self, host: str) -> list[int]:
        return list(self.topology().ranks_on_host(host))

    def local_leader(self, host: str) -> int:
        """Lowest rank on a host (reference initLocalRemoteLeaders)."""
        ranks = self.topology().ranks_on_host(host)
        if not ranks:
            raise ValueError(f"No ranks on host {host}")
        return ranks[0]

    def hosts(self) -> list[str]:
        return list(self.topology().hosts)

    def device_for_rank(self, rank: int) -> int:
        self.broker.wait_for_mappings(self.group_id)
        return self.broker.get_device_for_idx(self.group_id, rank)

    # ------------------------------------------------------------------
    # Device path
    # ------------------------------------------------------------------
    def device_collectives(self):
        """Compiled XLA collectives over the mesh of this world's chips
        (rank i ↔ planner-assigned device of rank i)."""
        with self._lock:
            if self._device_collectives is None:
                from faabric_tpu.parallel.collectives import (
                    DeviceCollectives,
                    local_devices_for_ids,
                )

                device_ids = [self.device_for_rank(r) for r in range(self.size)]
                devices = local_devices_for_ids(device_ids)
                self._device_collectives = DeviceCollectives(devices)
            return self._device_collectives

    def device_send_recv(self, x, src_rank: int, dst_rank: int):
        """Device-plane p2p: rank ``src``'s shard lands on rank ``dst``'s
        chip in one compiled ICI transfer (others zero) — the device twin
        of the host send/recv below."""
        return self.device_collectives().send_recv(x, src_rank, dst_rank)

    # ------------------------------------------------------------------
    # Device collective plane (ISSUE 10, faabric_tpu/device_plane/)
    # ------------------------------------------------------------------
    def activate_device_plane(self, rank: int, device=None) -> bool:
        """Collective registration handshake: every rank calls this once
        (after the world forms, or again after a migration remap) with
        its device — default: the planner-assigned chip riding the PTP
        mappings. One host-path allgather exchanges the registrations;
        every rank then derives the SAME activate/fall-back verdict from
        the full row set (device_plane/registry.py), so the dispatch
        ladder can never desync. Returns True when the plane activated:
        from then on eligible allreduce/allgather/reduce_scatter run as
        compiled donated-buffer programs over the resolved mesh and put
        ZERO collective-payload bytes on the host shm/tcp planes."""
        import jax

        from faabric_tpu.device_plane import (
            DevicePlane,
            MeshMismatch,
            registration_row,
            resolve_local_device,
            resolve_mesh,
        )

        if not DEVICE_PLANE_ENABLED:
            return False
        if device is None:
            device = resolve_local_device(self, rank)
        # The handshake is the ONLY wire exchange; it must ride the
        # host ladder even if a previous activation is still live
        # (re-activation after migration), so clear the rung first
        with self._lock:
            gen = self._topology_gen
            plane = self._device_plane
            if plane is not None and plane.topology_gen != gen:
                self._device_plane = None
        rows = self.allgather(rank, registration_row(rank, device))
        with self._lock:
            plane = self._device_plane
            if (plane is not None and plane.topology_gen == gen
                    and plane.disabled_reason is None):
                return True  # a sibling local rank already resolved it
        try:
            devices = resolve_mesh(
                rows, self.size,
                local_ranks=self.ranks_on_host(self.broker.host),
                process_index=jax.process_index())
        except MeshMismatch as e:
            logger.info("Device plane for world %s not activated: %s",
                        self.id, e)
            return False
        plane = DevicePlane(
            self.id, devices,
            local_ranks=self.ranks_on_host(self.broker.host),
            topology_gen=gen)
        with self._lock:
            # First resolver publishes (a re-handshake REPLACES a
            # disabled plane — the collective activation call is the
            # recovery path after a backend error); a topology remap
            # racing the handshake leaves the rung down and reports so
            if self._topology_gen != gen:
                return False  # remap raced the handshake; re-activate
            cur = self._device_plane
            if (cur is None or cur.topology_gen != gen
                    or cur.disabled_reason is not None):
                self._device_plane = plane
        return True

    def device_plane(self):
        """The active DevicePlane rung, or None (host ladder only).
        Stale planes (migration remap bumped the topology generation)
        read as None — mesh mismatch falls back, never desyncs."""
        with self._lock:
            plane = self._device_plane
            if plane is not None and plane.topology_gen != self._topology_gen:
                return None
            return plane

    # ------------------------------------------------------------------
    # Point-to-point
    # ------------------------------------------------------------------
    def send(self, send_rank: int, recv_rank: int, data: np.ndarray,
             msg_type: MpiMessageType = MpiMessageType.NORMAL,
             request_id: int = 0, _copy: bool = True,
             _transfer: bool = False) -> None:
        """``_copy=False`` is for fan-out callers that already hold an
        immutable private buffer (broadcast trees) — skips the per-receiver
        defensive copy. ``_transfer=True`` additionally hands the buffer's
        OWNERSHIP to the receiver (the sender must drop every reference):
        the array stays writable so the receiver can fold into it in
        place (ring allreduce)."""
        if self.record_exec_graph:
            with self._lock:
                self._msg_count_to_rank[recv_rank] = \
                    self._msg_count_to_rank.get(recv_rank, 0) + 1
                key = (int(msg_type), recv_rank)
                self._msg_type_count[key] = self._msg_type_count.get(key, 0) + 1

        # Program order: a blocking send must not overtake this rank's
        # queued async sends to the same destination
        self._fence_sends(send_rank, recv_rank)

        # Same-host ranks skip serialization entirely: one defensive copy
        # (MPI semantics: the sender may reuse its buffer immediately) rides
        # the in-process queue as an array object — the analog of the
        # reference's malloc+memcpy onto the InMemoryMpiQueue
        # (MpiWorld.cpp:620-634), minus the wire pack/unpack copies.
        self.broker.wait_for_mappings(self.group_id)
        if self.broker.get_host_for_receiver(self.group_id, recv_rank) \
                == self.broker.host:
            arr = np.asarray(data)
            if _copy and not _transfer:
                arr = arr.copy()
            if not _transfer:
                arr.flags.writeable = False
            payload = _LocalMpiPayload(msg_type, arr,
                                       shared=not _copy and not _transfer,
                                       owned=_transfer)
        else:
            # Lazy wire form: the bulk plane sends header + array buffer
            # straight from this rank's memory, no concatenation copy.
            # The serialize span exists for the bandwidth-attribution
            # report: with zero-copy framing it SHOULD be ~0, and a fat
            # one (non-contiguous input forcing a copy) is a suspect.
            with span("mpi.wire", "serialize", rank=send_rank) \
                    if tracing_enabled() else NULL_SPAN:
                payload = MpiWirePayload(msg_type, np.asarray(data),
                                         request_id)
        self.broker.send_message(self.group_id, send_rank, recv_rank,
                                 payload, must_order=True)

    def _recv_raw(self, send_rank: int, recv_rank: int,
                  timeout: float | None = None
                  ) -> tuple[np.ndarray, MpiStatus]:
        """Internal receive: the array may be read-only / shared (zero-copy
        local path). Collectives use this — they never mutate received
        buffers in place unless the sender transferred ownership (see
        _recv_raw_owned)."""
        arr, status, _ = self._recv_raw_owned(send_rank, recv_rank,
                                              timeout=timeout)
        return arr, status

    def _recv_raw_owned(self, send_rank: int, recv_rank: int,
                        timeout: float | None = None
                        ) -> tuple[np.ndarray, MpiStatus, bool]:
        """Internal receive + ownership bit: True iff the sender
        TRANSFERRED the buffer (ring fold path), so the receiver may
        mutate it in place."""
        raw = self.broker.recv_message(self.group_id, send_rank, recv_rank,
                                       must_order=True, timeout=timeout)
        if isinstance(raw, _LocalMpiPayload):
            arr = raw.data
            owned = raw.owned
        else:
            _, arr, _req = self._unpack_wire(raw)
            # Wire arrays are exclusively ours but frombuffer-read-only;
            # writable ones (bytearray-backed) may be folded in place
            owned = arr.flags.writeable
        status = MpiStatus(source=send_rank, count=arr.size,
                           dtype=int(mpi_dtype_for(arr.dtype)))
        return arr, status, owned

    @staticmethod
    def _unpack_wire(raw):
        """Wire unpack with a deserialize span for the attribution
        report (zero-copy wrap for bulk-plane buffers; the span being
        fat means the RPC plane's bytes→array copy is the suspect)."""
        with span("mpi.wire", "deserialize", bytes=len(raw)) \
                if tracing_enabled() else NULL_SPAN:
            return unpack_mpi_payload(raw)

    def recv(self, send_rank: int, recv_rank: int,
             timeout: float | None = None) -> tuple[np.ndarray, MpiStatus]:
        """Public receive: the returned buffer is caller-owned and
        writable (MPI semantics)."""
        raw = self.broker.recv_message(self.group_id, send_rank, recv_rank,
                                       must_order=True, timeout=timeout)
        if isinstance(raw, _LocalMpiPayload):
            arr = raw.data
            if raw.shared:
                arr = arr.copy()  # several receivers hold this buffer
            elif not arr.flags.writeable:
                try:
                    # Exclusively ours (the sender's private copy): flip the
                    # owning array back to writable, no copy
                    arr.flags.writeable = True
                except ValueError:
                    arr = arr.copy()
        else:
            _, arr, _req = self._unpack_wire(raw)
            if not arr.flags.writeable:
                # Zero-copy coded-stream delivery (transport/codec.py)
                # shares the receiver's immutable base cache; the
                # public recv contract is a caller-owned writable array
                arr = arr.copy()
        status = MpiStatus(source=send_rank, count=arr.size,
                           dtype=int(mpi_dtype_for(arr.dtype)))
        return arr, status

    def recv_shared(self, send_rank: int, recv_rank: int,
                    timeout: float | None = None
                    ) -> tuple[np.ndarray, MpiStatus]:
        """Zero-copy receive: like ``recv`` but the returned array may
        be READ-ONLY and shared — with other local receivers of a
        fan-out, or with the transport's receive-side delta cache
        (repeated payloads on a coded stream deliver as the SAME
        immutable buffer, ISSUE 11). The faabric analog of serving
        state from the mapped shared-memory region instead of copying
        it out. Safe indefinitely: shared buffers are immutable by
        construction, and a consumer's reference keeps one alive past
        cache eviction. Use for read-only consumers (serving weights,
        assembling into your own destination); call ``recv`` when you
        need a private writable array."""
        return self._recv_raw(send_rank, recv_rank, timeout=timeout)

    def probe(self, send_rank: int, recv_rank: int,
              timeout: float | None = None) -> MpiStatus:
        """Blocking MPI_Probe: status of the next pending message from
        ``send_rank`` without consuming it (reference mpi.h MPI_Probe)."""
        raw = self.broker.probe_message(self.group_id, send_rank, recv_rank,
                                        timeout=timeout)
        return self._status_of(send_rank, raw)

    def iprobe(self, send_rank: int, recv_rank: int) -> Optional[MpiStatus]:
        """Non-blocking MPI_Iprobe: status or None."""
        raw = self.broker.try_probe_message(self.group_id, send_rank,
                                            recv_rank)
        if raw is None:
            return None
        return self._status_of(send_rank, raw)

    @staticmethod
    def _status_of(send_rank: int, raw) -> MpiStatus:
        if isinstance(raw, _LocalMpiPayload):
            return MpiStatus(source=send_rank, count=raw.data.size,
                             dtype=int(mpi_dtype_for(raw.data.dtype)))
        # Wire payload: count/dtype come from the fixed header — probing
        # a pending 100 MiB message must not deserialize it
        import struct as _struct

        from faabric_tpu.mpi.types import MPI_HEADER_FMT, MPI_HEADER_LEN

        _mt, dtype, _, count, _rid = _struct.unpack(
            MPI_HEADER_FMT, bytes(raw[:MPI_HEADER_LEN]))
        return MpiStatus(source=send_rank, count=count, dtype=dtype)

    def sendrecv(self, send_data: np.ndarray, send_rank: int, dst: int,
                 src: int, recv_rank: int) -> tuple[np.ndarray, MpiStatus]:
        """Concurrent send+recv for one rank (reference :752-785 uses an
        async send; sends here never block on the receiver). ``send_rank``
        is the sending index of the outbound message; ``recv_rank`` the
        receiving index of the inbound one (normally the same rank)."""
        self.send(send_rank, dst, send_data, MpiMessageType.SENDRECV)
        return self.recv(src, recv_rank)

    # -- async (reference :496-540 encodes requests + UNACKED buffers;
    # here a registry + per-rank send workers) ---------------------------
    def _send_worker(self, rank: int) -> "_SendWorker":
        """One daemon worker per sending rank: submission order per rank
        keeps (source, dest) streams non-overtaking, and one rank's slow
        transfer never stalls another rank's async sends."""
        with self._lock:
            w = self._send_workers.get(rank)
            if w is None:
                w = _SendWorker(f"mpi/send@{self.id}-r{rank}")
                self._send_workers[rank] = w
            return w

    def _fence_sends(self, rank: int, recv_rank: int) -> None:
        """Order a blocking send after the rank's queued isends TO THE
        SAME DESTINATION (MPI non-overtaking is per (source, dest) pair).
        Skipped on the send worker itself — it IS the queue."""
        if not self._send_workers:
            return  # no remote isend ever issued: nothing to fence
        if getattr(self._in_send_pool, "flag", False):
            return
        with self._lock:
            futs = [entry[1] for entry in
                    self._requests.get(rank, {}).values()
                    if entry[0] == "send" and entry[1] is not None
                    and entry[2] == recv_rank]
        for f in futs:
            f.exception()  # wait; errors surface at wait()

    def isend(self, send_rank: int, recv_rank: int, data: np.ndarray) -> int:
        with self._lock:
            rid = self._next_request_id
            self._next_request_id += 1

        self.broker.wait_for_mappings(self.group_id)
        remote = self.broker.get_host_for_receiver(
            self.group_id, recv_rank) != self.broker.host
        if remote:
            # Remote sends can block on TCP: run on the rank's send
            # worker so isend returns immediately (the reference's
            # UNACKED-buffer progress analog). Copy now — MPI lets the
            # caller reuse the buffer as soon as isend returns.
            payload = np.asarray(data).copy()

            def _do_send():
                self._in_send_pool.flag = True
                self.send(send_rank, recv_rank, payload, request_id=rid)

            fut = self._send_worker(send_rank).submit(_do_send)
            with self._lock:
                self._requests.setdefault(send_rank, {})[rid] = (
                    "send", fut, recv_rank)
        else:
            # Local enqueue never blocks; fire inline
            self.send(send_rank, recv_rank, data, request_id=rid)
            with self._lock:
                self._requests.setdefault(send_rank, {})[rid] = (
                    "send", None, recv_rank)
        return rid

    def irecv(self, send_rank: int, recv_rank: int) -> int:
        with self._lock:
            rid = self._next_request_id
            self._next_request_id += 1
            self._requests.setdefault(recv_rank, {})[rid] = (
                "recv", send_rank, recv_rank)
        return rid

    def await_async(self, rank: int, request_id: int
                    ) -> Optional[tuple[np.ndarray, MpiStatus]]:
        """MPI_Wait. Recvs complete here (lazy, like the reference's
        recvBatchReturnLast :1963-2030); local sends completed at isend,
        remote isends join their send worker here (errors surface now)."""
        with self._lock:
            entry = self._requests.get(rank, {}).pop(request_id, None)
        if entry is None:
            raise KeyError(f"Unknown MPI request {request_id} for rank {rank}")
        if entry[0] == "send":
            fut = entry[1]
            if fut is not None:
                fut.result()  # join the send worker; surfaces send errors
            return None
        _, send_rank, recv_rank = entry
        return self.recv(send_rank, recv_rank)

    def pending_requests(self, rank: int) -> int:
        with self._lock:
            return len(self._requests.get(rank, {}))

    def request_free(self, rank: int, request_id: int) -> None:
        """MPI_Request_free: drop the handle without waiting. Sends
        complete in their worker regardless. A freed irecv whose message
        already arrived consumes and discards it (so it can't be handed
        to a later unrelated recv); freeing a still-unmatched irecv just
        drops the handle — the standard itself calls that erroneous on
        the user's part (a message sent for it would go to the next
        matching recv)."""
        with self._lock:
            entry = self._requests.get(rank, {}).pop(request_id, None)
        if entry is None:
            return  # already completed/freed — MPI_REQUEST_NULL no-op
        if entry[0] == "recv":
            _, send_rank, recv_rank = entry
            if self.broker.try_probe_message(self.group_id, send_rank,
                                             recv_rank) is not None:
                self.recv(send_rank, recv_rank)  # consume + discard

    def request_ready(self, rank: int, request_id: int) -> bool:
        """True when await_async would complete without blocking (local
        sends at isend, remote isends when their send worker finishes,
        recvs when their message has arrived)."""
        with self._lock:
            entry = self._requests.get(rank, {}).get(request_id)
        if entry is None:
            raise KeyError(f"Unknown MPI request {request_id} for rank {rank}")
        if entry[0] == "send":
            fut = entry[1]
            return fut is None or fut.done()
        _, send_rank, recv_rank = entry
        return self.broker.try_probe_message(self.group_id, send_rank,
                                             recv_rank) is not None

    def waitall(self, rank: int, request_ids: list[int]
                ) -> list[Optional[tuple[np.ndarray, MpiStatus]]]:
        """MPI_Waitall: complete every request, results in input order."""
        return [self.await_async(rank, rid) for rid in request_ids]

    def waitany(self, rank: int, request_ids: list[int],
                timeout: float | None = None
                ) -> tuple[int, Optional[tuple[np.ndarray, MpiStatus]]]:
        """MPI_Waitany: (index, result) of the first completable request.
        Local sends are instantly ready, remote isends once their send
        worker finishes them, recvs when their message arrives. Ids
        already completed by an earlier wait are skipped (the standard
        repeated-waitany loop); an empty/fully-completed list returns
        (-1, None) — MPI_UNDEFINED."""
        import time as _time

        deadline = None if timeout is None else _time.monotonic() + timeout
        while True:
            live = 0
            for i, rid in enumerate(request_ids):
                try:
                    ready = self.request_ready(rank, rid)
                except KeyError:
                    continue  # completed by an earlier wait
                live += 1
                if ready:
                    return i, self.await_async(rank, rid)
            if live == 0:
                return -1, None
            if deadline is not None and _time.monotonic() >= deadline:
                raise TimeoutError("MPI_Waitany timed out")
            _time.sleep(0.0005)

    # ------------------------------------------------------------------
    # Collective schedule compiler (ISSUE 13, mpi/schedule.py)
    # ------------------------------------------------------------------
    def _sched_key(self, collective: str, op=None, dtype=None,
                   nbytes=None, root: int = 0) -> tuple:
        """Cache key: (topology-generation, collective, root, op-class,
        dtype-class, size-class) — the device-plane executable-cache
        discipline. Every component is identical on every rank of a
        call (MPI requires matching payload shapes; scatterv receivers,
        which know nothing of the payload, key class-less), so
        per-process caches stay in lockstep and migration remaps
        (generation bumps) invalidate world-wide."""
        from faabric_tpu.telemetry.perfprofile import size_class

        self.topology()  # ensure the generation matches a built topology
        with self._lock:
            gen = self._topology_gen
        opc = ("-" if op is None
               else "u" if isinstance(op, UserOp) else f"b{int(op)}")
        dtc = "-" if dtype is None else np.dtype(dtype).str
        szc = "-" if nbytes is None else size_class(int(nbytes))
        return (gen, collective, root, opc, dtc, szc)

    def _sched_family(self, rank: int, key: tuple, collective: str,
                      nbytes: int | None) -> str:
        """World-agreed schedule family for ``key``. Selection reads
        THIS process's perf-profile store — which measures different
        links on every process — so the verdict is computed on rank 0
        only and distributed by a one-shot broadcast (the selection
        sync round); a locally-derived choice could desync the world's
        message pattern and hang the collective. A rank joins the round
        exactly when its OWN call sequence first meets ``key`` — that
        predicate is identical on every rank (same call sequence, same
        keys), unlike the process-shared cache a sibling rank thread
        may already have filled."""
        from faabric_tpu.mpi.schedule_compile import (
            FAMILIES,
            FAMILY_IDS,
            choose_family,
        )

        with self._lock:
            seen = self._sched_seen.setdefault(rank, set())
            need_round = key not in seen
        if not need_round:
            fam = self._sched_cache.family_of(key)
            assert fam is not None, f"selection ran but {key} uncached"
            return fam
        if rank == MAIN_RANK:
            fam = self._sched_cache.family_of(key)
            if fam is None:
                fam = choose_family(collective, self.topology(),
                                    nbytes or 0, self.sched_enabled)
            self._broadcast_impl(
                MAIN_RANK, rank,
                np.array([FAMILY_IDS[fam]], dtype=np.int64))
        else:
            arr = self._broadcast_impl(MAIN_RANK, rank,
                                       np.empty(1, dtype=np.int64))
            fam = FAMILIES[int(arr.reshape(-1)[0])]
        # Ledger write BEFORE the seen-mark: a rank that will skip all
        # future rounds for this key must always be able to recover
        # the agreed verdict, even across schedule-entry eviction or a
        # compile failure after this point
        self._sched_cache.note_family(key, fam)
        with self._lock:
            seen = self._sched_seen[rank]
            # Generations are monotonic, so keys of other generations
            # can never be looked up again — shed them here or a
            # migration-churned long-lived world leaks one seen-set
            # entry per (rank, key, generation) forever
            stale = {k for k in seen if k[0] != key[0]}
            if stale:
                seen -= stale
            seen.add(key)
        return fam

    def _sched_get(self, rank: int, collective: str, op=None, dtype=None,
                   nbytes=None, root: int = 0):
        """(schedule, family) for one collective call: selection sync on
        first encounter, then compile-verify-cache once per process.
        Every schedule handed out is verified — get_or_compile runs the
        verifier before caching and the runner refuses unverified
        schedules, so nothing executes uncached or unverified."""
        from faabric_tpu.mpi.schedule_compile import compile_schedule

        key = self._sched_key(collective, op=op, dtype=dtype,
                              nbytes=nbytes, root=root)
        family = self._sched_family(rank, key, collective, nbytes)
        topo = self.topology()
        sched = self._sched_cache.get_or_compile(
            key, family,
            lambda: compile_schedule(family, collective, topo, root=root))
        return sched, family

    @staticmethod
    def _sched_phase_groups(steps):
        groups: list[tuple[str, list]] = []
        for st in steps:
            if not groups or groups[-1][0] != st.phase:
                groups.append((st.phase, []))
            groups[-1][1].append(st)
        return groups

    def _run_schedule(self, rank: int, sched, env: dict, op,
                      resolver, msg_type: MpiMessageType) -> dict:
        """The generic schedule runner: execute ``rank``'s step program
        over ``env`` (block key → flat ndarray). Sends concatenate
        blocks into one message; recvs split by ``resolver``-bound
        sizes (single-block recvs discover their size from the wire);
        folds apply ``op`` in the schedule's operand order; copies are
        reference moves (assembly copies where ownership demands).
        Per-phase spans ride ``mpi.phase`` like the hand-written
        hierarchical paths, so /perf's critical path decomposes
        schedule rounds the same way.

        Phases annotated with an execution TARGET (``spec["targets"]``,
        ISSUE 15 — the device-ring permute executor) are offered to the
        registered target first; a decline (None) or a partial run (the
        target returns how many leading steps it executed) falls
        through to the per-step host path for the remainder, so a
        target can never change the message pattern it does not fully
        own."""
        from faabric_tpu.mpi.schedule import (
            COPY,
            FOLD,
            RECV,
            SEND,
            ScheduleError,
            get_step_target,
        )

        if not sched.verified:
            raise ScheduleError(
                f"refusing to execute unverified schedule {sched.name}")
        steps = sched.steps.get(rank, ())
        traced = tracing_enabled()
        phase_targets = sched.spec.get("targets") or {}
        for phase, group in self._sched_phase_groups(steps):
            done = 0
            tname = phase_targets.get(phase)
            if tname:
                target = get_step_target(tname)
                if target is not None:
                    handled = target.try_run(self, rank, sched, phase,
                                             group, env, resolver)
                    if handled:
                        done = handled
            if done >= len(group):
                continue
            with span("mpi.phase", phase or "run", rank=rank) \
                    if traced else NULL_SPAN:
                for st in group[done:]:
                    if st.op == SEND:
                        bufs = [np.asarray(env[k]).reshape(-1)
                                for k in st.keys]
                        payload = (bufs[0] if len(bufs) == 1
                                   else np.concatenate(bufs))
                        self.send(rank, st.peer, payload, msg_type)
                    elif st.op == RECV:
                        arr, _ = self._recv_raw(st.peer, rank)
                        arr = arr.reshape(-1)
                        if len(st.keys) == 1:
                            env[st.keys[0]] = arr
                            continue
                        pos = 0
                        for k, sym in zip(st.keys, st.syms):
                            count = int(resolver(sym, env))
                            env[k] = arr[pos:pos + count]
                            pos += count
                        if pos != arr.size:
                            raise ScheduleError(
                                f"{sched.name}: rank {rank} recv from "
                                f"{st.peer} split {pos} of {arr.size} "
                                f"elements (framing desync)")
                    elif st.op == FOLD:
                        env[st.dst] = np.asarray(
                            apply_op(op, env[st.a], env[st.b])
                        ).reshape(-1)
                    elif st.op == COPY:
                        env[st.dst] = np.asarray(env[st.src]).reshape(-1)
        return env

    # ------------------------------------------------------------------
    # Collectives — locality-aware leader trees on the host path
    # ------------------------------------------------------------------
    def barrier(self, rank: int) -> None:
        # Gather-to-0 + broadcast (reference :1753-1775) — delegated to the
        # group barrier, which already has a single-host fast path
        _count_collective("barrier", 0)
        with span("mpi", "barrier", rank=rank, size=self.size):
            self.broker.wait_for_mappings(self.group_id)
            group = self.broker.get_group(self.group_id)
            group.barrier(rank)

    def _try_device(self, kind: str, dplane, rank: int, arr: np.ndarray,
                    op=None):
        """The ``plane=device`` rung (ISSUE 10): run the collective as a
        compiled program over the activated mesh. Returns the result, or
        None after a clean fallback — a backend error disabled the plane
        (symmetrically: the compiled collective is synchronous across
        processes) and the caller re-runs on the host ladder. The
        fallback re-run counts the collective a second time; that is the
        truthful reading (two attempts were made) and only occurs on the
        plane's terminal failure."""
        from faabric_tpu.device_plane import DevicePlaneFallback

        _count_collective(kind, int(arr.nbytes))
        with span("mpi", kind, rank=rank, size=self.size,
                  bytes=int(arr.nbytes), algo="device"):
            try:
                if kind == "allreduce":
                    return dplane.allreduce(rank, arr, op)
                if kind == "allgather":
                    return dplane.allgather(rank, arr)
                return dplane.reduce_scatter(rank, arr, op)
            except DevicePlaneFallback as e:
                logger.warning("Device %s (world %s) fell back to the "
                               "host ladder: %s", kind, self.id, e)
                return None

    # Above this, collectives stream in chunks so tree stages overlap:
    # while a leader reduces chunk k, chunk k+1 is on the wire and chunk
    # k-1 is being folded at the root — the host-path analog of a
    # pipelined ring. 4 MiB rides the kernel socket buffer cap on the
    # cross-host wire; a single-host world has no wire leg to overlap,
    # so bigger chunks win (fewer queue wakeups per GiB — measured +10%
    # effective on the 4-rank 97 MiB allreduce bench).
    CHUNK_BYTES = 4 * 1024 * 1024
    CHUNK_BYTES_LOCAL = 16 * 1024 * 1024

    def _chunk_bounds(self, arr: np.ndarray) -> list[tuple[int, int]]:
        chunk_bytes = (self.CHUNK_BYTES_LOCAL if len(self.hosts()) == 1
                       else self.CHUNK_BYTES)
        elems = max(1, chunk_bytes // max(1, arr.itemsize))
        flat_n = arr.size
        return [(lo, min(lo + elems, flat_n))
                for lo in range(0, flat_n, elems)]

    def broadcast(self, send_rank: int, recv_rank: int, data: np.ndarray
                  ) -> np.ndarray:
        data = np.asarray(data)
        _count_collective("broadcast", int(data.nbytes))
        with span("mpi", "broadcast", rank=recv_rank, root=send_rank,
                  bytes=int(data.nbytes)):
            return self._broadcast_impl(send_rank, recv_rank, data)

    def _broadcast_impl(self, send_rank: int, recv_rank: int,
                        data: np.ndarray) -> np.ndarray:
        """Reference :786-853: root sends once per remote host (to its
        local leader) + to its own host's ranks; leaders re-broadcast
        locally.

        Large payloads stream chunk-pipelined. The stream is
        SELF-DESCRIBING: the root prefixes a CHUNK_HEADER message, so
        receivers follow the root's chunking decision and never need a
        correctly-sized local template (mpi_bcast(buf=None) callers)."""
        data = np.asarray(data)
        my_host = self.host_for_rank(recv_rank)
        root_host = self.host_for_rank(send_rank)

        # -- root: decide chunking from the REAL payload ----------------
        if recv_rank == send_rank:
            local = [r for r in self.ranks_on_host(root_host)
                     if r != send_rank]
            remote_leaders = [self.local_leader(h) for h in self.hosts()
                              if h != root_host]
            dests_remote_first = remote_leaders + local

            if data.nbytes >= self.CHUNK_BYTES * 2:
                flat = data.reshape(-1)
                bounds = self._chunk_bounds(flat)
                shared = np.array(flat, copy=True)
                shared.flags.writeable = False
                header = self._chunk_header(len(bounds), flat)
                for d in dests_remote_first:
                    self.send(send_rank, d, header,
                              MpiMessageType.CHUNK_HEADER)
                for lo, hi in bounds:
                    chunk = shared[lo:hi]
                    # Remote first: get the wire moving before local fan-out
                    for d in dests_remote_first:
                        self.send(send_rank, d, chunk,
                                  MpiMessageType.BROADCAST, _copy=False)
            else:
                shared = np.array(data, copy=True)
                for d in dests_remote_first:
                    self.send(send_rank, d, shared,
                              MpiMessageType.BROADCAST, _copy=False)
            return data

        # -- leaders: follow the incoming stream, forwarding locally ----
        leader = self.local_leader(my_host)
        if my_host != root_host and recv_rank == leader:
            local = [r for r in self.ranks_on_host(my_host)
                     if r != recv_rank]

            def forward(arr, msg_type=MpiMessageType.BROADCAST):
                for r in local:
                    self.send(recv_rank, r, arr, msg_type, _copy=False)

            msg_type, first = self._recv_typed(send_rank, recv_rank)
            if msg_type != MpiMessageType.CHUNK_HEADER:
                forward(first)
                return self._private_result(first, data)
            n_chunks, out = self._parse_chunk_header(first)
            # Local ranks follow the same self-describing stream shape
            forward(first, MpiMessageType.CHUNK_HEADER)
            pos = 0
            for _ in range(n_chunks):
                arr, _ = self._recv_raw(send_rank, recv_rank)
                out[pos:pos + arr.size] = arr
                ro = out[pos:pos + arr.size]
                ro.flags.writeable = False
                forward(ro)
                pos += arr.size
            # out's chunk views were shared read-only with local
            # receivers; hand the caller a private copy
            return self._private_result(out.copy(), data, private=True)

        # -- plain receivers --------------------------------------------
        src = send_rank if my_host == root_host else leader
        msg_type, first = self._recv_typed(src, recv_rank)
        if msg_type != MpiMessageType.CHUNK_HEADER:
            return self._private_result(first, data)
        n_chunks, out = self._parse_chunk_header(first)
        pos = 0
        for _ in range(n_chunks):
            arr, _ = self._recv_raw(src, recv_rank)
            out[pos:pos + arr.size] = arr
            pos += arr.size
        return self._private_result(out, data, private=True)

    @staticmethod
    def _chunk_header(n_chunks: int, flat: np.ndarray) -> np.ndarray:
        return np.array([n_chunks, flat.size,
                         int(mpi_dtype_for(flat.dtype))], dtype=np.int64)

    @staticmethod
    def _parse_chunk_header(header: np.ndarray) -> tuple[int, np.ndarray]:
        from faabric_tpu.mpi.types import MpiDataType, np_dtype_for

        n_chunks, total, dtype_code = (int(x) for x in header[:3])
        return n_chunks, np.empty(total,
                                  dtype=np_dtype_for(MpiDataType(dtype_code)))

    def _recv_typed(self, send_rank: int, recv_rank: int
                    ) -> tuple[MpiMessageType, np.ndarray]:
        """Receive preserving the message type; the array may be shared/
        read-only (zero-copy paths) — see _private_result."""
        raw = self.broker.recv_message(self.group_id, send_rank, recv_rank,
                                       must_order=True)
        if isinstance(raw, _LocalMpiPayload):
            return raw.msg_type, raw.data
        msg_type, arr, _req = self._unpack_wire(raw)
        return msg_type, arr

    @staticmethod
    def _private_result(arr: np.ndarray, template: np.ndarray,
                        private: bool = False) -> np.ndarray:
        """Caller-owned writable result, reshaped to the template when the
        sizes agree (lenient size-less templates stay flat). ``private``
        marks buffers this rank already exclusively owns."""
        if not private and not arr.flags.writeable:
            arr = arr.copy()  # shared zero-copy fan-out buffer
        if template.size == arr.size and template.shape != arr.shape:
            arr = arr.reshape(template.shape)
        return arr

    def reduce(self, rank: int, root: int, data: np.ndarray,
               op: MpiOp = MpiOp.SUM,
               _shared_ok: bool = False) -> Optional[np.ndarray]:
        data = np.asarray(data)
        _count_collective("reduce", int(data.nbytes))
        with span("mpi", "reduce", rank=rank, root=root,
                  bytes=int(data.nbytes)):
            return self._reduce_impl(rank, root, data, op, _shared_ok)

    def _reduce_impl(self, rank: int, root: int, data: np.ndarray,
                     op: MpiOp = MpiOp.SUM,
                     _shared_ok: bool = False) -> Optional[np.ndarray]:
        """Reference :1127-1249: non-leaders send to their local leader;
        leaders partially reduce and forward one message to root.
        Large payloads stream chunk-pipelined."""
        data = np.asarray(data)
        if data.nbytes >= self.CHUNK_BYTES * 2:
            return self._reduce_chunked(rank, root, data, op, _shared_ok)
        my_host = self.host_for_rank(rank)
        root_host = self.host_for_rank(root)
        leader = self.local_leader(my_host)

        if rank == root:
            acc = data.copy()
            # Local ranks send directly (root acts as its host's sink)
            for r in self.ranks_on_host(root_host):
                if r != root:
                    arr, _ = self._recv_raw(r, root)
                    acc = apply_op_inplace(op, acc, arr)
            # One partial result per remote host
            for host in self.hosts():
                if host != root_host:
                    arr, _ = self._recv_raw(self.local_leader(host), root)
                    acc = apply_op_inplace(op, acc, arr)
            return acc

        if my_host == root_host:
            # Same host as root: send directly
            self.send(rank, root, data, MpiMessageType.REDUCE)
            return None

        if rank == leader:
            acc = data.copy()
            for r in self.ranks_on_host(my_host):
                if r != rank:
                    arr, _ = self._recv_raw(r, rank)
                    acc = apply_op_inplace(op, acc, arr)
            self.send(rank, root, acc, MpiMessageType.REDUCE)
            return None

        self.send(rank, leader, data, MpiMessageType.REDUCE)
        return None

    def _reduce_chunked(self, rank: int, root: int, data: np.ndarray,
                        op: MpiOp, _shared_ok: bool = False
                        ) -> Optional[np.ndarray]:
        """Chunk-pipelined leader-tree reduce: leaders fold and forward
        chunk k while chunk k+1 is still arriving; the root folds chunks
        as its senders' streams land.

        ``_shared_ok`` (allreduce-only): senders' local chunks ride the
        queues as read-only views with NO defensive copy — safe because
        allreduce's trailing broadcast guarantees every contribution is
        consumed before any caller regains control of its buffer. A bare
        reduce() must copy (MPI says the send buffer is reusable on
        return, but a lagging receiver may still be reading it)."""
        my_host = self.host_for_rank(rank)
        root_host = self.host_for_rank(root)
        leader = self.local_leader(my_host)
        flat = data.reshape(-1)
        bounds = self._chunk_bounds(flat)

        def send_chunk(dst: int, chunk: np.ndarray) -> None:
            if _shared_ok:
                view = chunk[:]
                view.flags.writeable = False
                self.send(rank, dst, view, MpiMessageType.REDUCE,
                          _copy=False)
            else:
                self.send(rank, dst, chunk, MpiMessageType.REDUCE)

        if rank == root:
            senders = [r for r in self.ranks_on_host(root_host)
                       if r != root]
            senders += [self.local_leader(h) for h in self.hosts()
                        if h != root_host]
            acc = flat.copy()
            for lo, hi in bounds:
                acc_chunk = acc[lo:hi]
                for s in senders:
                    arr, _ = self._recv_raw(s, root)
                    res = apply_op_inplace(op, acc_chunk, arr)
                    if res is not acc_chunk:  # non-inplace op fallback
                        acc[lo:hi] = res
                        acc_chunk = acc[lo:hi]
            return acc.reshape(data.shape)

        if my_host == root_host:
            for lo, hi in bounds:
                send_chunk(root, flat[lo:hi])
            return None

        if rank == leader:
            locals_ = [r for r in self.ranks_on_host(my_host) if r != rank]
            acc = flat.copy()
            for lo, hi in bounds:
                acc_chunk = acc[lo:hi]
                for s in locals_:
                    arr, _ = self._recv_raw(s, rank)
                    res = apply_op_inplace(op, acc_chunk, arr)
                    if res is not acc_chunk:  # non-inplace op fallback
                        acc[lo:hi] = res
                        acc_chunk = acc[lo:hi]
                # acc is leader-private: forward upstream without a copy
                self.send(rank, root, acc_chunk, MpiMessageType.REDUCE)
            return None

        for lo, hi in bounds:
            send_chunk(leader, flat[lo:hi])
        return None

    def _stage_host(self, arr):
        """Device-resident payloads that cannot (or did not) ride the
        device rung take ONE explicit device→host staging copy —
        counted on the ``faabric_device_copy_*`` surface (reason
        ``staging``) so the fallback cost is observable, never silent.
        Host arrays pass through untouched."""
        from faabric_tpu.device_plane.plane import is_device_payload

        if not is_device_payload(arr):
            return arr
        from faabric_tpu.device_plane.copies import D2H, count_copy

        out = np.asarray(arr)
        count_copy(D2H, int(out.nbytes), "staging")
        return out

    def allreduce(self, rank: int, data, op: MpiOp = MpiOp.SUM):
        from faabric_tpu.device_plane.plane import is_device_payload

        # jax.Array payloads stay device-resident through dispatch: the
        # eligibility question is answered from shape/dtype alone and
        # the device rung consumes the array in place (ISSUE 15). Only
        # a host-ladder fallback materializes it (one counted copy).
        arr = data if is_device_payload(data) else np.asarray(data)
        if not _PROFILER.enabled:
            return self._allreduce_entry(rank, arr, op)
        # Collective fold-in (ISSUE 12): the wall-anchored ENTRY stamp
        # is what straggler analysis compares across ranks — in a
        # synchronous collective the late arriver inflates everyone's
        # total equally, so only arrival skew can identify it
        _PROFILER.record_phase(self.id, "allreduce", rank, "enter_ts",
                               time.time())
        t0 = time.monotonic()
        try:
            return self._allreduce_entry(rank, arr, op)
        finally:
            _PROFILER.record_phase(self.id, "allreduce", rank, "total",
                                   time.monotonic() - t0,
                                   int(arr.nbytes))

    def _allreduce_entry(self, rank: int, arr: np.ndarray,
                         op: MpiOp) -> np.ndarray:
        # Large single-host payloads: ring reduce-scatter + allgather.
        # The root-serialized leader tree bottlenecks on ONE thread doing
        # every add and every fan-out send; the ring splits the fold
        # np ways across the already-running rank threads (the same
        # reason the device plane reduces via psum_scatter+all_gather).
        # Multi-host worlds keep the leader tree: it sends exactly one
        # message per remote host over the wire, which the ring does not.
        # Rung 0 — the device plane (shm → tcp → DEVICE): an activated
        # world's eligible payloads run as one compiled program over the
        # mesh; everything below is the host ladder it falls back to
        dplane = self.device_plane()
        if dplane is not None and dplane.eligible("allreduce", arr, op):
            out = self._try_device("allreduce", dplane, rank, arr, op)
            if out is not None:
                return out
        arr = self._stage_host(arr)
        if self._sched_reduction_eligible(op):
            return self._reduction_sched(rank, "allreduce", arr, op)
        use_hier = self._hier_eligible(arr, op)
        use_ring = (not use_hier and arr.size >= self.size
                    and self._ring_eligible(arr, op))
        _count_collective("allreduce", int(arr.nbytes))
        with span("mpi", "allreduce", rank=rank, size=self.size,
                  bytes=int(arr.nbytes),
                  algo=("hier" if use_hier
                        else "ring" if use_ring else "tree")):
            if use_hier:
                return self._allreduce_hier(rank, arr, op)
            if use_ring:
                return self._allreduce_ring(rank, arr, op)
            # reduce to 0 + broadcast (reference :1251-1264). The trailing
            # broadcast is the completion barrier that makes zero-copy local
            # contribution sends safe (_shared_ok).
            with span("mpi.phase", "reduce", rank=rank):
                reduced = self._reduce_impl(rank, MAIN_RANK, arr, op,
                                            _shared_ok=True)
            with span("mpi.phase", "broadcast", rank=rank):
                return self._broadcast_impl(
                    MAIN_RANK, rank,
                    reduced if rank == MAIN_RANK else arr)

    def _sched_reduction_eligible(self, op=None) -> bool:
        """Whether the hierarchical reduction LOWERINGS execute instead
        of the hand-written paths: explicit double opt-in (knob "force"
        + world.sched_reductions, set identically on every process) —
        they exist to prove the IR covers the tuned paths and are
        bitwise-pinned against them; the zero-copy hand-written rings
        remain the throughput defaults."""
        if self.sched_enabled != "force" or not self.sched_reductions:
            return False
        if op is not None and isinstance(op, UserOp) and not op.commute:
            return False
        return self.size > 1 and self.topology().n_hosts > 1

    def _reduction_sched(self, rank: int, collective: str,
                         data: np.ndarray, op: MpiOp) -> np.ndarray:
        """Run allreduce / reduce_scatter / allgather as its verified
        schedule lowering (mpi/schedule_compile.py): intra-host fold or
        gather to the leader, leader ring / pairwise host-block
        exchange, in-process redistribute — the schedule twin of the
        hand-written hierarchical paths."""
        flat = np.asarray(data).reshape(-1)
        op_arg = None if collective == "allgather" else op
        sched, family = self._sched_get(
            rank, collective, op=op_arg, dtype=flat.dtype,
            nbytes=int(flat.nbytes))
        _count_collective(collective, int(flat.nbytes))
        with span("mpi", collective, rank=rank, size=self.size,
                  bytes=int(flat.nbytes),
                  algo="sched:" + family.split(".", 1)[1]):
            env: dict = {}
            if collective == "allreduce":
                segs = self._ring_segments(flat.size,
                                           sched.spec["segments"])
                for s, (lo, hi) in enumerate(segs):
                    env[("in", s)] = flat[lo:hi]

                def resolver(sym, e, _segs=segs):
                    return _segs[sym[1]][1] - _segs[sym[1]][0]

                self._run_schedule(rank, sched, env, op, resolver,
                                   MpiMessageType.ALLREDUCE)
                out = np.empty(flat.size, dtype=flat.dtype)
                for s, (lo, hi) in enumerate(segs):
                    out[lo:hi] = env[("out", s)]
                return out.reshape(np.asarray(data).shape)
            if collective == "reduce_scatter":
                k = flat.size // self.size
                for j in range(self.size):
                    env[("in", j)] = flat[j * k:(j + 1) * k]
                self._run_schedule(rank, sched, env, op,
                                   lambda sym, e: k,
                                   MpiMessageType.REDUCE)
                return np.array(env[("out", 0)])
            # allgather: contribution is the whole payload, k per rank
            k = flat.size
            env[("in", 0)] = flat
            self._run_schedule(rank, sched, env, None,
                               lambda sym, e: k,
                               MpiMessageType.ALLGATHER)
            out = np.empty(self.size * k, dtype=flat.dtype)
            for q in range(self.size):
                out[q * k:(q + 1) * k] = env[("out", q)]
            return out

    def _ring_eligible(self, arr: np.ndarray, op) -> bool:
        """Shared ring-path predicate for allreduce/reduce_scatter: big
        enough to beat the tree, all ranks on this machine, and a
        commuting op. No size ceiling: segments above one bulk frame
        stream as pipeline chunks (see RING_CHUNK_BYTES)."""
        return (self.size > 1 and arr.nbytes >= self.CHUNK_BYTES * 2
                and (not isinstance(op, UserOp) or op.commute)
                and self._all_hosts_same_machine())

    def _all_hosts_same_machine(self) -> bool:
        """True when every rank's host resolves to THIS machine (rank
        threads in one process, or worker processes sharing the box whose
        cross-process legs ride the shm ring). The ring's extra hop count
        is free on local bandwidth; over a real network the hierarchical
        leader tree's one-message-per-host wins instead."""
        from faabric_tpu.transport.common import resolve_host
        from faabric_tpu.util.network import is_local_ip

        with self._lock:
            if self._same_machine_cache is not None:
                return self._same_machine_cache
            gen = self._topology_gen
        hosts = self.hosts()
        # A single-host world is same-machine by definition — delivery is
        # in-process no matter what the host label resolves to
        result = len(hosts) == 1 or all(
            is_local_ip(resolve_host(h, 0)[0]) for h in hosts)
        with self._lock:
            # Only cache if no refresh_rank_hosts (migration remap) raced
            # this computation — a stale verdict would desync ring/tree
            # algorithm choice across processes and hang the collective
            if self._topology_gen == gen:
                self._same_machine_cache = result
        return result

    # ------------------------------------------------------------------
    # Hierarchical topology-composed collectives (ISSUE 9 / ROADMAP 1)
    # ------------------------------------------------------------------
    def _hier_eligible(self, arr: np.ndarray, op=None) -> bool:
        """Hierarchical-composition predicate: payload big enough to
        chunk-pipeline, a commuting op, and a Topology with BOTH
        multiple hosts and co-located ranks. The degenerate shapes —
        one host, or one rank per host — fall through to the flat
        ring / leader-tree paths, which are already optimal there (the
        1-host bench shape must keep the flat fast path)."""
        if not self.hier_enabled:
            return False
        if op is not None and isinstance(op, UserOp) and not op.commute:
            return False
        if arr.nbytes < self.CHUNK_BYTES * 2 or arr.size < self.size:
            return False
        return self.topology().hierarchical and self._hier_wins()

    def _hier_wins(self) -> bool:
        """Composing only pays when the leader ring's saved bytes cross
        a REAL machine boundary. When every host of the world resolves
        to this machine (simulated hosts, co-located worker procs) the
        "wire" is loopback/shm where bytes are nearly free, and the
        flat ring — which pipelines the fold across EVERY rank thread
        instead of serializing the wire leg through one leader per host
        — is measurably faster (host_allreduce_procs: 2.8–3.3 GiB/s
        ring vs ~1.6 composed). ``hier_enabled = "force"`` overrides
        for the simulated-host dist tests and benches, which exist to
        measure the composition itself."""
        return (self.hier_enabled == "force"
                or not self._all_hosts_same_machine())

    def _host_reduce(self, rank: int, data: np.ndarray, op: MpiOp,
                     locals_: list[int]):
        """Phase ``intra`` of the hierarchical collectives: a chunked
        ring reduce-scatter over THIS host's ranks (the fold spread
        across the co-located rank threads through the in-process
        queues), then non-leaders hand their folded segments to the
        local leader as ownership transfers while the leader assembles
        the full host-reduced vector.

        Returns ``(host_acc, restore_fn)``: ``host_acc`` is the
        host-reduced vector on the leader (the caller's own flat buffer
        when the host has a single rank) and None on non-leaders. Every
        caller must run ``restore_fn`` only once its own later phase
        proves the local successor consumed this rank's step-0 views —
        see the causality note in _allreduce_hier."""
        flat = data.reshape(-1)
        m = len(locals_)
        leader = locals_[0]
        if m == 1:
            return (flat if rank == leader else None), (lambda: None)
        with span("mpi.phase", "reduce_scatter", rank=rank,
                  phase="intra"):
            held, restore = self._ring_reduce_scatter(rank, data, op,
                                                      ring=locals_)
        with span("mpi.phase", "gather", rank=rank, phase="intra"):
            seg = self._ring_segments(flat.size, m)
            pos = locals_.index(rank)
            if rank != leader:
                # Folded chunks are receiver-private (allocated or
                # ownership-received during the fold): transfer outright
                for part in held:
                    self.send(rank, leader, part, MpiMessageType.REDUCE,
                              _transfer=True)
                return None, restore
            host_acc = np.empty(
                flat.size, dtype=held[0].dtype if held else flat.dtype)
            # Own held chunks cover segment (pos+1) % m ...
            write = seg[(pos + 1) % m][0]
            for part in held:
                host_acc[write:write + part.size] = part
                write += part.size
            # ... and local rank at position p holds segment (p+1) % m
            for p in range(m):
                if locals_[p] == rank:
                    continue
                slo, shi = seg[(p + 1) % m]
                # The INPUT itemsize is the protocol's agreed bound
                # unit (senders chunked by it; == host_acc.itemsize
                # since apply_op casts folds back to the input dtype)
                for clo, chi in self._ring_chunks(slo, shi,
                                                  flat.itemsize):
                    arr, _ = self._recv_raw(locals_[p], rank)
                    host_acc[clo:chi] = arr
            return host_acc, restore

    def _allreduce_hier(self, rank: int, data: np.ndarray,
                        op: MpiOp) -> np.ndarray:
        """Topology-composed allreduce (HiCCL-style composition): shm
        reduce-scatter within each host → chunk-pipelined ring over the
        per-host LEADERS only on the wire (striped bulk plane) →
        redistribution back down through the in-process queues. Only
        the leader ring leaves the host, so each host puts
        2·(H−1)/H·payload on the wire instead of one ring link per
        RANK — ~1/(ranks-per-host) of the flat ring's cross-host bytes
        under topology-blind placement.

        Phases (spans tagged ``phase=intra|leader|redistribute``):
        intra (_host_reduce), leader (leaders-only _allreduce_ring),
        redistribute (leader freezes the result and fans the reference
        out locally; every rank finishes with a private copy).

        Ownership causality: a rank's step-0 views are consumed by its
        local-ring successor before that successor's segment handover
        reaches the leader; the leader's fan-out (or, on the leader
        itself, completing the host assembly) therefore transitively
        proves consumption — restore runs last on every path. A
        single-rank host feeds its caller's buffer straight into the
        leader ring, whose trailing allgather provides the same
        guarantee the flat ring relies on."""
        topo = self.topology()
        locals_ = list(topo.ranks_on_host(topo.host_of(rank)))
        leader = locals_[0]
        # Per-phase fold-in (ISSUE 12): intra/leader/redistribute wall
        # durations land in the collective profiler so /perf's critical
        # path names the slow HIERARCHY LEVEL, not just the slow rank
        prof = _PROFILER.enabled
        t_ph = time.monotonic() if prof else 0.0
        host_acc, restore = self._host_reduce(rank, data, op, locals_)
        if prof:
            now = time.monotonic()
            _PROFILER.record_phase(self.id, "allreduce", rank, "intra",
                                   now - t_ph)
            t_ph = now

        if rank != leader:
            with span("mpi.phase", "broadcast", rank=rank,
                      phase="redistribute"):
                arr, _ = self._recv_raw(leader, rank)
                out = self._private_result(arr, data)
            if prof:
                _PROFILER.record_phase(self.id, "allreduce", rank,
                                       "redistribute",
                                       time.monotonic() - t_ph)
            restore()
            return out

        # Opt-in int8 wire quantization on the leader ring's fold leg
        # only (mpi/quant.py) — the cross-machine links are the
        # bandwidth-bound segment EQuARX targets; intra-host phases
        # stay exact fp32. The mode resolves through the wire-codec
        # governor (ISSUE 11): the legacy knob forces every hop, the
        # governor's `quant` token enables it per-LINK (each sender
        # decides for its own next-hop, carried in-band via the
        # NaN-scale raw passthrough form, never inferred).
        result = self._allreduce_ring(
            rank, host_acc, op, ring=list(topo.leaders), phase="leader",
            codec=leader_ring_codec(
                resolve_quant_mode(self.allreduce_quant),
                host_acc.dtype, op))
        if prof:
            now = time.monotonic()
            _PROFILER.record_phase(self.id, "allreduce", rank, "leader",
                                   now - t_ph)
            t_ph = now
        with span("mpi.phase", "broadcast", rank=rank,
                  phase="redistribute"):
            if len(locals_) > 1:
                shared = result.reshape(-1)
                shared.flags.writeable = False
                for r in locals_[1:]:
                    self.send(rank, r, shared, MpiMessageType.BROADCAST,
                              _copy=False)
                # Receivers keep the frozen buffer; the caller gets a
                # private copy it may mutate immediately
                result = shared.copy()
        if prof:
            _PROFILER.record_phase(self.id, "allreduce", rank,
                                   "redistribute",
                                   time.monotonic() - t_ph)
        restore()
        return self._private_result(result, data, private=True)

    def _allreduce_ring(self, rank: int, data: np.ndarray,
                        op: MpiOp, ring: list[int] | None = None,
                        phase: str | None = None,
                        codec=None) -> np.ndarray:
        """Zero-copy CHUNK-PIPELINED ring allreduce over the rank
        threads: np-1 reduce-scatter steps (each rank folds 1/np of the
        data per step) then np-1 allgather steps that pass chunk
        REFERENCES through the in-process queues — the only bulk copies
        are the fold itself and one assembly write per chunk, and the
        folds run on ALL rank threads concurrently instead of serially
        on the root. Segments above RING_CHUNK_BYTES stream as multiple
        chunk messages, so while this rank folds chunk k its
        predecessor's chunk k+1 is already crossing the wire and its
        successor is folding chunk k-1 (hop-level pipelining; no
        RING_MSG_CAP bail-out for big segments anymore).

        Ownership protocol (what makes zero-copy safe):
        - step 0 sends READ-ONLY chunk views of the caller's buffer; the
          ring's causal chain (every rank's return transitively requires
          its successor to have consumed those messages) guarantees
          consumption before any caller regains control.
        - a received partial chunk is exclusively owned by the receiver,
          which folds its own contribution INTO it in place — unless it
          is a read-only step-0 view, where the fold allocates.
        - after the fold a chunk is sent on and never written again;
          allgather forwards the same objects, every holder read-only.
        Requires an associative+commutative op, which MPI mandates.

        ``ring`` restricts the ring to an ordered rank subset (the
        hierarchical path's leader ring); callers outside it must not
        call. ``phase`` tags the spans with the hierarchy level."""
        flat = data.reshape(-1)
        if ring is None:
            ring = list(range(self.size))
        n = len(ring)
        pos = ring.index(rank)
        seg = self._ring_segments(flat.size, n)
        nxt, prv = ring[(pos + 1) % n], ring[(pos - 1) % n]
        lvl = {"phase": phase} if phase else {}
        with span("mpi.phase", "reduce_scatter", rank=rank, **lvl):
            held, restore = self._ring_reduce_scatter(rank, data, op,
                                                      ring=ring,
                                                      codec=codec)
        out = np.empty(flat.size,
                       dtype=held[0].dtype if held else flat.dtype)
        with span("mpi.phase", "allgather", rank=rank, **lvl):
            # Assemble our fully-reduced segment while its chunks are
            # still in hand (they leave at allgather step 0)
            start = seg[(pos + 1) % n][0]
            for part in held:
                out[start:start + part.size] = part
                start += part.size
            # Circulate the complete segments chunk by chunk, writing
            # each received chunk straight into the result (the assembly
            # copy IS the receive) and forwarding the same object on
            parts: dict[int, list[np.ndarray]] = {(pos + 1) % n: held}
            for step in range(n - 1):
                send_seg = (pos + 1 - step) % n
                for part in parts.pop(send_seg):
                    if part.flags.writeable:
                        part.flags.writeable = False
                    self.send(rank, nxt, part, MpiMessageType.REDUCE,
                              _copy=False)
                recv_seg = (pos - step) % n
                rlo, rhi = seg[recv_seg]
                recv_parts = []
                for clo, chi in self._ring_chunks(rlo, rhi,
                                                  flat.itemsize):
                    arr, _ = self._recv_raw(prv, rank)
                    out[clo:chi] = arr
                    recv_parts.append(arr)
                parts[recv_seg] = recv_parts
        # Our last allgather recv causally implies nxt completed its
        # whole fold phase (chain length n-1), i.e. consumed our step-0
        # views — only now may the caller's buffer go writable again
        restore()
        return out.reshape(data.shape)

    def _ring_segments(self, n_elems: int,
                       n: int | None = None) -> list[tuple[int, int]]:
        if n is None:
            n = self.size
        return [((i * n_elems) // n, ((i + 1) * n_elems) // n)
                for i in range(n)]

    @staticmethod
    def _ring_chunks(lo: int, hi: int, itemsize: int
                     ) -> list[tuple[int, int]]:
        """Pipeline-chunk bounds of one segment [lo, hi): a pure function
        of the bounds, so every rank derives the identical stream shape
        for every link without a header exchange."""
        elems = max(1, RING_CHUNK_BYTES // max(1, itemsize))
        return [(c, min(c + elems, hi)) for c in range(lo, hi, elems)]

    def _quant_link_ok(self, peer: int) -> bool:
        """Whether the leader-ring hop to ``peer`` should actually
        quantize (wire-codec governor, ISSUE 11). The legacy knob
        forces every hop; governor-token quant skips same-machine hops
        in auto mode. The verdict is carried in-band per chunk (the
        NaN-scale raw passthrough form), so peers never need to agree
        on it — only on the codec FRAMING, which resolves from
        world-level configuration."""
        from faabric_tpu.transport.codec import get_wire_governor

        gov = get_wire_governor()
        host = self.host_for_rank(peer)
        if host == self.broker.host:
            local = True
        else:
            from faabric_tpu.transport.common import host_is_local

            local = host_is_local(host)
        return gov.quant_for_link(self.allreduce_quant, host, local)

    def _ring_reduce_scatter(self, rank: int, data: np.ndarray,
                             op: MpiOp, ring: list[int] | None = None,
                             seg: list[tuple[int, int]] | None = None,
                             codec=None):
        """The ring's fold phase: n-1 steps, each participant folding
        1/n of the data into the partials it receives, one pipeline
        chunk at a time (ownership rides the payload — folding based on
        the numpy writeable FLAG would race the sender restoring its
        step-0 views' writability). Returns (chunks of the fully reduced
        segment (pos+1) % n in offset order, restore_fn): the CALLER
        must run restore_fn only after its trailing ring phase — one
        more full circulation — guarantees every neighbour consumed the
        step-0 views of this rank's buffer.

        ``ring`` restricts the ring to an ordered rank subset (the
        hierarchical leader ring); position in ``ring`` replaces the
        rank in all segment arithmetic. ``seg`` overrides the segment
        partition (len(ring) (lo, hi) spans covering the flat array) —
        any partition works as long as every participant passes the
        same one; the hierarchical reduce_scatter uses per-HOST spans
        so each leader ends up holding exactly its own host's output.

        ``codec`` (mpi/quant.py) switches the ring's wire format: every
        chunk travels encoded (int8 + per-chunk scale), decoded into a
        receiver-private buffer before the fold and re-encoded for the
        next hop. Encoding copies, so the caller's buffer is never
        shared with a peer and restore() is a no-op; every participant
        must agree on the codec (world-level knob) or framing desyncs."""
        flat = data.reshape(-1)
        if ring is None:
            ring = list(range(self.size))
        n = len(ring)
        pos = ring.index(rank)
        if seg is None:
            seg = self._ring_segments(flat.size, n)
        nxt, prv = ring[(pos + 1) % n], ring[(pos - 1) % n]
        traced = tracing_enabled()

        lo, hi = seg[pos]
        first = flat[lo:hi]
        was_writeable = first.flags.writeable
        if codec is None:
            first.flags.writeable = False
        else:
            # Per-LINK codec selection (ISSUE 11): whether THIS rank's
            # next-hop actually quantizes is the governor's call — a
            # same-machine hop's bytes are nearly free, so it ships the
            # raw-fp32 passthrough form. Self-describing per chunk (NaN
            # scale), so mixed hops coexist on one ring.
            quant_link = self._quant_link_ok(nxt)
        for clo, chi in self._ring_chunks(lo, hi, flat.itemsize):
            if codec is not None:
                # Encoded chunks are private copies — zero-copy safe
                # without freezing the caller's views
                self.send(rank, nxt,
                          codec.encode(first[clo - lo:chi - lo],
                                       quantize=quant_link),
                          MpiMessageType.REDUCE, _copy=False)
            else:
                self.send(rank, nxt, first[clo - lo:chi - lo],
                          MpiMessageType.REDUCE, _copy=False)
        held: list[np.ndarray] = []
        for step in range(n - 1):
            slo, shi = seg[(pos - step - 1) % n]
            for clo, chi in self._ring_chunks(slo, shi, flat.itemsize):
                arr, _, owned = self._recv_raw_owned(prv, rank)
                mine = flat[clo:chi]
                with span("mpi.detail", "fold", rank=rank, step=step) \
                        if traced else NULL_SPAN:
                    if codec is not None:
                        # Decode allocates a private fp32 buffer; the
                        # fold lands in it in place
                        folded = apply_op_inplace(op, codec.decode(arr),
                                                  mine)
                    elif owned and arr.flags.writeable \
                            and arr.dtype == mine.dtype:
                        folded = apply_op_inplace(op, arr, mine)
                    else:  # step-0 shared view (or dtype-promoting op):
                        # non-inplace apply allocates + folds in ONE pass
                        folded = np.asarray(apply_op(op, arr, mine))
                if step < n - 2:
                    if codec is not None:
                        self.send(rank, nxt,
                                  codec.encode(folded,
                                               quantize=quant_link),
                                  MpiMessageType.REDUCE, _copy=False)
                    else:
                        # Ownership transfer: the receiver folds into
                        # this buffer in place; we drop our reference
                        # here — and the wire leg of chunk k overlaps
                        # our fold of chunk k+1 (the pipeline the
                        # chunking exists for)
                        self.send(rank, nxt, folded, MpiMessageType.REDUCE,
                                  _transfer=True)
                    del folded
                else:
                    held.append(folded)  # segment (rank+1) % n

        def restore():
            if codec is None and was_writeable:
                first.flags.writeable = True

        return held, restore

    def scatter(self, send_rank: int, recv_rank: int, data: np.ndarray,
                recv_count: int) -> np.ndarray:
        _count_collective("scatter", int(np.asarray(data).nbytes))
        if self.sched_enabled and self.size > 1:
            sched, family = self._sched_get(rank=recv_rank,
                                            collective="scatter",
                                            root=send_rank)
            with span("mpi", "scatter", rank=recv_rank, root=send_rank,
                      algo="sched:" + family.split(".", 1)[1]):
                return self._scatter_sched(send_rank, recv_rank, sched,
                                           data, recv_count=recv_count)
        with span("mpi", "scatter", rank=recv_rank, root=send_rank,
                  algo="direct"):
            return self._scatter_impl(send_rank, recv_rank, data,
                                      recv_count)

    def _scatter_sched(self, root: int, rank: int, sched,
                       data, recv_count: int | None = None,
                       counts=None) -> np.ndarray:
        """Schedule-path scatter/scatterv: the root binds its per-rank
        input blocks (and, for scatterv trees, the int64 count-vector
        header the leaders split by); every other rank's blocks arrive
        sized by the wire or the header."""
        env: dict = {}
        if rank == root:
            flat = np.asarray(data).reshape(-1)
            if counts is None:
                chunks = flat.reshape(self.size, recv_count)
                for j in range(self.size):
                    env[("in", j)] = chunks[j]
            else:
                offsets = np.cumsum([0] + list(counts[:-1]))
                for j in range(self.size):
                    env[("in", j)] = flat[offsets[j]:offsets[j]
                                          + counts[j]]
                if sched.spec.get("counts_header"):
                    env[("in", "cnt")] = np.asarray(counts,
                                                    dtype=np.int64)

        def resolver(sym, e):
            if sym == ("cnt",):
                return self.size
            j = sym[1]
            if counts is not None and rank == root:
                return int(counts[j])
            if recv_count is not None:
                return int(recv_count)
            return int(np.asarray(e[("tmp", "cnt")]).reshape(-1)[j])

        self._run_schedule(rank, sched, env, None, resolver,
                           MpiMessageType.SCATTER)
        # Out blocks may alias the root's input or a shared receive
        # buffer; the public contract is a caller-owned writable array
        return np.array(env[("out", 0)])

    def _scatter_impl(self, send_rank: int, recv_rank: int,
                      data: np.ndarray, recv_count: int) -> np.ndarray:
        """Root splits (size*recv_count) into per-rank chunks."""
        if recv_rank == send_rank:
            data = np.asarray(data)
            chunks = data.reshape(self.size, recv_count)
            for r in range(self.size):
                if r != send_rank:
                    self.send(send_rank, r, chunks[r], MpiMessageType.SCATTER)
            return chunks[send_rank].copy()
        arr, _ = self.recv(send_rank, recv_rank)
        return arr

    def gather(self, send_rank: int, root: int, data: np.ndarray
               ) -> Optional[np.ndarray]:
        data = np.asarray(data)
        _count_collective("gather", int(data.nbytes))
        with span("mpi", "gather", rank=send_rank, root=root,
                  bytes=int(data.nbytes)):
            return self._gather_impl(send_rank, root, data)

    def _gather_impl(self, send_rank: int, root: int, data: np.ndarray
                     ) -> Optional[np.ndarray]:
        """Two-step local-leader aggregation (reference :917-1080)."""
        my_host = self.host_for_rank(send_rank)
        root_host = self.host_for_rank(root)
        leader = self.local_leader(my_host)
        data = np.asarray(data)
        chunk = data.size

        if send_rank == root:
            out = np.empty((self.size, chunk), dtype=data.dtype)
            out[root] = data
            for r in self.ranks_on_host(root_host):
                if r != root:
                    arr, _ = self.recv(r, root)
                    out[r] = arr
            for host in self.hosts():
                if host != root_host:
                    remote_ranks = sorted(self.ranks_on_host(host))
                    arr, _ = self.recv(self.local_leader(host), root)
                    packed = arr.reshape(len(remote_ranks), chunk)
                    for i, r in enumerate(remote_ranks):
                        out[r] = packed[i]
            return out.reshape(-1)

        if my_host == root_host:
            self.send(send_rank, root, data, MpiMessageType.GATHER)
            return None

        if send_rank == leader:
            local_ranks = sorted(self.ranks_on_host(my_host))
            packed = np.empty((len(local_ranks), chunk), dtype=data.dtype)
            packed[local_ranks.index(send_rank)] = data
            for r in local_ranks:
                if r != send_rank:
                    arr, _ = self.recv(r, send_rank)
                    packed[local_ranks.index(r)] = arr
            self.send(send_rank, root, packed.reshape(-1),
                      MpiMessageType.GATHER)
            return None

        self.send(send_rank, leader, data, MpiMessageType.GATHER)
        return None

    # ------------------------------------------------------------------
    # v-variants (variable counts; reference mpi.h gatherv/scatterv/
    # alltoallv). Counts ride the wire with each message, so only the
    # root needs the count vector; transfers are direct sends (the
    # leader-tree optimisation applies to the uniform-count fast paths).
    # ------------------------------------------------------------------
    def gatherv(self, rank: int, root: int, data: np.ndarray
                ) -> Optional[tuple[np.ndarray, list[int]]]:
        """Root returns (concatenated values in rank order, counts)."""
        data = np.asarray(data).reshape(-1)
        if rank != root:
            self.send(rank, root, data, MpiMessageType.GATHER)
            return None
        parts: list[np.ndarray] = []
        for r in range(self.size):
            if r == root:
                parts.append(data)
            else:
                # _recv_raw: concatenate copies anyway, skip recv()'s
                # defensive copy
                arr, _ = self._recv_raw(r, root)
                parts.append(arr)
        return np.concatenate(parts), [int(p.size) for p in parts]

    def scatterv(self, send_rank: int, recv_rank: int,
                 data: Optional[np.ndarray],
                 counts: Optional[list[int]]) -> np.ndarray:
        """Root splits ``data`` into per-rank pieces of ``counts`` sizes.
        Schedule-compiled (ISSUE 13): the tree family packs one bundle
        per remote host behind an int64 count-vector header, so leaders
        split without a planner round-trip; receivers stay count-blind
        (sizes bind from the wire/header, exactly once, verified)."""
        if recv_rank == send_rank:
            flat = np.asarray(data).reshape(-1)
            if counts is None or len(counts) != self.size:
                raise ValueError("scatterv root needs one count per rank")
            if sum(counts) != flat.size:
                raise ValueError(
                    f"scatterv counts sum {sum(counts)} != data {flat.size}")
        # Payload bytes enter at the root only; receivers count the
        # invocation (the per-participating-rank convention)
        _count_collective(
            "scatterv",
            int(np.asarray(data).nbytes) if recv_rank == send_rank else 0)
        if self.sched_enabled and self.size > 1:
            sched, family = self._sched_get(rank=recv_rank,
                                            collective="scatterv",
                                            root=send_rank)
            with span("mpi", "scatterv", rank=recv_rank, root=send_rank,
                      algo="sched:" + family.split(".", 1)[1]):
                return self._scatter_sched(send_rank, recv_rank, sched,
                                           data, counts=counts)
        with span("mpi", "scatterv", rank=recv_rank, root=send_rank,
                  algo="direct"):
            return self._scatterv_direct(send_rank, recv_rank, data,
                                         counts)

    def _scatterv_direct(self, send_rank: int, recv_rank: int,
                         data: Optional[np.ndarray],
                         counts: Optional[list[int]]) -> np.ndarray:
        """Seed-era direct sends, kept as the knob-off fallback."""
        if recv_rank == send_rank:
            flat = np.asarray(data).reshape(-1)
            offsets = np.cumsum([0] + list(counts[:-1]))
            for r in range(self.size):
                if r != send_rank:
                    self.send(send_rank, r,
                              flat[offsets[r]:offsets[r] + counts[r]],
                              MpiMessageType.SCATTER)
            lo = offsets[send_rank]
            return flat[lo:lo + counts[send_rank]].copy()
        arr, _ = self.recv(send_rank, recv_rank)
        return arr

    def alltoallv(self, rank: int, data: np.ndarray,
                  send_counts: list[int]
                  ) -> tuple[np.ndarray, list[int]]:
        """Rank-``j`` slice of ``data`` (``send_counts[j]`` elements) goes
        to rank j; returns (concatenation of received blocks in rank
        order, received counts)."""
        flat = np.asarray(data).reshape(-1)
        if len(send_counts) != self.size:
            raise ValueError("alltoallv needs one send count per rank")
        if sum(send_counts) != flat.size:
            raise ValueError(
                f"alltoallv counts sum {sum(send_counts)} != {flat.size}")
        offsets = np.cumsum([0] + list(send_counts[:-1]))
        my_block = None
        for r in range(self.size):
            block = flat[offsets[r]:offsets[r] + send_counts[r]]
            if r == rank:
                my_block = block.copy()
            else:
                self.send(rank, r, block, MpiMessageType.ALLTOALL)
        parts: list[np.ndarray] = []
        for r in range(self.size):
            if r == rank:
                parts.append(my_block)
            else:
                arr, _ = self._recv_raw(r, rank)
                parts.append(arr)
        return np.concatenate(parts), [int(p.size) for p in parts]

    def reduce_scatter(self, rank: int, data,
                       op: MpiOp = MpiOp.SUM):
        """MPI_Reduce_scatter_block: reduce (size·k,) contributions, rank
        r keeps segment r (reference composes it the same way: reduce to
        root + scatter). Large same-machine payloads take the ring's
        reduce-scatter phase directly — every rank folds 1/np per step
        and the root never materialises the full reduction."""
        from faabric_tpu.device_plane.plane import is_device_payload

        data = (data.reshape(-1) if is_device_payload(data)
                else np.asarray(data).reshape(-1))
        if not _PROFILER.enabled:
            return self._reduce_scatter_entry(rank, data, op)
        _PROFILER.record_phase(self.id, "reduce_scatter", rank,
                               "enter_ts", time.time())
        t0 = time.monotonic()
        try:
            return self._reduce_scatter_entry(rank, data, op)
        finally:
            _PROFILER.record_phase(self.id, "reduce_scatter", rank,
                                   "total", time.monotonic() - t0,
                                   int(data.nbytes))

    def _reduce_scatter_entry(self, rank: int, data: np.ndarray,
                              op: MpiOp) -> np.ndarray:
        if data.size % self.size:
            raise ValueError(
                f"reduce_scatter needs size divisible by {self.size}")
        k = data.size // self.size
        dplane = self.device_plane()
        if dplane is not None and dplane.eligible("reduce_scatter",
                                                  data, op):
            out = self._try_device("reduce_scatter", dplane, rank, data,
                                   op)
            if out is not None:
                return out
        data = self._stage_host(data)
        if self._sched_reduction_eligible(op):
            return self._reduction_sched(rank, "reduce_scatter", data, op)
        # Scattered (non-gang-contiguous) placements compose too: the
        # leader ring folds over a PERMUTED span partition derived from
        # the Topology (see _reduce_scatter_hier), so the
        # hosts_contiguous() gate PR 9 shipped with is gone
        use_hier = self._hier_eligible(data, op)
        use_ring = not use_hier and self._ring_eligible(data, op)
        _count_collective("reduce_scatter", int(data.nbytes))
        with span("mpi", "reduce_scatter", rank=rank, size=self.size,
                  bytes=int(data.nbytes),
                  algo=("hier" if use_hier
                        else "ring" if use_ring else "tree")):
            if use_hier:
                return self._reduce_scatter_hier(rank, data, op)
            if use_ring:
                with span("mpi.phase", "reduce_scatter", rank=rank):
                    held, restore = self._ring_reduce_scatter(rank, data,
                                                              op)
                # The ring leaves rank holding segment (rank+1) — which
                # belongs to rank+1; rotate one hop forward (chunk by
                # chunk) so every rank ends with ITS OWN segment (rank-1
                # holds ours). Ownership transfers with the rotation:
                # the receiver returns the buffers to its caller outright
                with span("mpi.phase", "rotate", rank=rank):
                    for part in held:
                        self.send(rank, (rank + 1) % self.size,
                                  np.asarray(part), MpiMessageType.REDUCE,
                                  _transfer=True)
                    del held
                    slo, shi = self._ring_segments(data.size)[rank]
                    chunks = self._ring_chunks(slo, shi, data.itemsize)
                    out = pos = None
                    for clo, chi in chunks:
                        arr, _, owned = self._recv_raw_owned(
                            (rank - 1) % self.size, rank)
                        if len(chunks) == 1:
                            # Single-chunk segment: hand the received
                            # buffer over outright when we own it
                            out = (arr if owned and arr.flags.writeable
                                   else arr.copy())
                            break
                        if out is None:
                            out = np.empty(shi - slo, dtype=arr.dtype)
                            pos = 0
                        out[pos:pos + arr.size] = arr
                        pos += arr.size
                    # The rotation recv extends the causal chain to
                    # length n, so nxt has consumed our step-0 views:
                    # safe to restore
                    restore()
                    return out
            with span("mpi.phase", "reduce", rank=rank):
                reduced = self._reduce_impl(rank, MAIN_RANK, data, op)
            with span("mpi.phase", "scatter", rank=rank):
                return self._scatter_impl(
                    MAIN_RANK, rank,
                    reduced if rank == MAIN_RANK else np.empty(0), k)

    def _reduce_scatter_hier(self, rank: int, data: np.ndarray,
                             op: MpiOp) -> np.ndarray:
        """Hierarchical reduce_scatter: intra-host reduce-scatter +
        handover (_host_reduce), then the leader ring runs ONLY the
        fold phase over per-HOST segment spans — permuted so each
        leader finishes holding exactly its own host's output span
        ((H−1)/H·payload per wire link, no trailing allgather) — and
        scatters the per-rank slices back down in process. Covers BOTH
        gang-contiguous and scattered placements: the spans live in a
        permuted coordinate space derived from the Topology (identity
        when contiguous; see the order/spans construction below)."""
        topo = self.topology()
        k = data.size // self.size
        locals_ = list(topo.ranks_on_host(topo.host_of(rank)))
        leader = locals_[0]
        leaders = list(topo.leaders)
        n_hosts = len(leaders)
        host_acc, restore = self._host_reduce(rank, data, op, locals_)

        if rank != leader:
            with span("mpi.phase", "scatter", rank=rank,
                      phase="redistribute"):
                out, _ = self.recv(leader, rank)
            restore()
            return out

        # The leader ring folds over per-HOST spans of a PERMUTED
        # coordinate space: rank order grouped by host (topology host
        # order, ranks ascending within each host). For gang-contiguous
        # placements the permutation is the identity; for scattered
        # placements (the PR 9 headroom this closes) the leader gathers
        # its host-reduced vector's k-blocks into that order first, so
        # each host's output is one contiguous span again and the
        # fold-only ring works unchanged. Every leader derives the same
        # order from the shared Topology — no exchange.
        order = [r for h in topo.hosts for r in topo.ranks_on_host(h)]
        if order != list(range(self.size)):
            perm = np.empty(host_acc.size, dtype=host_acc.dtype)
            for j, r in enumerate(order):
                perm[j * k:(j + 1) * k] = host_acc[r * k:(r + 1) * k]
            host_acc = perm  # private by construction
        elif len(locals_) == 1:
            # The fold-only leader ring has no trailing circulation to
            # extend the causal chain, so the caller's buffer must not
            # feed it directly: a peer could still be reading its
            # step-0 views after this rank returns (the flat path
            # restores only after its rotation for the same reason)
            host_acc = host_acc.copy()

        # spans[p] = permuted-space span of ring position p's host; the
        # fold phase leaves position p holding seg[(p+1) % n], so pass
        # the partition rotated one position back
        spans = []
        off = 0
        for lead in leaders:
            m_host = len(topo.ranks_on_host(topo.host_of(lead)))
            spans.append((off, off + m_host * k))
            off += m_host * k
        seg = [spans[(q - 1) % n_hosts] for q in range(n_hosts)]
        # No codec here: FAABRIC_ALLREDUCE_QUANT scopes to ALLREDUCE —
        # reduce_scatter hands each rank a slice nothing re-replicates,
        # and silently lossy slices under an allreduce-named knob would
        # surprise (quantize it deliberately under its own knob if
        # ROADMAP 4 wants it)
        with span("mpi.phase", "reduce_scatter", rank=rank,
                  phase="leader"):
            held, _noop_restore = self._ring_reduce_scatter(
                rank, host_acc, op, ring=leaders, seg=seg)

        with span("mpi.phase", "scatter", rank=rank,
                  phase="redistribute"):
            slo, shi = spans[leaders.index(rank)]
            hostseg = np.empty(
                shi - slo, dtype=held[0].dtype if held else data.dtype)
            write = 0
            for part in held:
                hostseg[write:write + part.size] = part
                write += part.size
            del held
            # hostseg holds this host's per-rank outputs in LOCAL rank
            # order (ascending), whatever the global layout
            for i, r in enumerate(locals_[1:], start=1):
                self.send(rank, r, hostseg[i * k:(i + 1) * k],
                          MpiMessageType.SCATTER)
            out = hostseg[:k].copy()  # leader is local position 0
        restore()
        return out

    def allgather(self, rank: int, data):
        from faabric_tpu.device_plane.plane import is_device_payload

        data = data if is_device_payload(data) else np.asarray(data)
        if not _PROFILER.enabled:
            return self._allgather_entry(rank, data)
        _PROFILER.record_phase(self.id, "allgather", rank, "enter_ts",
                               time.time())
        t0 = time.monotonic()
        try:
            return self._allgather_entry(rank, data)
        finally:
            _PROFILER.record_phase(self.id, "allgather", rank, "total",
                                   time.monotonic() - t0,
                                   int(data.nbytes))

    def _allgather_entry(self, rank: int, data: np.ndarray) -> np.ndarray:
        # Large same-machine payloads: ring allgather — contributions
        # circulate as read-only chunk references through the in-process
        # queues (n-1 steps, one assembly write per chunk) instead of
        # funnelling through rank 0 twice. Contributions above one bulk
        # frame stream as pipeline chunks (no size cap).
        dplane = self.device_plane()
        if dplane is not None and dplane.eligible("allgather", data):
            out = self._try_device("allgather", dplane, rank, data)
            if out is not None:
                return out
        data = self._stage_host(data)
        if self._sched_reduction_eligible() and data.size > 0:
            return self._reduction_sched(rank, "allgather", data, None)
        # Hierarchy pays off once the OUTPUT (size × contribution) is
        # pipeline-sized; the per-rank contribution itself can be small
        use_hier = (self.hier_enabled and data.size > 0
                    and data.nbytes * self.size >= self.CHUNK_BYTES * 2
                    and self.topology().hierarchical
                    and self._hier_wins())
        use_ring = (not use_hier and self.size > 1
                    and data.nbytes >= self.CHUNK_BYTES
                    and self._all_hosts_same_machine())
        _count_collective("allgather", int(data.nbytes))
        with span("mpi", "allgather", rank=rank, size=self.size,
                  bytes=int(data.nbytes),
                  algo=("hier" if use_hier
                        else "ring" if use_ring else "tree")):
            if use_hier:
                return self._allgather_hier(rank, data)
            if use_ring:
                return self._allgather_ring(rank, data)
            # gather(0) + broadcast (reference :1082-1111). The broadcast
            # stream is self-describing (CHUNK_HEADER), so non-roots need
            # no sized template — they follow the root's framing.
            with span("mpi.phase", "gather", rank=rank):
                gathered = self._gather_impl(rank, MAIN_RANK, data)
            template = (gathered if rank == MAIN_RANK
                        else np.empty(0, dtype=data.dtype))
            with span("mpi.phase", "broadcast", rank=rank):
                return self._broadcast_impl(MAIN_RANK, rank, template)

    def _allgather_ring(self, rank: int, data: np.ndarray) -> np.ndarray:
        """Chunk-pipelined ring allgather: rank r's contribution is
        segment r; n-1 steps pass chunk references around the ring, each
        received chunk written straight into the result and forwarded.
        The contribution rides as private read-only copies (other ranks
        keep the references through their assembly even after this rank
        returns, so views of the caller's buffer — which MPI lets the
        caller reuse immediately — would be a torn-read hazard)."""
        flat = data.reshape(-1)
        n = self.size
        k = flat.size
        nxt, prv = (rank + 1) % n, (rank - 1) % n
        shared = flat.copy()
        shared.flags.writeable = False
        chunks = self._ring_chunks(0, k, flat.itemsize)
        out = np.empty(n * k, dtype=flat.dtype)
        out[rank * k:(rank + 1) * k] = flat
        parts: dict[int, list[np.ndarray]] = {
            rank: [shared[clo:chi] for clo, chi in chunks]}
        for step in range(n - 1):
            send_seg = (rank - step) % n
            for part in parts.pop(send_seg):
                if part.flags.writeable:
                    part.flags.writeable = False
                self.send(rank, nxt, part, MpiMessageType.ALLGATHER,
                          _copy=False)
            recv_seg = (rank - step - 1) % n
            base = recv_seg * k
            recv_parts = []
            for clo, chi in chunks:
                arr, _ = self._recv_raw(prv, rank)
                out[base + clo:base + chi] = arr
                recv_parts.append(arr)
            parts[recv_seg] = recv_parts
        return out

    def _allgather_hier(self, rank: int, data: np.ndarray) -> np.ndarray:
        """Hierarchical allgather: contributions gather to the local
        leader in process (phase ``intra``), the leaders circulate
        per-HOST blocks around the wire ring chunk-pipelined (phase
        ``leader`` — each link carries (N−m)/N of the output instead of
        every rank being a wire peer), and the assembled result fans
        back out as a frozen in-process reference (``redistribute``).
        Host blocks are keyed by the Topology's rank lists, so
        scattered (non-contiguous) placements reassemble correctly."""
        topo = self.topology()
        flat = data.reshape(-1)
        k = flat.size
        locals_ = list(topo.ranks_on_host(topo.host_of(rank)))
        leader = locals_[0]
        leaders = list(topo.leaders)
        n_hosts = len(leaders)

        if rank != leader:
            with span("mpi.phase", "gather", rank=rank, phase="intra"):
                self.send(rank, leader, flat, MpiMessageType.GATHER)
            with span("mpi.phase", "broadcast", rank=rank,
                      phase="redistribute"):
                arr, _ = self._recv_raw(leader, rank)
                return self._private_result(
                    arr, np.empty(0, dtype=flat.dtype))

        m = len(locals_)
        out = np.empty(self.size * k, dtype=flat.dtype)

        def place(host_ranks, block) -> None:
            for i, r in enumerate(host_ranks):
                out[r * k:(r + 1) * k] = block[i * k:(i + 1) * k]

        with span("mpi.phase", "gather", rank=rank, phase="intra"):
            block = np.empty(m * k, dtype=flat.dtype)
            block[:k] = flat  # leader is local position 0
            for i, r in enumerate(locals_[1:], start=1):
                arr, _ = self._recv_raw(r, rank)
                block[i * k:(i + 1) * k] = arr

        with span("mpi.phase", "allgather", rank=rank, phase="leader"):
            place(locals_, block)
            block.flags.writeable = False
            pos = leaders.index(rank)
            nxt = leaders[(pos + 1) % n_hosts]
            prv = leaders[(pos - 1) % n_hosts]
            blocks: dict[int, list[np.ndarray]] = {
                pos: [block[clo:chi] for clo, chi in
                      self._ring_chunks(0, block.size, block.itemsize)]}
            for step in range(n_hosts - 1):
                send_pos = (pos - step) % n_hosts
                for part in blocks.pop(send_pos):
                    if part.flags.writeable:
                        part.flags.writeable = False
                    self.send(rank, nxt, part, MpiMessageType.ALLGATHER,
                              _copy=False)
                recv_pos = (pos - step - 1) % n_hosts
                rranks = topo.ranks_on_host(
                    topo.host_of(leaders[recv_pos]))
                rblock = np.empty(len(rranks) * k, dtype=flat.dtype)
                parts = []
                write = 0
                for clo, chi in self._ring_chunks(0, rblock.size,
                                                  flat.itemsize):
                    arr, _ = self._recv_raw(prv, rank)
                    rblock[write:write + arr.size] = arr
                    parts.append(arr)
                    write += arr.size
                place(rranks, rblock)
                blocks[recv_pos] = parts

        with span("mpi.phase", "broadcast", rank=rank,
                  phase="redistribute"):
            if m > 1:
                out.flags.writeable = False
                for r in locals_[1:]:
                    self.send(rank, r, out, MpiMessageType.BROADCAST,
                              _copy=False)
                out = out.copy()  # receivers keep the frozen buffer
        return out

    def scan(self, rank: int, data: np.ndarray,
             op: MpiOp = MpiOp.SUM) -> np.ndarray:
        """MPI_Scan. Schedule-compiled (ISSUE 13): ``scan.chain`` is the
        reference linear chain (:1390-1431) as a verified step program
        — bit-identical fold order (prefix, mine) — and ``scan.hier``
        (gang-contiguous placements) runs intra-host chains + a carrier
        chain between hosts, ≈ ranks/host + hosts serial hops instead
        of N. Previously the one collective with neither a span nor a
        _count_collective — the comm-matrix/profiler blind spot ISSUE
        13's satellite closes."""
        data = np.asarray(data)
        _count_collective("scan", int(data.nbytes))
        if not (self.sched_enabled and self.size > 1):
            with span("mpi", "scan", rank=rank, size=self.size,
                      bytes=int(data.nbytes), algo="chain"):
                return self._scan_chain(rank, data, op)
        sched, family = self._sched_get(
            rank, "scan", op=op, dtype=data.dtype,
            nbytes=int(data.nbytes))
        with span("mpi", "scan", rank=rank, size=self.size,
                  bytes=int(data.nbytes),
                  algo="sched:" + family.split(".", 1)[1]):
            flat = data.reshape(-1)
            env: dict = {("in", 0): flat}
            self._run_schedule(rank, sched, env, op,
                               lambda sym, e: flat.size,
                               MpiMessageType.SCAN)
            out = np.array(env[("out", 0)]).reshape(data.shape)
            return out

    def _scan_chain(self, rank: int, data: np.ndarray,
                    op: MpiOp) -> np.ndarray:
        """Seed-era linear chain, kept as the knob-off fallback: rank r
        receives the prefix from r-1, merges, forwards to r+1."""
        if rank > 0:
            prev, _ = self.recv(rank - 1, rank)
            acc = apply_op(op, prev, data)
        else:
            acc = data.copy()
        if rank < self.size - 1:
            self.send(rank, rank + 1, acc, MpiMessageType.SCAN)
        return acc

    def alltoall(self, rank: int, data: np.ndarray) -> np.ndarray:
        """All-pairs exchange of equal chunks: data is (size*chunk,),
        row r goes to rank r. Schedule-compiled (ISSUE 13): the runner
        executes a verified step program — ``alltoall.hier`` packs host
        blocks through the local leaders (the reference's
        disabled-since-2024 locality-aware ALLTOALL_PACKED variant,
        cutting cross-host messages to ≈1/ranks-per-host² — bytes are
        invariant, alltoall is a permutation), ``alltoall.flat`` is the
        naive pairwise pattern as a schedule. FAABRIC_SCHED_COLLECTIVES
        =off keeps the seed-era hand-written loop."""
        data = np.asarray(data)
        _count_collective("alltoall", int(data.nbytes))
        if not (self.sched_enabled and self.size > 1):
            with span("mpi", "alltoall", rank=rank, size=self.size,
                      bytes=int(data.nbytes), algo="direct"):
                return self._alltoall_direct(rank, data)
        sched, family = self._sched_get(
            rank, "alltoall", dtype=data.dtype, nbytes=int(data.nbytes))
        with span("mpi", "alltoall", rank=rank, size=self.size,
                  bytes=int(data.nbytes),
                  algo="sched:" + family.split(".", 1)[1]):
            return self._alltoall_sched(rank, data, sched, family)

    def _alltoall_direct(self, rank: int, data: np.ndarray) -> np.ndarray:
        """Seed-era naive all-pairs loop (reference :1433-1736), kept as
        the knob-off fallback and the A/B baseline."""
        chunk = data.size // self.size
        rows = data.reshape(self.size, chunk)
        for r in range(self.size):
            if r != rank:
                self.send(rank, r, rows[r], MpiMessageType.ALLTOALL)
        out = np.empty_like(rows)
        out[rank] = rows[rank]
        for r in range(self.size):
            if r != rank:
                arr, _ = self.recv(r, rank)
                out[r] = arr
        return out.reshape(-1)

    def _alltoall_sched(self, rank: int, data: np.ndarray, sched,
                        family: str) -> np.ndarray:
        flat = data.reshape(-1)
        k = flat.size // self.size
        rows = flat.reshape(self.size, k)
        env: dict = {("in", j): rows[j] for j in range(self.size)}
        msg_type = (MpiMessageType.ALLTOALL_PACKED
                    if family == "alltoall.hier"
                    else MpiMessageType.ALLTOALL)
        self._run_schedule(rank, sched, env, None,
                           lambda sym, e: k, msg_type)
        out = np.empty(self.size * k, dtype=flat.dtype)
        for j in range(self.size):
            out[j * k:(j + 1) * k] = env[("out", j)]
        return out

    # ------------------------------------------------------------------
    # Cartesian topology (reference :369-493 — there fixed 2-D periodic,
    # LAMMPS-style; here user dims via cart_create, defaulting to the
    # reference's near-square 2-D factorisation)
    # ------------------------------------------------------------------
    _cart_user_dims: Optional[tuple[int, ...]] = None

    def cart_create(self, dims: Optional[Sequence[int]] = None
                    ) -> tuple[int, ...]:
        """MPI_Cart_create with user dims (all-periodic); ``None`` keeps
        the default 2-D factorisation."""
        if dims is None:
            self._cart_user_dims = None
            return self.cart_dims()
        dims = tuple(int(d) for d in dims)
        if any(d <= 0 for d in dims):
            raise ValueError(f"Cartesian dims must be positive: {dims}")
        if int(np.prod(dims)) != self.size:
            raise ValueError(
                f"Cartesian dims {dims} do not tile {self.size} ranks")
        self._cart_user_dims = dims
        return dims

    def cart_dims(self) -> tuple[int, ...]:
        if self._cart_user_dims is not None:
            return self._cart_user_dims
        side = int(np.floor(np.sqrt(self.size)))
        while side > 1 and self.size % side != 0:
            side -= 1
        return side, self.size // side

    def cart_coords(self, rank: int) -> tuple[int, ...]:
        return tuple(int(c) for c in
                     np.unravel_index(rank, self.cart_dims()))

    def cart_rank(self, coords: Sequence[int]) -> int:
        dims = self.cart_dims()
        wrapped = [c % d for c, d in zip(coords, dims)]
        return int(np.ravel_multi_index(wrapped, dims))

    def cart_shift(self, rank: int, dim: int, disp: int) -> tuple[int, int]:
        """(source, dest) for a periodic shift along dim."""
        coords = list(self.cart_coords(rank))
        src_coords = list(coords)
        dst_coords = list(coords)
        src_coords[dim] -= disp
        dst_coords[dim] += disp
        return self.cart_rank(src_coords), self.cart_rank(dst_coords)

    # ------------------------------------------------------------------
    # Sub-communicators (reference mpi.h MPI_Comm_split_type /
    # MPI_Comm_create / MPI_Comm_dup / MPI_Group_incl)
    # ------------------------------------------------------------------
    # Split-generation draws: ranks co-located on a host SHARE this world
    # object, so a plain per-world counter would hand concurrent callers
    # different values. Each rank draws a locally-unique number and the
    # split's allgather agrees on max(draws) — monotonic per collective
    # call and identical on every rank
    def _split_draw(self) -> int:
        with self._lock:
            self._split_seq += 1
            return self._split_seq

    @staticmethod
    def _derive_group_id(parent: int, seq: int, color: int) -> int:
        # Cryptographic mix (NOT Python hash(): randomized per process;
        # NOT linear arithmetic: colors are arbitrary ints and a linear
        # mix collides whenever color deltas cancel seq deltas), folded
        # into a distinct high range so derived ids can't collide with
        # planner-generated GIDs
        import hashlib

        digest = hashlib.sha256(
            f"{parent}:{seq}:{color}".encode()).digest()
        mixed = int.from_bytes(digest[:8], "little") & ((1 << 62) - 1)
        return (1 << 126) | mixed

    def make_subworld(self, member_ranks: list[int], sub_group_id: int
                      ) -> "MpiWorld":
        """A real MpiWorld whose rank i is parent rank member_ranks[i]:
        every member host derives the SAME mappings from the parent's, so
        no planner round-trip is needed. All existing point-to-point and
        collective machinery works unchanged on the result."""
        from faabric_tpu.batch_scheduler.decision import SchedulingDecision

        self.broker.wait_for_mappings(self.group_id)
        d = SchedulingDecision(app_id=sub_group_id, group_id=sub_group_id)
        for new_idx, parent_rank in enumerate(member_ranks):
            host = self.broker.get_host_for_receiver(self.group_id,
                                                     parent_rank)
            port = self.broker.get_mpi_port_for_receiver(self.group_id,
                                                         parent_rank)
            dev = self.broker.get_device_for_idx(self.group_id, parent_rank)
            d.add_message(host, sub_group_id + new_idx + 1, new_idx,
                          new_idx, mpi_port=port, device_id=dev)
        # Installed by every local member; idempotent per host
        self.broker.set_up_local_mappings_from_decision(d)
        sub = MpiWorld(self.broker, sub_group_id, len(member_ranks),
                       sub_group_id, user=self.user, function=self.function)
        sub.record_exec_graph = self.record_exec_graph
        return sub

    def split(self, rank: int, color: int, key: int = 0
              ) -> tuple[Optional["MpiWorld"], int]:
        """MPI_Comm_split: ranks with the same ``color`` form a subworld,
        ordered by (key, parent rank). color < 0 (MPI_UNDEFINED) opts
        out → (None, -1). Collective over the PARENT world."""
        triple = np.array([color, key, rank, self._split_draw()],
                          dtype=np.int64)
        gathered = self.allgather(rank, triple).reshape(self.size, 4)
        seq = int(gathered[:, 3].max())
        if color < 0:
            return None, -1
        members = sorted((int(k), int(r)) for c, k, r, _ in gathered
                         if int(c) == color)
        member_ranks = [r for _, r in members]
        sub_group_id = self._derive_group_id(self.group_id, seq, color)
        sub = self.make_subworld(member_ranks, sub_group_id)
        return sub, member_ranks.index(rank)

    def split_type_shared(self, rank: int, key: int = 0
                          ) -> tuple["MpiWorld", int]:
        """MPI_Comm_split_type(MPI_COMM_TYPE_SHARED): one subworld per
        HOST — co-located ranks that can share memory (the reference's
        split_type semantics, mpi.h:565)."""
        host = self.host_for_rank(rank)
        color = sorted(self.hosts()).index(host)
        sub, new_rank = self.split(rank, color, key)
        assert sub is not None
        return sub, new_rank

    def dup(self, rank: int) -> tuple["MpiWorld", int]:
        """MPI_Comm_dup: same membership, fresh communication context
        (a new group id → isolated queues/sequence state)."""
        return self.split(rank, color=0, key=rank)

    def create_group_comm(self, rank: int, member_ranks: list[int],
                          tag: int = 0) -> tuple[Optional["MpiWorld"], int]:
        """MPI_Comm_create_group: collective only over ``member_ranks``
        (every member passes the same list); non-members just get None.
        No parent-wide communication — the membership is given, so the
        derived id comes from (parent, members, tag) rather than the
        split counter (non-members never call this, and a shared counter
        would desync). Reuse with identical arguments needs a distinct
        ``tag``, as in MPI."""
        if rank not in member_ranks:
            return None, -1
        mix = 0
        for r in member_ranks:
            mix = (mix * 131 + int(r) + 1) & ((1 << 62) - 1)
        sub_group_id = self._derive_group_id(self.group_id, mix,
                                             tag + (1 << 20))
        sub = self.make_subworld(list(member_ranks), sub_group_id)
        return sub, list(member_ranks).index(rank)

    def close(self) -> None:
        """Stop this world's send workers (registry teardown)."""
        with self._lock:
            workers, self._send_workers = dict(self._send_workers), {}
        for w in workers.values():
            w.shutdown()

    # ------------------------------------------------------------------
    # Migration (reference prepareMigration :2095-2131)
    # ------------------------------------------------------------------
    def prepare_migration(self, rank: int, new_group_id: int | None = None) -> None:
        with self._lock:
            if any(self._requests.values()):
                raise RuntimeError(
                    "Cannot migrate an MPI world with pending async requests")
            if new_group_id is not None:
                self.group_id = new_group_id
            self._rank_hosts.clear()
            self._rank_devices.clear()
            self._topology_cache = None
            self._same_machine_cache = None
            self._topology_gen += 1
            self._device_collectives = None
            # Post-migration the rank→device map is stale: the rung
            # drops until every rank re-runs the activation handshake
            self._device_plane = None
        # Outstanding device-resident state handles (ISSUE 15) point at
        # HBM on the PRE-migration chip assignment: drop them all (the
        # re-handshake path re-pushes, minting fresh-generation
        # handles) so a migrated rank can never pull a stale reference.
        # Flight-recorded inside invalidate_world.
        from faabric_tpu.state.device_handle import invalidate_world

        invalidate_world(self.id)
        watch = getattr(self.broker, "watch_group", None)
        if watch is not None:
            watch(self.group_id)  # liveness checking follows the new gid

    # ------------------------------------------------------------------
    def exec_graph_details(self) -> dict[str, int]:
        with self._lock:
            out = {f"mpi-msgcount-torank-{r}": n
                   for r, n in self._msg_count_to_rank.items()}
            for (t, r), n in self._msg_type_count.items():
                out[f"mpi-msgtype-{t}-torank-{r}"] = n
            return out
