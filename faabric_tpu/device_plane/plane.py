"""The device collective plane: compiled, donated-buffer collectives.

The fourth rung of the MPI dispatch ladder (shm → tcp → device): when a
world's ranks all resolved onto devices of one JAX mesh (registry.py),
allreduce / allgather / reduce_scatter run as ONE compiled XLA program
over that mesh instead of chunk-pipelined host rings — on TPU the
collective rides ICI scheduled by XLA; on the CPU backend the same code
runs over virtual devices (cross-process via the gloo collectives
layer), which is what the tests drive.

Execution model (multi-controller SPMD): rank threads of one process
rendezvous per collective — each deposits its buffer, the LAST arriver
becomes the executor: it assembles the global array
(``make_array_from_single_device_arrays``), runs the cached compiled
executable, and hands each local rank the addressable shard of its own
device. Worlds spanning processes run the identical program in every
process, exactly like jax's multi-process SPMD model — no cross-process
bytes ever touch the host shm/tcp planes.

Device-resident payloads (ISSUE 15): a deposit that is already a
**committed single-device jax.Array on its rank's registered chip**
skips ``device_put`` entirely, and when every local deposit is resident
the round executes a **zero-host-copy** program: inputs are used in
place in HBM, the input is NOT donated (the callers still own their
arrays — jax arrays are immutable, so MPI's reuse-after-call contract
holds by construction), and each rank's result is returned as the
addressable shard still on its device. Host rounds keep the PR 10
shape: ``device_put`` in (donated — XLA may reuse the buffer), shard
readback out. Mixed-residency rounds stage the resident deposits to
host (one counted copy each) and run the host shape — correctness over
performance for the asymmetric edge case. Every host↔device byte either
path moves is stamped on the ``faabric_device_copy_*`` counters
(copies.py), so "zero host bytes AND zero host copies for a
device-resident allreduce" is an asserted invariant, not a claim.

Executables are cached per (kind, op, elems, dtype, resident) — the
residency flag keys the cache because the resident program differs in
donation/aliasing — and compilation is surfaced as a ``phase=compile``
span plus per-plane hit/compile/compile-ms stats on ``summary()`` and
``GET /topology`` (first-call latency spikes are attributable).

``ring_permute`` is the p2p stream primitive for device worlds: every
rank's payload lands on its ring neighbour's chip in one compiled step
(Pallas ``make_async_remote_copy`` on TPU, ``jax.lax.ppermute``
elsewhere — pallas_ring.py), the building block the schedule runner's
``device-ring`` execution target drives.

Failure contract: eligibility is a pure function of (shape, dtype, op)
plus the activation verdict — residency deliberately does NOT affect
it — so every rank of every process picks the same rung. A backend
error while executing disables the plane and raises
:class:`DevicePlaneFallback`, which MpiWorld catches to re-run the
collective on the host ladder (staging device-resident inputs to host
with one explicit counted copy). Caveat (documented in
docs/data_plane.md): the backend collective is itself synchronous
across processes, so a mid-collective backend failure surfaces in every
process; an error that somehow struck ONE process only would leave the
others waiting in the backend until its own timeout.
"""

from __future__ import annotations

import os
import threading
import time
import warnings
import weakref

import numpy as np

from faabric_tpu.device_plane.copies import D2H, H2D, count_copy
from faabric_tpu.device_plane.registry import DevicePlaneFallback
from faabric_tpu.mpi.types import MpiOp, UserOp
from faabric_tpu.telemetry import (
    get_collective_profiler,
    get_comm_matrix,
    get_metrics,
    get_perf_store,
    span,
)
from faabric_tpu.util.logging import get_logger

logger = get_logger(__name__)

# XLA backends without donation support (CPU) warn per executable; the
# donation is an optimization contract, not a correctness one — keep the
# logs quiet where it cannot be honoured (TPU honours it).
warnings.filterwarnings(
    "ignore", message=".*donated buffers were not usable.*")

# A rank thread waiting for its rendezvous peers (same process, same
# collective) — generous: peers are sibling threads, not the network,
# but a loaded 2-core container can park a thread for seconds
DEVICE_PLANE_TIMEOUT_S = float(
    os.environ.get("FAABRIC_DEVICE_PLANE_TIMEOUT", "120"))

_ALLREDUCE_OPS = (MpiOp.SUM, MpiOp.MAX, MpiOp.MIN, MpiOp.PROD)

_metrics = get_metrics()
_COLLECTIVES = {
    kind: _metrics.counter(
        "faabric_device_plane_collectives_total",
        "Collectives executed on the device plane (per rank)", op=kind)
    for kind in ("allreduce", "allgather", "reduce_scatter",
                 "ring_permute")}
_COMPILES = _metrics.counter(
    "faabric_device_plane_compiles_total",
    "Device-plane executable cache misses (compilations)")
_FALLBACKS = _metrics.counter(
    "faabric_device_plane_fallbacks_total",
    "Device plane disables (collectives re-routed to the host ladder)")
# ISSUE 12: compile/execute phases fold into the collective profiler
# (critical-path decomposition shows compile-storm rounds next to the
# steady state) and executed payloads feed the device-plane link profile
_PROFILER = get_collective_profiler()
_PERF = get_perf_store()

# Live planes of this process (observability: GET /topology and the
# worker telemetry block list their summaries). WeakSet — a destroyed
# world's plane must not be pinned alive by the scrape surface.
_PLANES: "weakref.WeakSet[DevicePlane]" = weakref.WeakSet()
_PLANES_LOCK = threading.Lock()


def is_device_payload(data) -> bool:
    """Duck-typed "is this a jax.Array" check that never imports jax
    and never materializes the buffer: numpy first (the common case),
    then the two attributes every jax Array carries and no ndarray
    does. Used by MpiWorld's dispatch entries on EVERY collective call,
    so it must stay allocation-free."""
    return (not isinstance(data, np.ndarray)
            and hasattr(data, "sharding")
            and hasattr(data, "addressable_shards"))


def device_planes_summary() -> list[dict]:
    """Summaries of this process's live planes (telemetry surface)."""
    with _PLANES_LOCK:
        planes = list(_PLANES)
    out = []
    for p in planes:
        try:
            out.append(p.summary())
        except Exception:  # noqa: BLE001 — scrape must not throw
            pass
    out.sort(key=lambda s: s.get("world_id", 0))
    return out


class _Round:
    """One rendezvous: the local rank threads of one collective call.
    Internally synchronized by the owning plane's lock + the ready
    event; fields are written before ready.set() and read after."""

    __slots__ = ("deposits", "results", "error", "ready")

    def __init__(self) -> None:
        self.deposits: dict[int, tuple] = {}  # rank → (key, buf, resident)
        self.results: dict[int, object] | None = None
        self.error: BaseException | None = None
        self.ready = threading.Event()


class DevicePlane:
    """Compiled collectives bound to one world's resolved mesh."""

    # Rendezvous state and the disable verdict mutate under _lock from
    # N rank threads; the executable cache under its own leaf lock (the
    # executor holds it across a compile — seconds — which must not
    # block peers' deposits for the NEXT round).
    GUARDS = {
        "_rounds": "_lock",
        "_rank_seq": "_lock",
        "_disabled": "_lock",
        "_cache": "_cache_lock",
        "_cache_hits": "_cache_lock",
        "_cache_compiles": "_cache_lock",
        "_compile_ms": "_cache_lock",
    }

    def __init__(self, world_id: int, devices, local_ranks,
                 topology_gen: int, axis_name: str = "ranks") -> None:
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        self.world_id = world_id
        self.devices = list(devices)          # rank i ↔ devices[i]
        self.n = len(self.devices)
        self.local_ranks = tuple(sorted(local_ranks))
        self.n_local = len(self.local_ranks)
        self.topology_gen = topology_gen
        self.axis = axis_name
        self.mesh = Mesh(np.array(self.devices), (axis_name,))
        self._in_sharding = NamedSharding(self.mesh, P(axis_name, None))
        self._rank_of_device = {d: r for r, d in enumerate(self.devices)}
        self._jax = jax

        self._lock = threading.Lock()
        self._rounds: dict[int, _Round] = {}
        self._rank_seq: dict[int, int] = {}
        self._disabled: str | None = None
        self._cache_lock = threading.Lock()
        self._cache: dict[tuple, object] = {}
        self._cache_hits = 0
        self._cache_compiles = 0
        self._compile_ms = 0.0
        with _PLANES_LOCK:
            _PLANES.add(self)

    # ------------------------------------------------------------------
    # Eligibility / residency / fallback ladder
    # ------------------------------------------------------------------
    def eligible(self, kind: str, arr, op=None) -> bool:
        """Pure function of (activation verdict, shape, dtype, op):
        every rank of every process derives the same rung. Ineligible
        shapes take the host ladder with no device-plane involvement.
        ``arr`` may be a numpy array OR a jax.Array — only its
        shape/dtype are consulted, never its bytes (a jax input must
        not be materialized to answer an eligibility question)."""
        with self._lock:
            if self._disabled is not None:
                return False
        size = int(getattr(arr, "size", 0))
        try:
            dtype = np.dtype(arr.dtype)
        except (AttributeError, TypeError):
            return False
        # Exact int folds and IEEE float reductions compile; bool,
        # complex, structured (MINLOC pairs) and object dtypes do not
        if size == 0 or dtype.kind not in "iuf":
            return False
        # Canonicalization guard: with jax_enable_x64 off (this repo
        # never enables it) device_put silently DOWNCASTS 64-bit
        # buffers to 32-bit — wrong result dtype and overflow-corrupt
        # sums past 2^31. Payloads whose canonical jax dtype differs
        # from their numpy dtype keep the exact host ladder. (The x64
        # flag, like every ladder input, must agree across the world's
        # processes — it is process-global jax config. jax.Array inputs
        # pass by construction: they already hold canonical dtypes.)
        if self._jax.dtypes.canonicalize_dtype(dtype) != dtype:
            return False
        if isinstance(op, UserOp):
            return False  # arbitrary python folds cannot compile
        if kind == "allreduce":
            return op in _ALLREDUCE_OPS
        if kind == "reduce_scatter":
            return op == MpiOp.SUM and size % self.n == 0
        if kind in ("allgather", "ring_permute"):
            return op is None
        return False

    def resident(self, rank: int, arr) -> bool:
        """True when ``arr`` is a committed single-device jax.Array
        living on ``rank``'s registered chip — the zero-copy deposit
        shape. Residency is an EXECUTION property, never an eligibility
        one: ranks may disagree on it without desyncing the ladder."""
        if not is_device_payload(arr):
            return False
        try:
            if not getattr(arr, "committed", False):
                return False
            if not arr.is_fully_addressable:
                return False
            devs = arr.sharding.device_set
            if len(devs) != 1:
                return False
            (dev,) = devs
        except Exception:  # noqa: BLE001 — exotic array types → host
            return False
        return 0 <= rank < self.n and dev == self.devices[rank]

    def disable(self, reason: str) -> None:
        """One-way: after any backend error / rendezvous breakdown the
        plane routes everything to the host ladder (re-activation means
        a fresh handshake on the next topology generation)."""
        with self._lock:
            if self._disabled is not None:
                return
            self._disabled = reason
        _FALLBACKS.inc()
        logger.warning("Device plane (world %s) disabled: %s",
                       self.world_id, reason)

    @property
    def disabled_reason(self) -> str | None:
        with self._lock:
            return self._disabled

    # ------------------------------------------------------------------
    # Collectives (MpiWorld-facing; per-rank buffers in and out — numpy
    # or device-resident jax arrays; result residency follows input)
    # ------------------------------------------------------------------
    def allreduce(self, rank: int, data, op: MpiOp = MpiOp.SUM):
        out = self._collective("allreduce", rank, data, op)
        return out.reshape(data.shape)

    def allgather(self, rank: int, data):
        return self._collective("allgather", rank, data, None)

    def reduce_scatter(self, rank: int, data, op: MpiOp = MpiOp.SUM):
        return self._collective("reduce_scatter", rank, data, op)

    def ring_permute(self, rank: int, data, shift: int = 1):
        """The p2p stream primitive: every rank's payload lands on rank
        ``(rank + shift) % n`` in ONE compiled mesh step — Pallas
        ``make_async_remote_copy`` over ICI on TPU, ``lax.ppermute``
        elsewhere (pallas_ring.py). Returns the payload of rank
        ``(rank - shift) % n``; result residency follows input."""
        shift = int(shift) % self.n
        if shift == 0:
            return data
        out = self._collective("ring_permute", rank, data, shift)
        return out.reshape(data.shape)

    # ------------------------------------------------------------------
    def _collective(self, kind: str, rank: int, data, op):
        resident = self.resident(rank, data)
        if resident:
            flat = data.reshape(-1)  # on-device; no host materialization
        else:
            if is_device_payload(data):
                # An eligible jax.Array the plane cannot prove resident
                # (uncommitted, foreign chip): materializing it here IS
                # a device→host transfer — stamp it like every other
                # boundary crossing (the every-copy-counted contract)
                count_copy(D2H, int(data.nbytes), "staging")
            flat = np.ascontiguousarray(np.asarray(data).reshape(-1))
        if kind == "ring_permute":
            op_code = int(op)  # the shift rides the op slot of the key
        else:
            op_code = int(op) if op is not None else -1
        key = (kind, op_code, int(flat.size), str(flat.dtype))
        with self._lock:
            if self._disabled is not None:
                raise DevicePlaneFallback(self._disabled)
            if rank not in self.local_ranks:
                raise DevicePlaneFallback(
                    f"rank {rank} is not local to this plane")
            # Collectives are globally ordered per world, so each
            # rank's Nth device collective belongs to rendezvous N
            seq = self._rank_seq.get(rank, 0)
            self._rank_seq[rank] = seq + 1
            rnd = self._rounds.get(seq)
            if rnd is None:
                rnd = _Round()
                self._rounds[seq] = rnd
            rnd.deposits[rank] = (key, flat, resident)
            last = len(rnd.deposits) == self.n_local

        if last:
            try:
                rnd.results = self._execute(kind, key, rnd.deposits)
            except BaseException as e:  # noqa: BLE001 — delivered to
                # every waiting peer below; backend errors additionally
                # disable the plane so later collectives skip the rung
                if not isinstance(e, DevicePlaneFallback):
                    self.disable(f"backend error: {e!r}")
                    e = DevicePlaneFallback(
                        f"device collective failed: {e!r}")
                rnd.error = e
            with self._lock:
                self._rounds.pop(seq, None)
            rnd.ready.set()
        else:
            while not rnd.ready.wait(DEVICE_PLANE_TIMEOUT_S):
                with self._lock:
                    gathered = len(rnd.deposits) == self.n_local
                if gathered:
                    # Every local rank deposited — the executor is
                    # running (a first-shape compile or the backend
                    # collective itself can outlast the window). Keep
                    # waiting, exactly like a blocked host collective:
                    # a timing out here would desync this rank from the
                    # executor, which WILL return a device result. The
                    # executor's own failure path sets error + ready.
                    continue
                # Peers genuinely missing: a local rank never entered
                # this collective — protocol breakdown, not slowness
                with self._lock:
                    self._rounds.pop(seq, None)
                self.disable(
                    f"rendezvous timeout: round {seq} gathered "
                    f"{len(rnd.deposits)}/{self.n_local} local ranks")
                raise DevicePlaneFallback(
                    "device-plane rendezvous timeout")

        if rnd.error is not None:
            raise rnd.error
        _COLLECTIVES[kind].inc()
        # Truthful accounting: this rank's contribution entered the
        # device plane (ring-neighbour attribution in mesh rank order;
        # the host planes saw none of it)
        get_comm_matrix().record(rank, (rank + 1) % self.n, "device",
                                 int(flat.nbytes))
        return rnd.results[rank]

    # ------------------------------------------------------------------
    def _execute(self, kind: str, key: tuple,
                 deposits: dict[int, tuple]) -> dict:
        """Executor body (one thread per process per round): global
        array assembly → compiled run → per-rank shard handout. An
        all-resident round assembles the callers' HBM shards in place,
        compiles WITHOUT donation (callers keep their arrays) and hands
        each rank its device shard back — zero host↔device copies. Host
        rounds keep the PR 10 shape (device_put in, donated run,
        readback out), every copy counted."""
        jax = self._jax
        for r, (k, _buf, _res) in deposits.items():
            if k != key:
                raise RuntimeError(  # protocol desync — NOT a fallback
                    f"device-plane rendezvous mismatch: rank {r} "
                    f"deposited {k}, executor saw {key}")
        kind_, op_code, m, dtype = key
        all_resident = all(res for (_k, _b, res) in deposits.values())

        shards = []
        for r, (_k, buf, res) in sorted(deposits.items()):
            if all_resident:
                shards.append(buf[None])  # on-device reshape to (1, m)
                continue
            if res:
                # Mixed-residency round: the resident deposit takes the
                # explicit staging copy and rides the host shape
                buf = np.asarray(buf)
                count_copy(D2H, int(buf.nbytes), "staging")
            count_copy(H2D, int(buf.nbytes), "input")
            shards.append(jax.device_put(buf[None], self.devices[r]))
        x = jax.make_array_from_single_device_arrays(
            (self.n, m), self._in_sharding, shards)
        executor_rank = min(deposits)

        exe_key = key + (all_resident,)
        with self._cache_lock:
            compiled = self._cache.get(exe_key)
            if compiled is not None:
                self._cache_hits += 1
        if compiled is None:
            # Rounds are sequential per plane (a rank cannot enter round
            # N+1 before round N released it), so one executor compiles
            # at a time — the lock only orders the publish
            _COMPILES.inc()
            t0 = time.monotonic()
            with span("mpi.phase", "compile", phase="compile",
                      world=self.world_id, kind=kind, elems=m,
                      dtype=dtype, resident=all_resident):
                jfn = self._build(kind, op_code, donate=not all_resident)
                compiled = jfn.lower(x).compile()
            elapsed = time.monotonic() - t0
            _PROFILER.record_phase(self.world_id, kind, executor_rank,
                                   "compile", elapsed)
            with self._cache_lock:
                self._cache[exe_key] = compiled
                self._cache_compiles += 1
                self._compile_ms += elapsed * 1e3

        t0 = time.monotonic()
        with span("mpi.phase", "execute", phase="execute",
                  world=self.world_id, kind=kind, elems=m, dtype=dtype,
                  resident=all_resident):
            y = compiled(x)
            out = self._distribute(kind, y, all_resident)
        elapsed = time.monotonic() - t0
        _PROFILER.record_phase(self.world_id, kind, executor_rank,
                               "execute", elapsed)
        # The whole mesh's payload moved through the device plane in
        # this one execute — a per-mesh rate, not a per-point link
        total_bytes = sum(buf.nbytes for _k, buf, _r in deposits.values())
        _PERF.observe("mesh", "device", total_bytes, elapsed)
        return out

    def _build(self, kind: str, op_code: int, donate: bool = True):
        """The jitted program for one (kind, op): a shard_map whose
        body is the single jax.lax collective. ``donate`` aliases the
        input buffer into the output (host rounds own their device_put
        inputs); resident rounds must NOT donate — the callers still
        hold the input arrays."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        axis = self.axis
        check_vma = True
        if kind == "allreduce":
            op = MpiOp(op_code)
            prim = {MpiOp.SUM: jax.lax.psum, MpiOp.MAX: jax.lax.pmax,
                    MpiOp.MIN: jax.lax.pmin}.get(op)
            if prim is not None:
                def f(shard):  # (1, m) → (1, m), every row the reduction
                    return prim(shard, axis)
            else:  # PROD: gather + fold (no pprod primitive)
                def f(shard):
                    g = jax.lax.all_gather(shard[0], axis, tiled=False)
                    return jnp.prod(g, axis=0,
                                    keepdims=True).astype(shard.dtype)
            out_spec = P(axis, None)
        elif kind == "reduce_scatter":
            def f(shard):  # (1, n·k) → (1, k)
                return jax.lax.psum_scatter(shard, axis,
                                            scatter_dimension=1,
                                            tiled=True)
            out_spec = P(axis, None)
        elif kind == "allgather":
            def f(shard):  # (1, k) → (n·k,) replicated
                return jax.lax.all_gather(shard[0], axis, tiled=True)
            out_spec = P()
            # Replicated output the static check cannot infer
            check_vma = False
        elif kind == "ring_permute":
            from faabric_tpu.device_plane.pallas_ring import permute_body

            # op_code carries the shift; the body is the Pallas
            # remote-copy kernel on TPU, lax.ppermute elsewhere
            f = permute_body(self.mesh, axis, op_code)
            out_spec = P(axis, None)
            # pallas_call's out_shape carries no varying-axes annotation
            check_vma = False
        else:
            raise RuntimeError(f"unknown device collective {kind}")

        fn = jax.shard_map(f, mesh=self.mesh, in_specs=P(axis, None),
                           out_specs=out_spec, check_vma=check_vma)
        return jax.jit(fn, donate_argnums=(0,) if donate else ())

    def _distribute(self, kind: str, y, resident: bool) -> dict:
        """Per-rank results from the output's addressable shards. A
        resident round hands each rank its device shard (still in HBM —
        an immutable jax array, the JAX-native result contract); a host
        round reads back private writable host copies (MPI result
        semantics), each readback counted."""
        if resident:
            out: dict[int, object] = {}
            for s in y.addressable_shards:
                r = self._rank_of_device.get(s.device)
                if r is None:
                    continue
                out[r] = s.data if kind == "allgather" else s.data[0]
            missing = [r for r in self.local_ranks if r not in out]
            if missing:
                raise RuntimeError(
                    f"output shards missing for local ranks {missing}")
            return out
        if kind == "allgather":
            # Replicated output: one readback, one private copy per rank
            full = np.array(y.addressable_shards[0].data)
            count_copy(D2H, int(full.nbytes), "readback")
            return {r: (full if i == 0 else full.copy())
                    for i, r in enumerate(self.local_ranks)}
        out = {}
        for s in y.addressable_shards:
            r = self._rank_of_device.get(s.device)
            if r is not None:
                host = np.array(s.data)[0]
                count_copy(D2H, int(host.nbytes), "readback")
                out[r] = host
        missing = [r for r in self.local_ranks if r not in out]
        if missing:
            raise RuntimeError(
                f"output shards missing for local ranks {missing}")
        return out

    def summary(self) -> dict:
        """Observability snapshot (tests / debugging endpoints /
        ``GET /topology``)."""
        from faabric_tpu.device_plane.copies import device_copy_totals

        with self._cache_lock:
            cached = sorted(str(k) for k in self._cache)
            cache_stats = {
                "entries": len(self._cache),
                "hits": self._cache_hits,
                "compiles": self._cache_compiles,
                "compile_ms_total": round(self._compile_ms, 3),
            }
        return {
            "world_id": self.world_id,
            "size": self.n,
            "local_ranks": list(self.local_ranks),
            "platform": self.devices[0].platform if self.devices else "",
            "topology_gen": self.topology_gen,
            "disabled": self.disabled_reason,
            "cached_executables": cached,
            "executable_cache": cache_stats,
            # PROCESS-wide host<->device copy accounting (copies.py) —
            # named so a consumer summing across listed planes cannot
            # mistake it for a per-plane figure and double-count
            "process_device_copies": device_copy_totals(),
        }
