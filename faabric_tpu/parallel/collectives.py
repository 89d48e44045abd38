"""Compiled device collectives over a JAX mesh.

This is the TPU-native data plane that replaces the reference's
leader-tree collectives over raw TCP (src/mpi/MpiWorld.cpp:786-1775): the
per-rank buffers live as shards of a global array laid out over a
``jax.sharding.Mesh``, and each collective is a jitted ``shard_map`` whose
``jax.lax`` collective XLA lowers onto ICI (psum/all_gather/psum_scatter/
all_to_all/ppermute). No host round-trips, no per-pair sockets — the
compiler owns the schedule.

Array convention (maps 1:1 onto MPI semantics):
- ``allreduce``: global shape (n_ranks, *buf) sharded on axis 0; every
  rank's output shard is the full reduction.
- ``allgather``: shard (k, *buf) per rank → replicated (n_ranks*k, *buf).
- ``reduce_scatter``: shard (n_ranks*k,) per rank → (k,) reduced segment.
- ``alltoall``: shard rows (n_ranks, *buf) per rank → row i of rank j
  lands as row j of rank i.
- ``broadcast``: root rank's shard replicated to every rank.

Compiled callables are cached per (kind, op, global shape, dtype) — the
first call pays XLA compilation, steady state is a cached executable.
"""

from __future__ import annotations

import threading
from typing import Any, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from faabric_tpu.mpi.types import MpiOp

_PRIMITIVE_REDUCERS = {
    MpiOp.SUM: jax.lax.psum,
    MpiOp.MAX: jax.lax.pmax,
    MpiOp.MIN: jax.lax.pmin,
}

_GATHER_REDUCERS = {
    MpiOp.PROD: jnp.prod,
    MpiOp.LAND: jnp.all,
    MpiOp.LOR: jnp.any,
}


class DeviceCollectives:
    """Collectives bound to an ordered set of devices (rank i ↔ device i)."""

    def __init__(self, devices: Sequence[Any], axis_name: str = "ranks") -> None:
        self.devices = list(devices)
        self.n = len(self.devices)
        self.axis = axis_name
        self.mesh = Mesh(np.array(self.devices), (axis_name,))
        self._cache: dict[tuple, Any] = {}
        self._cache_lock = threading.Lock()

    # ------------------------------------------------------------------
    def sharding(self, partitioned: bool = True) -> NamedSharding:
        return NamedSharding(self.mesh,
                             P(self.axis) if partitioned else P())

    def shard_stacked(self, per_rank: Sequence[np.ndarray]) -> jax.Array:
        """Place one buffer per rank onto its device as a stacked global
        array of shape (n_ranks, *buf). Single-controller form: this
        process must hold every rank's buffer (all devices addressable
        or the data replicated); on a multi-process plane use
        :meth:`shard_stacked_addressable`."""
        # Stacked on the host so each rank's row goes straight to its own
        # device instead of passing through the default one
        stacked = np.stack([np.asarray(b) for b in per_rank])
        return jax.device_put(stacked, self.sharding())

    def shard_stacked_addressable(self, local_per_rank,
                                  buf_shape: tuple,
                                  dtype) -> jax.Array:
        """Multi-process form of :meth:`shard_stacked`: each process
        supplies buffers ONLY for the ranks whose devices it owns
        (``local_per_rank``: rank → buffer mapping), and the global
        (n_ranks, *buf) array is assembled from the per-device shards —
        no process ever materialises another process's data. This is
        the construction every cross-process collective starts from
        (jax multi-controller SPMD: same jitted call in every process,
        one global array)."""
        my_proc = jax.process_index()
        shards = []
        for rank, dev in enumerate(self.devices):
            if dev.process_index != my_proc:
                continue
            if rank not in local_per_rank:
                raise KeyError(
                    f"process {my_proc} owns rank {rank} (device {dev}) "
                    "but no buffer was supplied for it")
            buf = np.asarray(local_per_rank[rank], dtype).reshape(buf_shape)
            shards.append(jax.device_put(buf[None], dev))
        return jax.make_array_from_single_device_arrays(
            (self.n, *buf_shape), self.sharding(), shards)

    def addressable_shard(self, x: jax.Array, rank: int) -> np.ndarray:
        """This process's view of ``rank``'s shard (raises if the rank's
        device belongs to another process)."""
        dev = self.devices[rank]
        for s in x.addressable_shards:
            if s.device == dev:
                return np.asarray(s.data)
        raise KeyError(f"rank {rank} shard lives on {dev}, not in "
                       f"process {jax.process_index()}")

    # ------------------------------------------------------------------
    def _compiled(self, key: tuple, build) -> Any:
        with self._cache_lock:
            fn = self._cache.get(key)
            if fn is None:
                fn = build()
                self._cache[key] = fn
            return fn

    def _shard_mapped(self, fn, in_spec, out_spec, replicated_out: bool = False):
        # all_gather/broadcast outputs ARE replicated, but the static
        # varying-axes check cannot infer it
        return jax.jit(jax.shard_map(fn, mesh=self.mesh, in_specs=in_spec,
                                     out_specs=out_spec,
                                     check_vma=not replicated_out))

    # ------------------------------------------------------------------
    # Collectives
    # ------------------------------------------------------------------
    def allreduce(self, x: jax.Array, op: MpiOp = MpiOp.SUM) -> jax.Array:
        key = ("allreduce", int(op), x.shape, str(x.dtype))

        def build():
            prim = _PRIMITIVE_REDUCERS.get(op)
            if prim is not None:
                def f(shard):
                    return prim(shard, self.axis)
            else:
                reducer = _GATHER_REDUCERS.get(op)
                if reducer is None:
                    raise NotImplementedError(f"Device allreduce op {op}")

                def f(shard):
                    gathered = jax.lax.all_gather(shard, self.axis)
                    return reducer(gathered, axis=0).astype(shard.dtype)
            return self._shard_mapped(f, P(self.axis), P(self.axis))

        return self._compiled(key, build)(x)

    def allreduce_loop(self, x: jax.Array, n: int,
                       op: MpiOp = MpiOp.SUM) -> jax.Array:
        """``n`` chained allreduces inside ONE compiled program
        (``fori_loop`` around the collective), returning exactly what a
        single :meth:`allreduce` would. One dispatch per n collectives —
        the benchmarking form for high-latency PJRT clients, where
        per-call dispatch would otherwise swamp the on-ICI time being
        measured.

        The loop body is the bare reduce (no per-hop work rides inside
        the timed region); for SUM the value grows ×ranks per extra hop
        and ONE post-loop rescale by ranks^(n−1) restores the plain sum.
        The rescale (a full elementwise HBM pass) exists only for n ≥ 2
        (growth is 1 at n = 1), so a two-point timing slope cancels it
        ONLY if both trip counts are ≥ 2 — time with n_lo=2, not 1, or
        the slope charges that pass to per-hop time (ADVICE r3). Interim
        SUM values must stay within the dtype's range for the chosen n
        (the caller bounds magnitudes; MAX/MIN are idempotent).
        """
        prim = _PRIMITIVE_REDUCERS.get(op)
        if prim is None:
            raise NotImplementedError(f"allreduce_loop op {op}")
        key = ("allreduce_loop", int(op), n, x.shape, str(x.dtype))
        growth = self.n ** (n - 1)

        def build():
            def f(shard):
                def body(_, y):
                    return prim(y, self.axis)
                r = jax.lax.fori_loop(0, n, body, shard)
                if op == MpiOp.SUM and growth > 1:
                    if jnp.issubdtype(r.dtype, jnp.inexact):
                        r = r * jnp.asarray(1.0 / growth, r.dtype)
                    else:
                        # Exact: the interim value is growth·sum
                        r = r // growth
                return r
            # The carry flips rank-varying → invariant after the first
            # reduce; the static replication check can't type that loop
            return self._shard_mapped(f, P(self.axis), P(self.axis),
                                      replicated_out=True)

        return self._compiled(key, build)(x)

    def allgather(self, x: jax.Array) -> jax.Array:
        """(n*k, *buf) global, shard (k,*buf) per rank → replicated
        (n*k, *buf)."""
        key = ("allgather", x.shape, str(x.dtype))

        def build():
            def f(shard):
                return jax.lax.all_gather(shard, self.axis, tiled=True)
            return self._shard_mapped(f, P(self.axis), P(),
                                      replicated_out=True)

        return self._compiled(key, build)(x)

    def reduce_scatter(self, x: jax.Array, op: MpiOp = MpiOp.SUM) -> jax.Array:
        """Each rank holds (n*k,) (global (n, n*k) stacked); output shard
        (k,) is the reduced segment — global (n, k)."""
        if op != MpiOp.SUM:
            raise NotImplementedError("Device reduce_scatter supports SUM")
        key = ("reduce_scatter", x.shape, str(x.dtype))

        def build():
            def f(shard):
                # shard: (1, n*k) → (1, k)
                return jax.lax.psum_scatter(shard, self.axis,
                                            scatter_dimension=1, tiled=True)
            return self._shard_mapped(f, P(self.axis), P(self.axis))

        return self._compiled(key, build)(x)

    def alltoall(self, x: jax.Array) -> jax.Array:
        """Global (n, n, *buf), shard (1, n, *buf) rows per rank; row i of
        rank j becomes row j of rank i."""
        key = ("alltoall", x.shape, str(x.dtype))

        def build():
            def f(shard):
                # shard (1, n, *buf): chunk j of rank i lands as chunk i of
                # rank j (MPI alltoall)
                rows = jax.lax.all_to_all(shard[0], self.axis, split_axis=0,
                                          concat_axis=0, tiled=True)
                return rows[None]
            return self._shard_mapped(f, P(self.axis), P(self.axis))

        return self._compiled(key, build)(x)

    def broadcast(self, x: jax.Array, root: int = 0) -> jax.Array:
        """Root rank's shard replicated to all ranks: (n, *buf) → (*buf)."""
        key = ("broadcast", int(root), x.shape, str(x.dtype))

        def build():
            def f(shard):
                gathered = jax.lax.all_gather(shard, self.axis, tiled=True)
                return gathered[root]
            return self._shard_mapped(f, P(self.axis), P(),
                                      replicated_out=True)

        return self._compiled(key, build)(x)

    def scan(self, x: jax.Array, op: MpiOp = MpiOp.SUM) -> jax.Array:
        """Inclusive prefix reduction across ranks (MPI_Scan)."""
        key = ("scan", int(op), x.shape, str(x.dtype))
        reducers = {MpiOp.SUM: jnp.cumsum,
                    MpiOp.PROD: jnp.cumprod,
                    MpiOp.MAX: lambda g, axis: jax.lax.cummax(g, axis=axis),
                    MpiOp.MIN: lambda g, axis: jax.lax.cummin(g, axis=axis)}
        reducer = reducers.get(op)
        if reducer is None:
            raise NotImplementedError(f"Device scan op {op}")

        def build():
            def f(shard):
                gathered = jax.lax.all_gather(shard, self.axis, tiled=True)
                idx = jax.lax.axis_index(self.axis)
                prefix = reducer(gathered, axis=0).astype(shard.dtype)
                return jax.lax.dynamic_slice_in_dim(prefix, idx, 1, axis=0)
            return self._shard_mapped(f, P(self.axis), P(self.axis))

        return self._compiled(key, build)(x)

    # ------------------------------------------------------------------
    # Point-to-point over ICI (the device analog of the PTP broker's
    # host dispatch — SURVEY §5.8: "PTP dispatch becomes device-to-device
    # transfers over ICI")
    # ------------------------------------------------------------------
    def permute(self, x: jax.Array,
                pairs: Sequence[tuple[int, int]]) -> jax.Array:
        """Move rank shards along (src, dst) pairs in ONE compiled
        ``ppermute`` (each a direct ICI transfer). Ranks that are not a
        destination receive zeros — MPI-style sendrecv chains compose
        from these primitives without host round-trips."""
        key = ("permute", tuple(pairs), x.shape, str(x.dtype))

        def build():
            perm = list(pairs)

            def f(shard):
                return jax.lax.ppermute(shard, self.axis, perm)
            return self._shard_mapped(f, P(self.axis), P(self.axis))

        return self._compiled(key, build)(x)

    def send_recv(self, x: jax.Array, src: int, dst: int) -> jax.Array:
        """Single device-to-device transfer: rank ``src``'s shard lands
        on rank ``dst`` (others zero)."""
        return self.permute(x, [(src, dst)])

    def shift(self, x: jax.Array, disp: int = 1) -> jax.Array:
        """Ring rotation by ``disp`` (every rank sends, every rank
        receives — the neighbour-exchange building block)."""
        return self.permute(
            x, [(i, (i + disp) % self.n) for i in range(self.n)])

    # ------------------------------------------------------------------
    def to_per_rank(self, x: jax.Array) -> list[np.ndarray]:
        """Read a stacked (n, *buf) array back as per-rank host buffers."""
        host = np.asarray(x)
        return [host[i] for i in range(self.n)]


def local_devices_for_ids(device_ids: Sequence[int]) -> list:
    """Resolve planner-assigned chip indexes to jax devices on this host.

    The planner numbers a host's chips 0..n_devices-1 (the count the
    worker registered), which indexes ``jax.local_devices()``. An index
    the host does not have, or two ranks on one chip, raises: a mesh
    silently folded onto fewer chips than the planner assigned would
    still compute the right answer."""
    all_devs = jax.local_devices()
    ids = [int(i) for i in device_ids]
    bad = [i for i in ids if not 0 <= i < len(all_devs)]
    if bad or len(set(ids)) != len(ids):
        raise ValueError(
            f"Device ids {ids} do not map onto distinct local devices "
            f"({len(all_devs)} available)")
    return [all_devs[i] for i in ids]
