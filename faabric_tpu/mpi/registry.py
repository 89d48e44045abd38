"""World registry + per-message MPI context.

Reference analog: src/mpi/MpiWorldRegistry.cpp:13-75 (createWorld for
rank 0 vs getOrInitialiseWorld for other ranks) and src/mpi/MpiContext.cpp
:14-50. Instantiable per worker runtime (like the broker/scheduler) so
in-process multi-host tests can run one registry per logical host.
"""

from __future__ import annotations

import threading
import time
from typing import Optional

from faabric_tpu.mpi.world import MpiWorld
from faabric_tpu.proto import BatchExecuteRequest, Message, batch_exec_factory
from faabric_tpu.util.logging import get_logger

logger = get_logger(__name__)

# How long a joining rank waits for this host's in-progress create_world
# (one planner round-trip to chain the ranks) before giving up
RESERVATION_WAIT_S = 60.0


class MpiWorldRegistry:
    # Concurrency contract (tools/concheck.py): world creation/join/
    # destroy race across executor threads; the id map is the shared
    # state (reservation under the lock is what makes duplicate create
    # fail instead of double-chaining ranks).
    GUARDS = {"_worlds": "_lock"}

    def __init__(self, broker, planner_client=None) -> None:
        self.broker = broker
        self.planner_client = planner_client
        self._lock = threading.Lock()
        # Signalled when a create_world reservation resolves (published
        # or withdrawn): local joiners wait on it
        self._resolved = threading.Condition(self._lock)
        self._worlds: dict[int, MpiWorld] = {}

    # ------------------------------------------------------------------
    def create_world(self, msg: Message, world_size: int | None = None) -> MpiWorld:
        """Rank 0 creates the world: chain (size-1) functions through the
        planner so every rank gets scheduled, a group, a chip, and an MPI
        port (reference MpiWorld::create :157-226)."""
        size = world_size or msg.mpi_world_size
        if size <= 0:
            raise ValueError(f"Invalid MPI world size {size}")
        world_id = msg.mpi_world_id
        with self._lock:
            # Reserve the id under the lock: a concurrent duplicate create
            # must fail here, not double-chain ranks through the planner
            if world_id in self._worlds:
                raise ValueError(f"World {world_id} already exists")
            self._worlds[world_id] = None  # type: ignore[assignment]

        try:
            if size > 1:
                if self.planner_client is None:
                    raise RuntimeError("No planner client to chain MPI ranks")
                req = BatchExecuteRequest(
                    app_id=msg.app_id, user=msg.user, function=msg.function)
                for rank in range(1, size):
                    chained = batch_exec_factory(msg.user, msg.function,
                                                 1).messages[0]
                    chained.app_id = msg.app_id
                    chained.app_idx = rank
                    chained.group_idx = rank
                    chained.is_mpi = True
                    chained.mpi_world_id = world_id
                    chained.mpi_world_size = size
                    chained.mpi_rank = rank
                    req.messages.append(chained)
                decision = self.planner_client.call_functions(req)
                group_id = decision.group_id or msg.group_id
            else:
                group_id = msg.group_id

            world = MpiWorld(self.broker, world_id, size, group_id,
                             user=msg.user, function=msg.function)
            world.record_exec_graph = msg.record_exec_graph
        except BaseException:
            with self._lock:
                if self._worlds.get(world_id) is None:
                    self._worlds.pop(world_id, None)
                self._resolved.notify_all()
            raise
        with self._lock:
            if world_id not in self._worlds:
                # clear() swept the registry (worker teardown) while we
                # were chaining ranks: don't resurrect a world into a
                # dead registry
                world.close()
                raise RuntimeError(
                    f"Registry cleared while creating world {world_id}")
            self._worlds[world_id] = world
            self._resolved.notify_all()
        logger.debug("Created MPI world %d (size=%d group=%d)", world_id,
                     size, group_id)
        return world

    def get_or_initialise_world(self, msg: Message) -> MpiWorld:
        """Non-zero ranks join from their dispatched message (reference
        getOrInitialiseWorld :54-75 — idempotent per host)."""
        world_id = msg.mpi_world_id
        with self._lock:
            # A None entry is a reservation by an in-progress create_world
            # on this host (the chained ranks are dispatched before the
            # creator has its world in hand). Wait for it: the ranks of one
            # process must share ONE world object — the device plane's
            # rendezvous is in-process state, and a rank with a private
            # view would wait there alone until the timeout sends its
            # collective down the host ladder
            deadline = time.monotonic() + RESERVATION_WAIT_S
            while (world_id in self._worlds
                   and self._worlds[world_id] is None):
                left = deadline - time.monotonic()
                if left <= 0:
                    raise RuntimeError(
                        f"World {world_id} is still being created on this "
                        f"host after {RESERVATION_WAIT_S:.0f}s")
                self._resolved.wait(left)
            world = self._worlds.get(world_id)
            if world is None:
                world = MpiWorld(self.broker, world_id,
                                 msg.mpi_world_size, msg.group_id,
                                 user=msg.user, function=msg.function)
                world.record_exec_graph = msg.record_exec_graph
                self._worlds[world_id] = world
            return world

    def get_world(self, world_id: int) -> MpiWorld:
        with self._lock:
            return self._worlds[world_id]

    def has_world(self, world_id: int) -> bool:
        with self._lock:
            return world_id in self._worlds

    def destroy_world(self, world_id: int) -> None:
        with self._lock:
            world = self._worlds.pop(world_id, None)
        if world is not None:
            world.close()
            self.broker.clear_group(world.group_id)

    def clear(self) -> None:
        with self._lock:
            worlds, self._worlds = dict(self._worlds), {}
            self._resolved.notify_all()
        for w in worlds.values():
            if w is not None:  # None = create_world's in-flight reservation
                w.close()


class MpiContext:
    """Per-executing-message MPI binding (reference MpiContext.cpp:14-50)."""

    def __init__(self, registry: MpiWorldRegistry) -> None:
        self.registry = registry
        self.world_id = 0
        self.rank = -1
        self._world: Optional[MpiWorld] = None

    def create_world(self, msg: Message, world_size: int | None = None) -> MpiWorld:
        if msg.mpi_rank != 0:
            raise ValueError("Only rank 0 creates the world")
        self._world = self.registry.create_world(msg, world_size)
        self.world_id = self._world.id
        self.rank = 0
        return self._world

    def join_world(self, msg: Message) -> MpiWorld:
        self._world = self.registry.get_or_initialise_world(msg)
        self.world_id = self._world.id
        self.rank = msg.mpi_rank
        return self._world

    @property
    def world(self) -> MpiWorld:
        if self._world is None:
            raise RuntimeError("MPI context not initialised")
        return self._world

    def is_mpi(self) -> bool:
        return self._world is not None


def get_mpi_context() -> MpiContext:
    """Build an MPI context for the currently executing task, using the
    host's broker/registry (guest-code entry point)."""
    from faabric_tpu.executor.context import ExecutorContext

    ctx = ExecutorContext.get()
    scheduler = ctx.executor.scheduler
    registry = getattr(scheduler, "mpi_registry", None)
    if registry is None:
        raise RuntimeError("This host has no MPI registry")
    return MpiContext(registry)
