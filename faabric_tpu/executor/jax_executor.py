"""First-class executor for JAX guest functions.

Reference analog: the (user, function)-keyed guest-callback registry the
reference uses for distributed tests (tests/dist/DistTestExecutor.cpp:16-58)
and that Faasm implements with WASM modules — promoted here to the
framework's native ExecutorFactory: TPU workloads register Python/JAX
callables, get gang-scheduled by the planner, and run with their
planner-assigned chip and MPI/PTP context in hand.

Usage::

    @register_function("demo", "train_step")
    def train_step(ctx):
        world = ctx.mpi_world()           # gang's MPI world (create/join)
        dev = ctx.device                  # the chip the planner pinned
        ...
        return b"result bytes"            # → msg.output_data

    runtime = WorkerRuntime(..., factory=JaxExecutorFactory())

Return conventions: ``bytes`` → output_data + SUCCESS; ``int`` → return
value; ``None`` → SUCCESS.
"""

from __future__ import annotations

import threading
from typing import Callable, Optional

import numpy as np

from faabric_tpu.executor.executor import Executor
from faabric_tpu.executor.factory import ExecutorFactory
from faabric_tpu.proto import ReturnValue
from faabric_tpu.util.logging import get_logger

logger = get_logger(__name__)

_registry: dict[tuple[str, str], Callable] = {}
_registry_lock = threading.Lock()


def register_function(user: str, name: str, fn: Optional[Callable] = None):
    """Register ``fn`` as guest function (user, name); usable as a
    decorator."""
    def _do(f: Callable) -> Callable:
        with _registry_lock:
            _registry[(user, name)] = f
        return f

    if fn is not None:
        return _do(fn)
    return _do


def unregister_function(user: str, name: str) -> None:
    with _registry_lock:
        _registry.pop((user, name), None)


def clear_registered_functions() -> None:
    with _registry_lock:
        _registry.clear()


class GuestContext:
    """What a guest function sees: its message/request, the broker, the
    chip the planner pinned this rank to, and MPI world helpers."""

    def __init__(self, executor: "JaxExecutor", msg, req) -> None:
        self.executor = executor
        self.message = msg
        self.request = req

    # -- placement ------------------------------------------------------
    @property
    def device_id(self) -> int:
        """Planner-assigned chip id (-1 when the gang carries none)."""
        broker = self.broker
        if broker is None or not self.message.group_id:
            return -1
        try:
            broker.wait_for_mappings(self.message.group_id, timeout=5.0)
            return broker.get_device_for_idx(self.message.group_id,
                                             self.message.group_idx)
        except Exception:  # noqa: BLE001 — no mappings = no pinning
            return -1

    @property
    def device(self):
        """The local jax device the planner pinned this rank to. Raises
        when it pinned none (the host registered no chips): defaulting
        to the first device would run every rank on one chip and still
        return the right answer."""
        from faabric_tpu.parallel.collectives import local_devices_for_ids

        did = self.device_id
        if did < 0:
            raise RuntimeError(
                f"no chip pinned for {self.message.user}/"
                f"{self.message.function} idx {self.message.group_idx}: "
                "the worker must register its chips (n_devices)")
        return local_devices_for_ids([did])[0]

    # -- messaging ------------------------------------------------------
    @property
    def broker(self):
        sched = self.executor.scheduler
        return getattr(sched, "ptp_broker", None) if sched else None

    def mpi_world(self):
        """Create (rank 0 of an un-created world) or join this gang's MPI
        world — the reference's MPI_Init flow."""
        from faabric_tpu.mpi import get_mpi_context

        ctx = get_mpi_context()
        msg = self.message
        if msg.mpi_rank == 0 and not msg.is_mpi:
            msg.is_mpi = True
            if not msg.mpi_world_id:
                msg.mpi_world_id = msg.app_id
            if not msg.mpi_world_size:
                msg.mpi_world_size = self.request.n_messages()
            world = ctx.create_world(msg)
        else:
            world = ctx.join_world(msg)
        world.refresh_rank_hosts()
        return world

    def state(self):
        """The host's State instance (KV get/set across the cluster)."""
        sched = self.executor.scheduler
        return getattr(sched, "state", None) if sched else None


class JaxExecutor(Executor):
    """Runs registered guest callables; memory is a plain numpy image so
    snapshot/dirty tracking work unchanged."""

    DEFAULT_MEM = 64 * 1024

    def __init__(self, msg) -> None:
        super().__init__(msg)
        self.memory = np.zeros(self.DEFAULT_MEM, dtype=np.uint8)

    def get_memory_view(self):
        return self.memory

    def set_memory_size(self, size: int) -> None:
        if size > self.memory.size:
            self.memory = np.concatenate(
                [self.memory, np.zeros(size - self.memory.size, np.uint8)])

    def execute_task(self, thread_pool_idx: int, msg_idx: int, req) -> int:
        msg = req.messages[msg_idx]
        with _registry_lock:
            fn = _registry.get((msg.user, msg.function))
        if fn is None:
            msg.output_data = (
                f"no registered function {msg.user}/{msg.function}".encode())
            return int(ReturnValue.FAILED)
        try:
            result = fn(GuestContext(self, msg, req))
        except Exception as e:  # noqa: BLE001 — guest failure, not ours
            logger.exception("Guest %s/%s failed", msg.user, msg.function)
            msg.output_data = repr(e).encode()[:512]
            return int(ReturnValue.FAILED)
        if isinstance(result, bytes):
            msg.output_data = result
            return int(ReturnValue.SUCCESS)
        if isinstance(result, int):
            return result
        return int(ReturnValue.SUCCESS)


class JaxExecutorFactory(ExecutorFactory):
    def create_executor(self, msg) -> JaxExecutor:
        return JaxExecutor(msg)
