"""Multi-process device plane: ONE jax mesh spanning worker processes.

The reference spans hosts with a per-rank-pair TCP mesh inside MpiWorld
(src/mpi/MpiWorld.cpp:1789-1934) over its docker-compose worker topology
(docker-compose.yml:42-62). The TPU-native equivalent is JAX's
multi-controller SPMD model: every worker process joins one
``jax.distributed`` coordination service, contributes its local chips,
and ``jax.devices()`` becomes the GLOBAL device set — collectives
compiled over a mesh of those devices ride ICI within a slice and DCN
across slices, scheduled by XLA rather than hand-built socket pairs.

Formation is planner-coordinated (``Planner.join_device_plane``): each
worker asks the planner to join at boot, the planner assigns process ids
in join order and elects the FIRST joiner's host to run the coordination
service on a planner-claimed port (the same pool that backs MPI
base-port claims). This mirrors how the planner already forms MPI gangs
— the device plane is one more gang, sized by configuration rather than
per-batch because ``jax.distributed.initialize`` is once-per-process:
a pod slice is claimed for the worker's lifetime, exactly like a real
TPU pod.

Single-machine testing: N worker processes × M virtual CPU devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=M``) form an N·M
device global mesh over the Gloo CPU backend — the driver-style dryrun
for multi-host without multi-host hardware.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from typing import Optional, Sequence

from faabric_tpu.util.logging import get_logger

logger = get_logger(__name__)

# Timeout for the whole plane to assemble (all processes must reach
# jax.distributed.initialize together; stragglers block everyone)
DEFAULT_INIT_TIMEOUT_S = 120.0

_state_lock = threading.Lock()
_joined_spec: Optional["DevicePlaneSpec"] = None


@dataclasses.dataclass(frozen=True)
class DevicePlaneSpec:
    """Everything a worker needs to join the plane. ``coordinator_host``
    is a LOGICAL host name — the dialable ip:port comes from the alias
    table (transport/common.py), so single-machine clusters on aliased
    loopback ports and real multi-host clusters use the same spec."""

    coordinator_host: str
    coordinator_port: int
    num_processes: int
    process_id: int

    def coordinator_address(self) -> str:
        from faabric_tpu.transport.common import resolve_host

        ip, port = resolve_host(self.coordinator_host,
                                self.coordinator_port)
        return f"{ip}:{port}"

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "DevicePlaneSpec":
        return cls(coordinator_host=d["coordinator_host"],
                   coordinator_port=int(d["coordinator_port"]),
                   num_processes=int(d["num_processes"]),
                   process_id=int(d["process_id"]))


def request_device_plane(planner_client, n_processes: int,
                         timeout: float = 60.0,
                         poll_interval: float = 0.2) -> DevicePlaneSpec:
    """Ask the planner to join the device plane, polling until the
    roster is full (every expected worker has asked). The planner
    assigns process ids in join order — deterministic and stable because
    each host's slot is remembered across polls."""
    deadline = time.monotonic() + timeout
    while True:
        spec = planner_client.join_device_plane(n_processes)
        if spec is not None:
            return spec
        if time.monotonic() > deadline:
            raise TimeoutError(
                f"device plane of {n_processes} never assembled "
                f"within {timeout}s (workers missing?)")
        time.sleep(poll_interval)


def join_device_plane(spec: DevicePlaneSpec,
                      local_device_ids: Optional[Sequence[int]] = None,
                      init_timeout_s: float = DEFAULT_INIT_TIMEOUT_S,
                      ) -> None:
    """Join the coordination service and initialise the global backend.

    Must run before anything initialises a JAX backend in this process
    (``jax.distributed.initialize`` is once-per-process). After it,
    ``jax.devices()`` is the plane-wide device list and
    ``jax.local_devices()`` this process's contribution.
    """
    global _joined_spec
    import jax

    with _state_lock:
        if _joined_spec is not None:
            if _joined_spec == spec:
                return  # idempotent re-join with the same spec
            raise RuntimeError(
                f"process already joined plane {_joined_spec}; "
                f"cannot join {spec}")
        addr = spec.coordinator_address()
        logger.info("Joining device plane: %s as process %d/%d",
                    addr, spec.process_id, spec.num_processes)
        # Cross-process collectives on the CPU backend ride gloo, which
        # must be selected BEFORE the backend initialises. TPU backends
        # ignore it.
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        jax.distributed.initialize(
            coordinator_address=addr,
            num_processes=spec.num_processes,
            process_id=spec.process_id,
            local_device_ids=(None if local_device_ids is None
                              else list(local_device_ids)),
            initialization_timeout=int(init_timeout_s))
        _joined_spec = spec


def leave_device_plane() -> None:
    """Tear down this process's membership (idempotent)."""
    global _joined_spec
    import jax

    with _state_lock:
        if _joined_spec is None:
            return
        try:
            jax.distributed.shutdown()
        except Exception:  # noqa: BLE001 — peers may already be gone
            logger.debug("jax.distributed.shutdown raised", exc_info=True)
        _joined_spec = None


def current_plane() -> Optional[DevicePlaneSpec]:
    with _state_lock:
        return _joined_spec


def plane_summary() -> dict:
    """Observability: what this process sees of the plane."""
    import jax

    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "global_devices": len(jax.devices()),
        "local_devices": len(jax.local_devices()),
        "platform": jax.default_backend(),
    }


def force_cpu_virtual_devices(n: int) -> None:
    """Single-machine plane testing: give this process EXACTLY ``n``
    virtual CPU devices, replacing any inherited device-count flag (a
    test harness parent exports its own). Must run before any JAX
    backend initialises. The flag only shapes the CPU backend; which
    backend runs is the caller's ``JAX_PLATFORMS=cpu``."""
    flags = [f for f in os.environ.get("XLA_FLAGS", "").split()
             if "xla_force_host_platform_device_count" not in f]
    flags.append(f"--xla_force_host_platform_device_count={n}")
    os.environ["XLA_FLAGS"] = " ".join(flags)
