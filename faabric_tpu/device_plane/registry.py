"""Device registration and mesh resolution for one MPI world.

The handshake (fabric-lib arXiv:2510.27656 gives the shape: peers
register their local memory/device handles, exchange them once, and
every peer independently validates the resulting connectivity before
any zero-copy path activates):

1. every rank resolves its OWN device — the planner-assigned chip
   carried in the PTP mappings by default, or an explicit override —
   and registers it with the world;
2. one host-path allgather moves each rank's ``(rank, global device
   id, jax process index)`` row to every participant (the only wire
   exchange; collectives after activation never touch the host
   planes);
3. every participant runs the SAME deterministic validation over the
   full row set (``resolve_mesh``). The plane activates only when the
   whole rank set resolves onto distinct devices of ONE mesh whose
   process split matches the world's host split; any violation raises
   :class:`MeshMismatch` and the world stays on the host ladder.

Because step 3 is a pure function of data every rank holds after the
allgather, all processes reach the identical activate/fall-back
verdict with no further coordination — the property that keeps the
dispatch ladder from desyncing across ranks.
"""

from __future__ import annotations

import numpy as np

from faabric_tpu.util.logging import get_logger

logger = get_logger(__name__)

# Pure functions over allgathered rows — no shared mutable state
GUARDS: dict = {}

# One handshake row per rank: [rank, global device id, process index]
ROW_FIELDS = 3


class DevicePlaneFallback(RuntimeError):
    """Route this collective (and, once raised from activation or a
    backend failure, every later one) back to the host ladder."""


class MeshMismatch(DevicePlaneFallback):
    """The registered rank→device set does not resolve to one mesh."""


def registration_row(rank: int, device) -> np.ndarray:
    """This rank's handshake row. ``device`` is a jax Device or None
    (no resolvable device — the row still travels so every peer reaches
    the same MeshMismatch verdict instead of hanging the handshake)."""
    if device is None:
        return np.array([rank, -1, -1], dtype=np.int64)
    return np.array([rank, int(device.id), int(device.process_index)],
                    dtype=np.int64)


def resolve_local_device(world, rank: int):
    """Default registration: the planner-assigned chip of ``rank``
    (decision ``device_ids`` riding the PTP mappings) as an index into
    this process's jax devices, the same way local_devices_for_ids maps
    it. None when the placement carries no device or names a chip this
    process does not have — the handshake then refuses the plane
    instead of folding two ranks onto one chip."""
    import jax

    try:
        dev_id = world.device_for_rank(rank)
    except Exception:  # noqa: BLE001 — stub brokers without device maps
        return None
    if dev_id is None or dev_id < 0:
        return None
    local = jax.local_devices()
    if dev_id >= len(local):
        return None
    return local[dev_id]


def resolve_mesh(rows: np.ndarray, size: int, local_ranks,
                 process_index: int) -> list:
    """Validate the allgathered registration rows and return the mesh's
    device list in rank order.

    ``local_ranks`` is the rank set THIS world object serves (the
    broker's host split); ``process_index`` this process's jax process
    id. Deterministic in its inputs: every process computes the same
    verdict from the same rows, differing only in which ranks it calls
    local — and the cross-check below makes those two splits agree or
    the whole plane refuses.
    """
    import jax

    rows = np.asarray(rows).reshape(-1, ROW_FIELDS)
    if rows.shape[0] != size:
        raise MeshMismatch(
            f"handshake returned {rows.shape[0]} rows for a "
            f"{size}-rank world")
    by_rank: dict[int, tuple[int, int]] = {}
    for r, dev_id, pidx in rows.tolist():
        if r in by_rank:
            raise MeshMismatch(f"rank {r} registered twice")
        by_rank[int(r)] = (int(dev_id), int(pidx))
    if sorted(by_rank) != list(range(size)):
        raise MeshMismatch(
            f"rank set {sorted(by_rank)[:8]}... is not 0..{size - 1}")

    dev_ids = [by_rank[r][0] for r in range(size)]
    if any(d < 0 for d in dev_ids):
        missing = [r for r in range(size) if by_rank[r][0] < 0]
        raise MeshMismatch(f"ranks {missing[:8]} registered no device")
    if len(set(dev_ids)) != size:
        raise MeshMismatch(
            f"device ids {dev_ids[:8]}... alias a chip across ranks")

    by_global_id = {d.id: d for d in jax.devices()}
    devices = []
    local_ranks = set(local_ranks)
    for r in range(size):
        dev_id, claimed_pidx = by_rank[r]
        dev = by_global_id.get(dev_id)
        if dev is None:
            raise MeshMismatch(
                f"rank {r}'s device {dev_id} is not in this backend's "
                f"global device set ({len(by_global_id)} devices)")
        if dev.process_index != claimed_pidx:
            raise MeshMismatch(
                f"rank {r} claims device {dev_id} on process "
                f"{claimed_pidx}, backend says {dev.process_index}")
        # The world's host split and the mesh's process split must be
        # the SAME partition: a rank this world object serves must own
        # an addressable chip (or the rendezvous could never build its
        # shard), and a remote rank's chip must NOT be addressable here
        # (two simulated hosts sharing one process would each see only
        # part of the shard set a single-controller array needs)
        if (dev.process_index == process_index) != (r in local_ranks):
            raise MeshMismatch(
                f"rank {r}: host split (local={r in local_ranks}) "
                f"disagrees with device process split "
                f"(process {dev.process_index} vs {process_index})")
        devices.append(dev)
    return devices
