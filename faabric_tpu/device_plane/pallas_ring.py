"""Pallas ring-permute p2p: ``make_async_remote_copy`` as a stream primitive.

The device plane's collectives (plane.py) are single compiled XLA
programs — good for reductions, but the schedule compiler
(mpi/schedule_compile.py) also lowers collectives into *step programs*
whose wire legs are pure neighbour permutes (the ``allgather.ring``
family: n−1 rounds of "send my block right, receive the left
neighbour's"). On a device world those legs should never touch the host
planes: this module provides

- :func:`permute_body` — the per-shard body of the compiled
  ``ring_permute`` program. On TPU it is a Pallas kernel driving
  ``pltpu.make_async_remote_copy`` chip→chip over ICI (SNIPPETS.md
  [1–3]; the async-RDMA pattern from the Pallas distributed guide):
  source ref in ANY/HBM memory space, one send + one receive DMA
  semaphore, logical neighbour addressing — the bytes go straight from
  HBM to the neighbour's HBM without staging through VMEM-sized
  compute. Everywhere else (the CPU backend the tests run on) the same
  signature lowers to ``jax.lax.ppermute``, so dispatch, eligibility,
  caching and numerics are exercised without a chip.
- :class:`DeviceRingTarget` — a schedule-runner **execution target**
  (mpi/schedule.py ``register_step_target``): when a verified
  schedule's phase is annotated ``target="device-ring"`` and the
  world's device plane is active, the runner hands the phase's
  SEND/RECV steps here and each permute round executes as ONE
  ``DevicePlane.ring_permute`` mesh step instead of 2(n−1) host
  messages. Declines (returns None) on any structural or eligibility
  mismatch — the host steps then run untouched, which is the fallback
  the CPU tests pin.

Knob: ``FAABRIC_PALLAS_RING`` (default on) disables both the kernel
selection and the execution target; like every ladder knob it must
agree across the world's processes.

Selftest: ``python -m faabric_tpu.device_plane.pallas_ring --selftest``
is the chip check of the kernel: it exits 0 only when the Pallas kernel
itself ran over ≥ 2 TPU chips and every permute verified. Without a TPU
it still checks the ``ppermute`` twin's numerics, says so, and exits 1.
"""

from __future__ import annotations

import functools
import os

import numpy as np

from faabric_tpu.util.logging import get_logger

logger = get_logger(__name__)


def pallas_ring_enabled() -> bool:
    return os.environ.get("FAABRIC_PALLAS_RING", "1").lower() \
        not in ("0", "false", "off")


def mesh_on_tpu(mesh) -> bool:
    devs = mesh.devices.reshape(-1)
    return bool(devs.size) and devs[0].platform == "tpu"


# ---------------------------------------------------------------------------
# The kernel
# ---------------------------------------------------------------------------
def _pallas_permute_call(shard, axis: str, shift: int, n: int,
                         interpret=False):
    """One ring hop as a Pallas TPU kernel: the whole (1, m) shard DMAs
    from this chip's HBM into the ``shift``-right neighbour's output
    buffer via ``make_async_remote_copy`` (ANY memory space: no VMEM
    round-trip, the DMA engine streams HBM→ICI→HBM).

    The two chips this one exchanges with (destination and source) are
    handshaken on the barrier semaphore first: a remote write may only
    start once its target has entered the kernel and owns its output
    buffer. ``interpret`` takes ``pltpu.InterpretParams`` so the CPU
    tests can run the very same kernel body."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(input_ref, output_ref, send_sem, recv_sem):
        my_id = jax.lax.axis_index(axis)
        dst = jax.lax.rem(my_id + shift, n)
        src = jax.lax.rem(my_id + (n - shift), n)
        barrier = pltpu.get_barrier_semaphore()
        for peer in (dst, src):
            pltpu.semaphore_signal(
                barrier, inc=1, device_id=(peer,),
                device_id_type=pltpu.DeviceIdType.MESH)
        pltpu.semaphore_wait(barrier, 2)
        rdma = pltpu.make_async_remote_copy(
            src_ref=input_ref,
            dst_ref=output_ref,
            send_sem=send_sem,
            recv_sem=recv_sem,
            device_id=(dst,),
            device_id_type=pltpu.DeviceIdType.MESH,
        )
        rdma.start()
        rdma.wait()

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=0,
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[pltpu.SemaphoreType.DMA] * 2,
    )
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(shard.shape, shard.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(has_side_effects=True,
                                             collective_id=0),
        interpret=interpret,
        name="ring_permute",
    )(shard)


def permute_body(mesh, axis: str, shift: int):
    """The per-shard body DevicePlane compiles for ``ring_permute``:
    rank r's shard lands on rank (r + shift) % n. Pallas remote-copy on
    TPU meshes (knob-gated), ``lax.ppermute`` everywhere else — the
    SAME contract, so tests on the CPU backend pin the numerics the
    kernel must reproduce."""
    import jax

    n = int(mesh.devices.size)
    shift = int(shift) % n
    if pallas_ring_enabled() and mesh_on_tpu(mesh):
        return functools.partial(_pallas_permute_call, axis=axis,
                                 shift=shift, n=n)
    perm = [(i, (i + shift) % n) for i in range(n)]

    def f(shard):  # (1, m) → (1, m): the left neighbour's payload
        return jax.lax.ppermute(shard, axis, perm)

    return f


def ring_backend(mesh) -> str:
    """Which implementation ``permute_body`` selects for this mesh —
    observability for summaries and the selftest report."""
    if pallas_ring_enabled() and mesh_on_tpu(mesh):
        return "pallas"
    return "xla"


# ---------------------------------------------------------------------------
# Schedule-runner execution target
# ---------------------------------------------------------------------------
class DeviceRingTarget:
    """Executes an annotated permute phase on the device plane.

    ``try_run`` returns the number of leading steps it executed, or
    None to decline (the runner then executes the phase's host steps
    unchanged). The verdict must be world-symmetric or ranks desync:
    every input it consults — the spec annotation, the step structure,
    the payload dtype/size, the plane's activation — is identical on
    every rank for a verified permute schedule (the plane's activation
    verdict is world-agreed by the registration handshake, and a
    mid-phase plane disable surfaces symmetrically in every process,
    after which ALL ranks resume the remaining pairs on the host path).
    """

    name = "device-ring"

    def try_run(self, world, rank: int, sched, phase: str, steps,
                env: dict, resolver):
        if not pallas_ring_enabled():
            return None
        if not sched.spec.get("ring_uniform"):
            return None
        plane = world.device_plane()
        if plane is None or plane.n != world.size:
            return None
        pairs = self._parse_pairs(steps, rank, world.size)
        if not pairs:
            return None
        # Single-key legs only (a multi-key leg would need host
        # concatenation — decline and let the host steps run), and
        # eligibility from the FIRST pair's payload dtype: later pairs'
        # send keys are filled by earlier recvs DURING execution, and
        # the ring_uniform contract makes their dtype/size identical
        if any(len(s.keys) != 1 or len(r.keys) != 1
               for s, r, _ in pairs):
            return None
        first = env.get(pairs[0][0].keys[0])
        if first is None or not plane.eligible("ring_permute", first,
                                               None):
            return None

        from faabric_tpu.device_plane.registry import DevicePlaneFallback

        done = 0
        for send_st, recv_st, shift in pairs:
            payload = env[send_st.keys[0]]
            if not isinstance(payload, np.ndarray) \
                    and not hasattr(payload, "sharding"):
                payload = np.asarray(payload)
            try:
                out = plane.ring_permute(rank, payload.reshape(-1),
                                         shift)
            except DevicePlaneFallback:
                # Symmetric mid-phase disable: every rank's pair k
                # fails together; the runner finishes steps[done:] on
                # the host path
                logger.warning(
                    "device-ring target fell back to host steps at "
                    "pair %d/%d (world %s)", done // 2, len(pairs),
                    world.id)
                return done if done else None
            env[recv_st.keys[0]] = out.reshape(-1)
            done += 2
        return done

    @staticmethod
    def _parse_pairs(steps, rank: int, n: int):
        """Decompose a phase group into (send, recv, shift) permute
        pairs; [] when the structure is not a pure uniform-shift ring
        (any FOLD/COPY, odd step count, inconsistent neighbours)."""
        from faabric_tpu.mpi.schedule import RECV, SEND

        if len(steps) < 2 or len(steps) % 2:
            return []
        pairs = []
        for i in range(0, len(steps), 2):
            s, r = steps[i], steps[i + 1]
            if s.op != SEND or r.op != RECV:
                return []
            shift = (s.peer - rank) % n
            if shift == 0 or (rank - r.peer) % n != shift:
                return []
            pairs.append((s, r, shift))
        return pairs


def ensure_registered() -> None:
    """Idempotently register the target (module import does this; the
    schedule runner's lazy lookup calls it as a fallback)."""
    from faabric_tpu.mpi.schedule import get_registered_target, \
        register_step_target

    if get_registered_target(DeviceRingTarget.name) is None:
        register_step_target(DeviceRingTarget())


# Import-time registration: the device_plane package __init__ imports
# this module, so touching the plane at all arms the target; the
# schedule runner's get_step_target lazily imports it as the fallback.
try:
    ensure_registered()
except Exception:  # noqa: BLE001 — registration is an optimization
    logger.exception("device-ring target registration failed")


# ---------------------------------------------------------------------------
# Selftest (the chip check of the kernel: run it where the chips are)
# ---------------------------------------------------------------------------
def selftest(verbose: bool = True) -> dict:
    """Compile ``permute_body`` over the local mesh and check the
    permute numerics for several shifts/dtypes. On TPU that IS the
    Pallas ``make_async_remote_copy`` kernel; elsewhere it is the
    ``lax.ppermute`` twin, and ``report["tpu_kernel"]`` says which."""
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.local_devices()
    n = min(4, len(devs))
    report = {
        "platform": devs[0].platform,
        "n_devices": n,
        "backend": None,
        "checked": 0,
        "tpu_kernel": False,
    }
    if n < 2:
        if verbose:
            print("pallas_ring selftest: a ring needs 2 devices, found "
                  f"{n} (platform={report['platform']})")
        return report
    mesh = Mesh(np.array(devs[:n]), ("ranks",))
    report["backend"] = ring_backend(mesh)
    sharding = NamedSharding(mesh, P("ranks", None))
    for dtype in (np.int32, np.float32):
        for shift in (1, n - 1):
            shards = [jax.device_put(
                np.full((1, 128), r + 1, dtype), devs[r])
                for r in range(n)]
            x = jax.make_array_from_single_device_arrays(
                (n, 128), sharding, shards)
            body = permute_body(mesh, "ranks", shift)
            fn = jax.jit(jax.shard_map(
                body, mesh=mesh, in_specs=P("ranks", None),
                out_specs=P("ranks", None), check_vma=False))
            y = np.asarray(fn(x))
            for r in range(n):
                src = (r - shift) % n
                expect = np.full(128, src + 1, dtype)
                if not np.array_equal(y[r], expect):
                    raise AssertionError(
                        f"ring_permute shift={shift} dtype={dtype}: "
                        f"rank {r} got {y[r][:4]}, want {expect[:4]}")
            report["checked"] += 1
    report["tpu_kernel"] = report["backend"] == "pallas"
    if verbose:
        tag = ("Pallas make_async_remote_copy kernel" if
               report["tpu_kernel"] else "XLA ppermute")
        print(f"pallas_ring selftest: {report['checked']} permutes "
              f"verified via {tag} on {report['platform']}x{n}")
    return report


def _main(argv) -> int:
    """Exit 0 only when the Pallas kernel itself ran and verified: no
    TPU, one chip, or the XLA twin standing in are all failures here."""
    if "--selftest" not in argv:
        print(__doc__)
        return 2
    try:
        report = selftest(verbose=True)
    except Exception as e:  # noqa: BLE001 — CLI surface
        print(f"pallas_ring selftest: FAILED — {e!r}")
        return 1
    if not report["tpu_kernel"]:
        print("pallas_ring selftest: FAILED — the Pallas kernel did not "
              f"run (platform={report['platform']}, "
              f"backend={report['backend']})")
        return 1
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(_main(sys.argv[1:]))
