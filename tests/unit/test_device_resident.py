"""ISSUE 15: device-resident arrays end to end.

Single-process worlds over the conftest 8-virtual-CPU-device mesh:
residency detection, the eligibility table for jax.Array payloads, the
zero-host-copy collective path (asserted via the new
``faabric_device_copy_*`` accounting), the exactly-once counted staging
fallback, bitwise identity of device-resident vs host-path results,
the ring-permute p2p primitive and its schedule-runner execution
target, the HBM state-handle registry with migration invalidation, and
the executable-cache stats surface. The cross-process acceptance form
lives in tests/dist/test_device_plane.py.
"""

import numpy as np
import pytest

from faabric_tpu.batch_scheduler.decision import SchedulingDecision
from faabric_tpu.device_plane import (
    device_copy_totals,
    is_device_payload,
    reset_device_copy_totals,
)
from faabric_tpu.mpi import MpiOp, MpiWorld
from faabric_tpu.mpi.types import UserOp
from faabric_tpu.transport.point_to_point import PointToPointBroker

N = 4


def _make_world(app_id):
    broker = PointToPointBroker("dres")
    d = SchedulingDecision(app_id=app_id, group_id=app_id)
    for r in range(N):
        d.add_message("dres", app_id * 10 + r, r, r, device_id=r)
    broker.set_up_local_mappings_from_decision(d)
    world = MpiWorld(broker, app_id, N, app_id)
    world.refresh_rank_hosts()
    return broker, world


@pytest.fixture
def device_world():
    broker, world = _make_world(820)
    yield world
    broker.clear()


def run_ranks(world, fn, n=N, timeout=60.0):
    from tests.conftest import run_threads

    results = {}

    def runner(rank):
        def run():
            results[rank] = fn(world, rank)
        return run

    run_threads([runner(r) for r in range(n)], timeout=timeout)
    return results


def activate(world, n=N):
    return run_ranks(world, lambda w, r: w.activate_device_plane(r), n=n)


def _dev_arrays(datas):
    import jax

    return {r: jax.device_put(datas[r], jax.local_devices()[r])
            for r in datas}


def _copies():
    return device_copy_totals()


# ---------------------------------------------------------------------------
# Residency detection + eligibility on jax payloads
# ---------------------------------------------------------------------------

def test_residency_detection_table(device_world):
    import jax
    import jax.numpy as jnp

    activate(device_world)
    plane = device_world.device_plane()
    devs = jax.local_devices()

    host = np.ones(16, np.float32)
    assert not is_device_payload(host)
    assert not plane.resident(0, host)
    assert not plane.resident(0, host.tolist())

    committed = jax.device_put(host, devs[0])
    assert is_device_payload(committed)
    assert plane.resident(0, committed)
    # ...but only on ITS OWN rank's registered chip
    assert not plane.resident(1, committed)
    # reshape/slice keep residency (what the dispatch path relies on)
    assert plane.resident(0, committed.reshape(-1))

    # uncommitted (default-placement) arrays are not resident — the
    # plane cannot prove which chip holds them
    uncommitted = jnp.ones(16, jnp.float32)
    assert is_device_payload(uncommitted)
    assert not plane.resident(0, uncommitted)

    # multi-device (sharded) arrays are not single-chip deposits
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharded = jax.device_put(
        np.ones((N, 4), np.float32),
        NamedSharding(plane.mesh, P("ranks", None)))
    assert not plane.resident(0, sharded)


def test_eligibility_accepts_jax_arrays_without_materializing(
        device_world):
    import jax

    activate(device_world)
    plane = device_world.device_plane()
    arr = jax.device_put(np.ones(64, np.int32), jax.local_devices()[0])
    reset_device_copy_totals()
    assert plane.eligible("allreduce", arr, MpiOp.SUM)
    assert plane.eligible("allgather", arr)
    assert plane.eligible("ring_permute", arr)
    assert not plane.eligible("allreduce", arr,
                              UserOp(lambda a, b: a + b, commute=True))
    assert not plane.eligible("allreduce", arr, MpiOp.LAND)
    # answering eligibility questions moved zero bytes
    assert _copies()["count"] == 0


# ---------------------------------------------------------------------------
# The zero-host-copy collective path
# ---------------------------------------------------------------------------

def test_device_resident_allreduce_zero_copies_and_bitwise(device_world):
    from faabric_tpu.telemetry import get_comm_matrix

    activate(device_world)
    rng = np.random.default_rng(3)
    datas = {r: rng.integers(-9999, 9999, 1000).astype(np.int32)
             for r in range(N)}
    # Host-path reference first (host numpy through the same plane)
    host_out = run_ranks(device_world,
                         lambda w, r: w.allreduce(r, datas[r].copy(),
                                                  MpiOp.SUM))

    dev = _dev_arrays(datas)

    def plane_bytes():
        cells = (get_comm_matrix().snapshot() or {}).get("cells", [])
        out: dict = {}
        for c in cells:
            out[c["plane"]] = out.get(c["plane"], 0) + c["bytes"]
        return out

    reset_device_copy_totals()
    b0 = plane_bytes()
    dev_out = run_ranks(device_world,
                        lambda w, r: w.allreduce(r, dev[r], MpiOp.SUM))
    b1 = plane_bytes()

    # THE tentpole invariant: zero host<->device copies AND zero host
    # payload bytes for a device-resident allreduce
    tot = _copies()
    assert tot["count"] == 0 and tot["bytes"] == 0, tot
    assert b1.get("device", 0) - b0.get("device", 0) \
        == N * datas[0].nbytes
    for host_plane in ("shm", "bulk-tcp"):
        assert b1.get(host_plane, 0) == b0.get(host_plane, 0)

    import jax

    for r in range(N):
        out = dev_out[r]
        # result is STILL device-resident, on the caller's own chip
        assert is_device_payload(out)
        assert list(out.devices()) == [jax.local_devices()[r]]
        host = np.asarray(out)
        assert host.dtype == np.int32
        # bitwise identical to the host path (exact dtype)
        np.testing.assert_array_equal(host, host_out[r])
    # no donation on the resident path: the inputs are still valid
    for r in range(N):
        np.testing.assert_array_equal(np.asarray(dev[r]), datas[r])


def test_device_resident_allgather_and_reduce_scatter(device_world):
    activate(device_world)
    rng = np.random.default_rng(5)
    ag_datas = {r: rng.integers(-99, 99, 64).astype(np.int32)
                for r in range(N)}
    rs_datas = {r: rng.integers(-99, 99, N * 16).astype(np.int32)
                for r in range(N)}
    ag_dev = _dev_arrays(ag_datas)
    rs_dev = _dev_arrays(rs_datas)

    reset_device_copy_totals()
    ag = run_ranks(device_world,
                   lambda w, r: w.allgather(r, ag_dev[r]))
    rs = run_ranks(device_world,
                   lambda w, r: w.reduce_scatter(r, rs_dev[r],
                                                 MpiOp.SUM))
    assert _copies()["count"] == 0

    ag_expected = np.concatenate([ag_datas[r] for r in range(N)])
    rs_expected = sum(rs_datas.values())
    for r in range(N):
        assert is_device_payload(ag[r])
        np.testing.assert_array_equal(np.asarray(ag[r]), ag_expected)
        assert is_device_payload(rs[r])
        np.testing.assert_array_equal(np.asarray(rs[r]),
                                      rs_expected[r * 16:(r + 1) * 16])


def test_uncommitted_jax_payload_counts_its_staging_copy(device_world):
    """An eligible jax.Array the plane cannot prove resident
    (uncommitted default placement) rides the device rung via the host
    shape — and its materialization is COUNTED (d2h staging), per the
    every-copy-counted contract."""
    import jax.numpy as jnp

    activate(device_world)
    datas = {r: np.full(64, r + 1, np.int32) for r in range(N)}
    uncommitted = {r: jnp.asarray(datas[r]) for r in range(N)}
    reset_device_copy_totals()
    out = run_ranks(device_world,
                    lambda w, r: w.allreduce(r, uncommitted[r],
                                             MpiOp.SUM))
    tot = _copies()
    assert tot["by_reason"]["d2h.staging"]["count"] == N, tot
    assert tot["by_reason"]["h2d.input"]["count"] == N, tot
    expected = np.full(64, N * (N + 1) // 2)
    for r in range(N):
        np.testing.assert_array_equal(np.asarray(out[r]), expected)


def test_mixed_residency_round_stages_and_agrees(device_world):
    """One rank deposits a device array, the rest host numpy: the round
    runs the host shape (resident deposit staged, counted) and every
    rank gets the right answer — correctness over performance for the
    asymmetric edge."""
    activate(device_world)
    datas = {r: np.full(64, r + 1, np.int32) for r in range(N)}
    dev0 = _dev_arrays({0: datas[0]})[0]

    reset_device_copy_totals()
    out = run_ranks(device_world,
                    lambda w, r: w.allreduce(
                        r, dev0 if r == 0 else datas[r].copy(),
                        MpiOp.SUM))
    tot = _copies()
    # rank 0's deposit staged exactly once; all four placed h2d
    assert tot["by_reason"]["d2h.staging"]["count"] == 1, tot
    assert tot["by_reason"]["h2d.input"]["count"] == N, tot
    expected = np.full(64, N * (N + 1) // 2)
    for r in range(N):
        np.testing.assert_array_equal(np.asarray(out[r]), expected)


def test_fallback_stages_exactly_once_per_rank(device_world):
    """A device payload the rung cannot serve (UserOp) takes ONE
    counted device→host staging copy per rank, then the host ladder —
    with the exact host-path result."""
    activate(device_world)
    datas = {r: np.full(64, r, np.int32) for r in range(N)}
    dev = _dev_arrays(datas)
    op = UserOp(lambda a, b: np.maximum(a, b), commute=True)

    reset_device_copy_totals()
    out = run_ranks(device_world,
                    lambda w, r: w.allreduce(r, dev[r], op))
    tot = _copies()
    assert tot["by_reason"]["d2h.staging"]["count"] == N, tot
    assert tot["by_reason"]["d2h.staging"]["bytes"] \
        == N * datas[0].nbytes
    assert set(tot["by_reason"]) == {"d2h.staging"}  # nothing else moved
    for r in range(N):
        np.testing.assert_array_equal(out[r], np.full(64, N - 1))


def test_inactive_plane_stages_device_payloads_once():
    """No activation handshake ever ran: a jax.Array payload still
    works — one counted staging copy, then the plain host ladder."""
    broker, world = _make_world(821)
    try:
        datas = {r: np.full(32, r + 1, np.int32) for r in range(N)}
        dev = _dev_arrays(datas)
        reset_device_copy_totals()
        out = run_ranks(world,
                        lambda w, r: w.allreduce(r, dev[r], MpiOp.SUM))
        tot = _copies()
        assert tot["by_reason"]["d2h.staging"]["count"] == N, tot
        expected = np.full(32, N * (N + 1) // 2)
        for r in range(N):
            assert isinstance(out[r], np.ndarray)
            np.testing.assert_array_equal(out[r], expected)
    finally:
        broker.clear()


def test_executable_cache_keyed_on_residency_and_stats(device_world):
    activate(device_world)
    plane = device_world.device_plane()
    datas = {r: np.arange(100, dtype=np.float32) * (r + 1)
             for r in range(N)}
    dev = _dev_arrays(datas)

    run_ranks(device_world,
              lambda w, r: w.allreduce(r, datas[r].copy(), MpiOp.SUM))
    s1 = plane.summary()["executable_cache"]
    assert s1["entries"] == 1 and s1["compiles"] == 1
    assert s1["compile_ms_total"] > 0

    # Same (kind, op, shape, dtype) but RESIDENT: a distinct executable
    # (the resident program must not donate the callers' arrays)
    run_ranks(device_world,
              lambda w, r: w.allreduce(r, dev[r], MpiOp.SUM))
    s2 = plane.summary()["executable_cache"]
    assert s2["entries"] == 2 and s2["compiles"] == 2

    # Cache hits on both keys now
    run_ranks(device_world,
              lambda w, r: w.allreduce(r, datas[r].copy(), MpiOp.SUM))
    run_ranks(device_world,
              lambda w, r: w.allreduce(r, dev[r], MpiOp.SUM))
    s3 = plane.summary()["executable_cache"]
    assert s3["entries"] == 2 and s3["compiles"] == 2
    # one executor cache-check per round → two hits for the two rounds
    assert s3["hits"] == s2["hits"] + 2, s3


# ---------------------------------------------------------------------------
# Ring permute (the p2p stream primitive) + schedule-runner target
# ---------------------------------------------------------------------------

def test_ring_permute_numerics_and_residency(device_world):
    activate(device_world)
    plane = device_world.device_plane()
    datas = {r: np.arange(50, dtype=np.int32) + 100 * r
             for r in range(N)}
    dev = _dev_arrays(datas)

    for shift in (1, 2, N - 1):
        out = run_ranks(device_world,
                        lambda w, r, _s=shift: plane.ring_permute(
                            r, dev[r], _s))
        for r in range(N):
            assert is_device_payload(out[r])
            np.testing.assert_array_equal(np.asarray(out[r]),
                                          datas[(r - shift) % N])
    # host payloads work too (device_put in, readback out — counted)
    reset_device_copy_totals()
    out = run_ranks(device_world,
                    lambda w, r: plane.ring_permute(
                        r, datas[r].copy(), 1))
    tot = _copies()
    assert tot["by_reason"]["h2d.input"]["count"] == N
    assert tot["by_reason"]["d2h.readback"]["count"] == N
    for r in range(N):
        assert isinstance(out[r], np.ndarray)
        np.testing.assert_array_equal(out[r], datas[(r - 1) % N])
    # shift 0 is the identity, no rendezvous
    assert plane.ring_permute(0, dev[0], 0) is dev[0]


def test_ring_permute_runs_the_pallas_kernel_body(device_world, monkeypatch):
    """The Pallas remote-copy kernel ITSELF — barrier handshake, mesh
    device ids, one async remote copy — through DevicePlane._build's
    shard_map, in TPU interpret mode over the CPU mesh. (The seed's
    kernel could not even trace on the installed JAX: the default
    check_vma rejected pallas_call's out_shape, and collective_id was
    refused without the barrier semaphore.)"""
    import functools

    from jax.experimental.pallas import tpu as pltpu

    from faabric_tpu.device_plane import pallas_ring

    monkeypatch.setattr(pallas_ring, "mesh_on_tpu", lambda mesh: True)
    monkeypatch.setattr(
        pallas_ring, "_pallas_permute_call",
        functools.partial(pallas_ring._pallas_permute_call,
                          interpret=pltpu.InterpretParams()))
    activate(device_world)
    plane = device_world.device_plane()
    assert pallas_ring.ring_backend(plane.mesh) == "pallas"
    datas = {r: np.arange(256, dtype=np.float32) + 1000 * r
             for r in range(N)}
    dev = _dev_arrays(datas)
    for shift in (1, N // 2, N - 1):
        out = run_ranks(device_world,
                        lambda w, r, _s=shift: plane.ring_permute(
                            r, dev[r], _s))
        for r in range(N):
            np.testing.assert_array_equal(np.asarray(out[r]),
                                          datas[(r - shift) % N])
    assert plane.disabled_reason is None


def test_ring_target_parses_only_pure_shift_groups():
    from faabric_tpu.device_plane.pallas_ring import DeviceRingTarget
    from faabric_tpu.mpi.schedule import RECV, SEND, Step

    t = DeviceRingTarget()
    good = [Step(SEND, peer=1, keys=(("out", 0),), syms=((("blk", 0)),),
                 phase="ring"),
            Step(RECV, peer=3, keys=(("out", 3),), syms=((("blk", 3)),),
                 phase="ring")]
    pairs = t._parse_pairs(good, rank=0, n=4)
    assert len(pairs) == 1 and pairs[0][2] == 1
    # odd step count / wrong order / inconsistent neighbours decline
    assert t._parse_pairs(good[:1], rank=0, n=4) == []
    assert t._parse_pairs(list(reversed(good)), rank=0, n=4) == []
    bad = [good[0],
           Step(RECV, peer=2, keys=(("out", 2),), syms=(("blk", 2),),
                phase="ring")]
    assert t._parse_pairs(bad, rank=0, n=4) == []


def test_allgather_ring_schedule_runs_on_device_target(device_world):
    """The verified ``allgather.ring`` schedule's annotated ring phase
    executes through the device plane when it is active — and produces
    the exact allgather result; with the plane down the SAME schedule
    runs its host steps (the dispatch/fallback contract)."""
    from faabric_tpu.mpi.schedule_compile import compile_schedule
    from faabric_tpu.mpi.types import MpiMessageType

    sched = compile_schedule("allgather.ring", "allgather",
                             device_world.topology())
    assert sched.spec["targets"] == {"ring": "device-ring"}
    datas = {r: (np.arange(32, dtype=np.int32) + 1000 * r)
             for r in range(N)}
    expected = np.concatenate([datas[r] for r in range(N)])

    def run_sched(w, r):
        env = {("in", 0): datas[r].copy()}
        w._run_schedule(r, sched, env, None, lambda sym, e: 32,
                        MpiMessageType.ALLGATHER)
        out = np.empty(N * 32, dtype=np.int32)
        for q in range(N):
            out[q * 32:(q + 1) * 32] = np.asarray(env[("out", q)])
        return out

    # Host path first: plane not yet activated → target declines
    host_out = run_ranks(device_world, run_sched)
    for r in range(N):
        np.testing.assert_array_equal(host_out[r], expected)

    # Activated: the ring phase rides the device plane — observable on
    # the ring_permute executable cache and the plane=device comm rows
    activate(device_world)
    plane = device_world.device_plane()
    dev_out = run_ranks(device_world, run_sched)
    for r in range(N):
        np.testing.assert_array_equal(dev_out[r], expected)
    cached = plane.summary()["cached_executables"]
    assert any("ring_permute" in k for k in cached), cached


def test_ring_target_knob_disables(device_world, monkeypatch):
    """FAABRIC_PALLAS_RING=0 keeps annotated schedules on their host
    steps even with an active plane."""
    from faabric_tpu.mpi.schedule_compile import compile_schedule
    from faabric_tpu.mpi.types import MpiMessageType

    monkeypatch.setenv("FAABRIC_PALLAS_RING", "0")
    activate(device_world)
    plane = device_world.device_plane()
    sched = compile_schedule("allgather.ring", "allgather",
                             device_world.topology())
    datas = {r: np.full(16, r + 1, np.int32) for r in range(N)}

    def run_sched(w, r):
        env = {("in", 0): datas[r].copy()}
        w._run_schedule(r, sched, env, None, lambda sym, e: 16,
                        MpiMessageType.ALLGATHER)
        return np.concatenate([np.asarray(env[("out", q)])
                               for q in range(N)])

    out = run_ranks(device_world, run_sched)
    expected = np.concatenate([datas[r] for r in range(N)])
    for r in range(N):
        np.testing.assert_array_equal(out[r], expected)
    assert not any("ring_permute" in k
                   for k in plane.summary()["cached_executables"])


def test_choose_family_picks_ring_for_one_rank_per_host():
    from faabric_tpu.mpi.schedule_compile import choose_family
    from faabric_tpu.mpi.topology import Topology

    gang = Topology({r: f"h{r}" for r in range(4)})      # 1 rank/host
    packed = Topology({r: f"h{r // 2}" for r in range(4)})
    assert choose_family("allgather", gang, 1 << 20, "force") \
        == "allgather.ring"
    assert choose_family("allgather", packed, 1 << 20, "force") \
        == "allgather.hier"


# ---------------------------------------------------------------------------
# HBM state handles
# ---------------------------------------------------------------------------

def test_device_handle_push_pull_by_reference():
    import jax

    from faabric_tpu.state import (
        DeviceHandleError,
        DeviceStateHandle,
        get_device_handle_registry,
        reset_device_handles,
    )

    reset_device_handles()
    reg = get_device_handle_registry()
    arr = jax.device_put(np.arange(256, dtype=np.float32),
                         jax.local_devices()[1])
    reset_device_copy_totals()
    h = reg.push(7, 1, "weights", arr)
    # push stages NOTHING: the registry holds the HBM reference
    assert _copies()["count"] == 0
    assert (h.world_id, h.rank, h.name) == (7, 1, "weights")
    assert h.shape == (256,) and h.dtype == "float32"
    assert h.nbytes == 1024

    # pull is by reference — the SAME array object, zero transfers
    assert reg.pull(h) is arr
    assert _copies()["count"] == 0

    # chains pass dicts, never payloads
    wire = h.to_dict()
    assert wire["shape"] == [256]
    h2 = DeviceStateHandle.from_dict(wire)
    assert reg.pull(h2) is arr
    assert reg.pull(wire) is arr  # raw dicts resolve too

    # explicit host materialization is the one counted copy
    host = reg.pull_host(h)
    np.testing.assert_array_equal(host,
                                  np.arange(256, dtype=np.float32))
    tot = _copies()
    assert tot["by_reason"]["d2h.state"] == {"count": 1, "bytes": 1024}

    # host values / uncommitted arrays are rejected, loudly
    with pytest.raises(DeviceHandleError):
        reg.push(7, 0, "bad", np.ones(4, np.float32))
    import jax.numpy as jnp

    with pytest.raises(DeviceHandleError):
        reg.push(7, 0, "bad", jnp.ones(4))
    reset_device_handles()


def test_device_handle_migration_invalidation():
    import jax

    from faabric_tpu.state import (
        StaleDeviceHandle,
        get_device_handle_registry,
        reset_device_handles,
    )

    reset_device_handles()
    reg = get_device_handle_registry()
    arr = jax.device_put(np.ones(64, np.int32), jax.local_devices()[0])
    h9 = reg.push(9, 0, "acts", arr)
    h8 = reg.push(8, 0, "other", arr)

    assert reg.invalidate_world(9) == 1
    with pytest.raises(StaleDeviceHandle):
        reg.pull(h9)
    with pytest.raises(StaleDeviceHandle):
        reg.pull_host(h9)
    # other worlds' handles unaffected
    assert reg.pull(h8) is arr

    # re-push after the (simulated) re-handshake mints a fresh handle
    # under the new generation
    h9b = reg.push(9, 0, "acts", arr)
    assert h9b.gen == h9.gen + 1
    assert reg.pull(h9b) is arr
    reset_device_handles()


def test_prepare_migration_invalidates_handles_and_flight_records():
    import jax

    from faabric_tpu.state import (
        StaleDeviceHandle,
        get_device_handle_registry,
        reset_device_handles,
    )
    from faabric_tpu.telemetry.flight import get_flight

    broker, world = _make_world(823)
    try:
        reset_device_handles()
        reg = get_device_handle_registry()
        arr = jax.device_put(np.ones(128, np.float32),
                             jax.local_devices()[0])
        h = reg.push(world.id, 0, "resid-state", arr)
        world.prepare_migration(0)
        with pytest.raises(StaleDeviceHandle):
            reg.pull(h)
        records = [r for r in get_flight().events()
                   if r.get("kind") == "device_handle_invalidate"
                   and r.get("world") == world.id]
        assert records, "invalidation was not flight-recorded"
        assert records[-1]["dropped"] == 1
        assert records[-1]["bytes"] == 512
    finally:
        reset_device_handles()
        broker.clear()


def test_device_handle_snapshot_bridge():
    """snapshot_of: on-device dirty diffing over a handle's live array
    — only flags + dirty pages cross to the host, and they are
    counted."""
    import jax

    from faabric_tpu.state import (
        get_device_handle_registry,
        reset_device_handles,
    )

    reset_device_handles()
    reg = get_device_handle_registry()
    base = np.zeros(4096, dtype=np.float32)
    arr = jax.device_put(base, jax.local_devices()[0])
    h = reg.push(5, 0, "snap", arr)
    snap = reg.snapshot_of(h)

    changed = base.copy()
    changed[0] = 1.5
    arr2 = jax.device_put(changed, jax.local_devices()[0])
    diffs = snap.diff(arr2)
    assert len(diffs) == 1 and diffs[0].offset == 0
    # the diff restores bitwise over the baseline
    restored = np.asarray(snap.restore()).copy().view(np.uint8)
    restored[diffs[0].offset:diffs[0].offset + len(diffs[0].data)] = \
        np.frombuffer(diffs[0].data, np.uint8)
    np.testing.assert_array_equal(restored.view(np.float32), changed)
    reset_device_handles()


# ---------------------------------------------------------------------------
# Observability surfaces
# ---------------------------------------------------------------------------

def test_summary_and_process_plane_listing(device_world):
    from faabric_tpu.device_plane import device_planes_summary

    activate(device_world)
    plane = device_world.device_plane()
    s = plane.summary()
    assert "executable_cache" in s and "process_device_copies" in s
    assert set(s["executable_cache"]) \
        == {"entries", "hits", "compiles", "compile_ms_total"}
    listed = device_planes_summary()
    assert any(p["world_id"] == device_world.id for p in listed)


def test_pallas_ring_selftest_fails_without_a_tpu():
    """The selftest is the chip check of the kernel: with no TPU it still
    verifies the ppermute twin's numerics, says which backend ran, and
    exits non-zero — it can never pass on the XLA twin alone."""
    import subprocess
    import sys

    p = subprocess.run(
        [sys.executable, "-m", "faabric_tpu.device_plane.pallas_ring",
         "--selftest"],
        capture_output=True, text=True, timeout=120,
        env={"PATH": "/usr/bin:/bin:/usr/local/bin",
             "JAX_PLATFORMS": "cpu",
             "XLA_FLAGS": "--xla_force_host_platform_device_count=4"})
    assert p.returncode == 1, (p.stdout, p.stderr)
    assert "4 permutes verified via XLA ppermute on cpux4" in p.stdout
    assert "FAILED" in p.stdout and "platform=cpu" in p.stdout


def test_device_copy_metrics_exported():
    """The counters ride the global registry → /metrics exposition."""
    from faabric_tpu.device_plane.copies import count_copy
    from faabric_tpu.telemetry import get_metrics

    count_copy("h2d", 512, "input")
    text = get_metrics().render_prometheus()
    assert "faabric_device_copy_total" in text
    assert "faabric_device_copy_bytes_total" in text
    assert 'direction="h2d"' in text
