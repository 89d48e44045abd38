"""ISSUE 10: the device collective plane (faabric_tpu/device_plane/).

Single-process worlds over the conftest 8-virtual-CPU-device mesh:
activation handshake, routing + numerics of all three collectives,
executable-cache keying, the eligibility/fallback ladder (UserOp,
dtypes, shape, mesh mismatch, backend error, migration remap), and the
``plane=device`` comm-matrix accounting. The cross-process form of the
same plane is tests/dist/test_device_plane.py.
"""

import threading

import numpy as np
import pytest

from faabric_tpu.batch_scheduler.decision import SchedulingDecision
from faabric_tpu.mpi import MpiOp, MpiWorld
from faabric_tpu.mpi.types import UserOp
from faabric_tpu.transport.point_to_point import PointToPointBroker

N = 4


def _make_world(device_ids=None, app_id=710):
    broker = PointToPointBroker("dplane")
    d = SchedulingDecision(app_id=app_id, group_id=app_id)
    for r in range(N):
        dev = device_ids[r] if device_ids is not None else r
        d.add_message("dplane", app_id * 10 + r, r, r, device_id=dev)
    broker.set_up_local_mappings_from_decision(d)
    world = MpiWorld(broker, app_id, N, app_id)
    world.refresh_rank_hosts()
    return broker, world


@pytest.fixture
def device_world():
    broker, world = _make_world()
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


# ---------------------------------------------------------------------------
# Activation + routing + numerics
# ---------------------------------------------------------------------------

def test_activation_resolves_mesh(device_world):
    acts = activate(device_world)
    assert all(acts.values()), acts
    plane = device_world.device_plane()
    assert plane is not None
    s = plane.summary()
    assert s["size"] == N and s["local_ranks"] == list(range(N))
    assert s["disabled"] is None
    # idempotent: a second collective activation round keeps the plane
    acts = activate(device_world)
    assert all(acts.values())
    assert device_world.device_plane() is plane


def test_device_collectives_match_host_semantics(device_world):
    from faabric_tpu.telemetry import reset_tracing, set_tracing, trace_events

    activate(device_world)
    rng = np.random.default_rng(42)
    # 32-bit payloads: the canonical jax dtypes under x64-off, so the
    # device rung serves them (64-bit falls back — see
    # test_64bit_payloads_fall_back_exact)
    ar_datas = {r: rng.integers(-9999, 9999, 1000).astype(np.int32)
                for r in range(N)}
    ag_datas = {r: rng.integers(-9999, 9999, 64).astype(np.int32)
                for r in range(N)}
    rs_datas = {r: rng.integers(-9999, 9999, N * 16).astype(np.int32)
                for r in range(N)}

    set_tracing(True)
    reset_tracing()
    try:
        ar = run_ranks(device_world,
                       lambda w, r: w.allreduce(r, ar_datas[r].copy(),
                                                MpiOp.SUM))
        ag = run_ranks(device_world,
                       lambda w, r: w.allgather(r, ag_datas[r].copy()))
        rs = run_ranks(device_world,
                       lambda w, r: w.reduce_scatter(r, rs_datas[r].copy(),
                                                     MpiOp.SUM))
        events = [e for e in trace_events() if e.get("ph") == "X"]
    finally:
        reset_tracing()
        set_tracing(False)

    ar_expected = sum(ar_datas.values())
    ag_expected = np.concatenate([ag_datas[r] for r in range(N)])
    rs_expected = sum(rs_datas.values())
    for r in range(N):
        np.testing.assert_array_equal(ar[r], ar_expected)
        assert ar[r].dtype == np.int32  # dtype preserved, not canonicalized
        assert ar[r].flags.writeable  # MPI result semantics
        np.testing.assert_array_equal(ag[r], ag_expected)
        assert ag[r].flags.writeable
        np.testing.assert_array_equal(rs[r], rs_expected[r * 16:(r + 1) * 16])
        assert rs[r].flags.writeable

    # Every collective span is tagged algo=device, and the executors
    # surfaced the compile-vs-execute split (cache misses visible)
    coll = [e for e in events if e["cat"] == "mpi"
            and e["name"] in ("allreduce", "allgather", "reduce_scatter")]
    assert len(coll) == 3 * N
    assert {e["args"]["algo"] for e in coll} == {"device"}
    phases = {e["args"].get("phase") for e in events
              if e["cat"] == "mpi.phase"}
    assert {"compile", "execute"} <= phases


def test_64bit_payloads_fall_back_exact(device_world):
    """With jax_enable_x64 off, device_put would silently downcast
    64-bit buffers to 32-bit (reproduced: int32 zeros from 2**40
    int64 sums). Such payloads must keep the exact host ladder — right
    dtype, no overflow — with the plane never involved."""
    from faabric_tpu.telemetry import reset_tracing, set_tracing, trace_events

    activate(device_world)
    big = 2 ** 40
    datas = {r: np.full(64, big + r, np.int64) for r in range(N)}
    set_tracing(True)
    reset_tracing()
    try:
        out = run_ranks(device_world,
                        lambda w, r: w.allreduce(r, datas[r].copy(),
                                                 MpiOp.SUM))
        algos = {e["args"]["algo"] for e in trace_events()
                 if e.get("ph") == "X" and e["cat"] == "mpi"
                 and e["name"] == "allreduce"}
    finally:
        reset_tracing()
        set_tracing(False)
    assert "device" not in algos
    expected = sum(datas.values())
    assert int(expected[0]) > 2 ** 31  # would overflow a downcast
    for r in range(N):
        assert out[r].dtype == np.int64
        np.testing.assert_array_equal(out[r], expected)
    # float64 precision likewise survives via the host ladder
    fdatas = {r: np.full(16, 1.0 + 1e-12 * (r + 1), np.float64)
              for r in range(N)}
    fout = run_ranks(device_world,
                     lambda w, r: w.allreduce(r, fdatas[r].copy(),
                                              MpiOp.SUM))
    fexpected = sum(fdatas.values())
    for r in range(N):
        assert fout[r].dtype == np.float64
        np.testing.assert_array_equal(fout[r], fexpected)


def test_allreduce_ops_and_dtypes(device_world):
    activate(device_world)
    rng = np.random.default_rng(7)
    datas = {r: rng.uniform(1.0, 2.0, 256).astype(np.float32)
             for r in range(N)}
    for op, npfn in ((MpiOp.MAX, np.max), (MpiOp.MIN, np.min),
                     (MpiOp.PROD, np.prod)):
        out = run_ranks(device_world,
                        lambda w, r, _op=op: w.allreduce(
                            r, datas[r].copy(), _op))
        expected = npfn(np.stack([datas[r] for r in range(N)]), axis=0)
        for r in range(N):
            np.testing.assert_allclose(out[r], expected, rtol=1e-5)


def test_executable_cache_keyed_by_shape_dtype_op(device_world):
    activate(device_world)
    plane = device_world.device_plane()

    def ar(payload, op=MpiOp.SUM):
        run_ranks(device_world,
                  lambda w, r: w.allreduce(r, payload.copy(), op))

    ar(np.arange(100, dtype=np.float32))
    n0 = len(plane.summary()["cached_executables"])
    ar(np.arange(100, dtype=np.float32) * 2)  # same key → cache hit
    assert len(plane.summary()["cached_executables"]) == n0
    ar(np.arange(100, dtype=np.int32))        # new dtype → miss
    assert len(plane.summary()["cached_executables"]) == n0 + 1
    ar(np.arange(101, dtype=np.float32))      # new shape → miss
    assert len(plane.summary()["cached_executables"]) == n0 + 2
    ar(np.arange(100, dtype=np.float32), MpiOp.MAX)  # new op → miss
    assert len(plane.summary()["cached_executables"]) == n0 + 3


# ---------------------------------------------------------------------------
# Eligibility / fallback ladder
# ---------------------------------------------------------------------------

def test_eligibility_rules(device_world):
    activate(device_world)
    plane = device_world.device_plane()
    f32 = np.ones(64, dtype=np.float32)
    assert plane.eligible("allreduce", f32, MpiOp.SUM)
    assert plane.eligible("allreduce", f32, MpiOp.PROD)
    # UserOps never compile — arbitrary python folds
    assert not plane.eligible("allreduce", f32,
                              UserOp(lambda a, b: a + b, commute=True))
    # op coverage: logical/bitwise folds stay on the host ladder
    assert not plane.eligible("allreduce", f32, MpiOp.LAND)
    # dtypes: bool / complex / structured are host-only
    assert not plane.eligible("allreduce", np.ones(8, dtype=bool),
                              MpiOp.SUM)
    assert not plane.eligible("allreduce", np.ones(8, np.complex64),
                              MpiOp.SUM)
    assert not plane.eligible("allreduce", np.empty(0, np.float32),
                              MpiOp.SUM)
    # 64-bit payloads: jax_enable_x64 is off, device_put would silently
    # downcast to 32-bit — they must keep the exact host ladder
    assert not plane.eligible("allreduce", np.ones(8, np.int64),
                              MpiOp.SUM)
    assert not plane.eligible("allreduce", np.ones(8, np.float64),
                              MpiOp.SUM)
    assert not plane.eligible("allgather", np.ones(8, np.uint64))
    # reduce_scatter: SUM only, size divisible by the world
    assert plane.eligible("reduce_scatter", np.ones(N * 4, np.float32),
                          MpiOp.SUM)
    assert not plane.eligible("reduce_scatter", np.ones(N * 4 + 1,
                                                        np.float32),
                              MpiOp.SUM)
    assert not plane.eligible("reduce_scatter", np.ones(N * 4, np.float32),
                              MpiOp.MAX)
    assert plane.eligible("allgather", np.ones(4, np.int32))


def test_ineligible_ops_run_host_ladder_correctly(device_world):
    from faabric_tpu.telemetry import reset_tracing, set_tracing, trace_events

    activate(device_world)
    op = UserOp(lambda a, b: np.maximum(a, b), commute=True)
    datas = {r: np.full(64, r, dtype=np.int64) for r in range(N)}
    set_tracing(True)
    reset_tracing()
    try:
        out = run_ranks(device_world,
                        lambda w, r: w.allreduce(r, datas[r].copy(), op))
        algos = {e["args"]["algo"] for e in trace_events()
                 if e.get("ph") == "X" and e["cat"] == "mpi"
                 and e["name"] == "allreduce"}
    finally:
        reset_tracing()
        set_tracing(False)
    assert "device" not in algos
    for r in range(N):
        np.testing.assert_array_equal(out[r], np.full(64, N - 1))


def test_mesh_mismatch_refuses_activation():
    """Two ranks sharing one chip cannot resolve a mesh: activation
    returns False on every rank and collectives keep the host ladder."""
    broker, world = _make_world(device_ids=[0, 1, 0, 1], app_id=711)
    try:
        acts = activate(world)
        assert not any(acts.values()), acts
        assert world.device_plane() is None
        out = run_ranks(world, lambda w, r: w.allreduce(
            r, np.full(32, r + 1, np.int64), MpiOp.SUM))
        for r in range(N):
            np.testing.assert_array_equal(
                out[r], np.full(32, N * (N + 1) // 2))
    finally:
        broker.clear()


def test_missing_device_assignment_refuses_activation():
    broker, world = _make_world(device_ids=[-1, -1, -1, -1], app_id=712)
    try:
        acts = activate(world)
        assert not any(acts.values())
        assert world.device_plane() is None
    finally:
        broker.clear()


def test_backend_error_disables_plane_and_falls_back(device_world):
    activate(device_world)
    plane = device_world.device_plane()

    def boom(*a, **k):
        raise RuntimeError("injected backend failure")

    plane._execute = boom
    datas = {r: np.full(64, r + 1, np.int32) for r in range(N)}
    out = run_ranks(device_world,
                    lambda w, r: w.allreduce(r, datas[r].copy(),
                                             MpiOp.SUM))
    for r in range(N):
        np.testing.assert_array_equal(out[r],
                                      np.full(64, N * (N + 1) // 2))
    assert plane.disabled_reason is not None
    assert device_world.device_plane() is None or \
        not device_world.device_plane().eligible(
            "allreduce", datas[0], MpiOp.SUM)
    # later collectives skip the rung without involving the plane
    out = run_ranks(device_world,
                    lambda w, r: w.allgather(r, np.full(8, r, np.int32)))
    expected = np.concatenate([np.full(8, r, np.int32) for r in range(N)])
    for r in range(N):
        np.testing.assert_array_equal(out[r], expected)


def test_waiter_outlasts_slow_executor(device_world, monkeypatch):
    """A fully-gathered round whose executor is slow (first-shape XLA
    compile, loaded box) must NOT time out the waiters — timing out
    would desync them from the executor, which WILL return a device
    result. The timeout only fires when peers are genuinely missing."""
    import time

    import faabric_tpu.device_plane.plane as plane_mod

    activate(device_world)
    plane = device_world.device_plane()
    # A window the four rank threads can gather in on a loaded box (at
    # 0.05 s two of four made it while five xdist workers ran beside)
    monkeypatch.setattr(plane_mod, "DEVICE_PLANE_TIMEOUT_S", 0.25)
    orig = plane._execute

    def slow_execute(*args, **kwargs):
        time.sleep(1.0)  # several timeout windows
        return orig(*args, **kwargs)

    plane._execute = slow_execute
    datas = {r: np.full(64, r + 1, np.int32) for r in range(N)}
    out = run_ranks(device_world,
                    lambda w, r: w.allreduce(r, datas[r].copy(),
                                             MpiOp.SUM))
    for r in range(N):
        np.testing.assert_array_equal(out[r],
                                      np.full(64, N * (N + 1) // 2))
    assert plane.disabled_reason is None


def test_reactivation_recovers_a_disabled_plane(device_world):
    """activate_device_plane is the recovery path after a backend
    error: a re-handshake must REPLACE the disabled plane (and must
    not return True on the strength of a dead sibling plane)."""
    activate(device_world)
    dead = device_world.device_plane()
    dead.disable("injected")
    acts = activate(device_world)
    assert all(acts.values())
    fresh = device_world.device_plane()
    assert fresh is not dead and fresh.disabled_reason is None
    out = run_ranks(device_world, lambda w, r: w.allreduce(
        r, np.full(32, r + 1, np.int32), MpiOp.SUM))
    for r in range(N):
        np.testing.assert_array_equal(out[r],
                                      np.full(32, N * (N + 1) // 2))
    assert fresh.summary()["cached_executables"]  # ran on the plane


def test_migration_remap_drops_the_rung(device_world):
    activate(device_world)
    assert device_world.device_plane() is not None
    device_world.prepare_migration(0)
    assert device_world.device_plane() is None
    # the stale mesh never serves a post-remap collective; after the
    # (simulated unchanged) remap a fresh handshake re-activates
    device_world.refresh_rank_hosts()
    acts = activate(device_world)
    assert all(acts.values())
    assert device_world.device_plane() is not None


def test_comm_matrix_device_rows_carry_the_traffic(device_world):
    from faabric_tpu.telemetry import get_comm_matrix

    activate(device_world)

    def plane_bytes():
        cells = (get_comm_matrix().snapshot() or {}).get("cells", [])
        out = {}
        for c in cells:
            out[c["plane"]] = out.get(c["plane"], 0) + c["bytes"]
        return out

    payload = np.ones(1024, dtype=np.float32)
    b0 = plane_bytes()
    run_ranks(device_world,
              lambda w, r: w.allreduce(r, payload.copy(), MpiOp.SUM))
    b1 = plane_bytes()
    assert b1.get("device", 0) - b0.get("device", 0) == N * payload.nbytes
    for host_plane in ("shm", "bulk-tcp"):
        assert b1.get(host_plane, 0) == b0.get(host_plane, 0)


# ---------------------------------------------------------------------------
# Registry-level mesh resolution
# ---------------------------------------------------------------------------

def test_resolve_mesh_verdicts():
    import jax

    from faabric_tpu.device_plane import MeshMismatch, resolve_mesh

    devs = jax.devices()[:N]
    pidx = jax.process_index()
    good = np.array([[r, devs[r].id, devs[r].process_index]
                     for r in range(N)], dtype=np.int64)
    out = resolve_mesh(good, N, local_ranks=range(N), process_index=pidx)
    assert [d.id for d in out] == [d.id for d in devs]

    with pytest.raises(MeshMismatch, match="registered twice"):
        bad = good.copy()
        bad[1, 0] = 0
        resolve_mesh(bad, N, range(N), pidx)
    with pytest.raises(MeshMismatch, match="alias a chip"):
        bad = good.copy()
        bad[1, 1] = bad[0, 1]
        resolve_mesh(bad, N, range(N), pidx)
    with pytest.raises(MeshMismatch, match="registered no device"):
        bad = good.copy()
        bad[2, 1] = -1
        resolve_mesh(bad, N, range(N), pidx)
    with pytest.raises(MeshMismatch, match="not in this backend"):
        bad = good.copy()
        bad[3, 1] = 10_000
        resolve_mesh(bad, N, range(N), pidx)
    with pytest.raises(MeshMismatch, match="backend says"):
        bad = good.copy()
        bad[0, 2] = 99  # claimed process != backend truth
        resolve_mesh(bad, N, range(N), pidx)
    with pytest.raises(MeshMismatch, match="disagrees with device"):
        # rank 0 NOT local to this world object, but its chip is
        resolve_mesh(good, N, local_ranks=range(1, N),
                     process_index=pidx)
    with pytest.raises(MeshMismatch, match="rows for a"):
        resolve_mesh(good[:2], N, range(N), pidx)


def test_two_simulated_hosts_in_one_process_refuse_activation():
    """The mpi_cluster shape: two broker 'hosts' sharing one OS process.
    The world's host split disagrees with the backend's process split,
    so the handshake must refuse on EVERY rank — a world object serving
    only half the ranks could never assemble the global arrays."""
    from tests.conftest import next_port_base, run_threads

    from faabric_tpu.transport.common import register_host_alias
    from faabric_tpu.transport.ptp_remote import PointToPointServer

    base = next_port_base()
    register_host_alias("dpA", "127.0.0.1", base)
    register_host_alias("dpB", "127.0.0.1", base + 1000)
    brokers = {h: PointToPointBroker(h) for h in ("dpA", "dpB")}
    servers = [PointToPointServer(b) for b in brokers.values()]
    for s in servers:
        s.start()
    d = SchedulingDecision(app_id=713, group_id=713)
    for r in range(4):
        d.add_message("dpA" if r < 2 else "dpB", 7130 + r, r, r,
                      device_id=r)
    for b in brokers.values():
        b.set_up_local_mappings_from_decision(d)
    worlds = {h: MpiWorld(b, 713, 4, 713) for h, b in brokers.items()}

    acts = {}

    def runner(rank):
        def run():
            w = worlds["dpA"] if rank < 2 else worlds["dpB"]
            acts[rank] = w.activate_device_plane(rank)
        return run

    try:
        run_threads([runner(r) for r in range(4)], timeout=60)
        assert not any(acts.values()), acts
        assert all(w.device_plane() is None for w in worlds.values())
    finally:
        for s in servers:
            s.stop()
        for b in brokers.values():
            b.clear()
