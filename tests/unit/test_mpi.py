"""MpiWorld host-path tests (reference: tests/test/mpi/test_mpi_world.cpp,
test_remote_mpi_worlds.cpp). Worlds run over two brokers with live PTP
servers; every collective is checked against numpy."""

import random
import threading
import time

import numpy as np
import pytest

from faabric_tpu.batch_scheduler.decision import SchedulingDecision
from faabric_tpu.mpi import MpiOp, MpiWorld, MpiWorldRegistry
from faabric_tpu.transport.common import register_host_alias
from faabric_tpu.transport.point_to_point import PointToPointBroker
from faabric_tpu.transport.ptp_remote import PointToPointServer

WORLD_ID = 4242
GROUP_ID = 4242


@pytest.fixture
def mpi_cluster():
    """Two logical hosts, 6 ranks split 3+3, live PTP servers."""
    from tests.conftest import next_port_base

    base = next_port_base()
    register_host_alias("mpiA", "127.0.0.1", base)
    register_host_alias("mpiB", "127.0.0.1", base + 1000)
    brokers = {h: PointToPointBroker(h) for h in ("mpiA", "mpiB")}
    servers = [PointToPointServer(b) for b in brokers.values()]
    for s in servers:
        s.start()

    decision = SchedulingDecision(app_id=GROUP_ID, group_id=GROUP_ID)
    for rank in range(6):
        host = "mpiA" if rank < 3 else "mpiB"
        decision.add_message(host, 2000 + rank, rank, rank,
                             mpi_port=8020 + rank, device_id=rank % 4)
    for b in brokers.values():
        b.set_up_local_mappings_from_decision(decision)

    worlds = {}
    for host, b in brokers.items():
        worlds[host] = MpiWorld(b, WORLD_ID, 6, GROUP_ID)

    def world_for_rank(rank):
        return worlds["mpiA"] if rank < 3 else worlds["mpiB"]

    yield world_for_rank

    for s in servers:
        s.stop()
    for b in brokers.values():
        b.clear()


def run_ranks(world_for_rank, fn, n=6, timeout=20.0):
    """Run fn(world, rank) on a thread per rank; returns results by rank."""
    from tests.conftest import run_threads

    results = {}

    def runner(rank):
        def run():
            results[rank] = fn(world_for_rank(rank), rank)
        return run

    run_threads([runner(r) for r in range(n)], timeout=timeout)
    return results


# ---------------------------------------------------------------------------
# Point-to-point
# ---------------------------------------------------------------------------

def test_send_recv_cross_host(mpi_cluster):
    data = np.arange(100, dtype=np.float64)

    def fn(world, rank):
        if rank == 0:
            world.send(0, 5, data)
            return None
        if rank == 5:
            arr, status = world.recv(0, 5)
            assert status.source == 0
            assert status.count == 100
            return arr
        return None

    results = run_ranks(mpi_cluster, fn)
    np.testing.assert_array_equal(results[5], data)


def test_sendrecv(mpi_cluster):
    def fn(world, rank):
        if rank not in (1, 2):
            return None
        other = 3 - rank
        out = np.full(4, rank, dtype=np.int32)
        arr, _ = world.sendrecv(out, rank, other, other, rank)
        return arr

    results = run_ranks(mpi_cluster, fn)
    np.testing.assert_array_equal(results[1], np.full(4, 2, dtype=np.int32))
    np.testing.assert_array_equal(results[2], np.full(4, 1, dtype=np.int32))


def test_isend_irecv_wait(mpi_cluster):
    payload = np.arange(10, dtype=np.int64)

    def fn(world, rank):
        if rank == 3:
            rid = world.isend(3, 4, payload)
            assert world.await_async(3, rid) is None
            assert world.pending_requests(3) == 0
            return None
        if rank == 4:
            rid = world.irecv(3, 4)
            arr, status = world.await_async(4, rid)
            assert status.count == 10
            return arr
        return None

    results = run_ranks(mpi_cluster, fn)
    np.testing.assert_array_equal(results[4], payload)


def test_message_ordering_per_channel(mpi_cluster):
    def fn(world, rank):
        if rank == 0:
            for i in range(50):
                world.send(0, 1, np.array([i], dtype=np.int32))
            return None
        if rank == 1:
            got = [int(world.recv(0, 1)[0][0]) for _ in range(50)]
            return got
        return None

    results = run_ranks(mpi_cluster, fn)
    assert results[1] == list(range(50))


# ---------------------------------------------------------------------------
# Collectives vs numpy
# ---------------------------------------------------------------------------

def per_rank_data(rank, n=8, dtype=np.float64):
    rng = np.random.RandomState(rank)
    return rng.rand(n).astype(dtype)


def test_broadcast_leader_tree(mpi_cluster):
    data = np.arange(16, dtype=np.float32)

    def fn(world, rank):
        return world.broadcast(2, rank, data if rank == 2 else np.empty(0))

    results = run_ranks(mpi_cluster, fn)
    for rank in range(6):
        np.testing.assert_array_equal(results[rank], data)


@pytest.mark.parametrize("op,npop", [
    (MpiOp.SUM, np.add),
    (MpiOp.MAX, np.maximum),
    (MpiOp.MIN, np.minimum),
    (MpiOp.PROD, np.multiply),
])
def test_allreduce_matches_numpy(mpi_cluster, op, npop):
    expected = per_rank_data(0)
    for r in range(1, 6):
        expected = npop(expected, per_rank_data(r))

    def fn(world, rank):
        return world.allreduce(rank, per_rank_data(rank), op)

    results = run_ranks(mpi_cluster, fn)
    for rank in range(6):
        np.testing.assert_allclose(results[rank], expected, rtol=1e-12)


@pytest.mark.parametrize("op,npop", [
    (MpiOp.SUM, np.add),
    (MpiOp.MAX, np.maximum),
])
@pytest.mark.parametrize("world_size", [2, 3, 4])
def test_allreduce_ring_single_host(op, npop, world_size, monkeypatch):
    """Large single-host payloads take the zero-copy ring path
    (reduce-scatter + allgather over ownership-transferred segments).
    Checks: values match numpy, the caller's buffer survives unmodified
    and writable, and odd sizes that don't divide by np still work."""
    monkeypatch.setattr(MpiWorld, "CHUNK_BYTES", 256)
    monkeypatch.setattr(MpiWorld, "CHUNK_BYTES_LOCAL", 256)
    broker = PointToPointBroker("ringhost")
    decision = SchedulingDecision(app_id=77, group_id=77)
    for rank in range(world_size):
        decision.add_message("ringhost", 3000 + rank, rank, rank)
    broker.set_up_local_mappings_from_decision(decision)
    world = MpiWorld(broker, 77, world_size, 77)

    n = 1003  # odd: uneven segment split
    datas = {r: per_rank_data(r, n) for r in range(world_size)}
    orig = {r: datas[r].copy() for r in range(world_size)}
    expected = datas[0]
    for r in range(1, world_size):
        expected = npop(expected, datas[r])

    def fn(world_, rank):
        return world_.allreduce(rank, datas[rank], op)

    results = run_ranks(lambda r: world, fn, n=world_size)
    for rank in range(world_size):
        np.testing.assert_allclose(results[rank], expected, rtol=1e-12)
        np.testing.assert_array_equal(datas[rank], orig[rank])
        assert datas[rank].flags.writeable
    broker.clear()


@pytest.mark.parametrize("world_size", [2, 3, 4])
def test_reduce_scatter_and_allgather_ring(world_size, monkeypatch):
    """Large same-machine reduce_scatter/allgather take the ring paths
    (fold phase + rotation; reference-circulating gather) — results must
    match numpy and the callers' buffers must survive writable."""
    monkeypatch.setattr(MpiWorld, "CHUNK_BYTES", 64)
    monkeypatch.setattr(MpiWorld, "CHUNK_BYTES_LOCAL", 64)
    broker = PointToPointBroker("ringhost2")
    decision = SchedulingDecision(app_id=78, group_id=78)
    for rank in range(world_size):
        decision.add_message("ringhost2", 3100 + rank, rank, rank)
    broker.set_up_local_mappings_from_decision(decision)
    world = MpiWorld(broker, 78, world_size, 78)

    k = 97  # per-rank segment length
    datas = {r: per_rank_data(r, world_size * k) for r in range(world_size)}
    orig = {r: datas[r].copy() for r in range(world_size)}
    total = sum(datas.values())

    def rs_fn(world_, rank):
        return world_.reduce_scatter(rank, datas[rank], MpiOp.SUM)

    results = run_ranks(lambda r: world, rs_fn, n=world_size)
    for rank in range(world_size):
        np.testing.assert_allclose(results[rank],
                                   total[rank * k:(rank + 1) * k],
                                   rtol=1e-12)
        np.testing.assert_array_equal(datas[rank], orig[rank])
        assert datas[rank].flags.writeable
        assert results[rank].flags.writeable  # caller owns its output

    ag_datas = {r: per_rank_data(100 + r, k) for r in range(world_size)}
    expected = np.concatenate([ag_datas[r] for r in range(world_size)])

    def ag_fn(world_, rank):
        return world_.allgather(rank, ag_datas[rank])

    results = run_ranks(lambda r: world, ag_fn, n=world_size)
    for rank in range(world_size):
        np.testing.assert_allclose(results[rank], expected, rtol=1e-12)
        assert results[rank].flags.writeable
        # MPI contract: the send buffer is immediately reusable
        ag_datas[rank][:] = -1
    broker.clear()


def test_allreduce_emits_phase_spans(mpi_cluster):
    """ISSUE 1: every rank's allreduce produces one mpi/allreduce span
    decomposed into named mpi.phase child spans (tree path: reduce +
    broadcast), and the per-op collective counters advance."""
    from faabric_tpu.telemetry import (
        get_metrics,
        reset_tracing,
        set_tracing,
        snapshot_delta,
        trace_events,
    )

    before = get_metrics().snapshot()
    set_tracing(True)
    reset_tracing()
    try:
        datas = {r: np.full(200_000, float(r), np.float64) for r in range(6)}

        def fn(world, rank):
            return world.allreduce(rank, datas[rank], MpiOp.SUM)

        results = run_ranks(mpi_cluster, fn)
        expected = sum(datas.values())
        for rank in range(6):
            np.testing.assert_allclose(results[rank], expected)

        events = [e for e in trace_events() if e.get("ph") == "X"]
        allreduces = [e for e in events if e["cat"] == "mpi"
                      and e["name"] == "allreduce"]
        assert len(allreduces) == 6  # one span per rank
        phases = [e for e in events if e["cat"] == "mpi.phase"]
        for ar in allreduces:
            assert ar["args"]["algo"] in ("tree", "ring")
            lo, hi = ar["ts"], ar["ts"] + ar["dur"]
            mine = [p for p in phases if p["tid"] == ar["tid"]
                    and p["ts"] >= lo - 1 and p["ts"] + p["dur"] <= hi + 1]
            names = {p["name"] for p in mine}
            if ar["args"]["algo"] == "tree":
                assert {"reduce", "broadcast"} <= names, names
            else:
                assert {"reduce_scatter", "allgather"} <= names, names
            assert all(p["args"]["parent"] == "mpi/allreduce" for p in mine)
            # The phases, not the dispatch glue, account for the span
            covered = sum(p["dur"] for p in mine)
            assert covered >= 0.5 * ar["dur"], (covered, ar["dur"])
    finally:
        reset_tracing()
        set_tracing(False)

    delta = snapshot_delta(before, get_metrics().snapshot())
    assert delta.get('faabric_mpi_collectives_total{op="allreduce"}') == 6
    assert delta.get(
        'faabric_mpi_collective_bytes_total{op="allreduce"}') == \
        6 * 200_000 * 8


# ---------------------------------------------------------------------------
# Hierarchical topology-composed collectives (ISSUE 9)
# ---------------------------------------------------------------------------

def _force_hier(world_for_rank, enabled=True, chunk=64 * 1024):
    """Make small test payloads hierarchy-eligible: shrink the pipeline
    chunk threshold on BOTH host worlds (identically — algorithm choice
    must agree across every process of a world) and flip the knob.
    "force" (not True) because the fixture's two simulated hosts live
    in one process — plain "on" composes only across real machines."""
    for world in {id(world_for_rank(r)): world_for_rank(r)
                  for r in range(6)}.values():
        world.hier_enabled = "force" if enabled else False
        world.CHUNK_BYTES = chunk


def test_world_topology_object(mpi_cluster):
    t = mpi_cluster(0).topology()
    assert t.size == 6 and t.hosts == ("mpiA", "mpiB")
    assert t.host_ranks == {"mpiA": (0, 1, 2), "mpiB": (3, 4, 5)}
    assert t.leaders == (0, 3)
    assert t.hierarchical and t.hosts_contiguous()
    # cached: same object until the rank map is refreshed
    assert mpi_cluster(0).topology() is t


def test_hier_allreduce_bitwise_matches_flat(mpi_cluster):
    """The composed path (shm reduce-scatter → leader ring →
    redistribute) must be bitwise-identical to the flat ring on exact
    dtypes, and tag its spans algo=hier with the three phase levels."""
    from faabric_tpu.telemetry import reset_tracing, set_tracing, trace_events

    rng = np.random.default_rng(11)
    datas = {r: rng.integers(-9999, 9999, 200_000).astype(np.int64)
             for r in range(6)}
    expected = sum(datas.values())

    def fn(world, rank):
        return world.allreduce(rank, datas[rank].copy(), MpiOp.SUM)

    _force_hier(mpi_cluster, enabled=False)
    flat = run_ranks(mpi_cluster, fn)

    _force_hier(mpi_cluster, enabled=True)
    set_tracing(True)
    reset_tracing()
    try:
        hier = run_ranks(mpi_cluster, fn)
        events = [e for e in trace_events() if e.get("ph") == "X"]
    finally:
        reset_tracing()
        set_tracing(False)

    for r in range(6):
        np.testing.assert_array_equal(hier[r], flat[r])
        np.testing.assert_array_equal(hier[r], expected)
        assert hier[r].flags.writeable  # private, caller-mutable

    allreduces = [e for e in events if e["cat"] == "mpi"
                  and e["name"] == "allreduce"]
    assert len(allreduces) == 6
    assert all(e["args"]["algo"] == "hier" for e in allreduces)
    phases = {e["args"].get("phase") for e in events
              if e["cat"] == "mpi.phase"}
    assert {"intra", "leader", "redistribute"} <= phases


def test_hier_reduce_scatter_and_allgather_match_flat(mpi_cluster):
    rng = np.random.default_rng(12)
    rs_datas = {r: rng.integers(-9999, 9999, 120_000).astype(np.int64)
                for r in range(6)}
    ag_datas = {r: rng.integers(-9999, 9999, 30_000).astype(np.int64)
                for r in range(6)}

    def rs_fn(world, rank):
        return world.reduce_scatter(rank, rs_datas[rank].copy(), MpiOp.SUM)

    def ag_fn(world, rank):
        return world.allgather(rank, ag_datas[rank].copy())

    _force_hier(mpi_cluster, enabled=False)
    rs_flat = run_ranks(mpi_cluster, rs_fn)
    ag_flat = run_ranks(mpi_cluster, ag_fn)

    _force_hier(mpi_cluster, enabled=True)
    rs_hier = run_ranks(mpi_cluster, rs_fn)
    ag_hier = run_ranks(mpi_cluster, ag_fn)

    total = sum(rs_datas.values())
    gathered = np.concatenate([ag_datas[r] for r in range(6)])
    for r in range(6):
        np.testing.assert_array_equal(rs_hier[r], rs_flat[r])
        np.testing.assert_array_equal(rs_hier[r],
                                      total[r * 20_000:(r + 1) * 20_000])
        np.testing.assert_array_equal(ag_hier[r], ag_flat[r])
        np.testing.assert_array_equal(ag_hier[r], gathered)
        assert rs_hier[r].flags.writeable
        assert ag_hier[r].flags.writeable


def test_hier_fallbacks_stay_flat(mpi_cluster):
    """Degenerate/ineligible shapes must keep the flat paths: knob off,
    sub-threshold payloads, and non-commuting user ops."""
    from faabric_tpu.mpi import UserOp
    from faabric_tpu.telemetry import reset_tracing, set_tracing, trace_events

    def algos_for(fn):
        set_tracing(True)
        reset_tracing()
        try:
            run_ranks(mpi_cluster, fn)
            return {e["args"]["algo"] for e in trace_events()
                    if e.get("ph") == "X" and e["cat"] == "mpi"
                    and e["name"] == "allreduce"}
        finally:
            reset_tracing()
            set_tracing(False)

    data = np.full(200_000, 1, dtype=np.int64)

    _force_hier(mpi_cluster, enabled=False)
    assert "hier" not in algos_for(
        lambda w, r: w.allreduce(r, data.copy(), MpiOp.SUM))

    _force_hier(mpi_cluster, enabled=True)
    small = np.full(64, 1, dtype=np.int64)  # below 2 pipeline chunks
    assert "hier" not in algos_for(
        lambda w, r: w.allreduce(r, small.copy(), MpiOp.SUM))

    noncommute = UserOp(lambda a, b: a + b, commute=False)
    assert "hier" not in algos_for(
        lambda w, r: w.allreduce(r, data.copy(), noncommute))

    # dtype-PROMOTING commuting UserOp stays eligible and correct:
    # apply_op casts every fold back to the input dtype, so the chunk
    # protocol's input-itemsize bounds hold on every rank
    promoting = UserOp(lambda a, b: (a + b).astype(np.float64),
                       commute=True)
    assert algos_for(
        lambda w, r: w.allreduce(r, data.copy(), promoting)) == {"hier"}

    # plain "on" (not "force"): both simulated hosts resolve to this
    # machine, where the flat ring out-pipelines the composition — the
    # host_allreduce_procs shape must keep its fast path (_hier_wins)
    _force_hier(mpi_cluster, enabled=True)
    for w in {id(mpi_cluster(r)): mpi_cluster(r) for r in range(6)}.values():
        w.hier_enabled = True
    assert "hier" not in algos_for(
        lambda w, r: w.allreduce(r, data.copy(), MpiOp.SUM))

    # eligible control: same payload, commuting op, forced → hier
    _force_hier(mpi_cluster, enabled=True)
    assert algos_for(
        lambda w, r: w.allreduce(r, data.copy(), MpiOp.SUM)) == {"hier"}


@pytest.fixture
def scattered_cluster():
    """Interleaved (non-gang-contiguous) placement: rank r on host
    r % 2 — the PR 9 headroom shape where hier reduce_scatter used to
    fall back flat."""
    from tests.conftest import next_port_base

    from faabric_tpu.transport.ptp_remote import PointToPointServer

    base = next_port_base()
    register_host_alias("scatA", "127.0.0.1", base)
    register_host_alias("scatB", "127.0.0.1", base + 1000)
    brokers = {h: PointToPointBroker(h) for h in ("scatA", "scatB")}
    servers = [PointToPointServer(b) for b in brokers.values()]
    for s in servers:
        s.start()
    decision = SchedulingDecision(app_id=GROUP_ID + 7, group_id=GROUP_ID + 7)
    for rank in range(6):
        decision.add_message("scatA" if rank % 2 == 0 else "scatB",
                             2600 + rank, rank, rank)
    for b in brokers.values():
        b.set_up_local_mappings_from_decision(decision)
    worlds = {h: MpiWorld(b, WORLD_ID + 7, 6, GROUP_ID + 7)
              for h, b in brokers.items()}

    def world_for_rank(rank):
        return worlds["scatA"] if rank % 2 == 0 else worlds["scatB"]

    yield world_for_rank

    for s in servers:
        s.stop()
    for b in brokers.values():
        b.clear()


def test_hier_reduce_scatter_scattered_placement(scattered_cluster):
    """ISSUE 10 satellite: scattered placements now take the composed
    path too — the leader ring folds over PERMUTED per-host spans, so
    each leader lands holding its own host's (non-contiguous) output.
    Bitwise vs the flat ring and numpy, and the span must say hier."""
    from faabric_tpu.telemetry import reset_tracing, set_tracing, trace_events

    topo = scattered_cluster(0).topology()
    assert topo.hierarchical and not topo.hosts_contiguous()

    rng = np.random.default_rng(21)
    datas = {r: rng.integers(-9999, 9999, 120_000).astype(np.int64)
             for r in range(6)}
    total = sum(datas.values())

    def fn(world, rank):
        return world.reduce_scatter(rank, datas[rank].copy(), MpiOp.SUM)

    _force_hier(scattered_cluster, enabled=False)
    flat = run_ranks(scattered_cluster, fn)
    _force_hier(scattered_cluster, enabled=True)
    set_tracing(True)
    reset_tracing()
    try:
        hier = run_ranks(scattered_cluster, fn)
        algos = {e["args"]["algo"] for e in trace_events()
                 if e.get("ph") == "X" and e["cat"] == "mpi"
                 and e["name"] == "reduce_scatter"}
    finally:
        reset_tracing()
        set_tracing(False)
    assert algos == {"hier"}
    for r in range(6):
        np.testing.assert_array_equal(hier[r], flat[r])
        np.testing.assert_array_equal(hier[r],
                                      total[r * 20_000:(r + 1) * 20_000])
        assert hier[r].flags.writeable


# ---------------------------------------------------------------------------
# FAABRIC_ALLREDUCE_QUANT (ISSUE 10 satellite, ROADMAP 4 groundwork)
# ---------------------------------------------------------------------------

def _set_quant(world_for_rank, mode):
    for world in {id(world_for_rank(r)): world_for_rank(r)
                  for r in range(6)}.values():
        world.allreduce_quant = mode


def test_quant_codec_roundtrip():
    from faabric_tpu.mpi.quant import Int8ChunkCodec, leader_ring_codec

    codec = Int8ChunkCodec()
    rng = np.random.default_rng(5)
    x = rng.uniform(-37.0, 37.0, 10_000).astype(np.float32)
    buf = codec.encode(x)
    assert buf.dtype == np.uint8 and buf.size == x.size + 4
    back = codec.decode(buf)
    assert back.dtype == np.float32 and back.flags.writeable
    scale = float(np.max(np.abs(x))) / 127.0
    assert float(np.max(np.abs(back - x))) <= scale / 2 + 1e-6
    # constants and zeros are exact
    np.testing.assert_array_equal(
        codec.decode(codec.encode(np.full(64, 3.5, np.float32))),
        np.full(64, 3.5, np.float32))
    np.testing.assert_array_equal(
        codec.decode(codec.encode(np.zeros(64, np.float32))),
        np.zeros(64, np.float32))
    # non-finite chunks ride the raw passthrough: NaN must survive
    # (quantizing would erase it to 0) and one Inf must not flood the
    # chunk with NaN
    bad = np.array([1.0, np.nan, 2.0, 3.0], np.float32)
    back_bad = codec.decode(codec.encode(bad))
    np.testing.assert_array_equal(back_bad, bad)  # NaN == NaN via equal_nan
    inf = np.array([1.0, np.inf, 2.0, 3.0], np.float32)
    np.testing.assert_array_equal(codec.decode(codec.encode(inf)), inf)
    assert codec.encode(bad).size == bad.nbytes + 4  # raw form, bigger
    # codec selection: fp32 SUM only, and only when the knob is on
    assert leader_ring_codec("int8", np.float32, MpiOp.SUM) is not None
    assert leader_ring_codec("", np.float32, MpiOp.SUM) is None
    assert leader_ring_codec("int8", np.int64, MpiOp.SUM) is None
    assert leader_ring_codec("int8", np.float32, MpiOp.MAX) is None
    from faabric_tpu.mpi import UserOp as _UserOp
    assert leader_ring_codec("int8", np.float32,
                             _UserOp(lambda a, b: a + b,
                                     commute=True)) is None


def test_hier_allreduce_quant_int8(mpi_cluster):
    """Opt-in int8 leader-ring quantization: all ranks agree bitwise on
    the (lossy) result, the error is bounded by the per-chunk scale
    model, and exact dtypes / disabled knob keep the exact path."""
    rng = np.random.default_rng(31)
    datas = {r: rng.uniform(-1000, 1000, 120_000).astype(np.float32)
             for r in range(6)}
    exact = sum(datas.values())

    def fn(world, rank):
        return world.allreduce(rank, datas[rank].copy(), MpiOp.SUM)

    _force_hier(mpi_cluster, enabled=True)
    _set_quant(mpi_cluster, "int8")
    try:
        quant = run_ranks(mpi_cluster, fn)
    finally:
        _set_quant(mpi_cluster, "")
    # every rank holds the IDENTICAL lossy result (the fold leg is
    # quantized once; the allgather leg circulates the same buffers)
    for r in range(1, 6):
        np.testing.assert_array_equal(quant[r], quant[0])
    err = float(np.max(np.abs(quant[0] - exact)))
    assert 0 < err < 100, err  # lossy, but scale-bounded
    # divergence propagates: a NaN in one rank's contribution reaches
    # every rank's result (the codec's raw passthrough, not 0-erasure)
    poisoned = {r: d.copy() for r, d in datas.items()}
    poisoned[2][12345] = np.nan
    _set_quant(mpi_cluster, "int8")
    try:
        nq = run_ranks(mpi_cluster, lambda w, r: w.allreduce(
            r, poisoned[r].copy(), MpiOp.SUM))
    finally:
        _set_quant(mpi_cluster, "")
    for r in range(6):
        assert np.isnan(nq[r][12345]), r
    # int64 payloads under the same knob stay exact (codec refuses)
    idatas = {r: rng.integers(-9999, 9999, 120_000).astype(np.int64)
              for r in range(6)}
    _set_quant(mpi_cluster, "int8")
    try:
        iout = run_ranks(mpi_cluster, lambda w, r: w.allreduce(
            r, idatas[r].copy(), MpiOp.SUM))
    finally:
        _set_quant(mpi_cluster, "")
    iexact = sum(idatas.values())
    for r in range(6):
        np.testing.assert_array_equal(iout[r], iexact)
    # knob off: fp32 hier matches the flat ring again up to fold-order
    # rounding (bitwise identity is pinned on exact dtypes above)
    hier = run_ranks(mpi_cluster, fn)
    _force_hier(mpi_cluster, enabled=False)
    flat = run_ranks(mpi_cluster, fn)
    for r in range(6):
        np.testing.assert_allclose(hier[r], flat[r], rtol=1e-4,
                                   atol=1e-2)


def test_quant_knob_never_touches_reduce_scatter(mpi_cluster):
    """The knob is named ALLREDUCE: hierarchical reduce_scatter must
    stay bitwise-exact with the knob on (same path as knob off)."""
    rng = np.random.default_rng(33)
    datas = {r: rng.uniform(-1000, 1000, 120_000).astype(np.float32)
             for r in range(6)}

    def fn(world, rank):
        return world.reduce_scatter(rank, datas[rank].copy(), MpiOp.SUM)

    _force_hier(mpi_cluster, enabled=True)
    exact = run_ranks(mpi_cluster, fn)
    _set_quant(mpi_cluster, "int8")
    try:
        quant = run_ranks(mpi_cluster, fn)
    finally:
        _set_quant(mpi_cluster, "")
    for r in range(6):
        np.testing.assert_array_equal(quant[r], exact[r])


def test_reduce_to_nonzero_root(mpi_cluster):
    expected = sum(per_rank_data(r) for r in range(6))

    def fn(world, rank):
        return world.reduce(rank, 4, per_rank_data(rank), MpiOp.SUM)

    results = run_ranks(mpi_cluster, fn)
    np.testing.assert_allclose(results[4], expected, rtol=1e-12)
    assert all(results[r] is None for r in range(6) if r != 4)


def test_gather_allgather(mpi_cluster):
    expected = np.concatenate([per_rank_data(r, 4) for r in range(6)])

    def gather_fn(world, rank):
        return world.gather(rank, 0, per_rank_data(rank, 4))

    results = run_ranks(mpi_cluster, gather_fn)
    np.testing.assert_allclose(results[0], expected, rtol=1e-12)

    def allgather_fn(world, rank):
        return world.allgather(rank, per_rank_data(rank, 4))

    results = run_ranks(mpi_cluster, allgather_fn)
    for rank in range(6):
        np.testing.assert_allclose(results[rank], expected, rtol=1e-12)


def test_scatter(mpi_cluster):
    root_data = np.arange(24, dtype=np.float64)

    def fn(world, rank):
        return world.scatter(1, rank, root_data if rank == 1 else np.empty(0), 4)

    results = run_ranks(mpi_cluster, fn)
    for rank in range(6):
        np.testing.assert_array_equal(results[rank],
                                      root_data[rank * 4:(rank + 1) * 4])


def test_scan(mpi_cluster):
    datas = [per_rank_data(r, 5) for r in range(6)]
    prefixes = np.cumsum(np.stack(datas), axis=0)

    def fn(world, rank):
        return world.scan(rank, datas[rank], MpiOp.SUM)

    results = run_ranks(mpi_cluster, fn)
    for rank in range(6):
        np.testing.assert_allclose(results[rank], prefixes[rank], rtol=1e-12)


def test_alltoall(mpi_cluster):
    # rank r sends row q of its matrix to rank q
    mats = {r: np.arange(12, dtype=np.int32) + 100 * r for r in range(6)}

    def fn(world, rank):
        return world.alltoall(rank, mats[rank])

    results = run_ranks(mpi_cluster, fn)
    for rank in range(6):
        expected = np.concatenate([
            mats[src].reshape(6, 2)[rank] for src in range(6)])
        np.testing.assert_array_equal(results[rank], expected)


def test_barrier(mpi_cluster):
    hits = []
    done = []

    def fn(world, rank):
        hits.append(rank)
        world.barrier(rank)
        done.append(rank)
        return None

    run_ranks(mpi_cluster, fn)
    assert sorted(hits) == list(range(6))
    assert sorted(done) == list(range(6))


# ---------------------------------------------------------------------------
# Topology helpers
# ---------------------------------------------------------------------------

def test_locality_helpers(mpi_cluster):
    world = mpi_cluster(0)
    assert world.ranks_on_host("mpiA") == [0, 1, 2]
    assert world.ranks_on_host("mpiB") == [3, 4, 5]
    assert world.local_leader("mpiA") == 0
    assert world.local_leader("mpiB") == 3
    assert world.hosts() == ["mpiA", "mpiB"]
    assert world.device_for_rank(5) == 1


def test_cartesian_topology(mpi_cluster):
    world = mpi_cluster(0)
    rows, cols = world.cart_dims()
    assert rows * cols == 6
    # round-trip coords
    for r in range(6):
        assert world.cart_rank(world.cart_coords(r)) == r
    src, dst = world.cart_shift(0, 0, 1)
    assert 0 <= src < 6 and 0 <= dst < 6


def test_exec_graph_accounting(mpi_cluster):
    def fn(world, rank):
        world.record_exec_graph = True
        if rank == 0:
            world.send(0, 1, np.zeros(1))
            world.send(0, 1, np.zeros(1))
        elif rank == 1:
            world.recv(0, 1)
            world.recv(0, 1)
        return None

    run_ranks(mpi_cluster, fn)
    details = mpi_cluster(0).exec_graph_details()
    assert details.get("mpi-msgcount-torank-1") == 2


def test_migration_blocked_with_pending_async(mpi_cluster):
    world = mpi_cluster(0)
    world.irecv(0, 0)
    with pytest.raises(RuntimeError):
        world.prepare_migration(0)


# ---------------------------------------------------------------------------
# Round-3 API breadth: probe, waitall/waitany, v-variants, MINLOC/MAXLOC,
# user-dims cartesian (reference mpi.h / MpiWorld.cpp:369-493)
# ---------------------------------------------------------------------------

def test_probe_and_iprobe(mpi_cluster):
    def fn(world, rank):
        if rank == 1:
            world.send(1, 0, np.arange(40, dtype=np.int32))
            return None
        if rank == 0:
            # iprobe polls until the message lands, without consuming it
            deadline = time.time() + 10
            st = None
            while st is None and time.time() < deadline:
                st = world.iprobe(1, 0)
            assert st is not None and st.count == 40
            # Blocking probe sees the SAME message, still unconsumed
            st2 = world.probe(1, 0, timeout=5.0)
            assert st2.count == 40
            arr, st3 = world.recv(1, 0)
            assert arr.size == 40 and st3.count == 40
            assert arr[-1] == 39
            # Nothing left
            assert world.iprobe(1, 0) is None
        return None

    run_ranks(mpi_cluster, fn, n=2)


def test_waitall_waitany(mpi_cluster):
    def fn(world, rank):
        if rank == 0:
            rids = [world.irecv(src, 0) for src in (1, 2, 3)]
            idx, result = world.waitany(0, rids, timeout=10.0)
            assert result is not None
            rest = [r for i, r in enumerate(rids) if i != idx]
            results = world.waitall(0, rest)
            got = sorted([int(result[0][0])]
                         + [int(r[0][0]) for r in results])
            assert got == [10, 20, 30]
        elif rank in (1, 2, 3):
            world.send(rank, 0, np.full(4, rank * 10, dtype=np.int32))
        return None

    run_ranks(mpi_cluster, fn, n=4)


def test_gatherv_scatterv(mpi_cluster):
    def fn(world, rank):
        # gatherv: rank r contributes r+1 values
        mine = np.full(rank + 1, rank, dtype=np.int32)
        out = world.gatherv(rank, 0, mine)
        if rank == 0:
            data, counts = out
            assert counts == [r + 1 for r in range(world.size)]
            expected = np.concatenate(
                [np.full(r + 1, r, np.int32) for r in range(world.size)])
            np.testing.assert_array_equal(data, expected)
        world.barrier(rank)
        # scatterv: reverse counts
        counts = [world.size - r for r in range(world.size)]
        if rank == 0:
            flat = np.concatenate(
                [np.full(c, i, np.int32) for i, c in enumerate(counts)])
            got = world.scatterv(0, 0, flat, counts)
        else:
            got = world.scatterv(0, rank, None, None)
        np.testing.assert_array_equal(
            got, np.full(world.size - rank, rank, np.int32))
        return None

    run_ranks(mpi_cluster, fn, n=6)


def test_alltoallv(mpi_cluster):
    def fn(world, rank):
        # rank r sends (j+1) copies of r*10+j to rank j
        counts = [j + 1 for j in range(world.size)]
        data = np.concatenate(
            [np.full(j + 1, rank * 10 + j, np.int32)
             for j in range(world.size)])
        got, recv_counts = world.alltoallv(rank, data, counts)
        assert recv_counts == [rank + 1] * world.size
        expected = np.concatenate(
            [np.full(rank + 1, src * 10 + rank, np.int32)
             for src in range(world.size)])
        np.testing.assert_array_equal(got, expected)
        return None

    run_ranks(mpi_cluster, fn, n=6)


def test_minloc_maxloc_allreduce(mpi_cluster):
    from faabric_tpu.mpi.types import DOUBLE_INT_DTYPE

    def fn(world, rank):
        pairs = np.zeros(3, dtype=DOUBLE_INT_DTYPE)
        # Values arranged so the min of slot i is at rank (i % size) and
        # ties (slot 2) resolve to the LOWEST rank
        pairs["val"] = [float(rank == 0), float((rank + 1) % world.size),
                        1.0]
        pairs["loc"] = rank
        got = world.allreduce(rank, pairs, MpiOp.MINLOC)
        assert got["loc"][2] == 0  # tie → lowest rank
        assert got["val"][0] == 0.0
        got_max = world.allreduce(rank, pairs, MpiOp.MAXLOC)
        assert got_max["val"][2] == 1.0 and got_max["loc"][2] == 0
        return None

    run_ranks(mpi_cluster, fn, n=6)


def test_cart_create_user_dims(mpi_cluster):
    def fn(world, rank):
        if rank == 0:
            dims = world.cart_create((3, 2, 1))
            assert dims == (3, 2, 1)
            assert world.cart_coords(5) == (2, 1, 0)
            assert world.cart_rank((2, 1, 0)) == 5
            # Periodic wrap in every dimension
            assert world.cart_rank((-1, 0, 0)) == world.cart_rank((2, 0, 0))
            src, dst = world.cart_shift(0, 0, 1)
            assert (src, dst) == (4, 2)
            with pytest.raises(ValueError, match="do not tile"):
                world.cart_create((4, 2))
            world.cart_create(None)  # back to the 2-D default
            assert world.cart_dims() == (2, 3)
        return None

    run_ranks(mpi_cluster, fn, n=1)


def test_isend_remote_async_with_ordering(mpi_cluster):
    """Remote isend runs on the send worker (caller returns immediately,
    buffer reusable) and a subsequent BLOCKING send from the same rank
    never overtakes it (program-order fence)."""
    def fn(world, rank):
        if rank == 0:
            buf = np.full(300_000, 7, dtype=np.int32)  # ~1.2 MB → bulk
            rid = world.isend(0, 3, buf)  # rank 3 lives on the other host
            buf[:] = -1  # caller may reuse the buffer right away
            world.send(0, 3, np.array([99], np.int32))  # must arrive 2nd
            world.await_async(0, rid)
        elif rank == 3:
            first, _ = world.recv(0, 3)
            assert first.size == 300_000 and first[0] == 7, first[:3]
            second, _ = world.recv(0, 3)
            assert second.tolist() == [99]
        return None

    run_ranks(mpi_cluster, fn, n=6)


def test_two_concurrent_worlds_are_isolated(mpi_cluster):
    """Two MPI worlds over the same brokers (reference
    test_multiple_mpi_worlds.cpp): traffic and collectives never cross
    group boundaries even when interleaved from the same threads."""
    # Second world on a second group over the same brokers
    base_group = GROUP_ID + 777
    d2 = SchedulingDecision(app_id=base_group, group_id=base_group)
    worlds_b = {}
    brokers = {h: mpi_cluster(0 if h == "mpiA" else 5).broker
               for h in ("mpiA", "mpiB")}
    for rank in range(6):
        host = "mpiA" if rank < 3 else "mpiB"
        d2.add_message(host, 3000 + rank, rank, rank,
                       mpi_port=8120 + rank, device_id=rank % 4)
    for h, b in brokers.items():
        b.set_up_local_mappings_from_decision(d2)
        worlds_b[h] = MpiWorld(b, base_group, 6, base_group)

    def fn(world_a, rank):
        world_b = worlds_b["mpiA" if rank < 3 else "mpiB"]
        # Interleave: allreduce in A, p2p in B, then allreduce in B
        out_a = world_a.allreduce(rank, np.full(8, rank, np.int64),
                                  MpiOp.SUM)
        if rank == 0:
            world_b.send(0, 5, np.array([1234], np.int64))
        if rank == 5:
            arr, _ = world_b.recv(0, 5)
            assert arr.tolist() == [1234]
        out_b = world_b.allreduce(rank, np.full(8, rank * 10, np.int64),
                                  MpiOp.SUM)
        return int(out_a[0]), int(out_b[0])

    results = run_ranks(mpi_cluster, fn, n=6)
    for rank in range(6):
        assert results[rank] == (15, 150)  # sums of 0..5 and 0..50


def test_reduce_scatter(mpi_cluster):
    def fn(world, rank):
        data = np.arange(12, dtype=np.int64) + rank  # 6 ranks × 2 elems
        return world.reduce_scatter(rank, data, MpiOp.SUM)

    results = run_ranks(mpi_cluster, fn)
    total = np.sum(np.stack([np.arange(12, dtype=np.int64) + r
                             for r in range(6)]), axis=0)
    for rank in range(6):
        np.testing.assert_array_equal(results[rank],
                                      total[rank * 2:(rank + 1) * 2])


# ---------------------------------------------------------------------------
# Sub-communicators (reference mpi.h MPI_Comm_split_type / Comm_dup /
# Comm_create_group)
# ---------------------------------------------------------------------------

def test_comm_split_even_odd(mpi_cluster):
    """Split the 6-rank world by parity: each subworld allreduces
    independently with renumbered ranks."""
    def fn(world, rank):
        sub, new_rank = world.split(rank, color=rank % 2)
        assert sub.size == 3
        assert new_rank == rank // 2  # parity groups keep rank order
        out = sub.allreduce(new_rank, np.full(4, rank, np.int64), MpiOp.SUM)
        # evens sum 0+2+4=6, odds 1+3+5=9
        return int(out[0])

    results = run_ranks(mpi_cluster, fn)
    for rank in range(6):
        assert results[rank] == (6 if rank % 2 == 0 else 9)


def test_comm_split_key_reorders_and_undefined_opts_out(mpi_cluster):
    def fn(world, rank):
        if rank == 5:
            sub, new_rank = world.split(rank, color=-1)  # MPI_UNDEFINED
            assert sub is None and new_rank == -1
            return None
        # Same color, DESCENDING key: new rank order reverses
        sub, new_rank = world.split(rank, color=7, key=-rank)
        assert sub.size == 5
        assert new_rank == 4 - rank
        # p2p in the subworld with the new numbering
        if new_rank == 0:
            sub.send(0, 4, np.array([42], np.int64))
        if new_rank == 4:
            arr, _ = sub.recv(0, 4)
            assert arr.tolist() == [42]
        sub.barrier(new_rank)
        return new_rank

    run_ranks(mpi_cluster, fn)


def test_comm_dup_is_isolated(mpi_cluster):
    """Messages on a dup'd communicator never cross into the parent."""
    def fn(world, rank):
        dup, dr = world.dup(rank)
        assert dup.size == world.size and dr == rank
        if rank == 0:
            dup.send(0, 1, np.array([111], np.int64))
            world.send(0, 1, np.array([222], np.int64))
        if rank == 1:
            parent_val, _ = world.recv(0, 1)
            dup_val, _ = dup.recv(0, 1)
            assert parent_val.tolist() == [222]
            assert dup_val.tolist() == [111]
        world.barrier(rank)
        return None

    run_ranks(mpi_cluster, fn)


def test_comm_create_group(mpi_cluster):
    """Collective only over the member list; cross-host members included."""
    members = [1, 3, 4]  # spans mpiA (1) and mpiB (3, 4)

    def fn(world, rank):
        sub, new_rank = world.create_group_comm(rank, members)
        if rank not in members:
            assert sub is None
            return None
        assert sub.size == 3 and new_rank == members.index(rank)
        out = sub.allreduce(new_rank, np.full(2, rank, np.int64), MpiOp.SUM)
        assert out[0] == sum(members)
        return None

    run_ranks(mpi_cluster, fn)


def test_comm_split_type_shared(mpi_cluster):
    """MPI_COMM_TYPE_SHARED: one subworld per host (3+3 split)."""
    def fn(world, rank):
        sub, new_rank = world.split_type_shared(rank)
        assert sub.size == 3
        assert new_rank == rank % 3  # ranks 0-2 on A, 3-5 on B
        out = sub.allreduce(new_rank, np.array([rank], np.int64),
                            MpiOp.SUM)
        return int(out[0])

    results = run_ranks(mpi_cluster, fn)
    for rank in range(6):
        assert results[rank] == (3 if rank < 3 else 12)  # 0+1+2 / 3+4+5


def test_subcomm_async_requests_resolve_correctly(mpi_cluster):
    """isend/irecv on a sub-communicator through the guest-API handles:
    MPI_Wait with NO comm argument still resolves against the subworld
    (regression: int handles resolved against the TLS parent world)."""
    from faabric_tpu.mpi.api import MpiRequest

    def fn(world, rank):
        sub, new_rank = world.split(rank, color=rank % 2, key=rank)
        # Handle-style async through the subworld, mimicking the api
        # layer's MpiRequest resolution
        nxt = (new_rank + 1) % sub.size
        prv = (new_rank - 1) % sub.size
        recv_rid = sub.irecv(prv, new_rank)
        send_rid = sub.isend(new_rank, nxt, np.array([rank], np.int64))
        req = MpiRequest(sub, new_rank, recv_rid)
        from faabric_tpu.mpi.api import mpi_wait

        got = mpi_wait(req)  # no comm passed: the handle carries it
        sub.await_async(new_rank, send_rid)
        return int(got[0][0])

    results = run_ranks(mpi_cluster, fn)
    # In each parity subworld the ring neighbour's PARENT rank arrives
    for rank in range(6):
        parity = [r for r in range(6) if r % 2 == rank % 2]
        prv_parent = parity[(parity.index(rank) - 1) % 3]
        assert results[rank] == prv_parent


def test_comm_create_collective_over_all(mpi_cluster):
    """mpi-style comm_create via split: all 6 ranks participate, only
    the group ([4, 0, 2], custom order) gets a communicator."""
    group = [4, 0, 2]

    def fn(world, rank):
        in_group = rank in group
        color = 0 if in_group else -1
        key = group.index(rank) if in_group else 0
        sub, new_rank = world.split(rank, color, key)
        if not in_group:
            assert sub is None
            return None
        assert sub.size == 3 and new_rank == group.index(rank)
        out = sub.allreduce(new_rank, np.array([rank], np.int64),
                            MpiOp.SUM)
        assert int(out[0]) == 6  # 4+0+2
        return new_rank

    run_ranks(mpi_cluster, fn)


def test_dims_create():
    from faabric_tpu.mpi.api import mpi_dims_create

    assert mpi_dims_create(12, 2) == [4, 3]
    assert mpi_dims_create(8, 3) == [2, 2, 2]
    assert mpi_dims_create(7, 2) == [7, 1]
    assert mpi_dims_create(16, 2) == [4, 4]
    import numpy as _np
    for n in range(1, 65):
        for d in (1, 2, 3):
            dims = mpi_dims_create(n, d)
            assert _np.prod(dims) == n and len(dims) == d
            assert dims == sorted(dims, reverse=True)


# ---------------------------------------------------------------------------
# Round-3 late surface: user ops, allgatherv, derived types, shared windows
# (the reference native shim throws notImplemented for user ops, v-variant
# allgather and all of MPI_Win_*/Put/Get — these are real here)
# ---------------------------------------------------------------------------

def test_user_op_allreduce_and_scan(mpi_cluster):
    from faabric_tpu.mpi.types import UserOp

    # Elementwise "absolute max keeping sign" — not a built-in op
    absmax = UserOp(
        lambda a, b: np.where(np.abs(b) > np.abs(a), b, a), name="absmax")
    vals = [np.array([r - 3, 3 - r, r], np.int64) for r in range(6)]

    def fn(world, rank):
        out = world.allreduce(rank, vals[rank], absmax)
        np.testing.assert_array_equal(out, np.array([-3, 3, 5], np.int64))
        scan = world.scan(rank, np.array([rank + 1], np.int64),
                          UserOp(np.add, name="sum"))
        # inclusive prefix-sum of 1..rank+1
        assert int(scan[0]) == (rank + 1) * (rank + 2) // 2

    run_ranks(mpi_cluster, fn)


def test_allgatherv_variable_counts(mpi_cluster):
    from faabric_tpu.mpi.api import MpiComm, mpi_allgatherv

    def fn(world, rank):
        # Exercise the real public wrapper via an explicit comm handle
        send = np.full(rank + 1, rank, np.int32)  # rank r sends r+1 elems
        data, counts = mpi_allgatherv(send, comm=MpiComm(world, rank))
        assert counts == [1, 2, 3, 4, 5, 6]
        expect = np.concatenate(
            [np.full(r + 1, r, np.int32) for r in range(6)])
        np.testing.assert_array_equal(np.asarray(data, np.int32), expect)

    run_ranks(mpi_cluster, fn)


def test_request_free_discards_arrived_message(mpi_cluster):
    from faabric_tpu.mpi.api import MpiComm, MpiRequest, mpi_request_free

    def fn(world, rank):
        if rank == 1:
            world.send(1, 0, np.array([111], np.int32))  # for the freed req
            world.send(1, 0, np.array([222], np.int32))  # for the real recv
        elif rank == 0:
            rid = world.irecv(1, 0)
            # Give the messages time to land, then free the handle: its
            # already-arrived message must be consumed and discarded
            deadline = time.monotonic() + 5.0
            while world.broker.try_probe_message(world.group_id, 1, 0) \
                    is None and time.monotonic() < deadline:
                time.sleep(0.005)
            mpi_request_free(MpiRequest(world, 0, rid))
            assert world.pending_requests(0) == 0  # no handle leak
            data, _ = world.recv(1, 0)
            assert int(data[0]) == 222  # not the freed request's 111

    run_ranks(mpi_cluster, fn)


def test_contiguous_type_and_version():
    from faabric_tpu.mpi.api import (
        MPI_THREAD_SERIALIZED,
        mpi_get_version,
        mpi_query_thread,
        mpi_type_commit,
        mpi_type_contiguous,
        mpi_type_free,
        mpi_type_size,
    )
    from faabric_tpu.mpi.types import MpiDataType

    t = mpi_type_contiguous(5, MpiDataType.DOUBLE)
    assert mpi_type_size(t) == 5 * 8
    nested = mpi_type_contiguous(3, t)
    assert mpi_type_size(nested) == 15 * 8
    mpi_type_commit(t)
    assert t.committed
    mpi_type_free(t)
    assert not t.committed
    assert mpi_get_version() == (3, 1)
    assert mpi_query_thread() == MPI_THREAD_SERIALIZED


def test_shared_window_put_get_fence(mpi_cluster):
    from faabric_tpu.mpi.window import (
        MPI_WIN_BASE,
        MPI_WIN_DISP_UNIT,
        MPI_WIN_SIZE,
        allocate_shared,
    )

    def fn(world, rank):
        sub, subrank = world.split_type_shared(rank)
        win = allocate_shared(sub, subrank, 16)
        try:
            # Every rank writes its subrank byte into EVERY co-located
            # rank's segment at disp=subrank (one-sided, no recv)
            for target in range(sub.size):
                win.put(np.array([subrank], np.uint8), target,
                        target_disp=subrank)
            win.fence()
            seg = win.segment()
            assert list(seg[:sub.size]) == list(range(sub.size))
            # shared_query sees a co-located rank's segment directly
            other = (subrank + 1) % sub.size
            peer_seg = win.segment(other)
            assert list(peer_seg[:sub.size]) == list(range(sub.size))
            # attributes
            assert win.get_attr(MPI_WIN_SIZE) == 16
            assert win.get_attr(MPI_WIN_DISP_UNIT) == 1
            assert win.get_attr(MPI_WIN_BASE).size == 16
            # one-sided read-back
            got = win.get(other, 3, 0)
            assert list(got) == [0, 1, 2]
            win.fence()
        finally:
            win.free()

    run_ranks(mpi_cluster, fn)


def test_shared_window_rejects_cross_host_world(mpi_cluster):
    from faabric_tpu.mpi.window import allocate_shared

    def fn(world, rank):
        if rank != 0:
            return
        with pytest.raises(RuntimeError, match="co-located"):
            allocate_shared(world, rank, 16)  # full world spans 2 hosts

    run_ranks(mpi_cluster, fn)


def test_window_bounds_and_free_semantics(mpi_cluster):
    from faabric_tpu.mpi.window import allocate_shared

    def fn(world, rank):
        sub, subrank = world.split_type_shared(rank)
        win = allocate_shared(sub, subrank, 8)
        with pytest.raises(ValueError, match="overruns"):
            win.put(np.zeros(9, np.uint8), 0, 0)
        with pytest.raises(ValueError, match="overruns"):
            win.get(0, 4, 6)
        win.free()
        with pytest.raises(RuntimeError, match="freed"):
            win.put(np.zeros(1, np.uint8), 0, 0)

    run_ranks(mpi_cluster, fn)


# ---------------------------------------------------------------------------
# Collective schedule compiler (ISSUE 13): sched-vs-legacy bitwise
# pinning + numpy references for the neglected collectives
# ---------------------------------------------------------------------------

def _set_sched(world_for_rank, mode, reductions=False):
    """Flip the schedule knob identically on every process's world —
    like the hier knob, a desynced choice would mismatch message
    patterns (the fixture's two simulated hosts live in one process, so
    this is one loop over the distinct world objects)."""
    for world in {id(world_for_rank(r)): world_for_rank(r)
                  for r in range(6)}.values():
        world.sched_enabled = mode
        world.sched_reductions = reductions


@pytest.mark.parametrize("dtype", [np.int64, np.float32, np.int16])
def test_alltoall_sched_bitwise_vs_direct(mpi_cluster, dtype):
    """The compiled leader-composed alltoall is bitwise-identical to
    the naive path across dtypes (pure data movement: no arithmetic on
    any path)."""
    rng = np.random.RandomState(7)
    mats = {r: (rng.rand(6 * 5) * 100).astype(dtype) for r in range(6)}
    expected = {r: np.concatenate(
        [mats[src].reshape(6, 5)[r] for src in range(6)])
        for r in range(6)}

    def fn(world, rank):
        return world.alltoall(rank, mats[rank])

    out = {}
    for mode in (False, "force"):
        _set_sched(mpi_cluster, mode)
        out[mode] = run_ranks(mpi_cluster, fn)
    _set_sched(mpi_cluster, True)
    for rank in range(6):
        np.testing.assert_array_equal(out[False][rank], expected[rank])
        np.testing.assert_array_equal(out["force"][rank],
                                      expected[rank])
        assert out[False][rank].dtype == out["force"][rank].dtype


def test_alltoall_sched_scattered_placement(scattered_cluster):
    """Leader composition over a NON-contiguous placement (rank r on
    host r % 2): host blocks pack/unpack by Topology rank lists, not
    positional arithmetic."""
    mats = {r: np.arange(18, dtype=np.int64) + 1000 * r
            for r in range(6)}

    def fn(world, rank):
        world.sched_enabled = "force"
        return world.alltoall(rank, mats[rank])

    results = run_ranks(scattered_cluster, fn)
    for rank in range(6):
        expected = np.concatenate(
            [mats[src].reshape(6, 3)[rank] for src in range(6)])
        np.testing.assert_array_equal(results[rank], expected)


@pytest.mark.parametrize("dtype", [np.float64, np.int32])
def test_alltoallv_matches_numpy_across_dtypes(mpi_cluster, dtype):
    """alltoallv coverage (previously one test, one dtype): asymmetric
    count matrices against a numpy reference."""
    counts = {r: [(r + s) % 4 + 1 for s in range(6)] for r in range(6)}
    datas = {r: (np.arange(sum(counts[r])) * 10 + r).astype(dtype)
             for r in range(6)}

    def fn(world, rank):
        return world.alltoallv(rank, datas[rank], counts[rank])

    results = run_ranks(mpi_cluster, fn)
    for rank in range(6):
        got, recv_counts = results[rank]
        assert recv_counts == [counts[src][rank] for src in range(6)]
        parts = []
        for src in range(6):
            off = sum(counts[src][:rank])
            parts.append(datas[src][off:off + counts[src][rank]])
        np.testing.assert_array_equal(got, np.concatenate(parts))
        assert got.dtype == dtype


@pytest.mark.parametrize("dtype", [np.float64, np.int16])
def test_scatterv_sched_tree_bitwise_vs_direct(mpi_cluster, dtype):
    """scatterv through the packed tree schedule (count-vector header →
    leader splits) vs the direct legacy path, bitwise, plus a non-zero
    root."""
    counts = [r + 1 for r in range(6)]
    flat = (np.arange(sum(counts)) * 3 + 1).astype(dtype)
    root = 2

    def fn(world, rank):
        if rank == root:
            return world.scatterv(root, rank, flat, counts)
        return world.scatterv(root, rank, None, None)

    from faabric_tpu.telemetry import get_metrics, snapshot_delta

    before = get_metrics().snapshot()
    out = {}
    for mode in (False, "force"):
        _set_sched(mpi_cluster, mode)
        out[mode] = run_ranks(mpi_cluster, fn)
    _set_sched(mpi_cluster, True)
    # scatterv counts on BOTH paths (2 modes x 6 ranks)
    from faabric_tpu.telemetry.metrics import metrics_enabled

    if metrics_enabled():
        delta = snapshot_delta(before, get_metrics().snapshot())
        assert delta.get(
            'faabric_mpi_collectives_total{op="scatterv"}') == 12
    offsets = np.cumsum([0] + counts[:-1])
    for rank in range(6):
        expected = flat[offsets[rank]:offsets[rank] + counts[rank]]
        np.testing.assert_array_equal(out[False][rank], expected)
        np.testing.assert_array_equal(out["force"][rank], expected)
        assert out["force"][rank].dtype == dtype
        # Public contract: caller-owned writable result on every path
        assert out["force"][rank].flags.writeable


@pytest.mark.parametrize("dtype", [np.int64, np.float64])
def test_scan_sched_matches_chain_and_numpy(mpi_cluster, dtype):
    """scan through the schedule runner vs the legacy chain vs numpy
    cumsum. int64 is bitwise on BOTH families; float64 is bitwise on
    the chain family by fold-order construction and compared to the
    legacy path's own result for the hier family (re-association)."""
    datas = {r: (np.arange(40) % 7 + r).astype(dtype) for r in range(6)}
    prefixes = np.cumsum(np.stack([datas[r] for r in range(6)]), axis=0)

    def fn(world, rank):
        return world.scan(rank, datas[rank], MpiOp.SUM)

    out = {}
    for mode in (False, "force"):
        _set_sched(mpi_cluster, mode)
        out[mode] = run_ranks(mpi_cluster, fn)
    _set_sched(mpi_cluster, True)
    for rank in range(6):
        if np.issubdtype(dtype, np.integer):
            np.testing.assert_array_equal(out["force"][rank],
                                          prefixes[rank])
            np.testing.assert_array_equal(out[False][rank],
                                          prefixes[rank])
        else:
            np.testing.assert_allclose(out["force"][rank],
                                       prefixes[rank], rtol=1e-12)


def test_scan_sched_scattered_placement_uses_chain(scattered_cluster):
    """Non-contiguous placements cannot compose the carrier chain —
    selection must fall back to scan.chain and stay correct."""
    datas = {r: np.arange(10, dtype=np.int64) + r for r in range(6)}

    def fn(world, rank):
        world.sched_enabled = "force"
        out = world.scan(rank, datas[rank], MpiOp.SUM)
        key = next(iter(world._sched_cache._entries))
        return out, world._sched_cache.family_of(key)

    results = run_ranks(scattered_cluster, fn)
    prefixes = np.cumsum(np.stack([datas[r] for r in range(6)]), axis=0)
    for rank in range(6):
        out, family = results[rank]
        assert family == "scan.chain"
        np.testing.assert_array_equal(out, prefixes[rank])


def test_scan_user_op_through_scheduler(mpi_cluster):
    """Non-commutative (but associative, as MPI requires) user op — a
    2×2 matrix product — through the schedule path: the prefix operand
    order (prefix, mine) must be preserved by both the chain and the
    hierarchical carrier composition."""
    from faabric_tpu.mpi.types import UserOp

    def matprod(a, b):
        return (np.asarray(a).reshape(2, 2)
                @ np.asarray(b).reshape(2, 2)).reshape(-1)

    op = UserOp(matprod, commute=False)
    datas = {r: np.array([1, r + 1, 0, 1], dtype=np.int64)
             for r in range(6)}

    def fn(world, rank):
        return world.scan(rank, datas[rank], op)

    _set_sched(mpi_cluster, True)
    results = run_ranks(mpi_cluster, fn)
    acc = datas[0]
    expect = {0: acc.copy()}
    for r in range(1, 6):
        acc = matprod(acc, datas[r])
        expect[r] = acc.copy()
    for rank in range(6):
        np.testing.assert_array_equal(results[rank].reshape(-1),
                                      expect[rank])


def test_sched_reduction_lowerings_bitwise_vs_handwritten(mpi_cluster):
    """Acceptance pin: the allreduce / reduce_scatter / allgather
    schedule lowerings are bitwise-identical to the hand-written
    hierarchical paths (exact int64 payloads — float reorder tolerance
    is a non-goal, as in the hier tests)."""
    _force_hier(mpi_cluster, True)  # hand-written hier on small payloads
    rng = np.random.RandomState(3)
    n = 6 * 40_000
    datas = {r: rng.randint(-10_000, 10_000, n).astype(np.int64)
             for r in range(6)}
    small = {r: datas[r][:60_000] for r in range(6)}

    def fn(world, rank):
        ar = world.allreduce(rank, datas[rank].copy(), MpiOp.SUM)
        rs = world.reduce_scatter(rank, datas[rank].copy(), MpiOp.SUM)
        ag = world.allgather(rank, small[rank].copy())
        return ar, rs, ag

    _set_sched(mpi_cluster, False)
    legacy = run_ranks(mpi_cluster, fn)
    _set_sched(mpi_cluster, "force", reductions=True)
    sched = run_ranks(mpi_cluster, fn)
    _set_sched(mpi_cluster, True)
    _force_hier(mpi_cluster, False)

    total = sum(datas.values())
    k = n // 6
    for rank in range(6):
        for i in range(3):
            np.testing.assert_array_equal(legacy[rank][i],
                                          sched[rank][i])
        np.testing.assert_array_equal(sched[rank][0], total)
        np.testing.assert_array_equal(sched[rank][1],
                                      total[rank * k:(rank + 1) * k])
        np.testing.assert_array_equal(
            sched[rank][2],
            np.concatenate([small[q] for q in range(6)]))


def test_sched_cache_recompiles_after_remap(mpi_cluster):
    """Acceptance pin: migration/topology regeneration invalidates the
    schedule cache — the generation in the key stops matching and the
    next call re-selects and re-compiles."""
    mats = {r: np.arange(12, dtype=np.int64) + r for r in range(6)}

    def fn(world, rank):
        return world.alltoall(rank, mats[rank])

    _set_sched(mpi_cluster, "force")
    run_ranks(mpi_cluster, fn)
    worlds = {id(mpi_cluster(r)): mpi_cluster(r) for r in range(6)}
    compiles_before = {wid: w._sched_cache.compiles
                       for wid, w in worlds.items()}
    gens_before = {wid: w._topology_gen for wid, w in worlds.items()}
    for w in worlds.values():
        assert w._sched_cache.compiles == 1

    # Same-placement remap: the planner re-confirms mappings, the world
    # must still treat the new generation as a fresh topology
    for w in worlds.values():
        w.prepare_migration(0)
    results = run_ranks(mpi_cluster, fn)
    _set_sched(mpi_cluster, True)
    for rank in range(6):
        expected = np.concatenate(
            [mats[src].reshape(6, 2)[rank] for src in range(6)])
        np.testing.assert_array_equal(results[rank], expected)
    for wid, w in worlds.items():
        assert w._topology_gen > gens_before[wid]
        assert w._sched_cache.compiles == compiles_before[wid] + 1
        gens = {key[0] for key in w._sched_cache._entries}
        assert len(gens) == 2  # old + new generation entries coexist
        # The per-rank seen-ledgers shed dead generations (regression:
        # migration churn must not leak one entry per key forever)
        for rank_keys in w._sched_seen.values():
            assert all(k[0] == w._topology_gen for k in rank_keys)


def test_scan_emits_span_and_counter(mpi_cluster):
    """ISSUE 13 satellite: scan — previously the one collective with
    neither a span nor a _count_collective — now reports both, so
    comm-matrix/profiler coverage is complete."""
    from faabric_tpu.telemetry import (
        get_metrics,
        reset_tracing,
        set_tracing,
        snapshot_delta,
        trace_events,
    )

    before = get_metrics().snapshot()
    set_tracing(True)
    reset_tracing()
    try:
        datas = {r: np.full(1000, r + 1, np.int64) for r in range(6)}

        def fn(world, rank):
            return world.scan(rank, datas[rank], MpiOp.SUM)

        run_ranks(mpi_cluster, fn)
        events = [e for e in trace_events() if e.get("ph") == "X"]
        scans = [e for e in events if e["cat"] == "mpi"
                 and e["name"] == "scan"]
        assert len(scans) == 6
        for e in scans:
            assert e["args"]["algo"].startswith(("sched:", "chain"))
            assert e["args"]["bytes"] == 8000
    finally:
        reset_tracing()
        set_tracing(False)
    delta = snapshot_delta(before, get_metrics().snapshot())
    assert delta.get('faabric_mpi_collectives_total{op="scan"}') == 6
    assert delta.get(
        'faabric_mpi_collective_bytes_total{op="scan"}') == 6 * 8000


def test_registry_joiners_share_the_creators_world():
    """The chained ranks of a gang are dispatched BEFORE rank 0 has its
    world in hand. A rank that joins meanwhile must wait for that world,
    not build a private one: the device plane's rendezvous is in-process
    state, and a rank with its own view would wait there alone until the
    timeout silently sends its collective down the host ladder."""
    import threading
    import time

    from faabric_tpu.proto import batch_exec_factory

    chaining = threading.Event()
    release = threading.Event()

    class SlowPlanner:
        def call_functions(self, req):
            chaining.set()
            assert release.wait(10)
            return SchedulingDecision(app_id=req.app_id, group_id=777)

    registry = MpiWorldRegistry(PointToPointBroker("regA"), SlowPlanner())
    msgs = batch_exec_factory("demo", "gang", 3).messages
    for rank, m in enumerate(msgs):
        m.mpi_world_id, m.mpi_world_size, m.mpi_rank = 5151, 3, rank
        m.group_id = 777

    got = {}
    threads = [threading.Thread(
        target=lambda: got.update(creator=registry.create_world(msgs[0])))]
    threads[0].start()
    assert chaining.wait(10)
    for rank in (1, 2):
        threads.append(threading.Thread(
            target=lambda r=rank: got.update(
                {r: registry.get_or_initialise_world(msgs[r])})))
        threads[-1].start()
    time.sleep(0.2)
    assert not got, "a joiner did not wait for the world being created"
    release.set()
    for t in threads:
        t.join(10)
        assert not t.is_alive()
    assert got[1] is got["creator"] and got[2] is got["creator"]
    registry.clear()
